"""Streaming serving CLI: per-session windowed inference over the
dynamic-batching engine.

Two workloads share the identical windowing + dispatch path (ISSUE 10):

* ``--workload kws`` (default) — synthetic KWS-6 keyword spotting:
  per-class spectral prototypes, thermometer-booleanized by a sliding
  window, per-window argmax smoothed by a majority vote — the paper's
  always-on audio deployment.
* ``--workload anomaly`` — multichannel sensor anomaly detection:
  2-class TM trained on windows labeled 1 iff any frame overlaps an
  injected fault burst, served in ``margin`` decision mode (alert iff
  the anomaly class's class-sum margin clears ``--margin-threshold``).

``--latency-sessions N`` runs the first N sessions under the
``latency`` QoS class (early small-batch cuts) while the rest ride
``bulk`` — the summary then carries the per-class percentile block.

  PYTHONPATH=src python -m repro.launch.stream --sessions 8
  PYTHONPATH=src python -m repro.launch.stream --workload anomaly \\
      --latency-sessions 4
  PYTHONPATH=src python -m repro.launch.stream --async-serve \\
      --host-devices 8 --mesh 4   # sharded + overlapped
"""

from __future__ import annotations

import argparse
import json
import sys

from repro.launch.hostdev import force_host_devices

force_host_devices(sys.argv[1:])   # must precede the first jax import

import jax
import numpy as np

from repro.core import tm, tm_train
from repro.core.booleanize import StreamingBooleanizer, fit_quantile
from repro.core.tm import TMConfig
from repro.core.variations import VariationConfig
from repro.data.tm_datasets import (kws6_windows, sensor_anomaly_windows,
                                    synthetic_kws6,
                                    synthetic_sensor_anomaly)
from repro.launch.mesh import parse_mesh_spec
from repro.serve import (QOS_LATENCY, AsyncServeEngine, BatcherConfig,
                         EngineConfig, ServeEngine, StreamConfig,
                         StreamServer)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="kws",
                    choices=("kws", "anomaly"),
                    help="kws: keyword argmax+vote; anomaly: 2-class "
                         "sensor fault detection in margin decision mode")
    ap.add_argument("--sessions", type=int, default=8)
    ap.add_argument("--latency-sessions", type=int, default=0,
                    help="run the first N sessions under the latency QoS "
                         "class (the rest stay bulk)")
    ap.add_argument("--frames", type=int, default=128,
                    help="frames streamed per session")
    ap.add_argument("--mels", type=int, default=12)
    ap.add_argument("--sensors", type=int, default=8,
                    help="sensor channels (anomaly workload)")
    ap.add_argument("--margin-threshold", type=float, default=0.0,
                    help="class-sum margin the anomaly class must clear "
                         "to alert (anomaly workload)")
    ap.add_argument("--bits", type=int, default=4,
                    help="thermometer bits per mel bin")
    ap.add_argument("--window", type=int, default=8)
    ap.add_argument("--hop", type=int, default=4)
    ap.add_argument("--vote", type=int, default=5,
                    help="majority-vote horizon (windows)")
    ap.add_argument("--clauses", type=int, default=10,
                    help="clauses per keyword class")
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--batch", type=int, default=64,
                    help="max dynamic batch (largest kernel bucket)")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--routing", default="round_robin",
                    choices=("round_robin", "least_loaded", "ensemble"))
    ap.add_argument("--backend", default=None,
                    choices=("analog-pallas-packed", "analog-pallas",
                             "analog-jnp"))
    ap.add_argument("--packed", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--lazy-tune", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="measure shape-aware kernel tiles on first sight "
                         "of this model's shape bucket (default on)")
    ap.add_argument("--mesh", default=None, metavar="RxB",
                    help="shard the replica pool over a device mesh")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force N CPU host devices before jax init")
    ap.add_argument("--async-serve", action="store_true")
    ap.add_argument("--max-in-flight", type=int, default=2)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="snapshot the programmed pool here at startup "
                         "(rollback point for live hot-swaps; absent = "
                         "identical serving behavior, no restore point)")
    ap.add_argument("--nominal", action="store_true",
                    help="disable D2D/C2C/CSA variation")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    # ------------------------------------------------ data + booleanizer
    anomaly = args.workload == "anomaly"
    n_ch = args.sensors if anomaly else args.mels    # channels per frame
    n_feat = args.window * n_ch * args.bits
    cfg = TMConfig(n_classes=(2 if anomaly else 6),
                   clauses_per_class=args.clauses,
                   n_features=n_feat, n_states=100, threshold=15,
                   specificity=5.0)
    if anomaly:
        xtr, ltr = synthetic_sensor_anomaly(jax.random.PRNGKey(0),
                                            n_streams=120, n_frames=32,
                                            n_sensors=n_ch)
        xte, lte = synthetic_sensor_anomaly(jax.random.PRNGKey(1),
                                            n_streams=40, n_frames=32,
                                            n_sensors=n_ch)
    else:
        xtr, ytr = synthetic_kws6(jax.random.PRNGKey(0), n_utterances=120,
                                  n_frames=32, n_mels=n_ch)
        xte, yte = synthetic_kws6(jax.random.PRNGKey(1), n_utterances=40,
                                  n_frames=32, n_mels=n_ch)
    booleanizer = fit_quantile(
        np.asarray(xtr).reshape(-1, n_ch), bits=args.bits)
    windower = StreamingBooleanizer(booleanizer, args.window, args.hop)
    if anomaly:
        rtr, wytr = sensor_anomaly_windows(xtr, ltr, windower)
        rte, wyte = sensor_anomaly_windows(xte, lte, windower)
    else:
        rtr, wytr = kws6_windows(xtr, ytr, windower)
        rte, wyte = kws6_windows(xte, yte, windower)
    print(f"[stream] {args.workload} windows: {len(rtr)} train / "
          f"{len(rte)} test, {n_feat} Boolean features "
          f"(C={cfg.n_clauses}, L={cfg.n_literals})")

    # --------------------------------------------------------- train TM
    ta = tm.init_ta_state(jax.random.PRNGKey(2), cfg)
    ta = tm_train.fit(ta, jax.random.PRNGKey(3), rtr, wytr, cfg,
                      epochs=args.epochs, batch_size=200, parallel=True)
    acc = float(tm.accuracy(ta, rte, wyte, cfg))
    print(f"[stream] digital per-window accuracy {acc:.3f}")

    # ------------------------------------------------------------ engine
    vcfg = (VariationConfig.nominal() if args.nominal
            else VariationConfig(csa_offset=False))
    ecfg = EngineConfig(
        batcher=BatcherConfig.for_max_batch(args.batch),
        routing=args.routing, backend=args.backend, packed=args.packed,
        max_in_flight=args.max_in_flight, lazy_tune=args.lazy_tune)
    mesh = parse_mesh_spec(args.mesh) if args.mesh else None
    cls = AsyncServeEngine if args.async_serve else ServeEngine
    engine = cls.from_ta_state(ta, cfg, n_replicas=args.replicas,
                               key=jax.random.PRNGKey(4), vcfg=vcfg,
                               ecfg=ecfg, mesh=mesh)
    print(f"[stream] pool of {args.replicas} crossbars "
          f"(pool version {engine.version}), "
          f"routing={args.routing}, backend={engine.backend.name}, "
          f"shape bucket {engine.shape_key} "
          f"(tiles {(engine.tuning or {}).get('tiles') or 'default'}"
          f"{', lazily measured' if (engine.tuning or {}).get('lazy') else ''})")
    if args.checkpoint_dir:
        from repro.serve import snapshot_pool
        path = snapshot_pool(engine.pool, args.checkpoint_dir)
        print(f"[stream] pool v{engine.version} snapshot -> {path}")
    if engine.selection.fell_back:
        print(f"[stream] BACKEND FALLBACK: "
              f"{engine.selection.fallback_reason}")
    if engine.mesh is not None:
        print(f"[stream] pool sharded over mesh {dict(engine.mesh.shape)} "
              f"({jax.device_count()} devices visible)")

    # ------------------------------------------------- streaming sessions
    scfg = StreamConfig(window=args.window, hop=args.hop, vote=args.vote,
                        decision=("margin" if anomaly else "argmax"),
                        margin_class=1,
                        margin_threshold=args.margin_threshold)
    server = StreamServer(engine, booleanizer, scfg)
    streams, truth = [], []
    for s in range(args.sessions):
        if anomaly:
            x, lab = synthetic_sensor_anomaly(
                jax.random.PRNGKey(10 + s), n_streams=1,
                n_frames=args.frames, n_sensors=n_ch)
            streams.append(np.asarray(x)[0])
            truth.append(np.asarray(lab)[0])            # per-frame 0/1
        else:
            x, y = synthetic_kws6(jax.random.PRNGKey(10 + s),
                                  n_utterances=max(1, args.frames // 32),
                                  n_frames=32, n_mels=n_ch)
            streams.append(np.asarray(x).reshape(-1, n_ch)[:args.frames])
            truth.append(np.repeat(np.asarray(y), 32)[:args.frames])
    n_frames = min(args.frames, min(len(s) for s in streams))
    for i in range(args.sessions):
        server.session(f"client-{i}",
                       qos=(QOS_LATENCY if i < args.latency_sessions
                            else None))
    for lo in range(0, n_frames, args.hop):
        for i, stream in enumerate(streams):
            server.feed(f"client-{i}", stream[lo:lo + args.hop])
        server.pump()
    server.drain()

    # Scoring.  KWS: the SMOOTHED keyword vs the label of the utterance
    # the window's last frame is in.  Anomaly: the raw margin decision
    # vs the window's rolled-up label (1 iff any frame in the window is
    # inside a fault burst — same roll-up as sensor_anomaly_windows).
    correct = total = 0
    for i in range(args.sessions):
        sess = server.sessions[f"client-{i}"]
        for d in sess.decisions:
            span = truth[i][d.index * args.hop:
                            d.index * args.hop + args.window]
            want = int(span.max()) if anomaly else span[-1]
            got = d.pred if anomaly else d.keyword
            correct += int(got == want)
            total += 1
    summary = server.summary()
    summary["decision_accuracy"] = correct / max(total, 1)
    summary["keyword_accuracy"] = summary["decision_accuracy"]
    summary["digital_window_accuracy"] = acc

    if args.json:
        print(json.dumps(summary, indent=2, default=str))
        return summary
    sess = summary.get("sessions", {})
    rates = [v["decisions_per_s"] for v in sess.values()
             if v["decisions_per_s"]]
    p50s = [v["p50_ms"] for v in sess.values()]
    label = "alert accuracy" if anomaly else "keyword accuracy"
    print(f"[stream] {total} decisions across {args.sessions} sessions: "
          f"{label} {summary['decision_accuracy']:.3f} "
          + (f"(margin >= {args.margin_threshold:g} on class 1 over "
             f"{summary['digital_window_accuracy']:.3f} per-window)"
             if anomaly else
             f"(vote={args.vote} smoothing over "
             f"{summary['digital_window_accuracy']:.3f} per-window)"))
    for qc, q in summary.get("qos", {}).items():
        print(f"[stream]   qos[{qc}]: {q['requests']} served, "
              f"p99 {q['p99_ms']:.1f} ms "
              f"(queue p99 {q['queue_p99_ms']:.1f} ms), "
              f"rejected {q['rejected']}, expired {q['expired']}")
    print(f"[stream] {summary['batches']} batches, mean "
          f"{summary['mean_batch']:.1f} windows/batch "
          f"({100 * summary['padding_overhead']:.1f}% padding) — "
          f"cross-session batching at work")
    rate_p50 = np.median(rates) if rates else float("nan")
    lat_p50 = np.median(p50s) if p50s else float("nan")
    print(f"[stream] per-session decision rate p50 "
          f"{rate_p50:.1f}/s, window latency p50 "
          f"{lat_p50:.1f} ms, overlap "
          f"{100 * summary['overlap_fraction']:.0f}%")
    return summary


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
