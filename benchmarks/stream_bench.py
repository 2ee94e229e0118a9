"""Streaming KWS-6 serving benchmark: sessions x hop-rate sweep.

The paper's KWS-6 workload is the always-on case for "program once, read
forever": S concurrent keyword sessions each complete one window per hop
and every window is one classifier read.  This bench measures the
streaming front-end (``repro.serve.stream``) end to end on the synthetic
KWS-6 model shape — NOT the serve-bench shape, which is exactly why the
engines run with ``lazy_tune=True``: the first engine construction
triggers the shape-aware autotuner's lazy measurement for the
(backend, KWS shape bucket) cell and every later engine reuses it.

Rows:

* **sweep** — sessions x hop-rate grid on the synchronous engine:
  wall-clock decisions/s, per-session decision latency, padding/bytes
  from the shared batcher.  More sessions at a faster hop rate means
  more rows per batcher cut — cross-session batching is the entire
  point of sharing one engine.
* **sync/async pair** — the headline cell timed with the two engines
  interleaved run-for-run (host drift can't fake the win), like
  serve_bench's pair.
* **sharded** — the same cell with the replica pool split over a device
  mesh (needs >1 device: pass ``--host-devices 8``).  On forced CPU
  host devices this measures mechanics, not a speedup.
* **mixed_qos** — the standing heavy-traffic scenario (ISSUE 10): two
  ``latency`` and two ``bulk`` sessions saturating ONE shared engine,
  bulk feeding bursts under a long batching deadline while latency
  windows cut early.  The row carries the per-class p50/p95/p99 from
  ``summary()["qos"]`` and the full run asserts the point of QoS
  classes: latency-class p99 measurably below bulk p99 on the same
  engine.
* **anomaly** — the second streaming workload (ISSUE 10): sensor
  streams served in ``margin`` decision mode (threshold the class-sum
  margin of the anomaly class), reusing the identical windowing and
  dispatch path as KWS.

Bit-exactness is asserted in every mode before timing: the streamed
per-window predictions must equal offline batched ``api.predict`` over
``StreamingBooleanizer.transform_offline`` of the same frames — and the
streamed anomaly *margins* must equal the digital-oracle margins on the
same windows.

  PYTHONPATH=src python -m benchmarks.stream_bench --host-devices 8
  PYTHONPATH=src python -m benchmarks.stream_bench --smoke   # CI, no JSON
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from repro.launch.hostdev import force_host_devices

force_host_devices(sys.argv[1:])   # must precede the first jax import

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.core import tm
from repro.core.booleanize import StreamingBooleanizer, fit_quantile
from repro.core.tm import TMConfig
from repro.core.variations import VariationConfig
from repro.data.tm_datasets import synthetic_kws6, synthetic_sensor_anomaly
from repro.launch.mesh import make_replica_mesh
from repro.serve import (QOS_BULK, QOS_LATENCY, AsyncServeEngine,
                         BatcherConfig, EngineConfig, ServeEngine,
                         StreamConfig, StreamServer, margin_of)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Full-size stream geometry — matches kernels.autotune.KWS_SHAPE
# (window * mels * bits = 384 Boolean features, 6 x 10 clauses).
FULL = dict(n_mels=12, bits=4, window=8, clauses_per_class=10)
# CI smoke geometry: same code paths, interpret-mode-friendly shape.
SMOKE = dict(n_mels=6, bits=2, window=4, clauses_per_class=8)

# Anomaly workload geometry (second streaming workload, ISSUE 10):
# 2-class margin-mode detection over multichannel sensor streams.
ANOMALY_FULL = dict(n_sensors=8, bits=2, window=8, hop=4,
                    clauses_per_class=10)
ANOMALY_SMOKE = dict(n_sensors=4, bits=2, window=4, hop=2,
                     clauses_per_class=8)


def make_kws_model(key, *, n_mels, bits, window, clauses_per_class):
    """Synthetic KWS-6 booleanizer + training-free sparse TM at the
    streaming shape (the bench measures serving mechanics, not
    accuracy — ``launch/stream.py`` trains a real one)."""
    kf, ki = jax.random.split(jax.random.PRNGKey(0) if key is None else key)
    frames, _ = synthetic_kws6(kf, n_utterances=24, n_frames=32,
                               n_mels=n_mels)
    booleanizer = fit_quantile(
        np.asarray(frames).reshape(-1, n_mels), bits=bits)
    cfg = TMConfig(n_classes=6, clauses_per_class=clauses_per_class,
                   n_features=window * n_mels * bits, n_states=100)
    inc = jax.random.bernoulli(ki, 0.1, (cfg.n_clauses, cfg.n_literals))
    ta = jnp.where(inc, cfg.n_states + 1, cfg.n_states).astype(
        cfg.state_dtype)
    return cfg, ta, booleanizer


def make_anomaly_model(key, *, n_sensors, bits, window, hop,
                       clauses_per_class):
    """Sensor-anomaly booleanizer + training-free sparse 2-class TM at
    the streaming shape (mechanics, not accuracy — same rationale as
    :func:`make_kws_model`)."""
    kf, ki = jax.random.split(jax.random.PRNGKey(11) if key is None
                              else key)
    frames, _ = synthetic_sensor_anomaly(kf, n_streams=12, n_frames=32,
                                         n_sensors=n_sensors)
    booleanizer = fit_quantile(
        np.asarray(frames).reshape(-1, n_sensors), bits=bits)
    cfg = TMConfig(n_classes=2, clauses_per_class=clauses_per_class,
                   n_features=window * n_sensors * bits, n_states=100)
    inc = jax.random.bernoulli(ki, 0.1, (cfg.n_clauses, cfg.n_literals))
    ta = jnp.where(inc, cfg.n_states + 1, cfg.n_states).astype(
        cfg.state_dtype)
    return cfg, ta, booleanizer


def sensor_streams(n_sessions, n_frames, n_sensors, seed=21):
    """One long sensor stream per session."""
    streams = []
    for s in range(n_sessions):
        x, _ = synthetic_sensor_anomaly(jax.random.PRNGKey(seed + s),
                                        n_streams=1, n_frames=n_frames,
                                        n_sensors=n_sensors)
        streams.append(np.asarray(x)[0])
    return streams


def session_streams(n_sessions, n_frames, n_mels, seed=7):
    """One long frame stream per session (concatenated utterances)."""
    streams = []
    for s in range(n_sessions):
        x, _ = synthetic_kws6(jax.random.PRNGKey(seed + s),
                              n_utterances=max(1, n_frames // 32),
                              n_frames=32, n_mels=n_mels)
        streams.append(np.asarray(x).reshape(-1, n_mels)[:n_frames])
    return streams


def make_engine(cfg, ta, *, engine_cls=ServeEngine, mesh=None, backend=None,
                packed=True, max_batch=64, n_replicas=2,
                routing="round_robin", nominal=False, batcher=None):
    # Timed cells run with the realistic noise model (c2c on); the
    # bit-exactness checks build their OWN engine at nominal() — the
    # streamed == offline invariant only holds without read noise
    # (offline api.predict draws none).
    return engine_cls.from_ta_state(
        ta, cfg, n_replicas=n_replicas, key=jax.random.PRNGKey(3),
        vcfg=(VariationConfig.nominal() if nominal
              else VariationConfig(csa_offset=False)),
        ecfg=EngineConfig(batcher=(batcher if batcher is not None
                                   else BatcherConfig.for_max_batch(
                                       max_batch)),
                          routing=routing, backend=backend, packed=packed,
                          lazy_tune=True),
        mesh=mesh)


def stream_once(engine, booleanizer, scfg, streams, tag):
    """Feed every session one hop of frames per tick (round-robin),
    pumping between ticks; drain at the end.  Returns (wall_s,
    n_decisions)."""
    server = StreamServer(engine, booleanizer, scfg)
    n_frames = min(len(s) for s in streams)
    t0 = time.monotonic()
    for lo in range(0, n_frames, scfg.hop):
        for i, stream in enumerate(streams):
            server.feed(f"{tag}-s{i}", stream[lo:lo + scfg.hop])
        server.pump()
    server.drain()
    wall = time.monotonic() - t0
    return wall, server, sum(len(s.decisions)
                             for s in server.sessions.values())


def check_bit_exact(cfg, ta, booleanizer, scfg, streams, **engine_kw):
    """Streamed per-window preds == offline batched api.predict over the
    same windows (the invariant that makes streaming safe).  Builds its
    own engine at ``VariationConfig.nominal()`` — the invariant is only
    promised without read noise."""
    engine = make_engine(cfg, ta, nominal=True, **engine_kw)
    server = StreamServer(engine, booleanizer, scfg)
    for i, stream in enumerate(streams):
        for lo in range(0, len(stream), scfg.hop):
            server.feed(f"check-s{i}", stream[lo:lo + scfg.hop])
            server.pump()
    server.drain()
    sb = StreamingBooleanizer(booleanizer, scfg.window, scfg.hop)
    for i, stream in enumerate(streams):
        rows = sb.transform_offline(stream)
        offline = np.asarray(api.predict(engine.state, jnp.asarray(rows)))
        streamed = np.array(
            [d.pred for d in server.sessions[f"check-s{i}"].decisions])
        np.testing.assert_array_equal(streamed, offline)
    return True


def check_margin_bit_exact(acfg, ata, booleanizer, scfg, streams):
    """Streamed margin-mode decisions == the digital oracle on the same
    windows: margins equal ``margin_of`` over ``tm.forward`` class sums
    and preds follow the threshold rule.  Single replica at nominal so
    the oracle comparison is direct."""
    engine = make_engine(acfg, ata, nominal=True, n_replicas=1)
    server = StreamServer(engine, booleanizer, scfg)
    for i, stream in enumerate(streams):
        for lo in range(0, len(stream), scfg.hop):
            server.feed(f"anom-s{i}", stream[lo:lo + scfg.hop])
            server.pump()
    server.drain()
    sb = StreamingBooleanizer(booleanizer, scfg.window, scfg.hop)
    mc = scfg.margin_class
    for i, stream in enumerate(streams):
        rows = sb.transform_offline(stream)
        sums = np.asarray(tm.forward(ata, jnp.asarray(rows), acfg))
        offline_margins = np.array([margin_of(s, mc) for s in sums])
        decs = server.sessions[f"anom-s{i}"].decisions
        streamed_margins = np.array([d.margin for d in decs])
        np.testing.assert_array_equal(streamed_margins, offline_margins)
        for d, s in zip(decs, sums):
            want = (mc if d.margin >= scfg.margin_threshold
                    else int(np.delete(np.arange(acfg.n_classes), mc)[
                        np.delete(s, mc).argmax()]))
            assert d.pred == want, (d, s)
    return True


def run_mixed_qos_cell(cfg, ta, booleanizer, *, window, hop, frames,
                       backend=None, packed=True, n_replicas=2,
                       bulk_wait_s=0.25, latency_wait_s=1e-3,
                       bulk_burst=4, max_batch=64):
    """The standing heavy-traffic scenario: two latency + two bulk
    sessions saturating ONE shared engine.  Latency sessions feed one
    hop per tick under a ~1 ms batching deadline; bulk sessions feed
    ``bulk_burst`` hops per tick under a long deadline, so bulk windows
    accumulate across ticks and ride big buckets while latency windows
    cut early.  Returns the summary row with the per-class ``qos``
    percentile block — the committed evidence that latency p99 sits
    below bulk p99 on the same engine."""
    bcfg = BatcherConfig.for_max_batch(max_batch, max_wait_s=bulk_wait_s,
                                       latency_max_wait_s=latency_wait_s)
    engine = make_engine(cfg, ta, backend=backend, packed=packed,
                         n_replicas=n_replicas, batcher=bcfg)
    scfg = StreamConfig(window=window, hop=hop, vote=5)
    n_mels = cfg_mels(booleanizer)
    lat_streams = session_streams(2, frames, n_mels, seed=31)
    bulk_streams = session_streams(2, frames * bulk_burst, n_mels, seed=41)
    # synthetic_kws6 emits whole utterances: clamp to what both stream
    # sets actually hold so every tick's slices are non-empty.
    frames = min(min(len(s) for s in lat_streams),
                 min(len(s) for s in bulk_streams) // bulk_burst)

    def tick_feed(server, prefix, lo):
        for i in range(2):
            server.feed(f"{prefix}lat-s{i}", lat_streams[i][lo:lo + hop])
            blo = lo * bulk_burst
            server.feed(f"{prefix}bulk-s{i}",
                        bulk_streams[i][blo:blo + hop * bulk_burst])

    # Warm pass: the first dispatch per BUCKET SHAPE pays JIT compile —
    # seconds each in interpret mode.  Bulk only reaches the big
    # buckets once its long deadline fires, so a few warm ticks never
    # hit them and the compile stall would land inside the timed loop
    # (dominating BOTH classes' p99 and faking the comparison).  Warm
    # every bucket in the ladder explicitly, then reset metrics.
    sb = StreamingBooleanizer(booleanizer, window, hop)
    row0 = sb.transform_offline(lat_streams[0][:window])[0]
    for b in engine.batcher.cfg.bucket_sizes:
        for _ in range(b):
            engine.submit(row0)
        engine.drain()
    engine.metrics = type(engine.metrics)()

    server = StreamServer(engine, booleanizer, scfg)
    for i in range(2):                       # pin each session's class
        server.session(f"lat-s{i}", qos=QOS_LATENCY)
        server.session(f"bulk-s{i}", qos=QOS_BULK)
    t0 = time.monotonic()
    n_dec = 0
    for lo in range(0, frames, hop):
        tick_feed(server, "", lo)
        n_dec += len(server.pump())
    n_dec += len(server.drain())
    wall = time.monotonic() - t0
    row = dict(server.summary())
    row.pop("sessions", None)
    row.update(latency_sessions=2, bulk_sessions=2, bulk_burst=bulk_burst,
               hop=hop, window=window, frames_per_session=frames,
               bulk_wait_s=bulk_wait_s, latency_wait_s=latency_wait_s,
               decisions=n_dec, wall_s=wall,
               decisions_per_s_wall=n_dec / wall, n_replicas=n_replicas)
    return row


def run_anomaly_cell(acfg, ata, booleanizer, geo, *, frames, sessions=4,
                     backend=None, packed=True, n_replicas=2):
    """Second streaming workload: sensor sessions in margin decision
    mode on the latency class.  Times the cell and reports alert
    mechanics (decision count, alert fraction, margin spread)."""
    engine = make_engine(acfg, ata, backend=backend, packed=packed,
                         n_replicas=n_replicas)
    scfg = StreamConfig(window=geo["window"], hop=geo["hop"], vote=3,
                        qos=QOS_LATENCY, decision="margin",
                        margin_class=1, margin_threshold=0.0)
    streams = sensor_streams(sessions, frames, geo["n_sensors"])
    t0 = time.monotonic()
    server = StreamServer(engine, booleanizer, scfg)
    for lo in range(0, frames, scfg.hop):
        for i, stream in enumerate(streams):
            server.feed(f"sensor-s{i}", stream[lo:lo + scfg.hop])
        server.pump()
    server.drain()
    wall = time.monotonic() - t0
    decs = [d for s in server.sessions.values() for d in s.decisions]
    margins = np.array([d.margin for d in decs])
    row = dict(server.summary())
    row.pop("sessions", None)
    row.update(sessions=sessions, window=geo["window"], hop=geo["hop"],
               frames_per_session=frames, decisions=len(decs),
               wall_s=wall, decisions_per_s_wall=len(decs) / wall,
               alert_fraction=(float(np.mean(
                   [d.pred == scfg.margin_class for d in decs]))
                   if decs else None),
               margin_p50=float(np.median(margins)) if len(margins)
                   else None,
               n_replicas=n_replicas)
    return row


def run_cell(cfg, ta, booleanizer, *, sessions, hop, window, vote=5,
             frames=96, repeats=3, engine_cls=ServeEngine, mesh=None,
             backend=None, packed=True, n_replicas=2,
             routing="round_robin"):
    """One timed benchmark cell (best of ``repeats``, warmed engine)."""
    engine = make_engine(cfg, ta, engine_cls=engine_cls, mesh=mesh,
                         backend=backend, packed=packed,
                         n_replicas=n_replicas, routing=routing)
    scfg = StreamConfig(window=window, hop=hop, vote=vote)
    streams = session_streams(sessions, frames, cfg_mels(booleanizer))
    stream_once(engine, booleanizer, scfg, streams, "warm")   # warm kernels
    best = (float("inf"), None, 0)
    for r in range(max(1, repeats)):
        engine.metrics = type(engine.metrics)()
        wall, server, n_dec = stream_once(engine, booleanizer, scfg,
                                          streams, f"r{r}")
        if wall < best[0]:
            best = (wall, server.summary(), n_dec)
    wall, summary, n_dec = best
    row = dict(summary)
    # per-session summaries are bulky in JSON: keep an aggregate
    sess = row.pop("sessions", {})
    lat = [v["p50_ms"] for v in sess.values()]
    row.update(sessions=sessions, hop=hop, window=window,
               frames_per_session=frames, decisions=n_dec,
               wall_s=wall, decisions_per_s_wall=n_dec / wall,
               async_engine=engine_cls is AsyncServeEngine,
               n_replicas=n_replicas, routing=routing,
               session_p50_ms_median=(float(np.median(lat)) if lat
                                      else None),
               per_session_decisions=(n_dec / sessions if sessions else 0))
    return row, engine


def cfg_mels(booleanizer) -> int:
    return booleanizer.thresholds.shape[0]


def run_pair(cfg, ta, booleanizer, *, sessions, hop, window, frames,
             repeats, backend=None, packed=True, mesh=None, n_replicas=2):
    """Sync vs async on the SAME streaming workload, runs interleaved
    (same de-drifting rationale as serve_bench.run_async_pair)."""
    scfg = StreamConfig(window=window, hop=hop, vote=5)
    streams = session_streams(sessions, frames, cfg_mels(booleanizer))
    engines = {}
    for is_async in (False, True):
        eng = make_engine(cfg, ta,
                          engine_cls=(AsyncServeEngine if is_async
                                      else ServeEngine),
                          mesh=mesh, backend=backend, packed=packed,
                          n_replicas=n_replicas)
        stream_once(eng, booleanizer, scfg, streams, "warm")
        engines[is_async] = eng
    best = {False: (float("inf"), None, 0), True: (float("inf"), None, 0)}
    for r in range(max(1, repeats)):
        for is_async in (False, True):
            eng = engines[is_async]
            eng.metrics = type(eng.metrics)()
            wall, server, n_dec = stream_once(eng, booleanizer, scfg,
                                              streams, f"p{r}")
            if wall < best[is_async][0]:
                best[is_async] = (wall, server.summary(), n_dec)
    rows = {}
    for is_async in (False, True):
        wall, summary, n_dec = best[is_async]
        row = dict(summary)
        row.pop("sessions", None)
        row.update(sessions=sessions, hop=hop, window=window, wall_s=wall,
                   decisions=n_dec, decisions_per_s_wall=n_dec / wall,
                   async_engine=is_async, n_replicas=n_replicas)
        rows[is_async] = row
    return rows[False], rows[True]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=96,
                    help="frames streamed per session per cell")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed runs per cell (best reported)")
    ap.add_argument("--backend", default=None,
                    choices=("analog-pallas-packed", "analog-pallas",
                             "analog-jnp"),
                    help="forward-backend preference (repro.api name)")
    ap.add_argument("--packed", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny model + one cell + bit-exactness "
                         "and lazy-tuning assertions; committed JSON "
                         "untouched")
    ap.add_argument("--smoke-out", default=None,
                    help="write the smoke report JSON here (CI uploads it "
                         "as a workflow artifact); default: no write")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force N CPU host devices before jax init so the "
                         "sharded rows run")
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_stream.json"))
    args = ap.parse_args(argv)

    geo = SMOKE if args.smoke else FULL
    if args.smoke:
        args.frames = min(args.frames, 48)
        args.repeats = 1
    window = geo["window"]

    cfg, ta, booleanizer = make_kws_model(jax.random.PRNGKey(0), **geo)
    shape_key = api.shape_bucket_key(cfg.n_clauses, cfg.n_literals)
    print(f"[stream_bench] KWS-6 model: C={cfg.n_clauses} "
          f"L={cfg.n_literals} (shape bucket {shape_key}), "
          f"{jax.device_count()} device(s)")

    # Lazy shape-aware tuning: the first engine construction measures
    # this (backend, shape bucket) cell; assert it is then REUSED.
    t0 = time.monotonic()
    eng0 = make_engine(cfg, ta, backend=args.backend, packed=args.packed,
                       n_replicas=args.replicas)
    t_first = time.monotonic() - t0
    t0 = time.monotonic()
    eng1 = make_engine(cfg, ta, backend=args.backend, packed=args.packed,
                       n_replicas=args.replicas)
    t_second = time.monotonic() - t0
    tuning = eng1.tuning or {}
    lazy_info = {
        "backend": eng1.backend.name, "shape_key": shape_key,
        "tiles": tuning.get("tiles"), "bucket_sizes":
            tuning.get("bucket_sizes"), "lazy": bool(tuning.get("lazy")),
        "first_construction_s": t_first, "reuse_construction_s": t_second,
    }
    assert eng0.tuning == eng1.tuning, "lazy entry must be reused"
    src = ("measured lazily once" if lazy_info["lazy"]
           else "from the committed table")
    print(f"[stream_bench] shape tuning @ {shape_key}: "
          f"tiles={lazy_info['tiles']} buckets={lazy_info['bucket_sizes']} "
          f"({src}; constructions {t_first:.2f}s then {t_second:.2f}s)")

    scfg = StreamConfig(window=window, hop=4, vote=5)
    streams2 = session_streams(2, min(args.frames, 64), geo["n_mels"])
    n_dev = jax.device_count()
    check_bit_exact(cfg, ta, booleanizer, scfg, streams2,
                    backend=args.backend, packed=args.packed,
                    n_replicas=args.replicas)
    print("[stream_bench] bit-exactness: streamed == offline batched "
          "predict (sync)")

    if args.smoke:
        check_bit_exact(cfg, ta, booleanizer, scfg, streams2,
                        engine_cls=AsyncServeEngine, backend=args.backend,
                        packed=args.packed, n_replicas=args.replicas)
        print("[stream_bench] bit-exactness: streamed == offline (async)")
        mesh_checked = False
        if n_dev > 1:          # multidevice leg: exercise the mesh path
            r = min(4, n_dev)
            check_bit_exact(cfg, ta, booleanizer, scfg, streams2,
                            mesh=make_replica_mesh(r, 1), n_replicas=r,
                            routing="ensemble", packed=args.packed)
            mesh_checked = True
            print(f"[stream_bench] bit-exactness: streamed == offline "
                  f"(mesh R={r} ensemble)")
        row, eng = run_cell(cfg, ta, booleanizer, sessions=4, hop=4,
                            window=window, frames=args.frames,
                            repeats=1, backend=args.backend,
                            packed=args.packed, n_replicas=args.replicas)
        ok = (row["decisions"] > 0 and row["forward_fallbacks"] == []
              and (eng.tuning or {}).get("lazy"))
        print(f"[stream_bench] SMOKE {'PASS' if ok else 'FAIL'}: "
              f"{row['decisions']} decisions at "
              f"{row['decisions_per_s_wall']:.0f}/s on {row['backend']} "
              f"(lazy-tuned @ {row['shape_key']})")

        # Mixed-QoS leg (ISSUE 10): two latency + two bulk sessions on
        # one shared engine.  Smoke asserts the per-class percentile
        # block is PRESENT and populated for both classes — the p99
        # *ordering* is only asserted in the full (committed) run, where
        # the load is saturating enough not to flake CI.
        qrow = run_mixed_qos_cell(cfg, ta, booleanizer, window=window,
                                  hop=4, frames=args.frames,
                                  backend=args.backend,
                                  packed=args.packed,
                                  n_replicas=args.replicas,
                                  bulk_wait_s=0.05, bulk_burst=2,
                                  max_batch=32)
        qs = qrow.get("qos")
        assert qs is not None, "mixed-QoS summary must carry a qos block"
        for qc in (QOS_LATENCY, QOS_BULK):
            assert qs[qc]["requests"] > 0, (qc, qs)
            assert qs[qc]["p99_ms"] is not None, (qc, qs)
            assert qs[qc]["queue_p99_ms"] is not None, (qc, qs)
        print(f"[stream_bench] mixed-QoS smoke: latency p99 "
              f"{qs[QOS_LATENCY]['p99_ms']:.1f} ms vs bulk "
              f"{qs[QOS_BULK]['p99_ms']:.1f} ms "
              f"({qrow['decisions']} decisions, per-class block present)")

        # Anomaly workload (ISSUE 10): margin-mode decisions must
        # bit-equal the digital oracle's margins at nominal.
        ageo = ANOMALY_SMOKE
        acfg, ata, abool = make_anomaly_model(jax.random.PRNGKey(1),
                                              **ageo)
        ascfg = StreamConfig(window=ageo["window"], hop=ageo["hop"],
                             vote=3, decision="margin", margin_class=1,
                             margin_threshold=0.0)
        check_margin_bit_exact(acfg, ata, abool, ascfg,
                               sensor_streams(2, 32, ageo["n_sensors"]))
        print("[stream_bench] bit-exactness: streamed anomaly margins == "
              "digital oracle (margin mode)")
        arow = run_anomaly_cell(acfg, ata, abool, ageo, frames=32,
                                sessions=2, backend=args.backend,
                                packed=args.packed,
                                n_replicas=args.replicas)
        assert arow["decisions"] > 0
        print(f"[stream_bench] anomaly smoke: {arow['decisions']} "
              f"margin decisions, alert fraction "
              f"{arow['alert_fraction']:.2f}")

        if args.smoke_out:
            with open(args.smoke_out, "w") as f:
                json.dump({"smoke": True, "devices": n_dev,
                           "mesh_bit_exact_checked": mesh_checked,
                           "lazy_tuning": lazy_info, "cell": row,
                           "mixed_qos": qrow, "anomaly": arow,
                           "margin_bit_exact": True},
                          f, indent=2, default=str)
            print(f"[stream_bench] wrote smoke report to {args.smoke_out}")
        if not ok:
            raise SystemExit(1)
        return None

    # ------------------------------------------------- sessions x hop rate
    sweep = []
    for sessions in (1, 4, 16):
        for hop in (2, 4, 8):
            row, _ = run_cell(cfg, ta, booleanizer, sessions=sessions,
                              hop=hop, window=window, frames=args.frames,
                              repeats=args.repeats, backend=args.backend,
                              packed=args.packed,
                              n_replicas=args.replicas)
            sweep.append(row)
            print(f"[stream_bench]   S={sessions:>2} hop={hop}: "
                  f"{row['decisions_per_s_wall']:.0f} decisions/s "
                  f"({row['decisions']} windows, mean batch "
                  f"{row['mean_batch']:.1f}, padding "
                  f"{100 * row['padding_overhead']:.0f}%)")

    # ------------------------------------------- sync/async headline pair
    sync_row, async_row = run_pair(cfg, ta, booleanizer, sessions=8, hop=4,
                                   window=window, frames=args.frames,
                                   repeats=args.repeats,
                                   backend=args.backend,
                                   packed=args.packed,
                                   n_replicas=args.replicas)
    speedup = (async_row["decisions_per_s_wall"]
               / sync_row["decisions_per_s_wall"])
    print(f"[stream_bench]   async S=8 hop=4: "
          f"{async_row['decisions_per_s_wall']:.0f} decisions/s = "
          f"{speedup:.2f}x sync "
          f"({sync_row['decisions_per_s_wall']:.0f}), overlap "
          f"{100 * async_row['overlap_fraction']:.0f}% vs "
          f"{100 * sync_row['overlap_fraction']:.0f}%")

    # ------------------------------------------------------- sharded rows
    sharded = []
    for n_replicas, use_async, routing in ((4, False, "round_robin"),
                                           (4, True, "round_robin"),
                                           (8, False, "ensemble")):
        if n_replicas > n_dev:
            continue
        mesh = make_replica_mesh(n_replicas, 1)
        row, eng = run_cell(cfg, ta, booleanizer, sessions=8, hop=4,
                            window=window, frames=args.frames,
                            repeats=args.repeats, backend=args.backend,
                            packed=args.packed, mesh=mesh,
                            n_replicas=n_replicas, routing=routing,
                            engine_cls=(AsyncServeEngine if use_async
                                        else ServeEngine))
        check_bit_exact(cfg, ta, booleanizer, scfg, streams2,
                        engine_cls=(AsyncServeEngine if use_async
                                    else ServeEngine),
                        mesh=mesh, n_replicas=n_replicas, routing=routing,
                        packed=args.packed)
        sharded.append(row)
        print(f"[stream_bench]   sharded R={n_replicas} "
              f"({routing}{', async' if use_async else ''}): "
              f"{row['decisions_per_s_wall']:.0f} decisions/s on "
              f"{row['backend']}, mesh {row['mesh']} (bit-exact)")
    if not sharded:
        print(f"[stream_bench]   sharded rows skipped: {n_dev} device(s) "
              "visible (pass --host-devices 8)")

    # ------------------------------------------ mixed-QoS heavy traffic
    # The standing scenario behind QoS classes: bulk saturates the
    # engine under a long batching deadline, latency rides ~1 ms cuts
    # on the SAME engine.  The acceptance bar is the ordering itself.
    qos_row = run_mixed_qos_cell(cfg, ta, booleanizer, window=window,
                                 hop=4, frames=args.frames,
                                 backend=args.backend, packed=args.packed,
                                 n_replicas=args.replicas)
    qs = qos_row["qos"]
    assert qs[QOS_LATENCY]["p99_ms"] < qs[QOS_BULK]["p99_ms"], qs
    assert (qs[QOS_LATENCY]["queue_p99_ms"]
            < qs[QOS_BULK]["queue_p99_ms"]), qs
    print(f"[stream_bench]   mixed QoS (2 latency + 2 bulk, burst x"
          f"{qos_row['bulk_burst']}): latency p99 "
          f"{qs[QOS_LATENCY]['p99_ms']:.1f} ms < bulk p99 "
          f"{qs[QOS_BULK]['p99_ms']:.1f} ms on one engine "
          f"({qos_row['decisions']} decisions, queue p99 "
          f"{qs[QOS_LATENCY]['queue_p99_ms']:.1f} vs "
          f"{qs[QOS_BULK]['queue_p99_ms']:.1f} ms)")

    # -------------------------------------------------- anomaly workload
    ageo = ANOMALY_FULL
    acfg, ata, abool = make_anomaly_model(jax.random.PRNGKey(1), **ageo)
    ascfg = StreamConfig(window=ageo["window"], hop=ageo["hop"], vote=3,
                         decision="margin", margin_class=1,
                         margin_threshold=0.0)
    check_margin_bit_exact(acfg, ata, abool, ascfg,
                           sensor_streams(2, 64, ageo["n_sensors"]))
    anomaly_row = run_anomaly_cell(acfg, ata, abool, ageo,
                                   frames=args.frames,
                                   backend=args.backend,
                                   packed=args.packed,
                                   n_replicas=args.replicas)
    anomaly_row["margin_bit_exact"] = True
    print(f"[stream_bench]   anomaly (margin mode, latency class): "
          f"{anomaly_row['decisions']} decisions at "
          f"{anomaly_row['decisions_per_s_wall']:.0f}/s, alert fraction "
          f"{anomaly_row['alert_fraction']:.2f} (margins bit-exact vs "
          "digital oracle)")

    report = {
        "model": {"n_clauses": cfg.n_clauses, "n_literals": cfg.n_literals,
                  "n_classes": cfg.n_classes},
        "stream": {"window": window, "vote": 5, "n_mels": geo["n_mels"],
                   "bits": geo["bits"],
                   "frames_per_session": args.frames},
        "backend": jax.default_backend(),
        "devices": n_dev,
        "host_cpus": os.cpu_count(),
        "repeats": args.repeats,
        "lazy_tuning": lazy_info,
        "sweep": sweep,
        "sync_s8_h4": sync_row,
        "async_s8_h4": async_row,
        "async_speedup_vs_sync_s8_h4": speedup,
        "sharded": sharded,
        "mixed_qos": qos_row,
        "anomaly": anomaly_row,
        "note": ("interpret-mode Pallas on CPU: decisions/s are simulator "
                 "figures; the transferable quantities are the relative "
                 "sweep shape, the cross-session batching (mean_batch), "
                 "and bytes/dispatch"),
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, default=str)
    print(f"[stream_bench] wrote {args.out}")
    return report


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
