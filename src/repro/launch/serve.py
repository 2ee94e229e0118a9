"""IMBUE inference serving CLI: a thin front-end over ``repro.serve``.

Trains (or random-initializes) a TM, programs a replica pool of
crossbars, then streams individual requests through the dynamic-batching
engine — the deployment model of the paper (program once, read forever),
scaled out to R chips.  Reports the engine's latency/throughput metrics
alongside the crossbar's hardware figures of merit.

  PYTHONPATH=src python -m repro.launch.serve --requests 256 --replicas 4
  PYTHONPATH=src python -m repro.launch.serve --routing ensemble
  PYTHONPATH=src python -m repro.launch.serve --host-devices 8 \\
      --mesh 2x4 --replicas 8 --async-serve   # sharded + overlapped
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
from repro.core.tm import TMConfig
from repro.core.variations import VariationConfig
from repro.data.tm_datasets import synthetic_image_dataset
from repro.launch.mesh import parse_mesh_spec
from repro.serve import (AsyncServeEngine, BatcherConfig, EngineConfig,
                         ServeEngine)


def build_engine(args, cfg: TMConfig, ta: jax.Array) -> ServeEngine:
    vcfg = (VariationConfig.nominal() if args.nominal
            else VariationConfig())
    ecfg = EngineConfig(
        batcher=BatcherConfig.for_max_batch(
            args.batch, max_wait_s=args.max_wait_ms * 1e-3),
        routing=args.routing,
        backend=args.backend,
        packed=args.packed,
        max_in_flight=args.max_in_flight)
    mesh = parse_mesh_spec(args.mesh) if args.mesh else None
    cls = AsyncServeEngine if args.async_serve else ServeEngine
    return cls.from_ta_state(
        ta, cfg, n_replicas=args.replicas, key=jax.random.PRNGKey(3),
        vcfg=vcfg, ecfg=ecfg, mesh=mesh)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--batch", type=int, default=64,
                    help="max dynamic batch (largest kernel bucket)")
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--routing", default="round_robin",
                    choices=("round_robin", "least_loaded", "ensemble"))
    ap.add_argument("--backend", default=None,
                    choices=("analog-pallas-packed", "analog-pallas",
                             "analog-jnp"),
                    help="forward-backend preference (repro.api name); "
                         "capability selection may fall back loudly")
    ap.add_argument("--packed", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="uint32 packed literal wire format (default on; "
                         "--no-packed forces the dense uint8 datapath)")
    ap.add_argument("--mesh", default=None, metavar="RxB",
                    help="shard the replica pool over a device mesh, "
                         "e.g. '8' or '2x4' (replica x batch axes); the "
                         "[R, C, L] stack splits over 'replica' and one "
                         "fused ensemble dispatch spans every device")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force N host (CPU) devices before jax init "
                         "(XLA_FLAGS=--xla_force_host_platform_device_"
                         "count); lets --mesh run on a laptop/CI box")
    ap.add_argument("--async-serve", action="store_true",
                    help="AsyncServeEngine: double-buffer dispatches so "
                         "host batching overlaps device compute")
    ap.add_argument("--max-in-flight", type=int, default=2,
                    help="async depth: un-collected dispatches allowed")
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="snapshot the programmed pool here at startup "
                         "(digest-verified restore point for live "
                         "hot-swap rollback — repro.launch.retrain / "
                         "serve.swap); without it the engine serves "
                         "exactly as before, just without a rollback "
                         "point")
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--nominal", action="store_true",
                    help="disable D2D/C2C/CSA variation")
    ap.add_argument("--json", action="store_true",
                    help="dump the summary as JSON")
    args = ap.parse_args(argv)
    if args.batch % 8 or args.batch > 128:
        ap.error("--batch must be a multiple of 8, at most 128 "
                 "(Pallas batch-tile buckets)")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")

    cfg = TMConfig(n_classes=10, clauses_per_class=20, n_features=784,
                   n_states=127, threshold=15, specificity=5.0)
    xtr, ytr, xte, yte = synthetic_image_dataset(
        jax.random.PRNGKey(0), n_train=2000, n_test=2048)
    print(f"[serve] training TM ({cfg.n_ta} TA cells)...")
    ta = tm.init_ta_state(jax.random.PRNGKey(1), cfg)
    ta = tm_train.fit(ta, jax.random.PRNGKey(2), xtr, ytr, cfg,
                      epochs=args.epochs, batch_size=200, parallel=True)
    stats = tm.include_stats(ta, cfg)
    print(f"[serve] digital accuracy "
          f"{float(tm.accuracy(ta, xte, yte, cfg)):.3f}, "
          f"includes {stats['include_pct']:.2f}%")

    engine = build_engine(args, cfg, ta)
    bcfg = engine.batcher.cfg
    print(f"[serve] pool of {args.replicas} crossbars programmed "
          f"(pool version {engine.version}), "
          f"routing={args.routing}, backend={engine.backend.name}, "
          f"packed_io={engine.packed_io}")
    if args.checkpoint_dir:
        from repro.serve import snapshot_pool
        path = snapshot_pool(engine.pool, args.checkpoint_dir)
        print(f"[serve] pool v{engine.version} snapshot -> {path}")
    if engine.mesh is not None:
        print(f"[serve] pool sharded over mesh {dict(engine.mesh.shape)} "
              f"({jax.device_count()} devices visible); "
              f"async={'on' if args.async_serve else 'off'}")
    print(f"[serve] buckets {list(bcfg.bucket_sizes)} "
          f"({'tuned for ' + bcfg.tuned_for if bcfg.tuned_for else 'static'}"
          f"), kernel tiles "
          f"{engine.tuning.get('tiles') if engine.tuning else 'default'}")
    if engine.selection.fell_back:
        print(f"[serve] BACKEND FALLBACK: "
              f"{engine.selection.fallback_reason}")

    # Stream individual requests; pump as they queue (the engine cuts a
    # batch when a bucket fills or the oldest request times out).
    rng = np.random.default_rng(0)
    xte_np = np.asarray(xte, dtype=np.uint8)
    yte_np = np.asarray(yte).astype(int)
    idx = rng.integers(0, xte_np.shape[0], size=args.requests)
    for i in idx:
        engine.submit(xte_np[i])
        engine.pump()
    responses = engine.drain()

    correct = sum(int(r.pred == yte_np[i])
                  for r, i in zip(responses, idx))
    summary = engine.summary(includes=stats["includes"])
    summary["analog_accuracy"] = correct / len(responses)

    if args.json:
        print(json.dumps(summary, indent=2))
        return summary
    hw = summary["hardware"]
    print(f"[serve] {summary['requests']} requests in "
          f"{summary['batches']} batches (mean {summary['mean_batch']:.1f}"
          f"/batch, {100 * summary['padding_overhead']:.1f}% padding): "
          f"analog acc {summary['analog_accuracy']:.3f}")
    # throughput_rps is None (not a number) until the served span is
    # positive — a one-tick run has no meaningful rate.
    tput = summary["throughput_rps"]
    print(f"[serve] sim latency p50/p95/p99: {summary['p50_ms']:.1f}/"
          f"{summary['p95_ms']:.1f}/{summary['p99_ms']:.1f} ms; "
          f"{f'{tput:.0f}' if tput is not None else 'n/a'} inf/s "
          f"(CPU interp); replica rows {summary['replica_load_rows']}")
    print(f"[serve] overlap: {100 * summary['overlap_fraction']:.0f}% of "
          f"device time hidden behind host work "
          f"(pack {summary['host_pack_s'] * 1e3:.1f} ms, blocked wait "
          f"{summary['device_wait_s'] * 1e3:.1f} ms)")
    print(f"[serve] crossbar figures: {hw['latency_ns']:.0f} ns/datapoint, "
          f"{hw['energy_nj_per_dp']:.3f} nJ/datapoint, "
          f"{hw['top_j_inv']:.0f} TopJ^-1, pool "
          f"{hw['pool_throughput_dps']:.2e} dp/s")
    return summary


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
