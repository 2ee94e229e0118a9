"""Serving-engine benchmark: dynamic batching x replica-pool sweep.

Measures the simulator's serving throughput/latency across
(max-batch, replica-count) configurations and against the seed's
per-request serial path (one kernel dispatch per request — what
``launch/serve.py`` did before the engine existed).  Writes
``BENCH_serve.json`` next to the repo root.

ISSUE 3 additions: the default datapath is the **packed** uint32
literal wire (packed once per request at submit; see
``serve/batching.py``) with **measured** kernel tiles and bucket
ladders from the registry tuning table (``kernels/autotune.py``).  The
report carries an explicit before/after pair at the headline cell
(R=4, batch 64): ``before_unpacked_static`` re-measures the PR-2
configuration (dense uint8 wire, static buckets, default tiles) on the
same host, next to the packed+tuned ``sweep`` rows.  Each timed
configuration is run ``--repeats`` times and the best run is reported —
wall-clock on a shared CPU container is noisy and every positive
excursion is interference, not the engine.

Interpret-mode Pallas on CPU means absolute numbers are simulator
figures, not hardware ones; the hardware figures of merit are reported
separately by ``repro.serve.metrics.hardware_figures``.  The quantities
that transfer are the *relative* win of batching/tuning and the
bytes-moved-per-dispatch column, which is exactly the HBM/interconnect
traffic a real accelerator would carry.

ISSUE 4 additions: **async** rows (``AsyncServeEngine`` double-buffers
dispatches so host packing overlaps device compute; the headline pair is
sync vs async at R=4 batch=64 on the same host) and **sharded** rows
(the pool's ``[R, C, L]`` stack split over a ``replica`` device mesh;
needs >1 device — pass ``--host-devices 8`` to force CPU host devices
before jax initializes).  Sharded rows ride the GSPMD jnp backend by
capability (``CAP_SHARDED``); on forced CPU devices they measure
*mechanics*, not a speedup — the fake devices share one physical socket.

ISSUE 6 additions: a **capacity head-to-head** at equal device budget —
the same 8-class workload served by a replicated per-class analog pool
(R=4 routed chips) vs ONE coalesced shared clause pool with half the
clause rows and the weighted digital tail (``run_capacity_pair``, runs
interleaved like the sync/async pair).  Rows carry ``host_cpus`` and
their total TA-cell budgets; the smoke adds a coalesced leg that must
select a ``coalesced*`` backend with zero fallbacks.

ISSUE 9 additions: the default datapath is now **plane-packed** — the
programmed conductance stack rides as a uint32 LRS/HRS index bitplane
(+ a per-cell deviation plane off-nominal) and serving selects the
``*-packed2`` backends.  The report adds a second before/after pair at
the headline cell: ``planes_before_r4_b64`` (packed wire, dense
resident planes, backend ``analog-pallas-packed``) vs the plane-packed
default, with the ``resident_bytes_per_dispatch`` drop — the resident
HBM traffic a real accelerator would stream per dispatch.

  PYTHONPATH=src python -m benchmarks.serve_bench [--requests 192]
  PYTHONPATH=src python -m benchmarks.serve_bench --host-devices 8
  PYTHONPATH=src python -m benchmarks.serve_bench --smoke   # CI, no JSON
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
from repro.core.tm import TMConfig
from repro.core.variations import VariationConfig
from repro.launch.mesh import make_replica_mesh
from repro.serve import (AsyncServeEngine, BatcherConfig, EngineConfig,
                         ServeEngine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_model(key):
    """Small trained-free TM (sparse random includes) — the bench measures
    serving mechanics, not accuracy."""
    cfg = TMConfig(n_classes=4, clauses_per_class=8, n_features=64,
                   n_states=100)
    inc = jax.random.bernoulli(key, 0.1, (cfg.n_clauses, cfg.n_literals))
    ta = jnp.where(inc, cfg.n_states + 1, cfg.n_states).astype(
        cfg.state_dtype)
    return cfg, ta


def make_engine(cfg, ta, *, max_batch, n_replicas, routing="round_robin",
                backend=None, packed=True, pack_planes=True,
                static_buckets=False, engine_cls=ServeEngine, mesh=None):
    # CSA offset off so serving stays on the fused Pallas kernel path
    # (capability selection would reject the pallas backends otherwise;
    # see repro.api.select_backend).
    if static_buckets:
        from repro.serve.batching import STATIC_BUCKETS
        sizes = tuple(b for b in STATIC_BUCKETS if b < max_batch)
        batcher = BatcherConfig(max_batch=max_batch,
                                bucket_sizes=sizes + (max_batch,))
    else:
        batcher = BatcherConfig.for_max_batch(max_batch)
    return engine_cls.from_ta_state(
        ta, cfg, n_replicas=n_replicas, key=jax.random.PRNGKey(3),
        vcfg=VariationConfig(csa_offset=False),
        ecfg=EngineConfig(batcher=batcher, routing=routing,
                          backend=backend, packed=packed,
                          pack_planes=pack_planes),
        mesh=mesh)


def run_batched(cfg, ta, xs, *, max_batch, n_replicas, routing,
                backend=None, packed=True, static_buckets=False,
                repeats=3, engine_cls=ServeEngine, mesh=None):
    """Submit everything, then drain: batches cut at ``max_batch``.

    Best of ``repeats`` timed runs (one warmed engine) — see module
    docstring for why best-of is the right de-noising on a shared host.
    """
    engine = make_engine(cfg, ta, max_batch=max_batch,
                         n_replicas=n_replicas, routing=routing,
                         backend=backend, packed=packed,
                         static_buckets=static_buckets,
                         engine_cls=engine_cls, mesh=mesh)
    engine.submit_many([xs[0]] * max_batch)   # warm the kernel cache
    engine.drain()
    best_wall, best_summary = float("inf"), None
    for _ in range(max(1, repeats)):
        engine.metrics = type(engine.metrics)()
        t0 = time.monotonic()
        engine.submit_many(list(xs))
        engine.drain()
        wall = time.monotonic() - t0
        if wall < best_wall:
            best_wall, best_summary = wall, engine.summary()
    out = best_summary
    out["wall_s"] = best_wall
    out["wall_throughput_rps"] = len(xs) / best_wall
    out["max_batch"] = max_batch
    out["async"] = engine_cls is AsyncServeEngine
    return out


def run_async_pair(cfg, ta, xs, *, max_batch, n_replicas, repeats=3,
                   backend=None, packed=True, mesh=None):
    """Sync vs async on the SAME workload, runs interleaved.

    Wall-clock on a shared host drifts over minutes; alternating the two
    engines run-for-run makes the sync/async ratio robust to that drift
    in a way two back-to-back sweeps are not.  Best-of per engine."""
    engines = {}
    for is_async in (False, True):
        eng = make_engine(cfg, ta, max_batch=max_batch,
                          n_replicas=n_replicas, routing="round_robin",
                          backend=backend, packed=packed, mesh=mesh,
                          engine_cls=(AsyncServeEngine if is_async
                                      else ServeEngine))
        eng.submit_many([xs[0]] * max_batch)      # warm the kernel cache
        eng.drain()
        engines[is_async] = eng
    best = {False: (float("inf"), None), True: (float("inf"), None)}
    for _ in range(max(1, repeats)):
        for is_async in (False, True):
            eng = engines[is_async]
            eng.metrics = type(eng.metrics)()
            t0 = time.monotonic()
            eng.submit_many(list(xs))
            eng.drain()
            wall = time.monotonic() - t0
            if wall < best[is_async][0]:
                best[is_async] = (wall, eng.summary())
    rows = {}
    for is_async in (False, True):
        wall, summary = best[is_async]
        summary["wall_s"] = wall
        summary["wall_throughput_rps"] = len(xs) / wall
        summary["max_batch"] = max_batch
        summary["async"] = is_async
        rows[is_async] = summary
    return rows[False], rows[True]


def run_planes_pair(cfg, ta, xs, *, max_batch, n_replicas, repeats=3,
                    packed=True):
    """Dense resident planes vs plane-packed at the headline cell,
    runs interleaved (ISSUE 9).

    Both engines use the packed literal wire and measured tuning; only
    the resident format differs — ``pack_planes=False`` serves on
    ``analog-pallas-packed`` (two dense f32 conductance/leak planes per
    dispatch), the default serves on ``analog-pallas-packed2`` (uint32
    index bitplane + deviation plane).  The transferable number is the
    ``resident_bytes_per_dispatch`` drop."""
    engines = {}
    for planes in (False, True):
        eng = make_engine(cfg, ta, max_batch=max_batch,
                          n_replicas=n_replicas, routing="round_robin",
                          packed=packed, pack_planes=planes)
        eng.submit_many([xs[0]] * max_batch)      # warm the kernel cache
        eng.drain()
        engines[planes] = eng
    best = {False: (float("inf"), None), True: (float("inf"), None)}
    for _ in range(max(1, repeats)):
        for planes in (False, True):
            eng = engines[planes]
            eng.metrics = type(eng.metrics)()
            t0 = time.monotonic()
            eng.submit_many(list(xs))
            eng.drain()
            wall = time.monotonic() - t0
            if wall < best[planes][0]:
                best[planes] = (wall, eng.summary())
    rows = {}
    for planes in (False, True):
        wall, summary = best[planes]
        summary["wall_s"] = wall
        summary["wall_throughput_rps"] = len(xs) / wall
        summary["max_batch"] = max_batch
        rows[planes] = summary
    return rows[False], rows[True]


def make_capacity_models(key):
    """The equal-device-budget head-to-head pair: one 8-class workload,
    two architectures.

    * **analog**: the per-class TM (8 classes x 8 clauses = 64 clause
      rows) replicated across R routed chips — capacity scales by
      adding crossbars.
    * **coalesced**: ONE shared pool with HALF the clause rows (the
      coalesced capacity lever: clauses are shared between classes, so
      the same accuracy needs ~2x fewer TA cells — paper §V / the CoTM
      result) plus the weighted digital tail, on a single chip.

    Both serve the same requests on the same host devices; weights are
    random (the bench measures serving mechanics, not accuracy)."""
    from repro.core.coalesced import CoalescedConfig
    k1, k2, k3 = jax.random.split(key, 3)
    acfg = TMConfig(n_classes=8, clauses_per_class=8, n_features=64,
                    n_states=100)
    inc = jax.random.bernoulli(k1, 0.1, (acfg.n_clauses, acfg.n_literals))
    ta = jnp.where(inc, acfg.n_states + 1, acfg.n_states).astype(
        acfg.state_dtype)
    ccfg = CoalescedConfig(n_classes=8, n_clauses=acfg.n_clauses // 2,
                           n_features=64, n_states=100)
    cinc = jax.random.bernoulli(k2, 0.1, (ccfg.n_clauses, ccfg.n_literals))
    cta = jnp.where(cinc, ccfg.n_states + 1, ccfg.n_states).astype(
        ccfg.state_dtype)
    w = jax.random.randint(k3, (ccfg.n_clauses, ccfg.n_classes),
                           -ccfg.max_weight, ccfg.max_weight + 1, jnp.int32)
    return acfg, ta, ccfg, cta, w


def run_capacity_pair(xs, *, max_batch, n_replicas=4, repeats=3,
                      packed=True):
    """Replicated analog vs coalesced shared pool, runs interleaved.

    Same de-noising argument as :func:`run_async_pair`: alternating the
    two engines run-for-run keeps the ratio robust to host drift.  Each
    row carries ``host_cpus`` and its total TA-cell budget so the
    energy/capacity story is auditable next to the throughput."""
    acfg, ta, ccfg, cta, w = make_capacity_models(jax.random.PRNGKey(7))
    ecfg = EngineConfig(batcher=BatcherConfig.for_max_batch(max_batch),
                       routing="round_robin", packed=packed)
    engines = {
        "analog": ServeEngine.from_ta_state(
            ta, acfg, n_replicas=n_replicas, key=jax.random.PRNGKey(3),
            vcfg=VariationConfig(csa_offset=False), ecfg=ecfg),
        "coalesced": ServeEngine.from_coalesced(
            cta, w, ccfg, key=jax.random.PRNGKey(3), ecfg=ecfg),
    }
    for eng in engines.values():
        eng.submit_many([xs[0]] * max_batch)   # warm the kernel cache
        eng.drain()
    best = {name: (float("inf"), None) for name in engines}
    for _ in range(max(1, repeats)):
        for name, eng in engines.items():      # interleaved
            eng.metrics = type(eng.metrics)()
            t0 = time.monotonic()
            eng.submit_many(list(xs))
            eng.drain()
            wall = time.monotonic() - t0
            if wall < best[name][0]:
                best[name] = (wall, eng.summary())
    rows = {}
    for name, (wall, summary) in best.items():
        summary["wall_s"] = wall
        summary["wall_throughput_rps"] = len(xs) / wall
        summary["max_batch"] = max_batch
        summary["host_cpus"] = os.cpu_count()
        rows[name] = summary
    rows["analog"]["n_ta_total"] = int(acfg.n_ta) * n_replicas
    rows["coalesced"]["n_ta_total"] = int(ccfg.n_ta)
    return rows["analog"], rows["coalesced"]


def run_serial(cfg, ta, xs, *, n_replicas=1, backend=None, packed=True,
               repeats=3):
    """The seed's per-request path: one dispatch per request."""
    engine = make_engine(cfg, ta, max_batch=8, n_replicas=n_replicas,
                         backend=backend, packed=packed)
    engine.submit(xs[0])
    engine.drain()                             # warm the bucket-8 kernel
    best_wall, best_summary = float("inf"), None
    for _ in range(max(1, repeats)):
        engine.metrics = type(engine.metrics)()
        t0 = time.monotonic()
        for x in xs:
            engine.submit(x)
            engine.drain()                     # force: batch of 1, now
        wall = time.monotonic() - t0
        if wall < best_wall:
            best_wall, best_summary = wall, engine.summary()
    out = best_summary
    out["wall_s"] = best_wall
    out["wall_throughput_rps"] = len(xs) / best_wall
    out["max_batch"] = 1
    return out


def run_before_unpacked_static(cfg, ta, xs, *, repeats=3):
    """The PR-2 configuration on this host: dense uint8 wire, static
    bucket ladder, default (untuned) kernel tiles — the "before" half of
    the headline before/after pair."""
    saved = api.tuning_snapshot()
    api.clear_tuning()
    try:
        return run_batched(cfg, ta, xs, max_batch=64, n_replicas=4,
                           routing="round_robin", packed=False,
                           static_buckets=True, repeats=repeats)
    finally:
        api.restore_tuning(saved)


def run_degraded(cfg, ta, xs, *, max_batch, n_replicas=4, packed=True,
                 repeats=3):
    """ISSUE 8 leg: ensemble throughput with one replica injured and
    quarantined, next to the same engine's healthy figure.

    Builds an R-replica ensemble engine with health probing enabled
    (d2d-only noise so probe scores are deterministic), times a healthy
    pass, injects stuck-at faults into replica 1, probes (which
    quarantines the chip), times a degraded pass over the healthy
    majority, then auto-repairs via ``RepairPolicy`` and re-probes.  The
    interesting number is ``degraded_vs_healthy``: the ensemble keeps
    serving while a chip is down, paying only the lost replica's share
    of vote diversity — dispatch shape (and hence throughput) is
    unchanged because the vote mask is a traced argument."""
    from repro.core.variations import FaultConfig
    from repro.serve import HealthConfig, RepairConfig, RepairPolicy
    ecfg = EngineConfig(batcher=BatcherConfig.for_max_batch(max_batch),
                        routing="ensemble", packed=packed,
                        health=HealthConfig(n_probes=64, seed=5))
    engine = ServeEngine.from_ta_state(
        ta, cfg, n_replicas=n_replicas, key=jax.random.PRNGKey(3),
        vcfg=VariationConfig(c2c=False, csa_offset=False), ecfg=ecfg)
    engine.submit_many([xs[0]] * max_batch)    # warm the kernel cache
    engine.drain()

    def timed_pass():
        best = float("inf")
        for _ in range(max(1, repeats)):
            engine.metrics = type(engine.metrics)()
            t0 = time.monotonic()
            engine.submit_many(list(xs))
            engine.drain()
            best = min(best, time.monotonic() - t0)
        return len(xs) / best

    healthy_rps = timed_pass()
    baseline_health = engine.probe()
    engine.inject_faults(
        jax.random.PRNGKey(99),
        FaultConfig(stuck_lrs_rate=0.15, stuck_hrs_rate=0.15),
        replicas=[1])
    injured_health = engine.probe()            # quarantines replica 1
    quarantined = sorted(engine.quarantined)
    degraded_rps = timed_pass()                # healthy-majority serving
    tick = RepairPolicy(engine, RepairConfig()).check()
    row = engine.summary()
    row.update({
        "max_batch": max_batch,
        "healthy_rps": healthy_rps,
        "degraded_rps": degraded_rps,
        "degraded_vs_healthy": degraded_rps / healthy_rps,
        "baseline_health": baseline_health,
        "injured_health": injured_health,
        "quarantined_during_degraded": quarantined,
        "repairs": tick["repairs"],
        "post_repair_health": tick["health"],
        "recovered": not engine.quarantined,
    })
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=192,
                    help="requests per batched configuration")
    ap.add_argument("--serial-requests", type=int, default=48,
                    help="requests for the serial baseline (slow path)")
    ap.add_argument("--backend", default=None,
                    choices=("analog-pallas-packed2",
                             "analog-pallas-packed", "analog-pallas",
                             "analog-jnp"),
                    help="forward-backend preference (repro.api name)")
    ap.add_argument("--packed", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="uint32 literal wire format (default on)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed runs per configuration (best reported)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: one tiny sweep cell; the committed "
                         "baseline JSON is never touched")
    ap.add_argument("--smoke-out", default=None,
                    help="write the smoke report JSON here (CI uploads "
                         "it as a workflow artifact); default: no write")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force N CPU host devices before jax init so "
                         "the sharded rows run (XLA_FLAGS=--xla_force_"
                         "host_platform_device_count)")
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_serve.json"))
    args = ap.parse_args(argv)
    if args.smoke:
        # Exercise the serve hot path (batched + ensemble dispatch through
        # the capability-selected backend) without the full sweep and
        # WITHOUT touching the committed BENCH_serve.json baseline.
        args.requests = min(args.requests, 64)
        args.serial_requests = min(args.serial_requests, 8)
        args.repeats = 1

    cfg, ta = make_model(jax.random.PRNGKey(0))
    xs = np.asarray(jax.random.bernoulli(
        jax.random.PRNGKey(1), 0.4,
        (args.requests, cfg.n_features))).astype(np.uint8)

    print("[serve_bench] serial baseline (per-request dispatch)...")
    serial = run_serial(cfg, ta, xs[:args.serial_requests],
                        backend=args.backend, packed=args.packed,
                        repeats=args.repeats)
    print(f"[serve_bench]   serial: "
          f"{serial['wall_throughput_rps']:.1f} req/s")

    sweep = []
    grid = (((4, 64),) if args.smoke
            else tuple((r, b) for r in (1, 2, 4) for b in (8, 32, 64)))
    for n_replicas, max_batch in grid:
        row = run_batched(cfg, ta, xs, max_batch=max_batch,
                          n_replicas=n_replicas,
                          routing="round_robin", backend=args.backend,
                          packed=args.packed, repeats=args.repeats)
        row["speedup_vs_serial"] = (row["wall_throughput_rps"]
                                    / serial["wall_throughput_rps"])
        sweep.append(row)
        print(f"[serve_bench]   R={n_replicas} batch={max_batch}: "
              f"{row['wall_throughput_rps']:.1f} req/s "
              f"({row['speedup_vs_serial']:.1f}x serial), "
              f"p99 {row['p99_ms']:.1f} ms [{row['backend']}, "
              f"{row['bytes_per_dispatch']:.0f} B/dispatch, "
              f"buckets {row['bucket_sizes']}]")
    ens = run_batched(cfg, ta, xs, max_batch=64, n_replicas=4,
                      routing="ensemble", backend=args.backend,
                      packed=args.packed, repeats=args.repeats)
    ens["speedup_vs_serial"] = (ens["wall_throughput_rps"]
                                / serial["wall_throughput_rps"])
    print(f"[serve_bench]   ensemble R=4 batch=64: "
          f"{ens['wall_throughput_rps']:.1f} req/s")

    # Async overlap at the headline cell: identical config to the sync
    # R=4 batch=64 sweep row, AsyncServeEngine dispatch schedule; the
    # two engines are timed interleaved so host drift can't fake a win.
    sync_row, async_row = run_async_pair(
        cfg, ta, xs, max_batch=64, n_replicas=4, backend=args.backend,
        packed=args.packed, repeats=args.repeats)
    for row in (sync_row, async_row):
        row["speedup_vs_serial"] = (row["wall_throughput_rps"]
                                    / serial["wall_throughput_rps"])
    async_speedup = (async_row["wall_throughput_rps"]
                     / sync_row["wall_throughput_rps"])
    print(f"[serve_bench]   async R=4 batch=64: "
          f"{async_row['wall_throughput_rps']:.1f} req/s = "
          f"{async_speedup:.2f}x sync "
          f"({sync_row['wall_throughput_rps']:.1f} req/s paired), "
          f"overlap {100 * async_row['overlap_fraction']:.0f}%")

    # Plane-packed resident format at the headline cell (ISSUE 9):
    # dense f32 conductance planes vs the uint32 index bitplane, runs
    # interleaved; the resident-bytes column is exact, the wall-clock
    # is interpret-mode color.
    planes_before, planes_after = run_planes_pair(
        cfg, ta, xs, max_batch=64, n_replicas=4, packed=args.packed,
        repeats=args.repeats)
    resident_ratio = (
        planes_after["resident_bytes_per_dispatch"]
        / planes_before["resident_bytes_per_dispatch"]
        if planes_before["resident_bytes_per_dispatch"] else None)
    print(f"[serve_bench]   planes R=4 batch=64: "
          f"{planes_after['backend']} resident "
          f"{planes_before['resident_bytes_per_dispatch']:.0f} -> "
          f"{planes_after['resident_bytes_per_dispatch']:.0f} B/dispatch "
          f"({resident_ratio:.4f}x), "
          f"{planes_after['wall_throughput_rps']:.1f} vs "
          f"{planes_before['wall_throughput_rps']:.1f} req/s paired")

    # Capacity head-to-head (ISSUE 6): replicated analog vs one
    # coalesced shared pool at equal device budget, runs interleaved —
    # the same 8-class workload served by R routed per-class chips vs a
    # single half-size shared clause pool with the weighted tail.
    cap_analog, cap_coalesced = run_capacity_pair(
        xs, max_batch=64, n_replicas=4, packed=args.packed,
        repeats=args.repeats)
    cap_ratio = (cap_coalesced["wall_throughput_rps"]
                 / cap_analog["wall_throughput_rps"])
    print(f"[serve_bench]   capacity head-to-head batch=64: coalesced "
          f"{cap_coalesced['wall_throughput_rps']:.1f} req/s on "
          f"{cap_coalesced['backend']} "
          f"({cap_coalesced['n_ta_total']} TA cells) vs analog R=4 "
          f"{cap_analog['wall_throughput_rps']:.1f} req/s on "
          f"{cap_analog['backend']} ({cap_analog['n_ta_total']} TA "
          f"cells) = {cap_ratio:.2f}x")

    # Sharded rows: the pool split over a replica device mesh.  On
    # forced CPU host devices this measures mechanics (the jnp GSPMD
    # backend on fake devices sharing one socket), not a speedup.
    sharded = []
    n_dev = jax.device_count()
    for n_replicas, use_async, routing in (
            (4, False, "round_robin"), (4, True, "round_robin"),
            (8, True, "round_robin"), (8, False, "ensemble")):
        if n_replicas > n_dev or args.smoke:
            continue
        mesh = make_replica_mesh(n_replicas, 1)
        row = run_batched(cfg, ta, xs, max_batch=64,
                          n_replicas=n_replicas, routing=routing,
                          backend=args.backend, packed=args.packed,
                          repeats=args.repeats, mesh=mesh,
                          engine_cls=(AsyncServeEngine if use_async
                                      else ServeEngine))
        row["speedup_vs_serial"] = (row["wall_throughput_rps"]
                                    / serial["wall_throughput_rps"])
        sharded.append(row)
        print(f"[serve_bench]   sharded R={n_replicas} batch=64 "
              f"({routing}{', async' if use_async else ''}): "
              f"{row['wall_throughput_rps']:.1f} req/s on "
              f"{row['backend']}, mesh {row['mesh']}, overlap "
              f"{100 * row['overlap_fraction']:.0f}%")
    if not sharded and not args.smoke:
        print(f"[serve_bench]   sharded rows skipped: {n_dev} device(s) "
              "visible (pass --host-devices 8)")

    if args.smoke:
        # Degraded-serving leg (ISSUE 8): one replica injured, probed,
        # quarantined, served around, repaired — smoke-only so the
        # committed BENCH_serve.json schema is untouched.
        deg = run_degraded(cfg, ta, xs, max_batch=64, n_replicas=4,
                           packed=args.packed, repeats=args.repeats)
        print(f"[serve_bench]   degraded R=4 batch=64: "
              f"{deg['degraded_rps']:.1f} req/s with "
              f"{deg['quarantined_during_degraded']} quarantined = "
              f"{deg['degraded_vs_healthy']:.2f}x healthy "
              f"({deg['healthy_rps']:.1f} req/s), "
              f"recovered={deg['recovered']}")
        row = sweep[0]
        coalesced_ok = (
            cap_coalesced["backend"].startswith("coalesced")
            and cap_coalesced["forward_fallbacks"] == [])
        degraded_ok = (deg["quarantined_during_degraded"] == [1]
                       and deg["recovered"]
                       and deg["forward_fallbacks"] == [])
        planes_ok = (
            planes_after["backend"] == "analog-pallas-packed2"
            and planes_after["forward_fallbacks"] == []
            and planes_after["resident_bytes_per_dispatch"]
            < planes_before["resident_bytes_per_dispatch"])
        ok = (row["speedup_vs_serial"] >= 1.5
              and row["forward_fallbacks"] == []
              and async_row["forward_fallbacks"] == []
              and coalesced_ok
              and degraded_ok
              and planes_ok)
        print(f"[serve_bench] SMOKE {'PASS' if ok else 'FAIL'}: "
              f"{row['speedup_vs_serial']:.1f}x serial on "
              f"{row['backend']}, async {async_speedup:.2f}x sync, "
              f"coalesced leg on {cap_coalesced['backend']} "
              f"({'clean' if coalesced_ok else 'FALLBACK'}), "
              f"degraded leg {'healed' if degraded_ok else 'FAILED'}, "
              f"planes leg {resident_ratio:.4f}x resident "
              f"({'clean' if planes_ok else 'FAILED'}) "
              f"(committed baseline untouched)")
        if args.smoke_out:
            with open(args.smoke_out, "w") as f:
                json.dump({"smoke": True, "devices": n_dev,
                           "serial_baseline": serial, "sweep": sweep,
                           "ensemble": ens, "async_r4_b64": async_row,
                           "async_speedup_vs_sync": async_speedup,
                           "capacity_analog_r4_b64": cap_analog,
                           "capacity_coalesced_b64": cap_coalesced,
                           "capacity_coalesced_vs_analog": cap_ratio,
                           "degraded_ensemble_r4_b64": deg,
                           "planes_before_r4_b64": planes_before,
                           "planes_after_r4_b64": planes_after,
                           "resident_bytes_ratio_planes": resident_ratio},
                          f, indent=2, default=str)
            print(f"[serve_bench] wrote smoke report to {args.smoke_out}")
        if not ok:
            raise SystemExit(1)
        return None

    print("[serve_bench] before: PR-2 config (unpacked, static buckets, "
          "default tiles) on this host...")
    before = run_before_unpacked_static(cfg, ta, xs, repeats=args.repeats)
    print(f"[serve_bench]   before R=4 batch=64: "
          f"{before['wall_throughput_rps']:.1f} req/s "
          f"[{before['backend']}, "
          f"{before['bytes_per_dispatch']:.0f} B/dispatch]")

    # The previously committed headline (possibly from another host /
    # another PR): captured before this run overwrites the file, so the
    # regenerated JSON always carries its own point of comparison.
    prev_rps = None
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                prev = json.load(f)
            prev_rps = prev.get("headline_r4_b64_rps")
            if prev_rps is None:        # PR-2 schema: find the sweep row
                prev_rps = next(
                    (r["wall_throughput_rps"] for r in prev.get("sweep", [])
                     if r.get("max_batch") == 64
                     and r.get("n_replicas") == 4), None)
            prev_rps = float(prev_rps) if prev_rps is not None else None
        except (OSError, json.JSONDecodeError, TypeError, ValueError):
            prev_rps = None

    at64 = [r for r in sweep
            if r["max_batch"] == 64 and r["n_replicas"] == 1]
    speedup64 = at64[0]["speedup_vs_serial"]
    after = sync_row
    headline = (after["wall_throughput_rps"]
                / before["wall_throughput_rps"])
    report = {
        "model": {"n_clauses": cfg.n_clauses,
                  "n_literals": cfg.n_literals,
                  "n_classes": cfg.n_classes},
        "backend": jax.default_backend(),
        "devices": n_dev,
        "host_cpus": os.cpu_count(),
        "requests": args.requests,
        "repeats": args.repeats,
        "serial_baseline": serial,
        "sweep": sweep,
        "ensemble": ens,
        "sync_r4_b64_paired": sync_row,
        "async_r4_b64": async_row,
        "async_speedup_vs_sync_r4_b64": async_speedup,
        "async_overlap_fraction": async_row["overlap_fraction"],
        "sync_overlap_fraction": sync_row["overlap_fraction"],
        "sharded": sharded,
        "capacity_analog_r4_b64": cap_analog,
        "capacity_coalesced_b64": cap_coalesced,
        "capacity_coalesced_vs_analog": cap_ratio,
        "before_unpacked_static": before,
        "speedup_batch64_vs_serial": speedup64,
        "headline_r4_b64_rps": after["wall_throughput_rps"],
        "headline_speedup_vs_before": headline,
        "previous_committed_r4_b64_rps": prev_rps,
        "headline_speedup_vs_previous_committed": (
            after["wall_throughput_rps"] / prev_rps if prev_rps else None),
        # Cross-commit throughput ratios compare different hosts/device
        # configs (e.g. --host-devices 8 adds fake-device overhead the
        # single-device baseline never paid); same-run pairs above are
        # the apples-to-apples numbers.
        "previous_committed_note": (
            "previous baseline may predate --host-devices forcing or come "
            f"from a larger host; this run saw {n_dev} device(s) on "
            f"{os.cpu_count()} CPU core(s)"),
        "bytes_per_dispatch_before": before["bytes_per_dispatch"],
        "bytes_per_dispatch_after": after["bytes_per_dispatch"],
        # ISSUE 9 pair: dense f32 resident planes vs the plane-packed
        # index bitplane at the same cell, runs interleaved.
        "planes_before_r4_b64": planes_before,
        "planes_after_r4_b64": planes_after,
        "resident_bytes_per_dispatch_before": (
            planes_before["resident_bytes_per_dispatch"]),
        "resident_bytes_per_dispatch_after": (
            planes_after["resident_bytes_per_dispatch"]),
        "resident_bytes_ratio_planes": resident_ratio,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, default=str)
    print(f"[serve_bench] wrote {args.out}")
    print(f"[serve_bench] dynamic batching at 64: "
          f"{speedup64:.1f}x the serial path "
          f"({'PASS' if speedup64 >= 1.5 else 'FAIL'} >= 1.5x)")
    print(f"[serve_bench] headline R=4 batch=64: "
          f"{after['wall_throughput_rps']:.1f} req/s = "
          f"{headline:.2f}x the same-host before-config; operand "
          f"bytes/dispatch {before['bytes_per_dispatch']:.0f} -> "
          f"{after['bytes_per_dispatch']:.0f}")
    print(f"[serve_bench] plane-packed resident R=4 batch=64: "
          f"{report['resident_bytes_per_dispatch_before']:.0f} -> "
          f"{report['resident_bytes_per_dispatch_after']:.0f} B/dispatch "
          f"({'PASS' if resident_ratio and resident_ratio < 1.0 else 'FAIL'}"
          f" < 1.0x)")
    print(f"[serve_bench] async overlap at R=4 batch=64: "
          f"{async_speedup:.2f}x the synchronous packed baseline "
          f"({'PASS' if async_speedup >= 1.0 else 'FAIL'} >= 1.0x), "
          f"overlap {100 * async_row['overlap_fraction']:.0f}% vs "
          f"{100 * sync_row['overlap_fraction']:.0f}% sync")
    if prev_rps:
        ratio = after["wall_throughput_rps"] / prev_rps
        print(f"[serve_bench] vs previously committed baseline "
              f"({prev_rps:.1f} req/s): {ratio:.2f}x "
              f"({'PASS' if ratio >= 1.0 else 'FAIL'} >= 1.0x, "
              f"no regression)")
    return report


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
