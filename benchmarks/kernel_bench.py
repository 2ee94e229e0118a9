"""Kernel micro-benchmarks, analytic TPU roofline, and the measured
per-backend autotuning sweep.

On this CPU container the Pallas kernels execute in interpret mode, so
wall-times are NOT TPU numbers; we report them for regression tracking
and derive the *analytic* kernel roofline from the block configuration
(VMEM footprint, MXU-aligned dims, arithmetic intensity) — the same
numbers the §Perf log iterates on.

As a CLI this module drives ``repro.kernels.autotune``: it measures
(bt, ct, kt) tile and bucket-size latency for every registered
fused-kernel backend and regenerates the committed tuning table
(``src/repro/kernels/tuning_table.json``) that ``ServeEngine`` and
``BatcherConfig.for_max_batch`` consume via the capability registry.

ISSUE 9 additions: the plane-packed backends (``analog-pallas-packed2``
/ ``coalesced-pallas-packed2``) join the sweep under their own
(backend, shape-bucket) keys, and the run reports a **before/after
pair** per shape bucket — the packed backend's best tile latency and
resident-model bytes per dispatch next to the plane-packed backend's.
Full mode writes the pair table to ``BENCH_kernel.json`` at the repo
root; the resident-bytes column is analytic (exact from the shapes), so
it transfers to hardware even though the latencies are interpret-mode.

  PYTHONPATH=src python -m benchmarks.kernel_bench            # full sweep,
                                                              # writes table
  PYTHONPATH=src python -m benchmarks.kernel_bench --smoke    # CI: tiny
                                                              # sweep, no
                                                              # write
"""

from __future__ import annotations

import argparse
import json
import math
import os
import time

import jax
import jax.numpy as jnp

from repro.core import imbue
from repro.core.tm import TMConfig, include_mask, init_ta_state, literals
from repro.core.variations import VariationConfig
from repro.kernels import autotune, ops
from repro.roofline.analysis import TARGET_KIND, peaks


def roofline_kind() -> str:
    """The chip whose peaks the analytic roofline uses: the attached TPU
    (an unlisted one raises in ``peaks``), else the dry-run's target."""
    dev = jax.devices()[0]
    return dev.device_kind if dev.platform == "tpu" else TARGET_KIND


def kernel_roofline(b, c, l, *, bt, ct, kt, dtype_bytes=4,
                    analog=False, width=32):
    """Analytic per-kernel roofline on :func:`roofline_kind`'s peaks."""
    flops = 2.0 * b * c * l * (2 if analog else 1)   # on+leak paths
    hbm = dtype_bytes * (b * l + c * l * (2 if analog else 1) + b * c / 8)
    vmem = dtype_bytes * (bt * kt * (2 if analog else 1)
                          + kt * ct * (2 if analog else 1) + bt * ct)
    intensity = flops / hbm
    pk = peaks(roofline_kind())
    t_comp = flops / pk.bf16_flops
    t_mem = hbm / pk.hbm_bw
    # MXU efficiency: contraction dim per pass (128 ideal)
    contract = width if analog else min(kt, 512)
    mxu_eff = min(contract, 128) / 128.0
    return {"flops": flops, "hbm_bytes": hbm, "vmem_bytes": vmem,
            "intensity": intensity, "t_compute_s": t_comp,
            "t_memory_s": t_mem, "mxu_eff": mxu_eff,
            "bound": "compute" if t_comp / max(mxu_eff, 1e-9) > t_mem
            else "memory"}


def bench(reps: int = 3):
    rows, checks = [], []
    cfg = TMConfig(n_classes=10, clauses_per_class=100, n_features=784,
                   n_states=127)
    ta = init_ta_state(jax.random.PRNGKey(0), cfg)
    x = jax.random.bernoulli(jax.random.PRNGKey(1), 0.3,
                             (256, cfg.n_features)).astype(jnp.uint8)
    lits = literals(x)
    inc = include_mask(ta, cfg).astype(jnp.uint8)

    def timeit(fn):
        out = fn()
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
            jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps * 1e6

    t_kernel = timeit(lambda: ops.tm_class_sums(lits, inc, cfg))
    from repro.kernels import ref
    pol = ops.polarity_matrix(cfg, inc)[:, :cfg.n_classes]
    t_ref = timeit(lambda: ref.tm_infer_ref(
        (1 - lits).astype(jnp.float32), inc.astype(jnp.float32), pol))
    rows.append(("tm_class_sums_pallas_interp_us", t_kernel, t_ref))

    xbar = imbue.program_crossbar(inc > 0, jax.random.PRNGKey(2),
                                  VariationConfig.nominal())
    t_analog = timeit(lambda: ops.imbue_class_sums(lits, xbar, cfg))
    rows.append(("imbue_class_sums_pallas_interp_us", t_analog, 0))

    # analytic rooflines for the MNIST-scale model (Table IV row)
    b, c, l = 8192, 2000, 1568
    dig = kernel_roofline(b, c, l, bt=128, ct=128, kt=512, dtype_bytes=2)
    ana = kernel_roofline(b, c, l, bt=128, ct=128, kt=256, dtype_bytes=4,
                          analog=True)
    rows.append(("digital_kernel_tpu_intensity", dig["intensity"],
                 dig["bound"]))
    rows.append(("analog_kernel_tpu_intensity", ana["intensity"],
                 ana["bound"]))
    rows.append(("digital_vmem_KB", dig["vmem_bytes"] / 1024, 0))
    rows.append(("analog_vmem_KB", ana["vmem_bytes"] / 1024, 0))
    checks.append(("kernel/vmem_fits",
                   dig["vmem_bytes"] < 16e6 and ana["vmem_bytes"] < 16e6,
                   f"{dig['vmem_bytes']/1e3:.0f}/"
                   f"{ana['vmem_bytes']/1e3:.0f} KB"))
    checks.append(("kernel/mxu_aligned",
                   dig["mxu_eff"] == 1.0, f"digital {dig['mxu_eff']}"))
    return rows, checks


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Plane-packed "after" backends next to their packed "before"
# counterparts (ISSUE 9): same math, resident conductance planes
# collapsed to the uint32 index bitplane + double-buffered HBM->VMEM
# streaming.
PLANE_PAIRS = (("analog-pallas-packed", "analog-pallas-packed2"),
               ("coalesced-pallas-packed", "coalesced-pallas-packed2"))


def resident_bytes(backend_name: str, shape: dict) -> int:
    """Analytic resident-model operand bytes ONE dispatch streams at
    ``shape`` (nominal programming — no deviation plane).

    The dense analog kernels stream two f32 planes (conductance +
    leak); the plane-packed analog kernel streams one uint32 LRS/HRS
    index bitplane — the 64x resident reduction.  Coalesced kernels
    stream the include plane (uint32 bitplane when packed)."""
    c = (shape["n_clauses"] if "n_clauses" in shape
         else shape["n_classes"] * shape["clauses_per_class"])
    l = 2 * shape["n_features"]
    lw = math.ceil(l / 32)
    if backend_name.startswith("coalesced"):
        return 4 * c * (lw if "packed" in backend_name else l)
    if backend_name.endswith("packed2"):
        return 4 * c * lw
    return 2 * 4 * c * l


def plane_pair_report(entries):
    """Before/after rows per (pair, shape bucket) out of the sweep:
    best-tile latency and analytic resident bytes per dispatch."""
    rows = []
    for before, after in PLANE_PAIRS:
        common = sorted(set(entries.get(before, {}))
                        & set(entries.get(after, {})))
        for skey in common:
            eb, ea = entries[before][skey], entries[after][skey]
            lat_b = min(eb["tile_latency_us"].values())
            lat_a = min(ea["tile_latency_us"].values())
            rb = resident_bytes(before, eb["shape"])
            ra = resident_bytes(after, ea["shape"])
            rows.append({
                "before": before, "after": after, "shape_bucket": skey,
                "latency_us_before": lat_b, "latency_us_after": lat_a,
                "latency_ratio": lat_a / lat_b if lat_b else None,
                "resident_bytes_before": rb, "resident_bytes_after": ra,
                "resident_bytes_ratio": ra / rb if rb else None,
            })
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny tile sweep, no table write")
    ap.add_argument("--reps", type=int, default=15,
                    help="timing reps per candidate (min-of-reps)")
    ap.add_argument("--out", default=autotune.DEFAULT_TABLE_PATH,
                    help="tuning-table JSON path (full mode only)")
    ap.add_argument("--require-backend", action="append", default=[],
                    metavar="NAME",
                    help="fail (smoke or full) unless this backend was "
                         "tuned; repeatable — CI pins the coalesced "
                         "family so a silently dropped registration "
                         "cannot pass the smoke")
    args = ap.parse_args(argv)

    mode = "smoke" if args.smoke else "full"
    print(f"[kernel_bench] measured autotune sweep ({mode}) on "
          f"{jax.default_backend()}...")
    # Full mode measures BOTH committed shapes — the serve-bench
    # reference and the KWS-6 streaming shape — under their own
    # (backend, shape bucket) keys; smoke keeps CI to the reference.
    entries = autotune.autotune(smoke=args.smoke, reps=args.reps)
    flat = [(name, skey, e) for name, shapes in sorted(entries.items())
            for skey, e in sorted(shapes.items())]
    for name, skey, e in flat:
        print(f"[kernel_bench]   {name} @ {skey}: tiles={e['tiles']} "
              f"buckets={e['bucket_sizes']} "
              f"(best tile {min(e['tile_latency_us'].values()):.0f} us)")
    missing = sorted(set(args.require_backend) - set(entries))
    if missing:
        print(f"[kernel_bench] FAIL: required backend(s) not tuned: "
              f"{missing} (tuned: {sorted(entries)})")
        raise SystemExit(1)

    # ISSUE 9 before/after: packed vs plane-packed per shape bucket.
    pairs = plane_pair_report(entries)
    for p in pairs:
        print(f"[kernel_bench]   {p['before']} -> {p['after']} "
              f"@ {p['shape_bucket']}: "
              f"{p['latency_us_before']:.0f} -> "
              f"{p['latency_us_after']:.0f} us "
              f"({p['latency_ratio']:.2f}x), resident "
              f"{p['resident_bytes_before']} -> "
              f"{p['resident_bytes_after']} B/dispatch "
              f"({p['resident_bytes_ratio']:.4f}x)")
    # The win the acceptance bar asks for: latency OR resident-bytes
    # improvement on at least one shape bucket of the analog pair.
    analog_pairs = [p for p in pairs
                    if p["after"] == "analog-pallas-packed2"]
    win = any(p["latency_ratio"] < 1.0 or p["resident_bytes_ratio"] < 1.0
              for p in analog_pairs)

    if args.smoke:
        ok = all(e["tiles"] and e["bucket_sizes"] for _, _, e in flat)
        ok = ok and (win or not analog_pairs)
        print(f"[kernel_bench] SMOKE {'PASS' if ok else 'FAIL'}: "
              f"{len(flat)} (backend, shape) cells tuned "
              "(nothing written)")
        if not ok:
            raise SystemExit(1)
        return None
    path = autotune.save_table(entries, args.out)
    print(f"[kernel_bench] wrote {path} ({len(flat)} cells)")
    if pairs:
        pair_path = os.path.join(REPO, "BENCH_kernel.json")
        with open(pair_path, "w") as f:
            json.dump({"jax_backend": jax.default_backend(),
                       "note": ("latencies are interpret-mode on this "
                                "backend unless jax_backend == tpu; the "
                                "resident-bytes columns are analytic and "
                                "transfer to hardware"),
                       "plane_pairs": pairs,
                       "analog_pair_win": win}, f, indent=2)
        print(f"[kernel_bench] wrote {pair_path} "
              f"(analog pair win: {win})")
    return entries


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
