#!/usr/bin/env python3
"""Serve a Table IV model on one TPU chip and check every answer.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # a 4-chip replica mesh vs one device

The model is F-MNIST's Table IV shape (``imbue-tm-fmnist``: 5,000
clauses x 1,568 literals, 10 classes) with Table IV's 25,742 includes,
drawn from a seed — no training, no dataset.  It is programmed into an
R=4 replica pool through ``ServeEngine.from_ta_state`` (the constructor
``python -m repro.launch.serve`` uses) and serves seeded requests through
``submit``/``pump``/``drain``.

* Phase A (nominal devices): the plane-packed, packed and dense fused
  kernels, the ``analog-jnp`` path, ensemble routing and a short
  ``AsyncServeEngine`` run.  Every path's class sums and predictions
  must equal the digital reference bit for bit (``tm.forward``, itself
  checked against a NumPy evaluation on the host).
* Phase B (D2D + C2C, no CSA offset): the three fused kernels under the
  same key must agree with each other bit for bit, and the noise must
  have moved some class sums off the digital ones (otherwise every
  decision sat far from the CSA reference and the comparison could not
  tell the kernels' arithmetic apart).
* ``--chips 4``: only the mesh path — the pool sharded over a 4-device
  ``replica`` mesh against the same pool on one device, at nominal and
  under D2D + C2C with the same key (where, again, the noise must have
  moved some class sums).

Requests are sparse (each feature bit set with probability
``REQUEST_DENSITY``), so nearly every literal of a positive-literal
column is driven and D2D + C2C pushes column currents across the CSA
reference; at density 0.5 no decision of this model moves.

Every fused engine must have selected its kernel without a fallback,
dispatched no fallback batch, and carry ``tpu_custom_call`` in its
lowered forward.  The script refuses to run off a TPU; it prints one
JSON line ``{"ok": true, "device": {...}}`` last, and only when every
check passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api.registry import CAP_FUSED_KERNEL  # noqa: E402
from repro.configs.imbue_tm import tm_config  # noqa: E402
from repro.core import tm  # noqa: E402
from repro.core.tm import TMConfig  # noqa: E402
from repro.core.variations import VariationConfig  # noqa: E402
from repro.serve import AsyncServeEngine, EngineConfig, ServeEngine  # noqa: E402

FMNIST_INCLUDES = 25_742       # Table IV (data/tm_datasets.py)
NOISY = VariationConfig(csa_offset=False)     # D2D + C2C, no CSA offset
REQUEST_DENSITY = 0.1          # P(feature bit = 1) in a request


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """The model, pool and traffic one smoke run serves."""

    tm_cfg: TMConfig = tm_config("imbue-tm-fmnist")
    includes: int = FMNIST_INCLUDES
    n_replicas: int = 4
    n_requests: int = 256
    seed: int = 0


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def make_model(sc: SmokeConfig):
    """Seeded TA state with exactly ``sc.includes`` includes, and seeded
    Boolean requests ``[n_requests, F]``."""
    cfg = sc.tm_cfg
    key = jax.random.PRNGKey(sc.seed)
    idx = jax.random.choice(key, cfg.n_ta, (sc.includes,), replace=False)
    ta = jnp.full((cfg.n_ta,), cfg.n_states, cfg.state_dtype)
    ta = ta.at[idx].set(cfg.n_states + 1).reshape(cfg.n_clauses,
                                                   cfg.n_literals)
    rng = np.random.default_rng(sc.seed)
    x = (rng.random((sc.n_requests, cfg.n_features)) < REQUEST_DENSITY
         ).astype(np.uint8)
    return ta, x


def reference_sums(ta, x: np.ndarray, cfg: TMConfig) -> np.ndarray:
    """``tm.forward`` class sums, checked against a host NumPy evaluation
    of the same Boolean semantics (violation count, empty-clause mask,
    interleaved polarity)."""
    include = np.asarray(tm.include_mask(ta, cfg))
    lits = np.concatenate([x, 1 - x], axis=1).astype(np.float32)
    viol = (1.0 - lits) @ include.T.astype(np.float32)
    fired = (viol == 0) & include.any(axis=1)[None, :]
    pol = np.where(np.arange(cfg.n_clauses) % 2 == 0, 1, -1)
    host = (fired * pol).reshape(len(x), cfg.n_classes,
                                 cfg.clauses_per_class).sum(-1)
    dev = np.asarray(tm.forward(ta, jnp.asarray(x), cfg))
    _check(np.array_equal(host, dev),
           f"tm.forward disagrees with the host reference on "
           f"{int((host != dev).any(1).sum())} of {len(x)} rows")
    return dev


def build(sc: SmokeConfig, ta, ecfg: EngineConfig, *, vcfg=None,
          cls=ServeEngine, mesh=None) -> ServeEngine:
    return cls.from_ta_state(
        ta, sc.tm_cfg, n_replicas=sc.n_replicas,
        key=jax.random.PRNGKey(sc.seed + 1),
        vcfg=vcfg if vcfg is not None else VariationConfig.nominal(),
        ecfg=ecfg, mesh=mesh)


def serve(engine: ServeEngine, x: np.ndarray):
    """Submit every row, pump, drain: ``(sums [n, M], preds [n], s)``."""
    t0 = time.perf_counter()
    rids = engine.submit_many(list(x))
    engine.pump()
    by_rid = {r.rid: r for r in engine.drain()}
    secs = time.perf_counter() - t0
    _check(all(rid in by_rid and not by_rid[rid].expired for rid in rids),
           "a request was dropped or expired")
    sums = np.stack([np.asarray(by_rid[r].class_sums) for r in rids])
    preds = np.asarray([by_rid[r].pred for r in rids])
    return sums, preds, secs


def check_engine(engine: ServeEngine, expect_backend: str,
                 sc: SmokeConfig) -> None:
    """The selected backend is the expected one; a fused engine fell back
    nowhere and, on a TPU, its forward lowers to a Mosaic kernel."""
    _check(engine.backend.name == expect_backend,
           f"selected {engine.backend.name}, expected {expect_backend}")
    if CAP_FUSED_KERNEL not in engine.backend.capabilities:
        return
    _check(not engine.selection.fell_back,
           f"fell back: {engine.selection.fallback_reason}")
    _check(engine.summary()["fallback_dispatches"] == 0,
           "a batch was served by a fallback backend")
    if jax.devices()[0].platform == "tpu":
        bucket = engine.batcher.cfg.max_batch
        width = (-(-sc.tm_cfg.n_literals // 32) if engine.packed_io
                 else sc.tm_cfg.n_literals)
        lits = jnp.zeros((bucket, width),
                         jnp.uint32 if engine.packed_io else jnp.uint8)
        key = None if engine._noise_free else jax.random.PRNGKey(0)
        ensemble = engine.ecfg.routing == "ensemble"
        state = engine.state if ensemble else engine._slices[0]
        mask = engine._healthy_mask if ensemble else engine._mask_one
        text = engine._fwd.lower(state, lits, key, mask, bt=bucket).as_text()
        _check("tpu_custom_call" in text,
               f"{engine.backend.name}: no tpu_custom_call in the forward")


def _report(engine: ServeEngine, cold_s: float, warm_s: float) -> dict:
    s = engine.summary()
    return {"backend": s["backend"], "routing": s["routing"],
            "tiles": s["kernel_tiles"] or "default",
            "buckets": s["bucket_sizes"],
            "resident_bytes": s["resident_nbytes_full"],
            "cold_s": round(cold_s, 3), "warm_s": round(warm_s, 3),
            "compile_s": round(max(cold_s - warm_s, 0.0), 3)}


PHASE_A = (   # (name, engine class, config, expected backend)
    ("packed2", ServeEngine, EngineConfig(), "analog-pallas-packed2"),
    ("packed", ServeEngine, EngineConfig(pack_planes=False),
     "analog-pallas-packed"),
    ("dense", ServeEngine, EngineConfig(packed=False), "analog-pallas"),
    ("analog-jnp", ServeEngine, EngineConfig(backend="analog-jnp"),
     "analog-jnp"),
    ("packed2-ensemble", ServeEngine, EngineConfig(routing="ensemble"),
     "analog-pallas-packed2"),
    ("packed2-async", AsyncServeEngine, EngineConfig(),
     "analog-pallas-packed2"),
)


def phase_a(sc: SmokeConfig, ta, x: np.ndarray, ref: np.ndarray) -> list:
    """Nominal devices: every serving path equals the digital reference
    bit for bit, twice (cold, then warm)."""
    reports = []
    ref_preds = ref.argmax(axis=1)
    for name, cls, ecfg, expect in PHASE_A:
        engine = build(sc, ta, ecfg, cls=cls)
        rows = x if cls is ServeEngine else x[:max(1, len(x) // 4)]
        want = ref[:len(rows)]
        if ecfg.routing == "ensemble":
            want = want * sc.n_replicas       # identical chips, summed
        (sums, preds, cold), (sums2, preds2, warm) = (serve(engine, rows),
                                                      serve(engine, rows))
        check_engine(engine, expect, sc)
        for s, p in ((sums, preds), (sums2, preds2)):
            bad = int((s != want).any(axis=1).sum())
            _check(bad == 0, f"{name}: {bad} of {len(rows)} rows' class "
                             "sums differ from the digital reference")
            _check(np.array_equal(p, ref_preds[:len(rows)]),
                   f"{name}: predictions differ from the reference")
        reports.append(dict(phase="A", variant=name, rows_equal=len(rows),
                            rows=len(rows), **_report(engine, cold, warm)))
        del engine
    return reports


def _rows_off(sums: np.ndarray, digital: np.ndarray, what: str) -> int:
    """Rows whose noisy class sums left the digital ones; none means no
    decision lay near the CSA reference, and a comparison under this
    noise would pass whatever the arithmetic."""
    off = int((sums != digital).any(axis=1).sum())
    _check(off > 0, f"{what}: D2D + C2C moved no class sum off the "
                    "digital reference, so this comparison cannot fail")
    return off


PHASE_B = (("packed2", EngineConfig(routing="ensemble")),
           ("packed", EngineConfig(routing="ensemble", pack_planes=False)),
           ("dense", EngineConfig(routing="ensemble", packed=False)))


def phase_b(sc: SmokeConfig, ta, x: np.ndarray, ref: np.ndarray) -> list:
    """D2D + C2C without CSA offset: the three fused kernels, programmed
    and read with the same key, agree with each other bit for bit."""
    reports, outs = [], {}
    expect = {"packed2": "analog-pallas-packed2",
              "packed": "analog-pallas-packed", "dense": "analog-pallas"}
    for name, ecfg in PHASE_B:
        engine = build(sc, ta, ecfg, vcfg=NOISY)
        (sums, preds, cold), (sums2, preds2, warm) = (serve(engine, x),
                                                      serve(engine, x))
        check_engine(engine, expect[name], sc)
        outs[name] = (sums, preds, sums2, preds2)
        off = _rows_off(sums, ref * sc.n_replicas, f"phase B {name}")
        reports.append(dict(phase="B", variant=name, rows_off_digital=off,
                            **_report(engine, cold, warm)))
        del engine
    base = outs["packed2"]
    for name in ("packed", "dense"):
        for a, b in zip(base, outs[name]):
            _check(np.array_equal(a, b),
                   f"phase B: {name} differs from packed2 on "
                   f"{int((a != b).reshape(len(a), -1).any(1).sum())} rows")
    for r in reports:
        r["rows_equal_across_kernels"] = len(x)
    return reports


def phase_mesh(sc: SmokeConfig, ta, x: np.ndarray, ref: np.ndarray,
               n_devices: int) -> list:
    """The pool sharded over an ``n_devices`` replica mesh equals the
    same pool on one device bit for bit, at nominal and under D2D + C2C
    with the same key; at nominal both equal the digital reference."""
    from repro.launch.mesh import make_replica_mesh
    mesh = make_replica_mesh(n_devices)
    reports = []
    for noise, vcfg in (("nominal", VariationConfig.nominal()),
                        ("d2d+c2c", NOISY)):
        for routing in ("ensemble", "round_robin"):
            ecfg = EngineConfig(routing=routing, packed=False,
                                backend="analog-jnp")
            one = build(sc, ta, ecfg, vcfg=vcfg)
            sharded = build(sc, ta, ecfg, vcfg=vcfg, mesh=mesh)
            _check(sharded.state.is_sharded and not one.state.is_sharded,
                   "the mesh engine's pool is not sharded")
            check_engine(one, "analog-jnp", sc)
            check_engine(sharded, "analog-jnp", sc)
            s1, p1, _ = serve(one, x)
            s2, p2, secs = serve(sharded, x)
            bad = int((s1 != s2).any(axis=1).sum())
            _check(bad == 0 and np.array_equal(p1, p2),
                   f"mesh {noise}/{routing}: {bad} rows differ from the "
                   "one-device pool")
            want = ref * (sc.n_replicas if routing == "ensemble" else 1)
            if noise == "nominal":
                _check(np.array_equal(s2, want),
                       f"mesh nominal/{routing}: sums differ from the "
                       "digital reference")
                off = 0
            else:
                off = _rows_off(s2, want, f"mesh {noise}/{routing}")
            reports.append(dict(phase="mesh", noise=noise, routing=routing,
                                devices=n_devices,
                                backend=sharded.backend.name,
                                rows_equal=len(x), rows_off_digital=off,
                                serve_s=round(secs, 3)))
            del one, sharded
    return reports


def _device() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-chip replica-mesh comparison")
    args = ap.parse_args(argv)
    device = _device()
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}",
              file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{device['count']} device(s)", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device {device}  compile cache {enable_compile_cache()}")

    sc = SmokeConfig()
    cfg = sc.tm_cfg
    ta, x = make_model(sc)
    n_inc = int(tm.include_mask(ta, cfg).sum())
    _check(n_inc == sc.includes, f"{n_inc} includes, want {sc.includes}")
    ref = reference_sums(ta, x, cfg)
    print(f"model {cfg.n_clauses} clauses x {cfg.n_literals} literals, "
          f"{cfg.n_classes} classes, {n_inc} includes; R={sc.n_replicas}; "
          f"{len(x)} requests; reference rows checked on host: {len(x)}")
    if args.chips == 4:
        reports = phase_mesh(sc, ta, x, ref, args.chips)
    else:
        reports = phase_a(sc, ta, x, ref) + phase_b(sc, ta, x, ref)
    for r in reports:
        print(" ".join(f"{k}={v}" for k, v in r.items()))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
