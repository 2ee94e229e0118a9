import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, without allocating a single parameter.

For each cell we build the real step function (train_step with optimizer,
prefill forward, or decode_step), jit it with full in/out shardings, and
``.lower().compile()`` against ShapeDtypeStruct inputs on:

  * single-pod mesh (16 x 16 = 256 chips), and
  * multi-pod mesh (2 x 16 x 16 = 512 chips).

The compiled artifact's ``memory_analysis()`` / ``cost_analysis()`` plus
our HLO collective-byte parse are recorded to JSON for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out dryrun_results.json
"""

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.distributed import sharding as shd  # noqa: E402
from repro.launch import shapes as shp  # noqa: E402
from repro.launch.mesh import make_production_mesh, rules_for  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.optim.optimizers import OptimizerConfig, make_optimizer  # noqa: E402
from repro.roofline.analysis import (analyze_compiled,  # noqa: E402
                                     collective_bytes)
from repro.train.train_step import make_train_step  # noqa: E402


def optimizer_for(cfg):
    """Arch-appropriate optimizer: 480B-class uses Adafactor with bf16
    momentum (memory fit, DESIGN.md §6), everything else AdamW."""
    if cfg.param_dtype == "bfloat16":
        return make_optimizer(OptimizerConfig(
            name="adafactor", state_dtype="bfloat16"))
    return make_optimizer(OptimizerConfig(name="adamw"))


def lower_cell(arch: str, shape: str, mesh, *, verbose=True):
    """Lower+compile one cell on ``mesh``; returns the result record."""
    cfg = shp.cell_config(arch, shape)
    spec = shp.SHAPES[shape]
    rules = rules_for(cfg, mesh, global_batch=spec.global_batch,
                      pure_dp=(arch in shp.PURE_DP_ARCHS
                               and spec.kind == "train"))
    params_abs = shp.abstract_params(cfg)
    p_sh = shd.tree_shardings(params_abs, mesh, rules)
    t0 = time.time()

    with shd.use_sharding(mesh, rules):
        if spec.kind == "train":
            opt = optimizer_for(cfg)
            opt_abs = jax.eval_shape(opt.init, params_abs)
            o_sh = shd.tree_shardings(opt_abs, mesh, rules)
            batch_abs = shp.input_specs(cfg, shape)
            b_sh = {k: NamedSharding(mesh, P(rules.batch))
                    for k in batch_abs}
            import jax.numpy as _jnp
            mb = shp.TRAIN_MICROBATCHES.get(arch, 1)
            step = make_train_step(
                cfg, opt, microbatches=mb,
                accum_dtype=_jnp.bfloat16
                if cfg.param_dtype == "bfloat16" else _jnp.float32)
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, o_sh, None, b_sh),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(
                params_abs, opt_abs,
                jax.ShapeDtypeStruct((), jnp.int32), batch_abs)
        elif spec.kind == "prefill":
            batch_abs = shp.input_specs(cfg, shape)
            b_sh = {k: NamedSharding(mesh, P(rules.batch))
                    for k in batch_abs}

            def prefill(params, batch):
                return tf.forward(params, batch, cfg, last_only=True)

            jitted = jax.jit(prefill, in_shardings=(p_sh, b_sh),
                             out_shardings=None)
            lowered = jitted.lower(params_abs, batch_abs)
        else:   # decode
            state_abs = shp.abstract_decode_state(cfg, shape)
            s_sh = shd.cache_shardings(state_abs, mesh, rules,
                                       spec.global_batch, spec.seq)
            ins = shp.input_specs(cfg, shape)
            tok_sh = NamedSharding(
                mesh, P(rules.batch if spec.global_batch > 1 else None,
                        None))

            def serve_step(params, state, token, pos):
                return tf.decode_step(params, state, token, pos, cfg)

            jitted = jax.jit(serve_step,
                             in_shardings=(p_sh, s_sh, tok_sh, None),
                             out_shardings=(None, s_sh),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_abs, state_abs, ins["token"],
                                   ins["pos"])

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    rec = analyze_compiled(arch, shape, mesh, cfg, compiled, cost, mem,
                           coll)
    rec["lower_s"] = round(t_lower, 1)
    rec["compile_s"] = round(t_compile, 1)
    if verbose:
        print(f"  memory_analysis: {mem}")
        print(f"  flops={rec['hlo_flops']:.3e} bytes={rec['hlo_bytes']:.3e}"
              f" collective_bytes={rec['collective_bytes']:.3e}")
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s")
    return rec


TM_SHAPES = {"tm_train_4k": ("train", 4096),
             "tm_infer_32k": ("infer", 32768),
             "imbue_infer_32k": ("analog", 32768)}
TM_CELL_ARCHS = ["imbue-tm-mnist", "imbue-tm-fmnist"]


def lower_tm_cell(arch: str, shape: str, mesh, *, verbose=True):
    """The paper's TM workload through the same dry-run machinery."""
    from repro.configs.imbue_tm import tm_config
    from repro.core import tm_distributed as tmd
    from repro.roofline.analysis import TARGET_KIND, HloCost, peaks

    cfg = tmd.pad_clauses_for_mesh(tm_config(arch), mesh)
    kind, batch = TM_SHAPES[shape]
    st_sh, x_sh, y_sh = tmd.tm_shardings(cfg, mesh, batch)
    c, l = cfg.n_clauses, cfg.n_literals
    x_abs = jax.ShapeDtypeStruct((batch, cfg.n_features), jnp.uint8)
    t0 = time.time()
    if kind == "train":
        st_abs = jax.ShapeDtypeStruct((c, l), jnp.int16)
        y_abs = jax.ShapeDtypeStruct((batch,), jnp.int32)
        k_abs = jax.ShapeDtypeStruct((2,), jnp.uint32)

        def step(st, key, x, y):
            return tmd.tm_train_step(st, key, x, y, cfg)

        jitted = jax.jit(step, in_shardings=(st_sh, None, x_sh, y_sh),
                         out_shardings=st_sh, donate_argnums=(0,))
        lowered = jitted.lower(st_abs, k_abs, x_abs, y_abs)
        mult, active = 4.0, 1.0   # fwd eval + delta passes (analytic)
    elif kind == "infer":
        st_abs = jax.ShapeDtypeStruct((c, l), jnp.int16)
        jitted = jax.jit(lambda st, x: tmd.tm_infer_step(st, x, cfg),
                         in_shardings=(st_sh, x_sh), out_shardings=y_sh)
        lowered = jitted.lower(st_abs, x_abs)
        mult, active = 2.0, 1.0
    else:   # analog
        from repro.core.imbue import IMBUEConfig
        g_abs = jax.ShapeDtypeStruct((c, l), jnp.float32)
        inc_abs = jax.ShapeDtypeStruct((c, l), jnp.bool_)
        # Electrical constants come from the unified-backend config (the
        # same IMBUEConfig that repro.api.CrossbarState carries as
        # aux_data), not a hand-copied literal.
        icfg = IMBUEConfig()

        def step(g_on, i_leak, inc, x):
            return tmd.imbue_infer_step(
                g_on, i_leak, inc, x, cfg, v_read=icfg.v_read,
                r_div=icfg.r_divider, v_ref=icfg.reference_voltage())

        jitted = jax.jit(step, in_shardings=(st_sh, st_sh, st_sh, x_sh),
                         out_shardings=y_sh)
        lowered = jitted.lower(g_abs, g_abs, inc_abs, x_abs)
        mult, active = 4.0, 1.0   # on-path + leak-path matmuls
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    hc = HloCost(compiled.as_text())
    n_dev = 1
    for v in mesh.shape.values():
        n_dev *= v
    model_flops = mult * batch * c * l * active
    pk = peaks(TARGET_KIND)
    compute_s = hc.flops / pk.bf16_flops
    memory_s = hc.bytes / pk.hbm_bw
    coll_s = hc.collective_bytes / pk.ici_bw
    dominant = max((("compute", compute_s), ("memory", memory_s),
                    ("collective", coll_s)), key=lambda kv: kv[1])[0]
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "x".join(f"{k}={v}" for k, v in mesh.shape.items()),
        "devices": n_dev, "kind": f"tm_{kind}",
        "hlo_flops": hc.flops * n_dev, "hlo_bytes": hc.bytes * n_dev,
        "collective_bytes": hc.collective_bytes * n_dev,
        "per_device": {"flops": hc.flops, "bytes": hc.bytes,
                       "collective_bytes": hc.collective_bytes},
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "dominant": dominant,
        "model_flops": model_flops,
        "useful_ratio": model_flops / max(hc.flops * n_dev, 1.0),
        "collective_ops": hc.collective_detail,
        "loops": hc.loops[:10],
        "memory_analysis": str(compiled.memory_analysis())[:400],
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }
    if verbose:
        print(f"  flops={rec['hlo_flops']:.3e} bytes={rec['hlo_bytes']:.3e}"
              f" collective_bytes={rec['collective_bytes']:.3e}"
              f" dominant={dominant}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tm", action="store_true",
                    help="include the paper's TM cells")
    ap.add_argument("--out", default="dryrun_results.json")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    if args.all:
        todo = [(a, s) for a, s, ok, _ in shp.cells() if ok]
        if args.tm:
            todo += [(a, s) for a in TM_CELL_ARCHS for s in TM_SHAPES]
    else:
        todo = [(args.arch, args.shape)]

    results, failures = [], []
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        mname = "multi(2x16x16)" if multi else "single(16x16)"
        for arch, shape in todo:
            print(f"[dryrun] {arch} x {shape} on {mname}", flush=True)
            try:
                if arch.startswith("imbue-tm"):
                    rec = lower_tm_cell(arch, shape, mesh)
                else:
                    rec = lower_cell(arch, shape, mesh)
                results.append(rec)
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                failures.append({"arch": arch, "shape": shape,
                                 "mesh": mname, "error": str(e)[:500]})
    skipped = [{"arch": a, "shape": s, "reason": why}
               for a, s, ok, why in shp.cells(include_skipped=True)
               if not ok]
    with open(args.out, "w") as f:
        json.dump({"results": results, "failures": failures,
                   "skipped": skipped}, f, indent=1)
    print(f"\n{len(results)} cells compiled, {len(failures)} failures, "
          f"{len(skipped)} skipped-by-rule -> {args.out}")
    if failures:
        for f_ in failures:
            print("FAIL:", f_["arch"], f_["shape"], f_["mesh"],
                  f_["error"][:200])
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
