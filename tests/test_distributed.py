"""Distribution tests on 8 forced CPU host devices.

Each test runs in a subprocess (XLA_FLAGS must be set before jax init;
the main pytest process keeps its single device).  Covered:

* sharded LM train step == single-device train step (bitwise semantics
  of pjit),
* elastic checkpoint restore (saved unsharded -> restored onto a 4x2
  mesh and vice versa),
* int8 gradient compression round-trip + error feedback,
* sharded TM training/inference == single-device TM,
* GPipe pipeline-parallel demo == sequential execution.
"""

import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_sharded_train_step_matches_single_device():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, smoke
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_debug_mesh, rules_for
        from repro.models import transformer as tf
        from repro.optim.optimizers import OptimizerConfig, make_optimizer
        from repro.train.train_step import make_train_step
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = smoke(get_config("qwen2-0.5b"), d_model=64)
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        opt = make_optimizer(OptimizerConfig(lr=1e-2))
        opt_state = opt.init(params)
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab_size)}
        step = make_train_step(cfg, opt)
        ref_p, ref_o, ref_m = jax.jit(step)(
            params, opt_state, jnp.int32(0), batch)

        mesh = make_debug_mesh(2, 4)
        rules = rules_for(cfg, mesh, global_batch=4)
        p_sh = shd.tree_shardings(params, mesh, rules)
        o_sh = shd.tree_shardings(opt_state, mesh, rules)
        b_sh = {"tokens": NamedSharding(mesh, P(rules.batch))}
        with shd.use_sharding(mesh, rules):
            got_p, got_o, got_m = jax.jit(
                step, in_shardings=(p_sh, o_sh, None, b_sh),
                out_shardings=(p_sh, o_sh, None))(
                    params, opt_state, jnp.int32(0), batch)
        np.testing.assert_allclose(float(got_m["loss"]),
                                   float(ref_m["loss"]), rtol=2e-4)
        err = jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            ref_p, got_p)
        worst = max(jax.tree.leaves(err))
        assert worst < 3e-3, worst
        print("OK sharded==single", float(got_m['loss']), worst)
    """)
    assert "OK sharded==single" in out


def test_elastic_checkpoint_restore():
    out = run_devices("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, smoke
        from repro.distributed import checkpoint as ckpt
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_debug_mesh, rules_for
        from repro.models import transformer as tf

        cfg = smoke(get_config("stablelm-1.6b"), d_model=64)
        params = tf.init_params(jax.random.PRNGKey(3), cfg)
        d = tempfile.mkdtemp()
        ckpt.save(d, 7, {"params": params}, extra={"arch": cfg.name})
        assert ckpt.latest_step(d) == 7

        # restore onto a 4x2 mesh (elastic: written on 1 device)
        mesh = make_debug_mesh(4, 2)
        rules = rules_for(cfg, mesh, global_batch=4)
        shardings = {"params": shd.tree_shardings(params, mesh, rules)}
        tree, man = ckpt.restore(d, 7, {"params": params}, shardings)
        assert man["extra"]["arch"] == cfg.name
        err = jax.tree.map(lambda a, b: float(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)).max()),
            params, tree["params"])
        assert max(jax.tree.leaves(err)) == 0.0
        # round 2: save the sharded tree, restore unsharded
        ckpt.save(d, 8, tree)
        tree2, _ = ckpt.restore(d, 8, {"params": params})
        err2 = jax.tree.map(lambda a, b: float(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)).max()),
            params, tree2["params"])
        assert max(jax.tree.leaves(err2)) == 0.0
        print("OK elastic")
    """)
    assert "OK elastic" in out


def test_gradient_compression_roundtrip():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.optim.compression import GradCompressor

        comp = GradCompressor(min_size=16)
        params = {"w": jnp.zeros((64, 64)), "b": jnp.zeros((3,))}
        state = comp.init_state(params)
        assert state["b"] is None and state["w"].shape == (64, 64)
        key = jax.random.PRNGKey(0)
        g = {"w": jax.random.normal(key, (64, 64)),
             "b": jnp.ones((3,))}
        total_err_before = None
        # error feedback: accumulated dequantized grads converge to the
        # accumulated true grads
        acc_true = jnp.zeros((64, 64)); acc_deq = jnp.zeros((64, 64))
        for i in range(20):
            gi = {"w": g["w"] * (1.0 + 0.01 * i), "b": g["b"]}
            deq, state = comp.compress_decompress(gi, state)
            acc_true += gi["w"]; acc_deq += deq["w"]
            assert deq["b"].dtype == jnp.float32
        rel = float(jnp.abs(acc_true - acc_deq).max()
                    / jnp.abs(acc_true).max())
        assert rel < 5e-3, rel
        print("OK compression", rel)
    """, n=1)
    assert "OK compression" in out


def test_tm_sharded_matches_single():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.tm import TMConfig, init_ta_state, predict
        from repro.core import tm_distributed as tmd
        from repro.data.tm_datasets import noisy_xor
        from repro.launch.mesh import make_debug_mesh

        cfg = TMConfig(n_classes=2, clauses_per_class=8, n_features=12)
        xtr, ytr, xte, yte = noisy_xor(jax.random.PRNGKey(0), 256, 128)
        ta = init_ta_state(jax.random.PRNGKey(1), cfg)
        key = jax.random.PRNGKey(2)
        ref = tmd.tm_train_step(ta, key, xtr, ytr, cfg)
        ref_pred = tmd.tm_infer_step(ref, xte, cfg)

        mesh = make_debug_mesh(2, 4)
        st_sh, x_sh, y_sh = tmd.tm_shardings(cfg, mesh, 256)
        ta_s = jax.device_put(ta, st_sh)
        xs = jax.device_put(xtr, x_sh)
        ys = jax.device_put(ytr, y_sh)
        got = jax.jit(tmd.tm_train_step, static_argnames=("cfg",),
                      in_shardings=(st_sh, None, x_sh, y_sh),
                      out_shardings=st_sh)(ta_s, key, xs, ys, cfg)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
        got_pred = jax.jit(tmd.tm_infer_step, static_argnames=("cfg",))(
            got, xte, cfg)
        np.testing.assert_array_equal(np.asarray(ref_pred),
                                      np.asarray(got_pred))
        # digital fused infer == reference TM predict (inference mode)
        np.testing.assert_array_equal(np.asarray(got_pred),
                                      np.asarray(predict(ref, xte, cfg)))
        print("OK tm sharded")
    """)
    assert "OK tm sharded" in out


def test_pipeline_parallel_demo():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import (pipeline_apply,
                                                sequential_apply,
                                                init_pipeline_params)
        from repro.launch.mesh import make_pipeline_mesh

        mesh = make_pipeline_mesh(4)
        params = init_pipeline_params(jax.random.PRNGKey(0), n_stages=4,
                                      d=32)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
        ref = sequential_apply(params, x)
        got = pipeline_apply(params, x, mesh, microbatches=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("OK pipeline")
    """)
    assert "OK pipeline" in out


def test_compressed_psum_grads():
    """Manual-DP int8-quantized gradient psum: matches the f32 reduction
    within quantization error AND the compiled HLO's gradient all-reduce
    runs on s16 words (2x fewer wire bytes than f32)."""
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_debug_mesh
        from repro.optim.compression import (GradCompressor,
                                             compressed_psum_grads)

        mesh = jax.make_mesh((8,), ("data",))
        params = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 64)),
                  "b": jnp.zeros((3,))}

        def loss(params, batch):
            h = jnp.tanh(batch @ params["w"])
            return (h ** 2).mean()

        grad_fn = jax.grad(loss)
        comp = GradCompressor(min_size=16)
        fn = compressed_psum_grads(grad_fn, mesh, "data", comp)
        ef0 = comp.init_state(params)
        batch = jax.random.normal(jax.random.PRNGKey(1), (32, 64))

        jitted = jax.jit(fn)
        grads, ef = jitted(params, batch, ef0)
        ref = jax.grad(lambda p: loss(p, batch))(params)
        err = float(jnp.abs(grads["w"] - ref["w"]).max()
                    / jnp.abs(ref["w"]).max())
        assert err < 0.05, err

        txt = jitted.lower(params, batch, ef0).compile().as_text()
        import re
        ars = [l for l in txt.splitlines() if re.search(
            r"= s16\\[64,64\\][^=]*all-reduce", l)]
        assert ars, "no s16 gradient all-reduce found"
        print("OK compressed psum", err)
    """)
    assert "OK compressed psum" in out
