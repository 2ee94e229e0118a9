"""Sharded + async serving tests on 8 forced CPU host devices.

Each test runs in a subprocess (XLA_FLAGS must be set before jax init;
the main pytest process keeps its single device) — the same pattern as
``tests/test_distributed.py``.  Covered:

* a mesh-sharded R=8 engine serves bit-identical responses to the
  single-device engine at the same seed (nominal variation), for both
  routed and ensemble modes, sync and async;
* ``pool.shard`` places the ``[R, C, L]`` stack over the ``replica``
  mesh axis and replicates the shared include plane;
* capability selection: a partitioned state requires ``CAP_SHARDED``,
  so the Pallas preference falls back LOUDLY to the GSPMD jnp path
  (same pattern as ``csa_offset``) and the engine accounts for it;
* the 1-fused-dispatch property holds under a sharded mesh (trace-count
  check mirroring the single-device 1-kernel-call stack test);
* full-noise sharded serving is bit-reproducible and equal to the
  single-device noise stream (partitionable threefry).
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Shared subprocess prologue: a tiny training-free model served two
# ways.  48 requests over max_batch 16 gives 3 batches, so the async
# double-buffer actually pipelines.
PROLOGUE = """
    import warnings
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.core import tm
    from repro.core.tm import TMConfig
    from repro.core.variations import VariationConfig
    from repro.launch.mesh import make_replica_mesh
    from repro.serve import (AsyncServeEngine, BatcherConfig,
                             EngineConfig, ServeEngine,
                             program_replica_pool)

    assert jax.device_count() == 8, jax.device_count()
    cfg = TMConfig(n_classes=4, clauses_per_class=8, n_features=32,
                   n_states=100)
    inc = jax.random.bernoulli(jax.random.PRNGKey(0), 0.1,
                               (cfg.n_clauses, cfg.n_literals))
    ta = jnp.where(inc, cfg.n_states + 1,
                   cfg.n_states).astype(cfg.state_dtype)
    xs = np.asarray(jax.random.bernoulli(
        jax.random.PRNGKey(1), 0.4,
        (48, cfg.n_features))).astype(np.uint8)
    BCFG = BatcherConfig(max_batch=16, bucket_sizes=(8, 16))

    def engine(n_replicas, mesh=None, cls=ServeEngine, vcfg=None, **ecfg):
        return cls.from_ta_state(
            ta, cfg, n_replicas=n_replicas, key=jax.random.PRNGKey(3),
            vcfg=VariationConfig.nominal() if vcfg is None else vcfg,
            ecfg=EngineConfig(batcher=BCFG, **ecfg), mesh=mesh)

    def served(eng):
        eng.submit_many(list(xs))
        rs = eng.drain()
        return (np.array([r.pred for r in rs]),
                np.stack([r.class_sums for r in rs]))
"""


def run_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    src = textwrap.dedent(PROLOGUE) + textwrap.dedent(code)
    out = subprocess.run(
        [sys.executable, "-c", src],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_sharded_engine_bit_identical_to_single_device():
    """Acceptance bar: a mesh-sharded R=8 engine == the single-device
    engine bit-for-bit at the same seed and nominal variation — preds
    AND class sums, routed and ensemble, sync and async — and both
    equal the digital TM."""
    out = run_devices("""
        digital = np.asarray(tm.predict(ta, jnp.asarray(xs), cfg))
        mesh = make_replica_mesh(8, 1)
        for routing in ("round_robin", "least_loaded", "ensemble"):
            p0, s0 = served(engine(8, routing=routing))
            p1, s1 = served(engine(8, mesh=mesh, routing=routing))
            np.testing.assert_array_equal(p0, p1, err_msg=routing)
            np.testing.assert_array_equal(s0, s1, err_msg=routing)
            np.testing.assert_array_equal(p1, digital, err_msg=routing)
            p2, s2 = served(engine(8, mesh=mesh, cls=AsyncServeEngine,
                                   routing=routing))
            np.testing.assert_array_equal(p2, digital, err_msg=routing)
            np.testing.assert_array_equal(s2, s0, err_msg=routing)
        # data-parallel reads: batch axis sharded too (16 % 2 == 0)
        p3, s3 = served(engine(4, mesh=make_replica_mesh(4, 2),
                               routing="ensemble"))
        p4, s4 = served(engine(4, routing="ensemble"))
        np.testing.assert_array_equal(p3, p4)
        np.testing.assert_array_equal(s3, s4)
        print("OK sharded bitwise")
    """)
    assert "OK sharded bitwise" in out


def test_pool_shard_places_replicas_across_devices():
    out = run_devices("""
        from jax.sharding import PartitionSpec as P
        pool = program_replica_pool(inc, jax.random.PRNGKey(2), 8,
                                    VariationConfig.nominal())
        mesh = make_replica_mesh(8, 1)
        sh = pool.shard(mesh, None)
        assert sh.is_sharded and not pool.is_sharded
        assert tuple(sh.r_stack.sharding.spec) == ("replica", None, None)
        assert len(sh.r_stack.sharding.device_set) == 8
        # the shared TA actions replicate on every device
        assert sh.include.sharding.is_fully_replicated
        # programming happened before placement: same bits
        np.testing.assert_array_equal(np.asarray(sh.r_stack),
                                      np.asarray(pool.r_stack))
        # the sharded pool is still a well-behaved pytree
        sh2 = jax.tree_util.tree_map(lambda x: x, sh)
        assert sh2.n_replicas == 8 and sh2.icfg == pool.icfg
        print("OK pool shard")
    """)
    assert "OK pool shard" in out


def test_sharded_state_falls_back_loudly():
    """CAP_SHARDED gating, same pattern as csa_offset: the Pallas
    kernels don't declare it, so a sharded state rejects them with an
    inspectable reason, the engine warns at construction, and every
    dispatch is counted in ServeMetrics."""
    out = run_devices("""
        mesh = make_replica_mesh(8, 1)
        pool = program_replica_pool(inc, jax.random.PRNGKey(2), 8,
                                    VariationConfig.nominal())
        state = pool.shard(mesh, None).state(cfg).pack()
        need = api.required_capabilities(state)
        assert api.CAP_SHARDED in need
        sel = api.select_backend(state, prefer="analog-pallas-packed")
        assert sel.fell_back and sel.backend.name == "analog-jnp"
        assert "sharded_dispatch" in sel.fallback_reason
        # unsharded twin: no CAP_SHARDED requirement, no fallback
        assert api.CAP_SHARDED not in api.required_capabilities(
            pool.state(cfg))
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            eng = engine(8, mesh=mesh, backend="analog-pallas-packed")
        assert eng.selection.fell_back
        assert any("fallback" in str(x.message) for x in w)
        eng.submit_many(list(xs[:16]))
        eng.drain()
        s = eng.summary()
        assert s["sharded"] is True and s["backend"] == "analog-jnp"
        assert s["fallback_dispatches"] == eng.metrics.batches > 0
        assert any("sharded_dispatch" in r for r in s["forward_fallbacks"])
        # the mesh default preference is the jnp path: quiet by design
        eng2 = engine(8, mesh=mesh)
        assert not eng2.selection.fell_back
        assert eng2.backend.name == "analog-jnp"
        print("OK loud fallback")
    """)
    assert "OK loud fallback" in out


def test_sharded_ensemble_single_fused_dispatch():
    """The 1-fused-dispatch property survives sharding: one ensemble
    batch over the mesh traces the stacked forward exactly once (no
    per-replica or per-device Python loop), and a second batch of the
    same bucket is a pure compile-cache hit."""
    out = run_devices("""
        from repro.core import imbue
        calls = []
        real = imbue.stacked_clause_outputs
        imbue.stacked_clause_outputs = (
            lambda *a, **kw: calls.append(1) or real(*a, **kw))
        try:
            eng = engine(8, mesh=make_replica_mesh(8, 1),
                         routing="ensemble")
            eng.submit_many(list(xs[:16]))
            eng.drain()
            assert len(calls) == 1, f"{len(calls)} stacked traces"
            eng.submit_many(list(xs[16:32]))     # same bucket: cache hit
            eng.drain()
            assert len(calls) == 1, f"{len(calls)} traces after rerun"
        finally:
            imbue.stacked_clause_outputs = real
        print("OK fused dispatch", len(calls))
    """)
    assert "OK fused dispatch" in out


def test_sharded_noise_stream_matches_single_device():
    """Full noise (C2C + CSA offset -> analog-jnp on both sides): the
    sharded engine draws the SAME noise bits as the single-device one
    (partitionable threefry), so even noisy ensemble serving is
    bit-identical at a fixed seed — and reproducible run-to-run."""
    out = run_devices("""
        mesh = make_replica_mesh(8, 1)
        runs = []
        for m in (None, mesh, mesh):
            p, s = served(engine(8, mesh=m, vcfg=VariationConfig(),
                                 routing="ensemble"))
            runs.append((p, s))
        np.testing.assert_array_equal(runs[0][0], runs[1][0])
        np.testing.assert_array_equal(runs[0][1], runs[1][1])
        np.testing.assert_array_equal(runs[1][1], runs[2][1])
        print("OK noise stream")
    """)
    assert "OK noise stream" in out


def test_async_overlap_metrics_on_mesh():
    """AsyncServeEngine over a mesh: responses in submission order,
    overlap accounting within [0, 1], and the double buffer actually
    held concurrent dispatches in flight."""
    out = run_devices("""
        eng = engine(8, mesh=make_replica_mesh(8, 1),
                     cls=AsyncServeEngine)
        seen = []
        orig = eng._issue
        def spy(batch):
            seen.append(eng.in_flight)
            return orig(batch)
        eng._issue = spy
        rids = eng.submit_many(list(xs))
        rs = eng.drain()
        assert [r.rid for r in rs] == rids
        assert eng.in_flight == 0
        assert max(seen) >= 1, seen          # pipelining really happened
        s = eng.summary()
        assert 0.0 <= s["overlap_fraction"] <= 1.0
        assert s["device_wait_s"] >= 0 and s["host_pack_s"] > 0
        print("OK async mesh", max(seen))
    """)
    assert "OK async mesh" in out


def test_coalesced_sharded_engine_class_parallel():
    """Coalesced GSPMD (ISSUE 6): a CoalescedPool sharded over the
    replica mesh axis splits the [C, M] weight plane class-parallel,
    replicates the shared TA plane, requires CAP_SHARDED (so the jnp
    ``coalesced`` backend is the quiet default), and serves sums
    bit-identical to the single-device engine."""
    out = run_devices("""
        from repro.core.coalesced import CoalescedConfig
        from repro.serve import CoalescedPool

        ccfg = CoalescedConfig(n_classes=8, n_clauses=32, n_features=32,
                               n_states=100)
        k1, k2 = jax.random.split(jax.random.PRNGKey(4))
        cinc = jax.random.bernoulli(
            k1, 0.1, (ccfg.n_clauses, ccfg.n_literals))
        cta = jnp.where(cinc, ccfg.n_states + 1,
                        ccfg.n_states).astype(ccfg.state_dtype)
        w = jax.random.randint(
            k2, (ccfg.n_clauses, ccfg.n_classes), -ccfg.max_weight,
            ccfg.max_weight + 1, jnp.int32)
        mesh = make_replica_mesh(8, 1)
        pool = CoalescedPool(ta_state=cta, weights=w, cfg=ccfg)
        sh = pool.shard(mesh, None)
        assert sh.is_sharded and not pool.is_sharded
        # class-parallel: the M axis of [C, M] splits over the mesh
        assert tuple(sh.weights.sharding.spec) == (None, "replica")
        assert sh.ta_state.sharding.is_fully_replicated
        # a sharded coalesced state needs CAP_SHARDED -> jnp GSPMD path
        state = sh.state()
        assert api.CAP_SHARDED in api.required_capabilities(state)
        sel = api.select_backend(state)
        assert sel.backend.name == "coalesced" and not sel.fell_back

        def cserved(mesh_=None):
            eng = ServeEngine.from_coalesced(
                cta, w, ccfg,
                ecfg=EngineConfig(batcher=BCFG), mesh=mesh_)
            eng.submit_many(list(xs))
            rs = eng.drain()
            return (eng, np.array([r.pred for r in rs]),
                    np.stack([r.class_sums for r in rs]))

        e0, p0, s0 = cserved()
        e1, p1, s1 = cserved(mesh)
        assert e1.state.is_sharded and e1.summary()["sharded"] is True
        assert e1.backend.name == "coalesced"
        assert not e1.selection.fell_back
        assert e1.summary()["forward_fallbacks"] == []
        np.testing.assert_array_equal(s1, s0)
        np.testing.assert_array_equal(p1, p0)
        print("OK coalesced sharded")
    """)
    assert "OK coalesced sharded" in out
