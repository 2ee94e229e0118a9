"""Compile every engine-selectable Pallas kernel for a described TPU v5e.

Interpret mode (every other kernel test) executes kernel bodies exactly
but never checks Mosaic's layout rules: block shapes whose lane dim is
neither 128-aligned nor full, dynamic lane slices, unsupported casts and
VMEM overflow only surface when the kernel is compiled for a chip.  These
tests compile each wrapper in ``kernels/ops.py`` with ``interpret=False``
for one chip of a described ``v5e:2x2`` topology — no chip is attached,
nothing runs — at the Table IV MNIST (2,000 x 1,568) and F-MNIST
(5,000 x 1,568) widths, default tiles, and the largest batch bucket.

The topology is described inside a module fixture (never at import), so
test collection is identical on every pytest-xdist worker and only the
worker that runs this file loads the TPU compiler.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.imbue_tm import tm_config
from repro.core.imbue import IMBUEConfig
from repro.kernels import bitpack, ops

BUCKET = 128            # largest serving bucket == the kernels' BT tile
N_REPLICAS = 4
MODELS = ("imbue-tm-mnist", "imbue-tm-fmnist")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _operands(model: str, sharding):
    cfg = tm_config(model)
    c, l, m = cfg.n_clauses, cfg.n_literals, cfg.n_classes
    lw = bitpack.words_for(l)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    l_pad = -(-l // ops.KT_ANALOG) * ops.KT_ANALOG
    c_pad = -(-c // ops.CT) * ops.CT
    return cfg, dict(
        col_diff_t=s((N_REPLICAS, l_pad, c_pad), jnp.float32),
        col_leak_t=s((N_REPLICAS, l_pad // 32, c_pad), jnp.float32),
        lits=s((BUCKET, l), jnp.uint8),
        litw=s((BUCKET, lw), jnp.uint32),
        include=s((c, l), jnp.bool_),
        incw=s((c, lw), jnp.uint32),
        weights=s((c, m), jnp.int32),
        r_stack=s((N_REPLICAS, c, l), jnp.float32),
        dev=s((N_REPLICAS, c, l), jnp.float32),
    )


def _lower(kernel: str, cfg, o):
    """The jit'd wrapper for ``kernel``, lowered on the described chip."""
    icfg = IMBUEConfig()
    off = dict(interpret=False)
    planes = dict(l_valid=cfg.n_literals, n_replicas=N_REPLICAS, **off)
    return {
        "clause_eval": lambda: ops.clause_eval.lower(
            o["lits"], o["include"], **off),
        "clause_eval_packed": lambda: ops.clause_eval_packed.lower(
            o["litw"], o["incw"], **off),
        "tm_infer": lambda: ops.tm_class_sums.lower(
            o["lits"], o["include"], cfg, **off),
        "tm_infer_packed": lambda: ops.tm_class_sums_packed.lower(
            o["litw"], o["incw"], cfg, **off),
        "coalesced": lambda: ops.coalesced_class_sums.lower(
            o["lits"], o["include"], o["weights"], **off),
        "coalesced_packed": lambda: ops.coalesced_class_sums_packed.lower(
            o["litw"], o["incw"], o["weights"], **off),
        "coalesced_planes": lambda: ops.coalesced_class_sums_planes.lower(
            o["litw"], o["incw"], o["weights"], **off),
        "imbue_infer_stack": lambda: ops.imbue_class_sums_stack.lower(
            o["lits"], o["r_stack"], o["include"], icfg, cfg, **off),
        "imbue_infer_packed_stack":
            lambda: ops.imbue_class_sums_stack_packed.lower(
                o["litw"], o["r_stack"], o["include"], icfg, cfg, **off),
        "imbue_infer_planes_nominal":
            lambda: ops.imbue_class_sums_stack_planes.lower(
                o["litw"], o["incw"], None, icfg, cfg, **planes),
        "imbue_infer_planes_dev":
            lambda: ops.imbue_class_sums_stack_planes.lower(
                o["litw"], o["incw"], o["dev"], icfg, cfg, **planes),
        "imbue_infer_planes_programmed":
            lambda: ops.imbue_class_sums_stack_planes.lower(
                o["litw"], o["incw"], o["dev"], icfg, cfg,
                col_diff_t=o["col_diff_t"], col_leak_t=o["col_leak_t"],
                **planes),
    }[kernel]()


KERNELS = ("clause_eval", "clause_eval_packed", "tm_infer", "tm_infer_packed",
           "coalesced", "coalesced_packed", "coalesced_planes",
           "imbue_infer_stack", "imbue_infer_packed_stack",
           "imbue_infer_planes_nominal", "imbue_infer_planes_dev",
           "imbue_infer_planes_programmed")


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(kernel, model, one_chip, no_compile_cache):
    cfg, o = _operands(model, one_chip)
    compiled = _lower(kernel, cfg, o).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The engines whose forward the benchmark's trace reduction reads: the
# KWS-6 Table IV width, nominal, one replica; the F-MNIST Table IV width
# under D2D variation, an R=4 ensemble.
TRACED_ENGINES = {
    "kws6-nominal-r1": dict(shape=(6, 300, 377), density=0.006,
                            replicas=1, d2d=False, routing="round_robin",
                            bucket=32, dots="default", columns=None),
    "fmnist-d2d-r4-ensemble": dict(shape=(10, 500, 784), density=0.0033,
                                   replicas=4, d2d=True, routing="ensemble",
                                   bucket=128, dots="bf16x3",
                                   columns="programmed"),
}


@pytest.mark.parametrize("engine_kind", sorted(TRACED_ENGINES))
def test_forward_and_kernel_keep_the_names_the_trace_reads(
        engine_kind, one_chip, no_compile_cache):
    """The benchmark's trace reduction (``bench/readings.py``) finds the
    serving step by its module name and the kernel by its op name: each
    engine's forward, compiled for the chip, must be module ``jit_fwd``
    holding a ``%imbue_class_sums*`` custom call."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench"))
    import readings
    from repro.core.tm import TMConfig
    from repro.core.variations import VariationConfig
    from repro.serve import BatcherConfig, EngineConfig, ServeEngine

    e = TRACED_ENGINES[engine_kind]
    assert (readings.FORWARD_MODULE, readings.KERNEL_OP) == (
        "jit_fwd", "%imbue_class_sums")
    classes, per_class, features = e["shape"]
    cfg = TMConfig(n_classes=classes, clauses_per_class=per_class,
                   n_features=features, n_states=127)
    inc = jax.random.bernoulli(jax.random.PRNGKey(5), e["density"],
                               (cfg.n_clauses, cfg.n_literals))
    ta = jnp.where(inc, cfg.n_states + 1, cfg.n_states).astype(
        cfg.state_dtype)
    vcfg = (VariationConfig(c2c=False, csa_offset=False) if e["d2d"]
            else VariationConfig.nominal())
    b = e["bucket"]
    engine = ServeEngine.from_ta_state(
        ta, cfg, n_replicas=e["replicas"], vcfg=vcfg,
        ecfg=EngineConfig(interpret=False, routing=e["routing"],
                          batcher=BatcherConfig(max_batch=b,
                                                bucket_sizes=(b,))))
    assert engine.backend.name == "analog-pallas-packed2"
    assert not engine.selection.fell_back
    assert engine.summary()["crossbar_dots"] == e["dots"]
    assert engine._columns_full == e["columns"]
    ensemble = e["routing"] == "ensemble"
    state = engine.state if ensemble else engine._slices[0]
    mask = engine._healthy_mask if ensemble else engine._mask_one

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    lits = jax.ShapeDtypeStruct((b, bitpack.words_for(cfg.n_literals)),
                                jnp.uint32, sharding=one_chip)
    text = engine._fwd.lower(jax.tree.map(spec, state), lits, None,
                             spec(mask), bt=b).compile().as_text()
    assert text.startswith(f"HloModule {readings.FORWARD_MODULE},")
    assert any(line.lstrip().startswith(readings.KERNEL_OP)
               and "custom-call" in line for line in text.splitlines())


def test_jnp_crossbar_dots_compile_at_highest_precision(
        one_chip, no_compile_cache):
    """The ``analog-jnp`` path (a sharded pool, a CSA-offset pool) reads
    column currents through ``core.imbue``'s einsums; compiled for the
    chip at the F-MNIST width, every contraction keeps f32 operands at
    the highest precision rather than the TPU's one-pass bfloat16."""
    import re
    from repro import api
    from repro.core.variations import VariationConfig
    cfg, o = _operands("imbue-tm-fmnist", one_chip)
    state = api.ReplicaStackState(
        r_stack=o["r_stack"], include=o["include"], tm_cfg=cfg,
        vcfg=VariationConfig(c2c=False, csa_offset=False))
    backend = api.get_backend("analog-jnp")
    text = jax.jit(backend.fn).lower(state, o["lits"]).compile().as_text()
    contractions = [line for line in text.splitlines()
                    if re.search(r"= f32\[[^]]*\]\{[^}]*\} "
                                 r"(dot|convolution)\(", line)]
    assert contractions
    assert all("operand_precision={highest,highest}" in line
               for line in contractions), contractions
