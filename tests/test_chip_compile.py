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

    return cfg, dict(
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
    }[kernel]()


KERNELS = ("clause_eval", "clause_eval_packed", "tm_infer", "tm_infer_packed",
           "coalesced", "coalesced_packed", "coalesced_planes",
           "imbue_infer_stack", "imbue_infer_packed_stack",
           "imbue_infer_planes_nominal", "imbue_infer_planes_dev")


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(kernel, model, one_chip, no_compile_cache):
    cfg, o = _operands(model, one_chip)
    compiled = _lower(kernel, cfg, o).compile()
    assert "tpu_custom_call" in compiled.as_text()
