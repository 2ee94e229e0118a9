"""Roofline analyzer tests: the HLO walker must multiply while-body costs
by trip counts (XLA's cost_analysis counts loop bodies once — verified
here) and parse collectives/dots from partitioned modules."""

import jax
import jax.numpy as jnp
import pytest

from repro.roofline.analysis import HloCost, parse_computations


def _scan_fn(x, ws):
    y, _ = jax.lax.scan(lambda c, w: (c @ w, ()), x, ws)
    return y


def _unrolled_fn(x, ws):
    for i in range(8):
        x = x @ ws[i]
    return x


@pytest.fixture(scope="module")
def compiled_pair():
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
    cs = jax.jit(_scan_fn).lower(x, ws).compile()
    cu = jax.jit(_unrolled_fn).lower(x, ws).compile()
    return cs, cu


def test_xla_cost_analysis_undercounts_scan(compiled_pair):
    """The motivating bug: XLA counts the while body once."""
    cs, cu = compiled_pair
    cost_s = cs.cost_analysis()
    cost_u = cu.cost_analysis()
    assert cost_s["flops"] < cost_u["flops"] / 4


def test_walker_matches_analytic_flops(compiled_pair):
    cs, cu = compiled_pair
    expected = 2.0 * 8 * 256 ** 3
    assert HloCost(cs.as_text()).flops == pytest.approx(expected, rel=1e-6)
    assert HloCost(cu.as_text()).flops == pytest.approx(expected, rel=1e-6)


def test_walker_counts_grad_of_scan():
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)

    def loss(x, ws):
        return (_scan_fn(x, ws) ** 2).sum()

    comp = jax.jit(jax.grad(loss, argnums=1)).lower(x, ws).compile()
    got = HloCost(comp.as_text()).flops
    # fwd + 2 bwd matmuls per layer = 3x
    assert got == pytest.approx(3 * 2.0 * 8 * 256 ** 3, rel=0.05)


def test_trip_count_detection(compiled_pair):
    cs, _ = compiled_pair
    hc = HloCost(cs.as_text())
    assert any(trip == 8 for _, trip in hc.loops)


def test_parse_synthetic_module():
    hlo = """HloModule test

%body (arg: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %arg = (s32[], f32[64,64]) parameter(0)
  %g = f32[64,64]{1,0} get-tuple-element(%arg), index=1
  %d = f32[64,64]{1,0} dot(%g, %g), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[64,64]{1,0} all-reduce(%d), replica_groups={}
}

%cond (arg: (s32[], f32[64,64])) -> pred[] {
  %arg2 = (s32[], f32[64,64]) parameter(0)
  %i = s32[] get-tuple-element(%arg2), index=0
  %n = s32[] constant(5)
  %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[64,64]) -> f32[64,64] {
  %x = f32[64,64]{1,0} parameter(0)
  %t = (s32[], f32[64,64]) tuple(%x, %x)
  %w = (s32[], f32[64,64]) while(%t), condition=%cond, body=%body
}
"""
    hc = HloCost(hlo)
    assert hc.flops == pytest.approx(5 * 2 * 64 ** 3)
    # all-reduce: 5 iterations x 2 (ring factor) x 16KB
    assert hc.collective_bytes == pytest.approx(5 * 2 * 64 * 64 * 4)
    comps, types = parse_computations(hlo)
    assert set(comps) == {"body", "cond", "@entry"}


def test_collective_detail_classification():
    hlo = """HloModule t

ENTRY %main (x: f32[128]) -> f32[128] {
  %x = f32[128]{0} parameter(0)
  %ag = f32[128]{0} all-gather(%x), dimensions={0}
  %aa = f32[128]{0} all-to-all(%ag), dimensions={0}
  %cp = f32[128]{0} collective-permute(%aa), source_target_pairs={{0,1}}
}
"""
    hc = HloCost(hlo)
    assert set(hc.collective_detail) == {"all-gather", "all-to-all",
                                         "collective-permute"}
    assert hc.collective_bytes == pytest.approx(3 * 128 * 4)


def test_dot_flops_with_batch_dims():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)
    a = jax.ShapeDtypeStruct((4, 32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 64, 16), jnp.float32)
    comp = jax.jit(f).lower(a, b).compile()
    got = HloCost(comp.as_text()).flops
    assert got == pytest.approx(2 * 4 * 32 * 64 * 16, rel=1e-6)


def test_peaks_keyed_by_device_kind():
    """Peaks come from a table keyed by ``device_kind`` with a source; a
    chip that is not in it is an error, not a silent v5e default."""
    from repro.roofline.analysis import TARGET_KIND, peaks
    v5e = peaks(TARGET_KIND)
    assert (v5e.bf16_flops, v5e.hbm_bw) == (197e12, 819e9)
    assert v5e.source
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")


def test_kernel_roofline_keys_by_the_attached_chip(monkeypatch):
    """Off a TPU the kernel bench models the target chip; beside a TPU
    it takes that chip's peaks, and an unlisted chip raises."""
    import importlib.util
    import types
    from pathlib import Path

    from repro.roofline.analysis import TARGET_KIND
    path = Path(__file__).resolve().parents[1] / "benchmarks/kernel_bench.py"
    spec = importlib.util.spec_from_file_location("kernel_bench", path)
    kb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kb)
    assert kb.roofline_kind() == TARGET_KIND
    unlisted = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(kb.jax, "devices", lambda *a: [unlisted])
    with pytest.raises(ValueError, match="no published peaks"):
        kb.kernel_roofline(128, 128, 128, bt=128, ct=128, kt=128)
