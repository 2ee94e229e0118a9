"""Hypothesis property tests for the plane-packed resident format
(ISSUE 9).

The plane-packed representation folds the programmed conductance stack
into the LRS/HRS index bitplane (shared with ``include_packed``) plus
an additive per-cell deviation plane (``r_mem - r_nom``, elided when
all-zero).  These properties pin the invariants the packed2 kernels
rely on:

* reconstruction is the identity — ``r_nom + plane_dev`` equals the
  programmed resistances bitwise (f32), for ragged C/L, D2D draws, and
  fault-overlaid stacks;
* at nominal programming the deviation plane is elided and the packed2
  backends reproduce the digital reference bit-for-bit;
* off-nominal (D2D and stuck-at overlays) the packed2 integer class
  sums equal the dense analog path's exactly.

Follows the repo convention: property tests live in ``*_properties.py``
modules that ``importorskip`` hypothesis, so tier-1 stays green when it
is absent (CI installs it; both paths must pass).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hyp = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro import api  # noqa: E402
from repro.core import tm, variations as var  # noqa: E402
from repro.core.tm import TMConfig  # noqa: E402
from repro.core.variations import FaultConfig, VariationConfig  # noqa: E402
from repro.kernels import bitpack, ops  # noqa: E402

NOMINAL = VariationConfig.nominal()
D2D = VariationConfig(d2d=True, c2c=False, csa_offset=False)


def _ragged_cfg(n_classes, clauses_per_class, n_features):
    return TMConfig(n_classes=n_classes, clauses_per_class=clauses_per_class,
                    n_features=n_features, n_states=100)


@settings(max_examples=15, deadline=None)
@given(n_classes=st.integers(2, 4),
       clauses_per_class=st.sampled_from([2, 4, 6]),
       n_features=st.integers(3, 40), seed=st.integers(0, 2**16))
def test_deviation_plane_reconstruction_is_identity(
        n_classes, clauses_per_class, n_features, seed):
    """``r_nom(plane_index) + plane_dev == r_mem`` bitwise (f32) for
    ragged C/L under D2D programming draws — pack time quantizes each
    cell to its own reconstruction (<= 0.5 ulp), so the identity is
    structural, not probabilistic; the index bitplane unpacks back to
    the include mask; nominal chips elide the plane entirely."""
    cfg = _ragged_cfg(n_classes, clauses_per_class, n_features)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    inc = jax.random.bernoulli(k1, 0.2, (cfg.n_clauses, cfg.n_literals))
    noisy = api.CrossbarState.program(inc, k2, cfg, D2D).pack_planes()
    include = np.asarray(
        bitpack.unpack_bits(noisy.plane_index, cfg.n_literals))
    np.testing.assert_array_equal(include,
                                  np.asarray(inc).astype(np.uint8))
    r_nom = np.where(np.asarray(inc), var.LRS_MEAN_OHM,
                     var.HRS_MEAN_OHM).astype(np.float32)
    assert noisy.plane_dev is not None  # D2D draws always deviate
    got = r_nom + np.asarray(noisy.plane_dev)
    np.testing.assert_array_equal(got, np.asarray(noisy.r_mem,
                                                  np.float32))
    # nominal chip: same index bitplane, no deviation plane at all
    clean = api.CrossbarState.program(inc, k2, cfg, NOMINAL).pack_planes()
    assert clean.plane_dev is None
    np.testing.assert_array_equal(np.asarray(clean.plane_index),
                                  np.asarray(noisy.plane_index))


@settings(max_examples=8, deadline=None)
@given(n_classes=st.integers(2, 4),
       clauses_per_class=st.sampled_from([2, 4]),
       n_features=st.integers(3, 33), b=st.integers(1, 6),
       seed=st.integers(0, 2**16))
def test_packed2_matches_digital_reference_at_nominal_ragged(
        n_classes, clauses_per_class, n_features, b, seed):
    """Bit-exactness at nominal over ragged C/L: the plane-packed
    analog kernel reproduces ``tm.forward`` exactly, including literal
    lengths nowhere near the 32-bit word or kernel tile boundaries."""
    cfg = _ragged_cfg(n_classes, clauses_per_class, n_features)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    inc = jax.random.bernoulli(k1, 0.2, (cfg.n_clauses, cfg.n_literals))
    x = jax.random.bernoulli(k2, 0.4, (b, cfg.n_features)).astype(
        jnp.uint8)
    state = api.CrossbarState.program(inc, k3, cfg, NOMINAL).pack_planes()
    sel = api.select_backend(state)
    assert sel.backend.name == "analog-pallas-packed2" and not sel.fell_back
    got = np.asarray(api.class_sums(state, tm.literals(x)))
    ta = jnp.where(inc, cfg.n_states + 1, cfg.n_states).astype(
        cfg.state_dtype)
    np.testing.assert_array_equal(got, np.asarray(tm.forward(ta, x, cfg)))


@settings(max_examples=6, deadline=None)
@given(n_features=st.integers(3, 24), n_replicas=st.integers(1, 3),
       lrs_rate=st.floats(0.0, 0.4), hrs_rate=st.floats(0.0, 0.4),
       seed=st.integers(0, 2**16))
def test_fault_overlaid_stack_roundtrips_and_matches_dense(
        n_features, n_replicas, lrs_rate, hrs_rate, seed):
    """Stuck-at overlays fold into the deviation plane: after
    ``inject_faults`` on a plane-packed stack, the index bitplane is
    untouched (intended actions), the deviation plane re-derives from
    the injured resistances exactly, and the packed2 integer class sums
    equal the dense ``analog-jnp`` path's bit-for-bit on the SAME
    injured state (the dense backend reads ``r_stack``, the packed2
    kernel reconstructs it from the planes — identical by the
    quantize-on-pack invariant)."""
    cfg = _ragged_cfg(2, 2, n_features)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    inc = jax.random.bernoulli(k1, 0.25, (cfg.n_clauses, cfg.n_literals))
    x = jax.random.bernoulli(k2, 0.4, (4, cfg.n_features)).astype(
        jnp.uint8)
    stack = api.ReplicaStackState.program(inc, k3, n_replicas, cfg, D2D)
    planes = stack.pack_planes()
    fcfg = FaultConfig(stuck_lrs_rate=lrs_rate, stuck_hrs_rate=hrs_rate)
    injured = planes.inject_faults(k4, fcfg)
    # the index bitplane records intended actions — faults never move it
    np.testing.assert_array_equal(np.asarray(injured.plane_index),
                                  np.asarray(planes.plane_index))
    if injured.plane_dev is not None:
        r_nom = np.where(np.asarray(inc), var.LRS_MEAN_OHM,
                         var.HRS_MEAN_OHM).astype(np.float32)
        np.testing.assert_array_equal(
            r_nom[None] + np.asarray(injured.plane_dev),
            np.asarray(injured.r_stack, np.float32))
    lits = tm.literals(x)
    got = np.asarray(api.class_sums(injured, lits,
                                    backend="analog-pallas-packed2"))
    # dense reference on the SAME injured state: analog-jnp ignores the
    # planes and streams r_stack directly
    want = np.asarray(api.class_sums(injured, lits, backend="analog-jnp"))
    np.testing.assert_array_equal(got, want)


@settings(max_examples=6, deadline=None)
@given(n_features=st.integers(3, 24), b=st.integers(1, 5),
       seed=st.integers(0, 2**16))
def test_packed2_equals_packed_backend_off_nominal(n_features, b, seed):
    """D2D-programmed chips: identical integer class sums from the
    plane-packed and dense-plane packed kernels on the SAME state (same
    physics, two resident formats — ``analog-pallas-packed`` accepts the
    plane-packed state since plane-packing implies packing, and reads
    its quantized ``r_mem`` dense)."""
    cfg = _ragged_cfg(3, 2, n_features)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    inc = jax.random.bernoulli(k1, 0.2, (cfg.n_clauses, cfg.n_literals))
    x = jax.random.bernoulli(k2, 0.4, (b, cfg.n_features)).astype(
        jnp.uint8)
    state = api.CrossbarState.program(inc, k3, cfg, D2D).pack_planes()
    lits = tm.literals(x)
    got = np.asarray(api.class_sums(state, lits,
                                    backend="analog-pallas-packed2"))
    want = np.asarray(api.class_sums(state, lits,
                                     backend="analog-pallas-packed"))
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------- programmed column operand

C2C = VariationConfig(d2d=True, c2c=True, csa_offset=False)
STUCK = FaultConfig(stuck_lrs_rate=0.2, stuck_hrs_rate=0.2)


def _d2d_stack(n_features, n_replicas, seed, vcfg=D2D, n_classes=3):
    cfg = _ragged_cfg(n_classes, 2, n_features)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    inc = jax.random.bernoulli(k1, 0.25, (cfg.n_clauses, cfg.n_literals))
    stack = api.ReplicaStackState.program(inc, k2, n_replicas, cfg, vcfg)
    return cfg, stack.pack_planes(), k3


def _columns_of(state, **tiles):
    return ops.planes_column_source(state.plane_dev, state.col_diff_t,
                                    state.vcfg, keyed=False, **tiles)


@settings(max_examples=8, deadline=None)
@given(n_features=st.integers(3, 40), n_replicas=st.integers(1, 3),
       stuck=st.booleans(), b=st.integers(1, 5), seed=st.integers(0, 2**16))
def test_programmed_column_operand_equals_every_path(
        n_features, n_replicas, stuck, b, seed):
    """A D2D stack (with or without a stuck-at overlay) read through its
    programmed column operand gives the integer class sums of the dense
    ``analog-jnp`` path, the dense-plane ``analog-pallas-packed`` kernel
    and the operand built in the dispatch's own graph (tiles whose
    ``kt`` does not divide the stored padding), bit for bit."""
    cfg, state, key = _d2d_stack(n_features, n_replicas, seed)
    k_fault, k_x = jax.random.split(key)
    if stuck:
        state = state.inject_faults(k_fault, STUCK)
    lits = tm.literals(jax.random.bernoulli(
        k_x, 0.4, (b, cfg.n_features)).astype(jnp.uint8))
    assert _columns_of(state) == ops.COLUMNS_PROGRAMMED
    got = np.asarray(api.class_sums(state, lits,
                                    backend="analog-pallas-packed2"))
    for name in ("analog-jnp", "analog-pallas-packed"):
        want = np.asarray(api.class_sums(state, lits, backend=name))
        np.testing.assert_array_equal(got, want, err_msg=name)
    assert _columns_of(state, kt=512) == ops.COLUMNS_BUILT
    built = api.get_backend("analog-pallas-packed2").fn(state, lits, kt=512)
    np.testing.assert_array_equal(got, np.asarray(built))


@settings(max_examples=6, deadline=None)
@given(n_features=st.integers(3, 40), n_replicas=st.integers(1, 3),
       seed=st.integers(0, 2**16))
def test_column_operand_follows_the_deviation_plane(n_features, n_replicas,
                                                    seed):
    """The operand is built with the deviation plane and lives and dies
    with it: each replica view carries its chip's slice, fault injection
    re-derives it from the injured resistances (equal to a fresh build),
    re-programming drops it, and a nominal chip has none."""
    cfg, state, key = _d2d_stack(n_features, n_replicas, seed)
    diff, leak = (np.asarray(state.col_diff_t), np.asarray(state.col_leak_t))
    assert diff.shape[1:] == (256, 128) and leak.shape[1:] == (8, 128)
    for i in range(n_replicas):
        one = state.replica_slice(i)
        np.testing.assert_array_equal(np.asarray(one.col_diff_t),
                                      diff[i:i + 1])
        np.testing.assert_array_equal(np.asarray(one.col_leak_t),
                                      leak[i:i + 1])
        chip = state.replica(i)
        np.testing.assert_array_equal(np.asarray(chip.col_diff_t), diff[i])
        np.testing.assert_array_equal(np.asarray(chip.col_leak_t), leak[i])
    for s in (state, state.replica(0)):
        injured = s.inject_faults(key, STUCK)
        fresh = ops.column_operand(injured.plane_index, injured.plane_dev,
                                   injured.icfg,
                                   l_valid=cfg.n_literals)
        for got, want in zip((injured.col_diff_t, injured.col_leak_t),
                             fresh):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert not np.array_equal(np.asarray(injured.col_diff_t),
                                  np.asarray(s.col_diff_t))
        again = injured.reprogram(injured.include, key)
        assert again.col_diff_t is None and again.col_leak_t is None
        assert again.pack_planes().col_diff_t is not None
    clean = api.CrossbarState.program(state.include, key, cfg,
                                      NOMINAL).pack_planes()
    assert clean.col_diff_t is None and clean.col_leak_t is None


@settings(max_examples=6, deadline=None)
@given(n_features=st.integers(3, 40), n_replicas=st.integers(1, 3),
       b=st.integers(1, 5), seed=st.integers(0, 2**16))
def test_keyed_c2c_read_builds_its_column_operand(n_features, n_replicas, b,
                                                  seed):
    """A keyed C2C read senses a fresh deviation per read, so it streams
    an operand built in its own graph from ``_read_dev``'s draw, not the
    programmed one, and still equals ``analog-pallas-packed`` under the
    same key."""
    cfg, state, key = _d2d_stack(n_features, n_replicas, seed, vcfg=C2C)
    k_read, k_x = jax.random.split(key)
    assert state.col_diff_t is not None
    assert ops.planes_column_source(state.plane_dev, state.col_diff_t,
                                    state.vcfg,
                                    keyed=True) == ops.COLUMNS_BUILT
    lits = tm.literals(jax.random.bernoulli(
        k_x, 0.4, (b, cfg.n_features)).astype(jnp.uint8))
    got = np.asarray(api.class_sums(state, lits, k_read,
                                    backend="analog-pallas-packed2"))
    want = np.asarray(api.class_sums(state, lits, k_read,
                                     backend="analog-pallas-packed"))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("routing", ["ensemble", "round_robin"])
def test_d2d_dispatch_streams_the_programmed_operand(routing):
    """Structure: the compiled replica-stack call of a D2D plane-packed
    stack re-pads and transposes nothing of the stack's padded
    ``[R, L_pad, C_pad]`` shape (the operand arrives in the kernel's
    layout), and every dispatch of a short serving run is counted as
    streaming the programmed operand, at its bytes."""
    import re
    from repro.serve import BatcherConfig, EngineConfig, ServeEngine
    cfg, state, key = _d2d_stack(20, 3, 7)
    lits = tm.literals(jax.random.bernoulli(
        key, 0.4, (8, cfg.n_features)).astype(jnp.uint8))
    backend = api.get_backend("analog-pallas-packed2")
    text = jax.jit(backend.fn).lower(state, lits).compile().as_text()
    r, l_pad, c_pad = state.col_diff_t.shape
    stack = re.compile(rf"= f32\[{r},{l_pad},{c_pad}\]\S* "
                       r"(pad|transpose)\(")
    assert not [line for line in text.splitlines() if stack.search(line)]

    ta = jnp.where(state.include, cfg.n_states + 1, cfg.n_states).astype(
        cfg.state_dtype)
    eng = ServeEngine.from_ta_state(
        ta, cfg, n_replicas=3, vcfg=D2D, key=key,
        ecfg=EngineConfig(routing=routing, batcher=BatcherConfig(
            max_batch=8, bucket_sizes=(8,))))
    x = np.asarray(jax.random.bernoulli(key, 0.4, (20, cfg.n_features)),
                   np.uint8)
    eng.submit_many(list(x))
    eng.drain()
    s = eng.summary()
    assert s["batches"] == 3
    assert s["column_operand"] == {"programmed": 3, "built": 0}
    served = eng.state if routing == "ensemble" else eng._slices[0]
    assert s["resident_bytes_per_dispatch"] == 4 * (
        served.col_diff_t.size + served.col_leak_t.size)
