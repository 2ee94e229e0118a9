"""Unified backend API tests (ISSUE 2).

Three guarantees:

1. **Pytree round-trips** — every registered state survives
   ``tree_flatten``/``tree_unflatten`` and ``tree_map`` with aux config
   intact, and passes through ``jit`` as a *traced* argument.
2. **Backend parity matrix** — every registered backend is bit-identical
   to the digital reference ``tm.forward`` at
   ``VariationConfig.nominal()``.
3. **Single-dispatch replica stacks** — ``analog-pallas`` over a
   ``ReplicaStackState`` invokes the kernel wrapper exactly once for the
   whole stack (vmap batching rule), not once per chip.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import tm
from repro.core.coalesced import CoalescedConfig
from repro.core.tm import TMConfig
from repro.core.variations import VariationConfig
from repro.kernels import ops

NOMINAL = VariationConfig.nominal()


@pytest.fixture(scope="module")
def states(small_cfg, random_ta, keys):
    """One instance of every registered state, all encoding the SAME
    model (so every backend must produce the same class sums)."""
    cfg = small_cfg
    inc = tm.include_mask(random_ta, cfg)
    # a coalesced state that emulates the vanilla TM: weights are the
    # signed polarity one-hot, so sums match tm.forward exactly
    ccfg = CoalescedConfig(n_classes=cfg.n_classes,
                           n_clauses=cfg.n_clauses,
                           n_features=cfg.n_features,
                           n_states=cfg.n_states)
    w = ops.polarity_matrix(cfg, inc,
                            n_class_pad=cfg.n_classes).astype(jnp.int32)
    out = {
        "digital": api.DigitalState.from_ta(random_ta, cfg),
        "crossbar": api.CrossbarState.program(inc, keys["program"], cfg,
                                              NOMINAL),
        "stack": api.ReplicaStackState.program(inc, keys["program"], 3,
                                               cfg, NOMINAL),
        "coalesced": api.CoalescedState(ta_state=random_ta, weights=w,
                                        cfg=ccfg),
    }
    # packed twins: same model, uint32 include bitplane attached
    out["digital_packed"] = out["digital"].pack()
    out["crossbar_packed"] = out["crossbar"].pack()
    out["stack_packed"] = out["stack"].pack()
    out["coalesced_packed"] = out["coalesced"].pack()
    # plane-packed twins (ISSUE 9): resident conductance planes folded
    # into the LRS/HRS index bitplane (deviation plane elided — the
    # fixture programs at nominal)
    out["crossbar_planes"] = out["crossbar"].pack_planes()
    out["stack_planes"] = out["stack"].pack_planes()
    out["coalesced_planes"] = out["coalesced"].pack_planes()
    return out


# ------------------------------------------------------ pytree round-trips

@pytest.mark.parametrize("name", ["digital", "crossbar", "stack",
                                  "coalesced", "digital_packed",
                                  "stack_packed", "coalesced_packed",
                                  "stack_planes", "coalesced_planes"])
def test_state_pytree_roundtrip(states, name):
    s = states[name]
    leaves, treedef = jax.tree_util.tree_flatten(s)
    s2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert type(s2) is type(s)
    assert jax.tree_util.tree_structure(s2) == \
        jax.tree_util.tree_structure(s)
    for a, b in zip(leaves, jax.tree_util.tree_leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # static config rides in aux_data, not in the leaves
    assert not any(isinstance(x, (TMConfig, CoalescedConfig))
                   for x in leaves)


@pytest.mark.parametrize("name", ["digital", "crossbar", "stack",
                                  "coalesced"])
def test_state_tree_map_preserves_type_and_config(states, name):
    s = states[name]
    s2 = jax.tree_util.tree_map(lambda x: x, s)
    assert type(s2) is type(s)
    cfg_field = "cfg" if name == "coalesced" else "tm_cfg"
    assert getattr(s2, cfg_field) == getattr(s, cfg_field)


@pytest.mark.parametrize("name,backend", [
    ("digital", "digital-jnp"), ("crossbar", "analog-jnp"),
    ("stack", "analog-jnp"), ("coalesced", "coalesced"),
])
def test_state_traces_through_jit(states, boolean_batch, name, backend):
    """States are valid *traced* jit arguments: configs hash as static
    aux_data, arrays trace as leaves."""
    s = states[name]
    lits = tm.literals(jnp.asarray(boolean_batch[:8]))

    @jax.jit
    def fwd(state, lits):
        return api.class_sums(state, lits, backend=backend)

    got = fwd(s, lits)
    want = api.class_sums(s, lits, backend=backend)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_device_put_roundtrip(states):
    s = jax.device_put(states["stack"])
    assert isinstance(s, api.ReplicaStackState)
    assert s.tm_cfg == states["stack"].tm_cfg


def test_replica_slice_and_single_replica(states):
    s = states["stack"]
    sl = s.replica_slice(1)
    assert isinstance(sl, api.ReplicaStackState) and sl.n_replicas == 1
    np.testing.assert_array_equal(np.asarray(sl.r_stack[0]),
                                  np.asarray(s.r_stack[1]))
    one = s.replica(2)
    assert isinstance(one, api.CrossbarState)
    np.testing.assert_array_equal(np.asarray(one.r_mem),
                                  np.asarray(s.r_stack[2]))


# --------------------------------------------------- backend parity matrix

def test_parity_matrix_all_backends_match_digital_reference(
        states, small_cfg, random_ta, boolean_batch):
    """EVERY registered backend == ``tm.forward`` bit-for-bit at nominal
    variation, over every state (packed and unpacked) it accepts.
    Iterates the registry so a newly registered backend is automatically
    held to the same bar; the packed backends are exercised with BOTH
    wire formats (pre-packed uint32 words and auto-packed uint8
    literals)."""
    from repro.kernels import ops
    x = jnp.asarray(boolean_batch)
    lits = tm.literals(x)
    litw = ops.pack_literals(lits)
    ref = np.asarray(tm.forward(random_ta, x, small_cfg))
    checked = 0
    for backend in api.list_backends():
        packed_io = api.CAP_PACKED_IO in backend.capabilities
        for name, state in states.items():
            if not backend.accepts(state):
                continue
            wires = (lits, litw) if packed_io else (lits,)
            for wire in wires:
                got = np.asarray(api.class_sums(state, wire,
                                                backend=backend.name))
                assert got.dtype == np.int32, (backend.name, got.dtype)
                if got.ndim == 3:                   # replica stack
                    for r in range(got.shape[0]):
                        np.testing.assert_array_equal(
                            got[r], ref, err_msg=f"{backend.name}/{name}")
                else:
                    np.testing.assert_array_equal(
                        got, ref, err_msg=f"{backend.name}/{name}")
            checked += 1
    # digital{jnp,pallas} x {digital, digital_packed} = 4,
    # digital-pallas-packed x {digital_packed} = 1,
    # analog{jnp,pallas} x {crossbar, stack} x {unpacked, packed} = 8,
    # analog-pallas-packed x {crossbar_packed, stack_packed} = 2,
    # coalesced{,-pallas} x {coalesced, coalesced_packed} = 4,
    # coalesced-pallas-packed x {coalesced_packed} = 1,
    # + plane-packed (ISSUE 9): {crossbar,stack}_planes accepted by the
    #   four analog backends = 8, coalesced_planes by the four
    #   coalesced backends = 4
    #   ->  32 (state, backend) cells
    assert checked >= 32


def test_predict_matches_digital_argmax(states, random_ta, small_cfg,
                                        boolean_batch):
    x = jnp.asarray(boolean_batch)
    want = np.asarray(tm.predict(random_ta, x, small_cfg))
    for name in ("digital", "crossbar", "stack", "coalesced"):
        got = np.asarray(api.predict(states[name], x))
        np.testing.assert_array_equal(got, want, err_msg=name)


# ------------------------------------------------- capability selection

def test_selection_prefers_fused_kernel_at_nominal(states):
    sel = api.select_backend(states["stack"])
    assert sel.backend.name == "analog-pallas" and not sel.fell_back


def test_selection_prefers_packed_backend_for_packed_state(states):
    """A packed state selects the packed_io kernel (highest priority);
    an unpacked state can never land on it (predicate gating); an
    explicit unpacked preference is still honored."""
    sel = api.select_backend(states["stack_packed"])
    assert sel.backend.name == "analog-pallas-packed" and not sel.fell_back
    assert api.CAP_PACKED_IO in sel.backend.capabilities
    sel_d = api.select_backend(states["digital_packed"])
    assert sel_d.backend.name == "digital-pallas-packed"
    # unpacked state: packed backends are not even candidates
    assert not api.get_backend("analog-pallas-packed").accepts(
        states["stack"])
    sel_u = api.select_backend(states["stack"])
    assert sel_u.backend.name == "analog-pallas"
    # explicit pin beats the packed preference, loudly satisfiable
    sel_pin = api.select_backend(states["stack_packed"],
                                 prefer="analog-pallas")
    assert sel_pin.backend.name == "analog-pallas" and not sel_pin.fell_back


def test_selection_prefers_planes_backend_for_plane_packed_state(states):
    """A plane-packed state selects the packed2 kernel (priority 40);
    a merely-packed state can never land on it (predicate gating)."""
    sel = api.select_backend(states["stack_planes"])
    assert sel.backend.name == "analog-pallas-packed2" and not sel.fell_back
    assert api.CAP_PACKED_PLANES in sel.backend.capabilities
    sel_c = api.select_backend(states["coalesced_planes"])
    assert sel_c.backend.name == "coalesced-pallas-packed2"
    assert not api.get_backend("analog-pallas-packed2").accepts(
        states["stack_packed"])
    # pack_planes implies pack: the index bitplane IS the include plane
    assert states["stack_planes"].packed
    assert states["stack_planes"].plane_index is \
        states["stack_planes"].include_packed


@pytest.mark.parametrize("chip,keyed,mode", [
    ("nominal", False, "default"),
    ("nominal", True, "default"),
    ("c2c", False, "default"),
    ("c2c", True, "bf16x3"),
    ("d2d", False, "bf16x3"),
])
def test_planes_dot_mode_follows_the_deviation_the_read_senses(
        states, keys, chip, keyed, mode):
    """The plane-packed backend reports the dot mode its kernel runs: f32
    products (``bf16x3``) whenever the read senses a deviation plane, a
    programmed D2D one or a keyed C2C draw; the default dots otherwise.
    The other analog backends report theirs; digital paths have none."""
    st = states["stack_planes"]
    if chip == "c2c":
        st = dataclasses.replace(st, vcfg=dataclasses.replace(NOMINAL,
                                                              c2c=True))
    elif chip == "d2d":
        inc = st.include
        st = api.ReplicaStackState.program(
            inc, keys["program"], 2, st.tm_cfg,
            VariationConfig(c2c=False, csa_offset=False)).pack_planes()
        assert st.plane_dev is not None
    assert api.get_backend("analog-pallas-packed2").dot_mode(
        st, keyed) == mode
    assert api.get_backend("analog-jnp").dot_mode(st, keyed) == "highest"
    assert api.get_backend("analog-pallas").dot_mode(st, keyed) == "default"
    assert all(b.dot_mode is None for b in api.list_backends()
               if api.CAP_ANALOG not in b.capabilities)


def test_selection_packed_state_with_csa_noise_falls_back(small_cfg, keys):
    """csa_offset still wins over packed preference: the packed kernel
    lacks models_csa_offset, so a noisy read falls back (loudly) to
    analog-jnp — which also forfeits packed io."""
    inc = jax.random.bernoulli(keys["init"], 0.1,
                               (small_cfg.n_clauses,
                                small_cfg.n_literals))
    noisy = api.ReplicaStackState.program(
        inc, keys["program"], 2, small_cfg, VariationConfig()).pack()
    sel = api.select_backend(noisy, key=jax.random.PRNGKey(0),
                             prefer="analog-pallas-packed")
    assert sel.fell_back and sel.backend.name == "analog-jnp"
    assert "models_csa_offset" in sel.fallback_reason


def test_pack_is_idempotent_and_preserves_model(states):
    s = states["stack"]
    p = s.pack()
    assert p.packed and p.pack() is p
    assert not s.packed                       # pack() is non-mutating
    np.testing.assert_array_equal(np.asarray(p.r_stack),
                                  np.asarray(s.r_stack))
    from repro.kernels import bitpack
    np.testing.assert_array_equal(
        np.asarray(bitpack.unpack_bits(p.include_packed,
                                       s.include.shape[-1])),
        np.asarray(s.include).astype(np.uint8))
    # replica_slice keeps the packed plane
    assert p.replica_slice(0).packed


def test_selection_falls_back_on_csa_offset(small_cfg, keys):
    inc = jax.random.bernoulli(keys["init"], 0.1,
                               (small_cfg.n_clauses,
                                small_cfg.n_literals))
    noisy = api.ReplicaStackState.program(inc, keys["program"], 2,
                                          small_cfg, VariationConfig())
    key = jax.random.PRNGKey(0)
    sel = api.select_backend(noisy, key=key, prefer="analog-pallas")
    assert sel.fell_back and sel.backend.name == "analog-jnp"
    assert "models_csa_offset" in sel.fallback_reason
    # without a read key there is no noise draw, so no fallback
    sel2 = api.select_backend(noisy, prefer="analog-pallas")
    assert not sel2.fell_back and sel2.backend.name == "analog-pallas"


def test_selection_rejects_wrong_state_type(states):
    sel = api.select_backend(states["digital"], prefer="analog-pallas")
    assert sel.fell_back and sel.backend.name == "digital-pallas"
    with pytest.raises(KeyError, match="unknown backend"):
        api.select_backend(states["digital"], prefer="no-such-backend")


def test_required_capabilities(states, small_cfg, keys):
    assert api.CAP_REPLICA_VMAP in \
        api.required_capabilities(states["stack"])
    assert api.CAP_DIGITAL in \
        api.required_capabilities(states["digital"])
    inc = jax.random.bernoulli(keys["init"], 0.1,
                               (small_cfg.n_clauses,
                                small_cfg.n_literals))
    noisy = api.CrossbarState.program(inc, keys["program"], small_cfg,
                                      VariationConfig())
    need = api.required_capabilities(noisy, key=jax.random.PRNGKey(0))
    assert {api.CAP_MODELS_CSA_OFFSET, api.CAP_MODELS_C2C} <= need


def test_register_backend_validates_vocabulary():
    with pytest.raises(ValueError, match="unknown capabilities"):
        api.register_backend("bogus", state_types=(api.DigitalState,),
                             capabilities={"not_a_capability"})(lambda s, l, k: None)


# --------------------------------------- single-dispatch replica hot path

def test_stack_dispatch_has_no_per_replica_loop(monkeypatch, keys):
    """The whole [R, C, L] stack goes through ONE ``imbue_class_sums_raw``
    invocation (vmap batching), not R of them.  A distinct shape forces a
    fresh trace so the count is meaningful."""
    cfg = TMConfig(n_classes=3, clauses_per_class=6, n_features=24,
                   n_states=100)
    inc = jax.random.bernoulli(keys["init"], 0.15,
                               (cfg.n_clauses, cfg.n_literals))
    state = api.ReplicaStackState.program(inc, keys["program"], 4, cfg,
                                          NOMINAL)
    lits = tm.literals(jax.random.bernoulli(
        keys["data"], 0.4, (8, cfg.n_features)).astype(jnp.uint8))

    calls = []
    real = ops.imbue_class_sums_raw
    monkeypatch.setattr(ops, "imbue_class_sums_raw",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    sums = api.class_sums(state, lits, backend="analog-pallas", bt=8)
    assert len(calls) == 1, f"{len(calls)} kernel invocations for R=4"
    ta = jnp.where(inc, cfg.n_states + 1, cfg.n_states).astype(
        cfg.state_dtype)
    ref = np.asarray(tm.forward(
        ta, jnp.asarray(lits[:, :cfg.n_features]), cfg))
    for r in range(4):
        np.testing.assert_array_equal(np.asarray(sums[r]), ref)


def test_deprecated_stacked_shim_matches_new_path(states, small_cfg,
                                                  boolean_batch):
    s = states["stack"]
    lits = tm.literals(jnp.asarray(boolean_batch[:8]))
    with pytest.warns(DeprecationWarning):
        old = ops.imbue_class_sums_stacked(lits, s.r_stack, s.include,
                                           s.icfg, small_cfg, vcfg=s.vcfg,
                                           bt=8)
    new = ops.imbue_class_sums_stack(lits, s.r_stack, s.include, s.icfg,
                                     small_cfg, vcfg=s.vcfg, bt=8)
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))


# ----------------------------------------------- satellite: ops hygiene

def test_polarity_matrix_validates_class_padding(small_cfg):
    with pytest.raises(ValueError, match="n_class_pad"):
        ops.polarity_matrix(small_cfg, n_class_pad=2)
    p = ops.polarity_matrix(small_cfg, n_class_pad=small_cfg.n_classes)
    assert p.shape == (small_cfg.n_clauses, small_cfg.n_classes)


# --------------------------------------------- serve pool pytree survival

def test_replica_pool_survives_tree_map(small_cfg, keys):
    from repro.serve import program_replica_pool
    inc = jax.random.bernoulli(keys["init"], 0.1,
                               (small_cfg.n_clauses,
                                small_cfg.n_literals))
    pool = program_replica_pool(inc, keys["program"], 3, NOMINAL)
    pool2 = jax.tree_util.tree_map(lambda x: x, pool)
    assert type(pool2) is type(pool) and pool2.n_replicas == 3
    assert pool2.icfg == pool.icfg and pool2.vcfg == pool.vcfg
    np.testing.assert_array_equal(np.asarray(pool2.r_stack),
                                  np.asarray(pool.r_stack))
    # routing counters are NOT device state: they live in RouterState
    assert not hasattr(pool2, "rows_dispatched")
    router = pool.router()
    router.note_dispatch(router.pick("round_robin"), 8)
    assert router.rows_dispatched == [8, 0, 0]
    assert dataclasses.fields(pool)  # frozen dataclass, still introspectable
