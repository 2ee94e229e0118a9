"""The registered forward backends + the uniform entry points.

All backends share one contract:

    class_sums(state, lits, key=None, **opts) -> int32 [..., M]

``lits`` is the ``[B, 2F]`` literal matrix (``repro.core.tm.literals``)
— or, for the ``packed_io`` backends, the ``[B, ceil(2F/32)]`` uint32
bitplane (``ops.pack_literals``); outputs are integer class sums (clause
votes are ±1, so every path — including the float32 Pallas kernels —
produces exact integers; the uniform API rounds them back to int32).
``ReplicaStackState`` inputs produce ``[R, B, M]``.

Registered backends:

=========================  =======================  =====================
name                       states                   capability notes
=========================  =======================  =====================
``digital-jnp``            Digital                  the bit-exact
                                                    reference
``digital-pallas``         Digital                  fused clause+polarity
                                                    kernel
``digital-pallas-packed``  Digital (packed)         uint32 bitplane wire,
                                                    AND+popcount kernel
``analog-jnp``             Crossbar, ReplicaStack   models C2C **and**
                                                    CSA offset
``analog-pallas``          Crossbar, ReplicaStack   fused kernel, scalar
                                                    v_ref (no CSA offset)
``analog-pallas-packed``   Crossbar, ReplicaStack   packed literal wire,
                           (packed)                 unpack per K tile in
                                                    VMEM
``analog-pallas-packed2``  Crossbar, ReplicaStack   + plane-packed resident
                           (plane-packed)           operand, double-buffered
                                                    HBM->VMEM DMA
``coalesced``              Coalesced                weighted digital tail;
                                                    GSPMD/sharded path
``coalesced-pallas``       Coalesced                fused kernel, W as the
                                                    combine matrix
``coalesced-pallas-packed`` Coalesced (packed)      packed literal wire +
                                                    weighted tail
``coalesced-pallas-packed2`` Coalesced              + resident bitplane kept
                           (plane-packed)           in HBM, double-buffered
                                                    DMA pipeline
=========================  =======================  =====================

The packed backends only accept states carrying the packed include plane
(``state.pack()``) and — having the highest priority — win selection for
packed states; unpacked ``uint8`` literals remain supported everywhere
(:func:`class_sums` auto-packs at the boundary).  The ``*-packed2``
backends additionally require the plane-packed resident format
(``state.pack_planes()``) and outrank the ``*-packed`` tier for states
that carry it.

Use :func:`class_sums` / :func:`predict` for capability-based dispatch,
or ``get_backend(name).fn`` to pin a backend explicitly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.api.registry import (CAP_ANALOG, CAP_COALESCED, CAP_DIGITAL,
                                CAP_FUSED_KERNEL, CAP_MODELS_C2C,
                                CAP_MODELS_CSA_OFFSET, CAP_PACKED_IO,
                                CAP_PACKED_PLANES, CAP_REPLICA_VMAP,
                                CAP_SHARDED, register_backend,
                                select_backend)
from repro.api.states import (CoalescedState, CrossbarState, DigitalState,
                              ReplicaStackState)
from repro.core import coalesced as co
from repro.core import imbue
from repro.core import tm
from repro.kernels import ops
from repro.kernels.imbue_infer import DOTS_DEFAULT


def _to_i32(sums: jax.Array) -> jax.Array:
    """Class sums are exact small integers on every path; unify dtype."""
    if jnp.issubdtype(sums.dtype, jnp.floating):
        return jnp.round(sums).astype(jnp.int32)
    return sums.astype(jnp.int32)


def _as_packed_lits(lits: jax.Array) -> jax.Array:
    """Accept either wire format: pack uint8 literals at the boundary.

    uint32 inputs are already packed words; anything else is a dense 0/1
    literal matrix and gets packed on device (the migration path — the
    unpacked entry points keep working against packed backends).
    """
    if lits.dtype == jnp.uint32:
        return lits
    return ops.pack_literals(lits)


# ------------------------------------------------------------- digital

@register_backend("digital-jnp", state_types=(DigitalState,),
                  capabilities={CAP_DIGITAL, CAP_SHARDED}, priority=10)
def digital_jnp(state: DigitalState, lits: jax.Array,
                key: Optional[jax.Array] = None) -> jax.Array:
    """Boolean-domain reference: violation matmul + polarity counters."""
    del key                                  # digital path is noise-free
    fired = tm.clause_outputs_from_include(state.include, lits)
    return _to_i32(tm.class_sums(fired, state.tm_cfg))


@register_backend("digital-pallas", state_types=(DigitalState,),
                  capabilities={CAP_DIGITAL, CAP_FUSED_KERNEL}, priority=20)
def digital_pallas(state: DigitalState, lits: jax.Array,
                   key: Optional[jax.Array] = None, **tiles) -> jax.Array:
    """Fused clause-eval + polarity-matmul Pallas kernel."""
    del key
    return _to_i32(ops.tm_class_sums(lits, state.include, state.tm_cfg,
                                     **tiles))


@register_backend("digital-pallas-packed", state_types=(DigitalState,),
                  capabilities={CAP_DIGITAL, CAP_FUSED_KERNEL,
                                CAP_PACKED_IO},
                  priority=30, predicate=lambda s: s.packed)
def digital_pallas_packed(state: DigitalState, lits: jax.Array,
                          key: Optional[jax.Array] = None,
                          **tiles) -> jax.Array:
    """Packed-wire digital kernel: uint32 bitplanes, AND+popcount."""
    del key
    return _to_i32(ops.tm_class_sums_packed(
        _as_packed_lits(lits), state.include_packed, state.tm_cfg, **tiles))


# -------------------------------------------------------------- analog

@register_backend("analog-jnp",
                  state_types=(CrossbarState, ReplicaStackState),
                  capabilities={CAP_ANALOG, CAP_MODELS_C2C,
                                CAP_MODELS_CSA_OFFSET, CAP_REPLICA_VMAP,
                                CAP_SHARDED},
                  priority=10,
                  dot_mode=lambda s, keyed: imbue.DOT_PRECISION.name.lower())
def analog_jnp(state, lits: jax.Array,
               key: Optional[jax.Array] = None) -> jax.Array:
    """Einsum KCL + per-column CSA compare (full noise model).

    Pure jnp ops, so GSPMD partitions the dispatch across a sharded
    ``r_stack`` — the only backend vocabulary that declares
    ``CAP_SHARDED`` alongside the full noise model."""
    if isinstance(state, ReplicaStackState):
        cls = imbue.stacked_clause_outputs(
            state.r_stack, state.include, lits, state.tm_cfg, key,
            state.vcfg, state.icfg)                        # [R, B, C]
        nonempty = state.include.any(axis=-1)
        cls = cls * nonempty[None, None, :].astype(cls.dtype)
    else:
        cls = imbue.analog_clause_outputs_raw(
            state.r_mem, state.include, lits, state.mapping, state.icfg,
            key, state.vcfg)                               # [B, C]
        nonempty = state.include.any(axis=-1)
        cls = cls * nonempty[None, :].astype(cls.dtype)
    return _to_i32(tm.class_sums(cls, state.tm_cfg))


@register_backend("analog-pallas",
                  state_types=(CrossbarState, ReplicaStackState),
                  capabilities={CAP_ANALOG, CAP_FUSED_KERNEL,
                                CAP_MODELS_C2C, CAP_REPLICA_VMAP},
                  priority=20, dot_mode=lambda s, keyed: DOTS_DEFAULT)
def analog_pallas(state, lits: jax.Array,
                  key: Optional[jax.Array] = None, **tiles) -> jax.Array:
    """Fused Boolean-to-Current Pallas kernel (scalar v_ref threshold).

    Replica stacks go through ONE vmapped kernel invocation
    (``ops.imbue_class_sums_stack``) — the serve-pool hot path."""
    if isinstance(state, ReplicaStackState):
        return _to_i32(ops.imbue_class_sums_stack(
            lits, state.r_stack, state.include, state.icfg, state.tm_cfg,
            key, vcfg=state.vcfg, **tiles))
    from repro.core.imbue import conductances
    g_on, i_leak = conductances(state.r_mem, state.include, state.icfg,
                                key, state.vcfg)
    return _to_i32(ops.imbue_class_sums_raw(
        lits, g_on, i_leak, state.include, state.icfg.v_read,
        state.icfg.r_divider, state.icfg.reference_voltage(),
        state.tm_cfg, width=state.icfg.width, **tiles))


@register_backend("analog-pallas-packed",
                  state_types=(CrossbarState, ReplicaStackState),
                  capabilities={CAP_ANALOG, CAP_FUSED_KERNEL,
                                CAP_MODELS_C2C, CAP_REPLICA_VMAP,
                                CAP_PACKED_IO},
                  priority=30, predicate=lambda s: s.packed,
                  dot_mode=lambda s, keyed: DOTS_DEFAULT)
def analog_pallas_packed(state, lits: jax.Array,
                         key: Optional[jax.Array] = None,
                         **tiles) -> jax.Array:
    """Packed-wire analog kernel: literals stream as uint32 words and
    unpack per K tile in VMEM (noise semantics == ``analog-pallas``)."""
    litw = _as_packed_lits(lits)
    if isinstance(state, ReplicaStackState):
        return _to_i32(ops.imbue_class_sums_stack_packed(
            litw, state.r_stack, state.include, state.icfg, state.tm_cfg,
            key, vcfg=state.vcfg, **tiles))
    from repro.core.imbue import conductances
    g_on, i_leak = conductances(state.r_mem, state.include, state.icfg,
                                key, state.vcfg)
    return _to_i32(ops.imbue_class_sums_raw_packed(
        litw, g_on, i_leak, state.include, state.icfg.v_read,
        state.icfg.r_divider, state.icfg.reference_voltage(),
        state.tm_cfg, width=state.icfg.width, **tiles))


@register_backend("analog-pallas-packed2",
                  state_types=(CrossbarState, ReplicaStackState),
                  capabilities={CAP_ANALOG, CAP_FUSED_KERNEL,
                                CAP_MODELS_C2C, CAP_REPLICA_VMAP,
                                CAP_PACKED_IO, CAP_PACKED_PLANES},
                  priority=40, predicate=lambda s: s.plane_packed,
                  dot_mode=lambda s, keyed: ops.planes_dot_mode(
                      s.plane_dev, s.vcfg, keyed=keyed),
                  column_operand=lambda s, keyed, **tiles:
                      ops.planes_column_source(s.plane_dev, s.col_diff_t,
                                               s.vcfg, keyed=keyed,
                                               **tiles))
def analog_pallas_packed2(state, lits: jax.Array,
                          key: Optional[jax.Array] = None,
                          **tiles) -> jax.Array:
    """Plane-packed analog kernel: the resident conductance stack stays
    compressed in HBM (LRS/HRS index bitplane + additive deviation
    plane, elided when nominal); the kernel reconstructs a nominal
    chip's ``g``/``leak`` tiles in VMEM behind double-buffered HBM->VMEM
    DMA, and streams a deviating chip's column operand (programmed, or
    built per read from a fresh C2C draw) the same way.  Noise
    semantics == ``analog-pallas-packed`` (C2C per read, scalar v_ref —
    no CSA offset, so those reads fall back loudly)."""
    litw = _as_packed_lits(lits)
    l_valid = int(state.include.shape[-1])
    cols = dict(col_diff_t=state.col_diff_t, col_leak_t=state.col_leak_t)
    if isinstance(state, ReplicaStackState):
        return _to_i32(ops.imbue_class_sums_stack_planes(
            litw, state.plane_index, state.plane_dev, state.icfg,
            state.tm_cfg, key, vcfg=state.vcfg, l_valid=l_valid,
            n_replicas=state.n_replicas, **cols, **tiles))
    return _to_i32(ops.imbue_class_sums_planes(
        litw, state.plane_index, state.plane_dev, state.icfg,
        state.tm_cfg, key, vcfg=state.vcfg, l_valid=l_valid, **cols,
        **tiles))


# ----------------------------------------------------------- coalesced

@register_backend("coalesced", state_types=(CoalescedState,),
                  capabilities={CAP_DIGITAL, CAP_COALESCED, CAP_SHARDED},
                  priority=10)
def coalesced_jnp(state: CoalescedState, lits: jax.Array,
                  key: Optional[jax.Array] = None) -> jax.Array:
    """Shared clause pool with a weighted digital tail (GSPMD path:
    the only coalesced backend safe under a class-sharded ``weights``
    placement, and the csa/sharded fallback for the fused kernels)."""
    del key
    cls = co.clause_outputs(state.ta_state, lits, state.cfg)
    return _to_i32(cls.astype(jnp.int32) @ state.weights)


@register_backend("coalesced-pallas", state_types=(CoalescedState,),
                  capabilities={CAP_DIGITAL, CAP_COALESCED,
                                CAP_FUSED_KERNEL},
                  priority=20)
def coalesced_pallas(state: CoalescedState, lits: jax.Array,
                     key: Optional[jax.Array] = None, **tiles) -> jax.Array:
    """Fused clause-eval + weighted-combine Pallas kernel: the digital
    kernel's arbitrary ``[C, M]`` combine matrix carries W instead of
    the signed one-hot polarity matrix."""
    del key
    return _to_i32(ops.coalesced_class_sums(lits, state.include,
                                            state.weights, **tiles))


@register_backend("coalesced-pallas-packed", state_types=(CoalescedState,),
                  capabilities={CAP_DIGITAL, CAP_COALESCED,
                                CAP_FUSED_KERNEL, CAP_PACKED_IO},
                  priority=30, predicate=lambda s: s.packed)
def coalesced_pallas_packed(state: CoalescedState, lits: jax.Array,
                            key: Optional[jax.Array] = None,
                            **tiles) -> jax.Array:
    """Packed-wire coalesced kernel: uint32 bitplanes, AND+popcount
    violation path, weighted combine tail."""
    del key
    return _to_i32(ops.coalesced_class_sums_packed(
        _as_packed_lits(lits), state.include_packed, state.weights,
        **tiles))


@register_backend("coalesced-pallas-packed2", state_types=(CoalescedState,),
                  capabilities={CAP_DIGITAL, CAP_COALESCED,
                                CAP_FUSED_KERNEL, CAP_PACKED_IO,
                                CAP_PACKED_PLANES},
                  priority=40, predicate=lambda s: s.plane_packed)
def coalesced_pallas_packed2(state: CoalescedState, lits: jax.Array,
                             key: Optional[jax.Array] = None,
                             **tiles) -> jax.Array:
    """Plane-packed coalesced kernel: the resident include bitplane
    stays in HBM and streams through the kernel's own double-buffered
    DMA pipeline (integer AND+popcount path — bit-identical to
    ``coalesced-pallas-packed``)."""
    del key
    return _to_i32(ops.coalesced_class_sums_planes(
        _as_packed_lits(lits), state.plane_index, state.weights,
        **tiles))


# ------------------------------------------------------- uniform entry

def class_sums(state, lits: jax.Array, key: Optional[jax.Array] = None, *,
               backend: Optional[str] = None, require=(),
               **opts) -> jax.Array:
    """Class sums via capability-based backend selection.

    ``backend`` pins a backend *preference*; if it cannot satisfy the
    state's required capabilities the selection falls back loudly (use
    :func:`repro.api.select_backend` directly to inspect the decision).
    """
    sel = select_backend(state, key=key, prefer=backend, require=require)
    return sel.backend.fn(state, lits, key, **opts)


def predict(state, x: jax.Array, key: Optional[jax.Array] = None, *,
            backend: Optional[str] = None, **opts) -> jax.Array:
    """Argmax classification from raw Boolean features ``[B, F]``.

    Replica stacks are ensemble-reduced by summing per-chip class sums
    before the argmax (use ``repro.serve.ensemble_vote`` for majority
    voting)."""
    sums = class_sums(state, tm.literals(x), key, backend=backend, **opts)
    if isinstance(state, ReplicaStackState):
        sums = sums.sum(axis=0)
    return jnp.argmax(sums, axis=-1)
