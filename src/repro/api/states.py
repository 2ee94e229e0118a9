"""Registered pytree states: the *data* half of the unified backend API.

Every inference backend (``repro.api.backends``) consumes one of four
state types.  Each is a frozen dataclass registered as a JAX pytree whose
**children are the device arrays** and whose **aux_data is the static
configuration** (``TMConfig`` / ``IMBUEConfig`` / ``VariationConfig`` /
``CoalescedConfig`` — all frozen, hence hashable), so states pass
directly through ``jax.jit`` (as traced arguments), ``jax.vmap``,
``jax.tree_util.tree_map``, ``jax.device_put`` and checkpoint
serialization without any custom plumbing:

* ``DigitalState``      — the Boolean-domain reference model
  (``include [C, L]`` bool, optionally the raw TA state);
* ``CrossbarState``     — one programmed IMBUE chip
  (``r_mem [C, L]`` Ω + ``include``);
* ``ReplicaStackState`` — R independently programmed chips
  (``r_stack [R, C, L]``) — the serve-pool hot path;
* ``CoalescedState``    — a shared clause pool with per-class integer
  weights (arXiv:2108.07594; the paper's §V future work).

Device layout is deliberately *state*, not a function argument: the
crossbar-constrained-mapping line of work (arXiv:1809.08195) and
IMPACT's one-time-program/many-read model (arXiv:2412.05327) both want
the programmed arrays to travel with their electrical config.

The include-carrying states additionally support the **packed wire
format** (ISSUE 3): ``state.pack()`` attaches the uint32 include
bitplane (``include_packed [.., C, ceil(L/32)]``) as an extra child, and
``select_backend`` then prefers the ``*-pallas-packed`` backends, which
stream packed operands (32x less HBM traffic than f32 for one-bit data).
Dense planes are kept, so every pre-existing backend still accepts a
packed state.

ISSUE 9 extends the same idea to the **resident operand** — the
programmed conductance stack itself: ``state.pack_planes()`` attaches

* ``plane_index`` — the LRS/HRS include-index bitplane (``[C, Lw]``
  uint32; include -> LRS, exclude -> HRS).  It shares the
  ``include_packed`` buffer, since both are ``pack_bits(include)``.
* ``plane_dev`` — the per-cell ADDITIVE resistance deviation
  ``r - r_nom`` (f32, ``[C, L]`` / ``[R, C, L]``), folding D2D draws and
  fault overlays into one plane.  It is **elided (None)** when every
  cell sits at its class-nominal resistance — a nominal chip's resident
  operand is then the index bitplane alone, ~64x smaller than the two
  f32 planes the dense kernels stream.

A deviating chip also carries its **programmed column operand**,
built with the deviation plane (``ops.column_operand``) in the planes
kernels' padded, transposed layout:

* ``col_diff_t`` — ``v_read * g - leak`` per cell (``[L_pad, C_pad]`` /
  ``[R, L_pad, C_pad]`` f32), the term the column dots contract;
* ``col_leak_t`` — each 32-cell column's leak sum
  (``[.., L_pad / 32, C_pad]`` f32).

Both depend only on the programmed resistances, so a read that draws
no fresh C2C noise streams them and the kernel only dots and compares
(a keyed C2C read builds the pair from the deviation it draws); they
are re-derived wherever the deviation plane is and dropped with it.

The ``*-pallas-packed2`` backends reconstruct a nominal chip's
conductance tiles from the index bitplane in VMEM
(``CAP_PACKED_PLANES``) behind double-buffered HBM->VMEM DMA, and
stream a deviating chip's column operand the same way; nominal
reconstruction is bit-exact by construction
(``dev == 0`` -> ``r = r_nom`` in exact f32 arithmetic).  Off-nominal,
packing *quantizes* each resistance to its own reconstruction
(``r := fl(r_nom + fl(r - r_nom))``, at most 0.5 ulp, far below
programming noise), so ``r == r_nom + plane_dev`` holds bitwise for
every plane-packed state and the dense and packed2 kernels stream
identical resistances — integer-sum parity is structural.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import variations as var
from repro.core.coalesced import CoalescedConfig
from repro.core.imbue import IMBUEConfig, ProgrammedCrossbar
from repro.core.mapping import CrossbarMapping
from repro.core.tm import TMConfig, include_mask
from repro.kernels import bitpack, ops


class _PackedMixin:
    """Packed-wire-format support shared by the include-carrying states.

    ``pack()`` adds the uint32 include bitplane (``[.., C, ceil(L/32)]``)
    as an extra pytree child; ``packed`` reports whether it is present.
    Packed states keep every dense plane, so non-packed backends accept
    them unchanged — packing only *adds* the packed-io wire format that
    ``select_backend`` prefers (the ``digital-pallas-packed`` /
    ``analog-pallas-packed`` backends).
    """

    @property
    def packed(self) -> bool:
        return self.include_packed is not None

    @property
    def plane_packed(self) -> bool:
        """True when the resident conductance planes are packed (the
        ``pack_planes()`` wire format the ``*-packed2`` backends key
        their selection predicate on)."""
        return getattr(self, "plane_index", None) is not None

    def pack(self):
        """This state with the packed include plane attached (idempotent)."""
        if self.packed:
            return self
        return dataclasses.replace(
            self, include_packed=bitpack.pack_bits(self.include))


def _deviation_plane(r: jax.Array, include: jax.Array
                     ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """``(r_quantized, r - r_nom)`` as f32, with the deviation ``None``
    when every cell is class-nominal.

    The resistances come back *quantized to their own reconstruction*:
    ``r_quantized == r_nom + dev`` holds **bitwise** for every cell, by
    construction.  ``fl(r - r_nom)`` alone cannot guarantee that —
    Sterbenz exactness only covers draws within ``[r_nom/2, 2*r_nom]``,
    and extreme D2D tails land outside it — so pack time snaps each
    cell to the nearest plane-representable resistance (at most 0.5 ulp
    away, orders of magnitude below programming noise).  Once a state
    is plane-packed, the dense planes and the packed2 kernels therefore
    stream *identical* resistances and integer parity is structural,
    not probabilistic.  Nominal cells are untouched: their deviation is
    exactly zero and the whole plane is elided.

    The elision check syncs to the host once, at pack time — never on
    the dispatch path — and is what makes a nominal chip's resident
    operand the index bitplane alone.
    """
    r_nom = jnp.where(include, var.LRS_MEAN_OHM, var.HRS_MEAN_OHM)
    dev = (r - r_nom).astype(jnp.float32)
    if not bool((dev != 0.0).any()):
        return r.astype(jnp.float32), None
    return (r_nom + dev).astype(jnp.float32), dev


def _with_deviation(state, field: str, r: jax.Array):
    """``state`` with resistances ``r`` stored in ``field``, quantized
    to their own reconstruction, and the deviation plane and programmed
    column operand derived from them (both None when nominal)."""
    r_q, dev = _deviation_plane(r, state.include)
    cols = (None, None) if dev is None else ops.column_operand(
        state.plane_index, dev, state.icfg,
        l_valid=int(state.include.shape[-1]))
    return dataclasses.replace(state, **{field: r_q}, plane_dev=dev,
                               col_diff_t=cols[0], col_leak_t=cols[1])


def _register(cls, data_fields: Tuple[str, ...], meta_fields: Tuple[str, ...]):
    """Register a frozen dataclass as a pytree: ``data_fields`` become
    children (arrays; ``None`` children flatten away cleanly), and
    ``meta_fields`` become hashable aux_data.

    Registration is *with keys* (``GetAttrKey`` per field) so path-based
    consumers — ``distributed.sharding.tree_shardings`` maps the
    ``r_stack`` leaf onto the ``replica`` mesh axis by name — see field
    names instead of flatten indices."""

    def flatten_with_keys(obj):
        return (tuple((jax.tree_util.GetAttrKey(f), getattr(obj, f))
                      for f in data_fields),
                tuple(getattr(obj, f) for f in meta_fields))

    def flatten(obj):
        return (tuple(getattr(obj, f) for f in data_fields),
                tuple(getattr(obj, f) for f in meta_fields))

    def unflatten(meta, children):
        return cls(**dict(zip(data_fields, children)),
                   **dict(zip(meta_fields, meta)))

    jax.tree_util.register_pytree_with_keys(cls, flatten_with_keys,
                                            unflatten, flatten)
    return cls


@dataclasses.dataclass(frozen=True)
class DigitalState(_PackedMixin):
    """The Boolean-domain TM: include actions (+ optional TA states)."""

    include: jax.Array                      # [C, L] bool TA actions
    ta_state: Optional[jax.Array]           # [C, L] int, or None
    tm_cfg: TMConfig                        # static
    include_packed: Optional[jax.Array] = None   # [C, L/32] uint32 bitplane

    @classmethod
    def from_ta(cls, ta_state: jax.Array, tm_cfg: TMConfig) -> "DigitalState":
        return cls(include=include_mask(ta_state, tm_cfg),
                   ta_state=ta_state, tm_cfg=tm_cfg)

    @classmethod
    def from_include(cls, include: jax.Array,
                     tm_cfg: TMConfig) -> "DigitalState":
        return cls(include=jnp.asarray(include, bool), ta_state=None,
                   tm_cfg=tm_cfg)

    @property
    def n_classes(self) -> int:
        return self.tm_cfg.n_classes


@dataclasses.dataclass(frozen=True)
class CrossbarState(_PackedMixin):
    """One programmed IMBUE chip: memristor resistances + TA actions."""

    r_mem: jax.Array                        # [C, L] programmed Ω
    include: jax.Array                      # [C, L] bool TA actions
    tm_cfg: TMConfig                        # static
    icfg: IMBUEConfig = IMBUEConfig()       # static (electrical)
    vcfg: var.VariationConfig = var.VariationConfig()   # static (noise)
    include_packed: Optional[jax.Array] = None   # [C, L/32] uint32 bitplane
    fault_mask: Optional[jax.Array] = None       # [C, L] int8 fault codes
    plane_index: Optional[jax.Array] = None      # [C, L/32] uint32 LRS/HRS
    plane_dev: Optional[jax.Array] = None        # [C, L] f32 r - r_nom
    col_diff_t: Optional[jax.Array] = None       # [L_pad, C_pad] on - leak
    col_leak_t: Optional[jax.Array] = None       # [L_pad/32, C_pad] f32

    def pack_planes(self) -> "CrossbarState":
        """This chip with the resident conductance plane packed: the
        LRS/HRS index bitplane plus the additive deviation plane and
        its programmed column operand (all elided for a nominal chip).
        Implies :meth:`pack` — the index bitplane IS the packed include
        plane, one shared buffer."""
        if self.plane_packed:
            return self
        packed = self.pack()
        return _with_deviation(dataclasses.replace(
            packed, plane_index=packed.include_packed), "r_mem",
            packed.r_mem)

    @classmethod
    def program(cls, include: jax.Array, key: jax.Array, tm_cfg: TMConfig,
                vcfg: var.VariationConfig = var.VariationConfig(),
                icfg: IMBUEConfig = IMBUEConfig()) -> "CrossbarState":
        """One-time programming: D2D resistance draws at SET/RESET time."""
        include = jnp.asarray(include, bool)
        r_mem = var.sample_device_resistance(key, include, vcfg)
        return cls(r_mem=r_mem, include=include, tm_cfg=tm_cfg,
                   icfg=icfg, vcfg=vcfg)

    @classmethod
    def from_crossbar(cls, xbar: ProgrammedCrossbar, tm_cfg: TMConfig,
                      vcfg: var.VariationConfig = var.VariationConfig()
                      ) -> "CrossbarState":
        """Adopt a legacy ``ProgrammedCrossbar`` (deprecated container)."""
        return cls(r_mem=xbar.r_mem, include=jnp.asarray(xbar.include, bool),
                   tm_cfg=tm_cfg, icfg=xbar.cfg, vcfg=vcfg)

    @property
    def mapping(self) -> CrossbarMapping:
        c, l = self.include.shape
        return CrossbarMapping(n_clauses=c, n_literals=l,
                               width=self.icfg.width)

    @property
    def n_classes(self) -> int:
        return self.tm_cfg.n_classes

    def reprogram(self, include: jax.Array,
                  key: jax.Array) -> "CrossbarState":
        """This chip re-programmed with NEW TA actions (ISSUE 7): fresh
        D2D resistance draws under the same electrical/noise configs.
        The stale packed include plane is dropped — callers re-``pack()``
        if they carry the packed wire format."""
        include = jnp.asarray(include, bool)
        if include.shape != self.include.shape:
            raise ValueError(
                f"reprogram include shape {include.shape} != chip shape "
                f"{self.include.shape} — hot re-programming keeps the "
                "crossbar geometry")
        r_mem = var.sample_device_resistance(key, include, self.vcfg)
        return dataclasses.replace(self, r_mem=r_mem, include=include,
                                   include_packed=None, fault_mask=None,
                                   plane_index=None, plane_dev=None,
                                   col_diff_t=None, col_leak_t=None)

    def inject_faults(self, key: jax.Array,
                      fcfg: Optional[var.FaultConfig] = None
                      ) -> "CrossbarState":
        """This chip with persistent device faults baked in (ISSUE 8).

        Stuck cells are overwritten to the nominal LRS/HRS resistance
        and every healthy cell ages by the retention drift; the drawn
        ``fault_mask`` rides along as an int8 pytree child for
        diagnostics.  The ``include`` plane is unchanged — it records
        the *intended* actions, which faulty cells now deviate from.
        ``fcfg`` defaults to ``vcfg.fault``; a missing/nominal config
        returns ``self`` untouched (the bit-exactness guarantee).
        Re-injection compounds: masks merge (new codes win) and drift
        stacks, like a chip aging further."""
        fcfg = fcfg if fcfg is not None else self.vcfg.fault
        if fcfg is None or fcfg.is_nominal:
            return self
        mask = var.sample_fault_mask(key, self.include.shape, fcfg)
        r_mem = var.apply_fault_overlay(self.r_mem, mask, fcfg)
        if self.fault_mask is not None:
            mask = jnp.where(mask != 0, mask, self.fault_mask)
        out = dataclasses.replace(self, r_mem=r_mem, fault_mask=mask)
        if self.plane_packed:
            # The fault overlay changed resistances, not TA actions: the
            # index bitplane stays valid and the deviation plane (with
            # its column operand) re-derives from the injured
            # resistances — keeping the old one would silently serve
            # healthy values.
            out = _with_deviation(out, "r_mem", r_mem)
        return out


@dataclasses.dataclass(frozen=True)
class ReplicaStackState(_PackedMixin):
    """R independently programmed chips sharing one set of TA actions.

    The serving hot path: backends dispatch the whole stack through ONE
    vmapped kernel invocation (no per-replica Python loop)."""

    r_stack: jax.Array                      # [R, C, L] programmed Ω
    include: jax.Array                      # [C, L] bool (shared actions)
    tm_cfg: TMConfig                        # static
    icfg: IMBUEConfig = IMBUEConfig()       # static
    vcfg: var.VariationConfig = var.VariationConfig()   # static
    include_packed: Optional[jax.Array] = None   # [C, L/32] uint32 bitplane
    fault_mask: Optional[jax.Array] = None       # [R, C, L] int8 fault codes
    plane_index: Optional[jax.Array] = None      # [C, L/32] uint32 LRS/HRS
    plane_dev: Optional[jax.Array] = None        # [R, C, L] f32 r - r_nom
    col_diff_t: Optional[jax.Array] = None       # [R, L_pad, C_pad] f32
    col_leak_t: Optional[jax.Array] = None       # [R, L_pad/32, C_pad] f32

    def pack_planes(self) -> "ReplicaStackState":
        """The stack with the resident conductance planes packed: ONE
        shared LRS/HRS index bitplane (TA actions are shared) plus the
        per-replica additive deviation plane and programmed column
        operand — elided entirely for a nominal stack, where all R
        chips' resident operand collapses to the single index bitplane.
        Implies :meth:`pack`."""
        if self.plane_packed:
            return self
        packed = self.pack()
        return _with_deviation(dataclasses.replace(
            packed, plane_index=packed.include_packed), "r_stack",
            packed.r_stack)

    @classmethod
    def program(cls, include: jax.Array, key: jax.Array, n_replicas: int,
                tm_cfg: TMConfig,
                vcfg: var.VariationConfig = var.VariationConfig(),
                icfg: IMBUEConfig = IMBUEConfig()) -> "ReplicaStackState":
        """Program R chips with independent D2D draws (one per chip)."""
        include = jnp.asarray(include, bool)
        keys = jax.random.split(key, n_replicas)
        r_stack = jax.vmap(
            lambda k: var.sample_device_resistance(k, include, vcfg))(keys)
        return cls(r_stack=r_stack, include=include, tm_cfg=tm_cfg,
                   icfg=icfg, vcfg=vcfg)

    @property
    def n_replicas(self) -> int:
        return int(self.r_stack.shape[0])

    @property
    def mapping(self) -> CrossbarMapping:
        c, l = self.include.shape
        return CrossbarMapping(n_clauses=c, n_literals=l,
                               width=self.icfg.width)

    @property
    def n_classes(self) -> int:
        return self.tm_cfg.n_classes

    def replica_slice(self, i: int) -> "ReplicaStackState":
        """Single-chip view ``[1, C, L]`` — shape is replica-independent,
        so routed dispatch reuses one compiled kernel for every chip."""
        def one(a):
            return None if a is None else a[i:i + 1]

        return dataclasses.replace(
            self, r_stack=self.r_stack[i:i + 1],
            fault_mask=one(self.fault_mask), plane_dev=one(self.plane_dev),
            col_diff_t=one(self.col_diff_t), col_leak_t=one(self.col_leak_t))

    @property
    def is_sharded(self) -> bool:
        """True when the stack is partitioned across >1 device (which
        adds ``CAP_SHARDED`` to the required capability set)."""
        from repro.distributed.sharding import tree_is_sharded
        return tree_is_sharded(self)

    def shard(self, mesh, rules=None) -> "ReplicaStackState":
        """This state placed onto ``mesh``: ``r_stack`` split over the
        ``replica`` logical axis (one shard of chips per device), the
        shared include planes replicated.  ``rules`` defaults to
        ``distributed.sharding.replica_rules(mesh)``.  Programming is
        unchanged — the same per-seed D2D draws land in each shard — so
        sharded serving stays bit-reproducible."""
        from repro.distributed.sharding import shard_tree
        return shard_tree(self, mesh, rules)

    def replica(self, i: int) -> CrossbarState:
        """Chip ``i`` as a standalone ``CrossbarState``."""
        def one(a):
            return None if a is None else a[i]

        return CrossbarState(r_mem=self.r_stack[i], include=self.include,
                             tm_cfg=self.tm_cfg, icfg=self.icfg,
                             vcfg=self.vcfg, fault_mask=one(self.fault_mask),
                             plane_index=self.plane_index,
                             plane_dev=one(self.plane_dev),
                             col_diff_t=one(self.col_diff_t),
                             col_leak_t=one(self.col_leak_t))

    def reprogram(self, include: jax.Array,
                  key: jax.Array) -> "ReplicaStackState":
        """All R chips re-programmed with NEW TA actions (ISSUE 7):
        independent fresh D2D draws per chip — identical key-splitting to
        :meth:`program`, so re-programming with key K is bit-equal to
        programming a fresh stack with key K (the hot-swap bit-equality
        bar leans on this).  The stale packed plane is dropped."""
        include = jnp.asarray(include, bool)
        if include.shape != self.include.shape:
            raise ValueError(
                f"reprogram include shape {include.shape} != stack shape "
                f"{self.include.shape} — hot re-programming keeps the "
                "crossbar geometry")
        keys = jax.random.split(key, self.n_replicas)
        r_stack = jax.vmap(
            lambda k: var.sample_device_resistance(k, include, self.vcfg)
        )(keys)
        return dataclasses.replace(self, r_stack=r_stack, include=include,
                                   include_packed=None, fault_mask=None,
                                   plane_index=None, plane_dev=None,
                                   col_diff_t=None, col_leak_t=None)

    def inject_faults(self, key: jax.Array,
                      fcfg: Optional[var.FaultConfig] = None,
                      replicas=None) -> "ReplicaStackState":
        """The stack with persistent faults baked into selected chips.

        Independent per-replica mask draws (one key split per chip, so
        chip ``i``'s defect pattern is reproducible regardless of which
        chips are targeted); ``replicas`` — an iterable of stack indices
        — restricts the injury, leaving the other chips bit-untouched.
        Semantics per chip match :meth:`CrossbarState.inject_faults`."""
        fcfg = fcfg if fcfg is not None else self.vcfg.fault
        if fcfg is None or fcfg.is_nominal:
            return self
        keys = jax.random.split(key, self.n_replicas)
        mask = jax.vmap(
            lambda k: var.sample_fault_mask(k, self.include.shape, fcfg)
        )(keys)
        injured = jax.vmap(
            lambda r, m: var.apply_fault_overlay(r, m, fcfg)
        )(self.r_stack, mask)
        if replicas is not None:
            sel = jnp.zeros(self.n_replicas, bool)
            sel = sel.at[jnp.asarray(list(replicas))].set(True)
            mask = jnp.where(sel[:, None, None], mask, jnp.int8(0))
            injured = jnp.where(sel[:, None, None], injured, self.r_stack)
        if self.fault_mask is not None:
            mask = jnp.where(mask != 0, mask, self.fault_mask)
        out = dataclasses.replace(self, r_stack=injured, fault_mask=mask)
        if self.plane_packed:
            # Same rule as CrossbarState: actions (index bitplane)
            # unchanged, deviations re-derived from the injured stack.
            out = _with_deviation(out, "r_stack", injured)
        return out


@dataclasses.dataclass(frozen=True)
class CoalescedState(_PackedMixin):
    """Shared clause pool + per-class integer weights (coalesced TM).

    Production-parity since ISSUE 6: ``pack()`` attaches the uint32
    include bitplane (the ``coalesced-pallas-packed`` wire format),
    ``shard(mesh)`` splits the per-class weight columns over the
    ``replica`` mesh axis (class-parallel serving — the IMPACT capacity
    lever), and the fused ``coalesced-pallas`` backends accept it."""

    ta_state: jax.Array                     # [C, L] int TA states
    weights: jax.Array                      # [C, M] int per-class weights
    cfg: CoalescedConfig                    # static
    include_packed: Optional[jax.Array] = None   # [C, L/32] uint32 bitplane
    fault_mask: Optional[jax.Array] = None       # [C, L] int8 fault codes
    plane_index: Optional[jax.Array] = None      # [C, L/32] uint32 bitplane

    def pack_planes(self) -> "CoalescedState":
        """The coalesced model in the plane-packed wire format.  The
        pool is digital — there is no conductance deviation to carry —
        so the "resident plane" is the include bitplane itself, marked
        as ``plane_index`` so ``select_backend`` routes to
        ``coalesced-pallas-packed2`` (the double-buffered DMA kernel).
        Implies :meth:`pack` (one shared buffer)."""
        if self.plane_packed:
            return self
        packed = self.pack()
        return dataclasses.replace(packed,
                                   plane_index=packed.include_packed)

    @property
    def include(self) -> jax.Array:
        """``[C, L]`` bool TA actions (include iff state > n_states)."""
        return self.ta_state > self.cfg.n_states

    @property
    def n_classes(self) -> int:
        return self.cfg.n_classes

    @property
    def n_clauses(self) -> int:
        return self.cfg.n_clauses

    @property
    def n_literals(self) -> int:
        return self.cfg.n_literals

    @property
    def is_sharded(self) -> bool:
        """True when the weight columns are partitioned across >1 device
        (which adds ``CAP_SHARDED`` to the required capability set)."""
        from repro.distributed.sharding import tree_is_sharded
        return tree_is_sharded(self)

    def shard(self, mesh, rules=None) -> "CoalescedState":
        """This state placed onto ``mesh``: the ``[C, M]`` weight matrix
        splits its class axis over the ``replica`` logical axis (each
        device serves a shard of classes from the SAME shared clause
        pool), while the TA/include planes replicate.  ``rules``
        defaults to ``distributed.sharding.replica_rules(mesh)``."""
        from repro.distributed.sharding import shard_tree
        return shard_tree(self, mesh, rules)

    def reprogram(self, ta_state: jax.Array,
                  weights: jax.Array) -> "CoalescedState":
        """This model re-programmed with freshly trained TA states and
        class weights (ISSUE 7).  The coalesced tail is digital, so
        re-programming is deterministic (no D2D draws); the stale packed
        include plane is dropped."""
        ta_state = jnp.asarray(ta_state)
        weights = jnp.asarray(weights)
        if (ta_state.shape != self.ta_state.shape
                or weights.shape != self.weights.shape):
            raise ValueError(
                f"reprogram shapes {ta_state.shape}/{weights.shape} != "
                f"model shapes {self.ta_state.shape}/{self.weights.shape}")
        return dataclasses.replace(self, ta_state=ta_state,
                                   weights=weights, include_packed=None,
                                   fault_mask=None, plane_index=None)

    def inject_faults(self, key: jax.Array,
                      fcfg: Optional[var.FaultConfig] = None
                      ) -> "CoalescedState":
        """Stuck-at faults baked into the TA plane (ISSUE 8).

        The coalesced tail is digital, so the fault model maps to the
        Boolean domain: a stuck-at-LRS cell reads as a hard *include*
        (TA pinned at the top state), stuck-at-HRS as a hard *exclude*
        (TA pinned at 1).  Retention drift has no digital analogue and
        is ignored here.  The packed include plane is dropped — faults
        change the include actions."""
        if fcfg is None or fcfg.is_nominal:
            return self
        mask = var.sample_fault_mask(key, self.ta_state.shape, fcfg)
        ta = jnp.where(mask == var.FAULT_STUCK_LRS, 2 * self.cfg.n_states,
                       jnp.where(mask == var.FAULT_STUCK_HRS, 1,
                                 self.ta_state)).astype(self.ta_state.dtype)
        if self.fault_mask is not None:
            mask = jnp.where(mask != 0, mask, self.fault_mask)
        return dataclasses.replace(self, ta_state=ta, fault_mask=mask,
                                   include_packed=None, plane_index=None)


_register(DigitalState, ("include", "ta_state", "include_packed"),
          ("tm_cfg",))
_register(CrossbarState, ("r_mem", "include", "include_packed",
                          "fault_mask", "plane_index", "plane_dev",
                          "col_diff_t", "col_leak_t"),
          ("tm_cfg", "icfg", "vcfg"))
_register(ReplicaStackState, ("r_stack", "include", "include_packed",
                              "fault_mask", "plane_index", "plane_dev",
                              "col_diff_t", "col_leak_t"),
          ("tm_cfg", "icfg", "vcfg"))
_register(CoalescedState, ("ta_state", "weights", "include_packed",
                           "fault_mask", "plane_index"),
          ("cfg",))

STATE_TYPES = (DigitalState, CrossbarState, ReplicaStackState,
               CoalescedState)
