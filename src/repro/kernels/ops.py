"""Jit'd public wrappers around the Pallas kernels.

Handles padding to MXU-aligned tiles, dtype conversion, polarity-matrix
construction, and falling back to ``interpret=True`` off-TPU (this
container is CPU-only; interpret mode executes the kernel bodies exactly).

Public API:
  ``clause_eval(lits, include)``                    -> [B, C] clause bits
  ``tm_class_sums(lits, include, cfg)``             -> [B, M] digital, fused
  ``imbue_class_sums(lits, xbar, cfg)``             -> [B, M] analog, fused
  ``imbue_class_sums_stack(lits, r_stack, ...)``    -> [R, B, M] one vmapped
                                                       dispatch per stack
  ``coalesced_class_sums(lits, include, w)``        -> [B, M] weighted tail,
                                                       shared clause pool
  ``polarity_matrix(cfg, include)``                 -> [C, M] signed one-hot
  ``coalesced_combine(w, nonempty)``                -> [C, M_pad] weighted
                                                       combine matrix

Packed (uint32 bitplane) wire-format variants — bits stay packed from the
host queue through HBM, unpacking (if at all) per K tile in VMEM:
  ``pack_literals(lits)`` / ``pack_include(inc)``   -> [.., ceil(L/32)] u32
  ``tm_class_sums_packed(litw, incw, cfg)``         -> [B, M] AND+popcount
  ``clause_eval_packed(litw, incw)``                -> [B, C] clause bits
  ``imbue_class_sums_stack_packed(litw, ...)``      -> [R, B, M]
  ``coalesced_class_sums_packed(litw, incw, w)``    -> [B, M] weighted tail

Plane-packed (resident-operand) variants — the *programmed conductance
stack* is also compressed: an LRS/HRS include-index bitplane (32x
smaller than one f32 plane) plus an optional per-cell additive
resistance-deviation plane (``dev = r - r_nom``; D2D draws and fault
overlays fold into it, nominal stacks elide it entirely), reconstructed
in VMEM per K chunk behind double-buffered HBM->VMEM DMA:
  ``imbue_class_sums_planes(litw, idx, dev, ...)``  -> [B, M]
  ``imbue_class_sums_stack_planes(litw, ...)``      -> [R, B, M]
  ``coalesced_class_sums_planes(litw, incw, w)``    -> [B, M] weighted tail

Packed K tiles count bits and must be multiples of 32 (one uint32 word);
padding therefore happens on the word axis (``kt // 32`` words).

Most callers should go through ``repro.api`` (capability-based backend
selection over registered pytree states) rather than calling these
wrappers directly; ``imbue_class_sums_stacked`` (per-chip loop) is a
deprecated shim kept for one release.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.tm import TMConfig
from repro.kernels import bitpack
from repro.kernels import clause_eval as _ce
from repro.kernels import imbue_infer as _ai

# Default MXU-aligned tile sizes (see §Perf for the sweep).  These are
# the static fallbacks; measured per-backend tables from
# ``kernels/autotune.py`` override them on the serve path.
BT, CT, KT = 128, 128, 512
KT_ANALOG = 256          # multiple of the 32-cell column width


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, pad)
    return jnp.pad(x, pads, constant_values=value)


def polarity_matrix(cfg: TMConfig, include: jax.Array | None = None,
                    n_class_pad: int = 128) -> jax.Array:
    """Signed one-hot ``[C, M_pad]``: P[c, m] = polarity(c) * [class(c)==m].

    Rows of empty clauses (no includes) are zeroed — the digital tail's
    inference-time empty-clause mask, folded into the matmul.
    """
    from repro.core.tm import polarity
    if cfg.n_classes > n_class_pad:
        raise ValueError(
            f"n_classes={cfg.n_classes} exceeds n_class_pad={n_class_pad}; "
            "widen the class padding (kernel outputs are sliced to "
            "n_classes, so silent overflow would drop classes)")
    c = cfg.n_clauses
    cls_of = jnp.arange(c) // cfg.clauses_per_class
    onehot = jax.nn.one_hot(cls_of, n_class_pad, dtype=jnp.float32)
    p = onehot * polarity(cfg)[:, None].astype(jnp.float32)
    if include is not None:
        p = p * include.any(axis=-1)[:, None].astype(jnp.float32)
    return p


def pack_literals(lits: jax.Array) -> jax.Array:
    """``[..., L]`` 0/1 literals -> ``[..., ceil(L/32)] uint32`` words.

    The packed wire format of the inference stack: what the serving
    queue holds, what crosses host->device, and what the packed kernels
    stream from HBM.  Ragged ``L`` zero-pads to the word boundary
    (pad bits read as literal 0 against zero-padded include/conductance
    columns, so they never contribute).
    """
    return bitpack.pack_bits(lits)


def pack_include(include: jax.Array) -> jax.Array:
    """``[..., C, L]`` bool include plane -> ``[..., C, ceil(L/32)]``
    uint32 words (the conductance-index plane of a programmed chip)."""
    return bitpack.pack_bits(include)


def _nonempty_from_packed(include_w: jax.Array) -> jax.Array:
    """``[C, Lw] uint32`` -> ``[C]`` bool "clause has any include"."""
    return (include_w != 0).any(axis=-1)


@partial(jax.jit, static_argnames=("bt", "ct", "kt", "interpret"))
def clause_eval(lits: jax.Array, include: jax.Array, *,
                bt: int = BT, ct: int = CT, kt: int = KT,
                interpret: bool | None = None) -> jax.Array:
    """Digital clause outputs ``[B, C]`` (training semantics: empty
    clauses fire).  ``lits`` [B, L] and ``include`` [C, L] are 0/1."""
    interp = (not _on_tpu()) if interpret is None else interpret
    b, c = lits.shape[0], include.shape[0]
    lit0 = _pad_to(_pad_to((1 - lits).astype(jnp.float32), 0, bt), 1, kt)
    inc_t = _pad_to(_pad_to(include.astype(jnp.float32), 0, ct),
                    1, kt).T
    out = _ce.clause_eval_call(lit0, inc_t, bt=bt, ct=ct, kt=kt,
                               interpret=interp)
    return out[:b, :c]


@partial(jax.jit, static_argnames=("cfg", "bt", "ct", "kt", "interpret"))
def tm_class_sums(lits: jax.Array, include: jax.Array, cfg: TMConfig, *,
                  bt: int = BT, ct: int = CT, kt: int = KT,
                  interpret: bool | None = None) -> jax.Array:
    """Fused digital inference: literals -> class sums ``[B, M]``."""
    interp = (not _on_tpu()) if interpret is None else interpret
    b = lits.shape[0]
    lit0 = _pad_to(_pad_to((1 - lits).astype(jnp.float32), 0, bt), 1, kt)
    inc_t = _pad_to(_pad_to(include.astype(jnp.float32), 0, ct), 1, kt).T
    pol = _pad_to(polarity_matrix(cfg, include), 0, ct)
    out = _ce.tm_infer_call(lit0, inc_t, pol, bt=bt, ct=ct, kt=kt,
                            interpret=interp)
    return out[:b, :cfg.n_classes]


@partial(jax.jit, static_argnames=("bt", "ct", "kt", "interpret"))
def clause_eval_packed(litw: jax.Array, include_w: jax.Array, *,
                       bt: int = BT, ct: int = CT, kt: int = KT,
                       interpret: bool | None = None) -> jax.Array:
    """Digital clause outputs ``[B, C]`` from packed operands.

    ``litw`` ``[B, ceil(L/32)]`` and ``include_w`` ``[C, ceil(L/32)]``
    are uint32 bitplanes (:func:`pack_literals` / :func:`pack_include`).
    Training semantics (empty clauses fire), same as :func:`clause_eval`.
    """
    interp = (not _on_tpu()) if interpret is None else interpret
    kw = kt // bitpack.WORD
    b, c = litw.shape[0], include_w.shape[0]
    litw_p = _pad_to(_pad_to(litw.astype(jnp.uint32), 0, bt), 1, kw)
    incw_t = _pad_to(_pad_to(include_w.astype(jnp.uint32), 0, ct),
                     1, kw).T
    out = _ce.clause_eval_packed_call(litw_p, incw_t, bt=bt, ct=ct, kt=kt,
                                      interpret=interp)
    return out[:b, :c]


@partial(jax.jit, static_argnames=("cfg", "bt", "ct", "kt", "interpret"))
def tm_class_sums_packed(litw: jax.Array, include_w: jax.Array,
                         cfg: TMConfig, *,
                         bt: int = BT, ct: int = CT, kt: int = KT,
                         interpret: bool | None = None) -> jax.Array:
    """Fused digital inference from packed bitplanes -> ``[B, M]``.

    Bit-exact vs :func:`tm_class_sums` on the unpacked operands; the
    empty-clause inference mask is derived from the packed include plane
    (a clause is empty iff all of its words are zero).
    """
    interp = (not _on_tpu()) if interpret is None else interpret
    kw = kt // bitpack.WORD
    b = litw.shape[0]
    litw_p = _pad_to(_pad_to(litw.astype(jnp.uint32), 0, bt), 1, kw)
    incw_t = _pad_to(_pad_to(include_w.astype(jnp.uint32), 0, ct),
                     1, kw).T
    pol = polarity_matrix(cfg)
    pol = pol * _nonempty_from_packed(include_w)[:, None].astype(jnp.float32)
    pol = _pad_to(pol, 0, ct)
    out = _ce.tm_infer_packed_call(litw_p, incw_t, pol, bt=bt, ct=ct,
                                   kt=kt, interpret=interp)
    return out[:b, :cfg.n_classes]


def coalesced_combine(weights: jax.Array, nonempty: jax.Array,
                      n_class_pad: int = 128) -> jax.Array:
    """``[C, M]`` integer weights -> ``[C, M_pad]`` f32 combine matrix.

    The coalesced analogue of :func:`polarity_matrix`: rows of empty
    clauses are zeroed (the inference-time empty-clause mask, folded
    into the matmul) and the class axis pads to the kernel's output
    width.  Integer weights are exact in f32 (|w| <= 127 << 2^24), so
    the weighted digital tail stays bit-exact through the float MXU
    path.
    """
    m = weights.shape[1]
    if m > n_class_pad:
        raise ValueError(
            f"n_classes={m} exceeds n_class_pad={n_class_pad}; widen the "
            "class padding (kernel outputs are sliced to n_classes, so "
            "silent overflow would drop classes)")
    w = weights.astype(jnp.float32) * nonempty[:, None].astype(jnp.float32)
    return _pad_to(w, 1, n_class_pad)


@partial(jax.jit, static_argnames=("bt", "ct", "kt", "interpret"))
def coalesced_class_sums(lits: jax.Array, include: jax.Array,
                         weights: jax.Array, *,
                         bt: int = BT, ct: int = CT, kt: int = KT,
                         interpret: bool | None = None) -> jax.Array:
    """Fused coalesced inference: shared clause pool ``[C, L]`` +
    per-class weights ``[C, M]`` -> class sums ``[B, M]``.

    Reuses the digital fused kernel's arbitrary combine-matrix path
    (``tm_infer_call``) with W in place of the signed one-hot polarity
    matrix — the crossbar half is UNCHANGED (same violation matmul);
    only the digital tail swaps ±1 counters for weighted counters.
    Bit-exact vs ``core.coalesced.forward``.
    """
    interp = (not _on_tpu()) if interpret is None else interpret
    b, m = lits.shape[0], weights.shape[1]
    lit0 = _pad_to(_pad_to((1 - lits).astype(jnp.float32), 0, bt), 1, kt)
    inc_t = _pad_to(_pad_to(include.astype(jnp.float32), 0, ct), 1, kt).T
    w = _pad_to(coalesced_combine(weights, include.any(axis=-1)), 0, ct)
    out = _ce.tm_infer_call(lit0, inc_t, w, bt=bt, ct=ct, kt=kt,
                            interpret=interp)
    return out[:b, :m]


@partial(jax.jit, static_argnames=("bt", "ct", "kt", "interpret"))
def coalesced_class_sums_packed(litw: jax.Array, include_w: jax.Array,
                                weights: jax.Array, *,
                                bt: int = BT, ct: int = CT, kt: int = KT,
                                interpret: bool | None = None) -> jax.Array:
    """Fused coalesced inference from packed bitplanes -> ``[B, M]``.

    ``litw`` ``[B, ceil(L/32)]`` / ``include_w`` ``[C, ceil(L/32)]`` are
    uint32 words (:func:`pack_literals` / :func:`pack_include`); the
    AND+popcount violation path is shared with
    :func:`tm_class_sums_packed`, the combine matrix is W.  Bit-exact vs
    :func:`coalesced_class_sums` on the unpacked operands.
    """
    interp = (not _on_tpu()) if interpret is None else interpret
    kw = kt // bitpack.WORD
    b, m = litw.shape[0], weights.shape[1]
    litw_p = _pad_to(_pad_to(litw.astype(jnp.uint32), 0, bt), 1, kw)
    incw_t = _pad_to(_pad_to(include_w.astype(jnp.uint32), 0, ct),
                     1, kw).T
    w = _pad_to(coalesced_combine(weights,
                                  _nonempty_from_packed(include_w)), 0, ct)
    out = _ce.tm_infer_packed_call(litw_p, incw_t, w, bt=bt, ct=ct,
                                   kt=kt, interpret=interp)
    return out[:b, :m]


@partial(jax.jit, static_argnames=("cfg", "width", "bt", "ct", "kt",
                                   "interpret"))
def imbue_class_sums_raw(
    lits: jax.Array,          # [B, L] uint8
    g_on: jax.Array,          # [C, L] on-path conductance (S)
    i_leak: jax.Array,        # [C, L] leak currents (A)
    include: jax.Array,       # [C, L] bool (for the empty-clause mask)
    v_read: float,
    r_div: float,
    v_ref: float,
    cfg: TMConfig,
    *,
    width: int = 32,
    bt: int = BT, ct: int = CT, kt: int = KT_ANALOG,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused analog inference on explicit conductances -> ``[B, M]``."""
    interp = (not _on_tpu()) if interpret is None else interpret
    b = lits.shape[0]
    lits_f = lits.astype(jnp.float32)
    v_drive = _pad_to(_pad_to((1.0 - lits_f) * v_read, 0, bt), 1, kt)
    lit1 = _pad_to(_pad_to(lits_f, 0, bt), 1, kt)
    g_t = _pad_to(_pad_to(g_on.astype(jnp.float32), 0, ct), 1, kt).T
    leak_t = _pad_to(_pad_to(i_leak.astype(jnp.float32), 0, ct), 1, kt).T
    pol = _pad_to(polarity_matrix(cfg, include), 0, ct)
    out = _ai.imbue_infer_call(v_drive, lit1, g_t, leak_t, pol, v_ref,
                               width=width, r_div=r_div, bt=bt, ct=ct,
                               kt=kt, interpret=interp)
    return out[:b, :cfg.n_classes]


def imbue_class_sums(lits: jax.Array, xbar, cfg: TMConfig, *,
                     key: jax.Array | None = None, vcfg=None,
                     **tiles) -> jax.Array:
    """Fused analog inference from a ``ProgrammedCrossbar``."""
    from repro.core.imbue import cell_conductances
    from repro.core.variations import VariationConfig
    vcfg = vcfg or VariationConfig.nominal()
    g_on, i_leak = cell_conductances(xbar, key, vcfg)
    return imbue_class_sums_raw(
        lits, g_on, i_leak, xbar.include,
        xbar.cfg.v_read, xbar.cfg.r_divider, xbar.cfg.reference_voltage(),
        cfg, width=xbar.cfg.width, **tiles)


@partial(jax.jit, static_argnames=("icfg", "cfg", "vcfg", "bt", "ct", "kt",
                                   "interpret"))
def imbue_class_sums_stack(
    lits: jax.Array,          # [B, L] uint8
    r_stack: jax.Array,       # [R, C, L] per-replica programmed resistance
    include: jax.Array,       # [C, L] bool (shared TA actions)
    icfg,                     # IMBUEConfig (static)
    cfg: TMConfig,
    key: jax.Array | None = None,
    *,
    vcfg=None,
    bt: int = BT, ct: int = CT, kt: int = KT_ANALOG,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused analog inference over a replica stack -> ``[R, B, M]``.

    ONE vmapped kernel invocation covers the whole stack: conductances
    are computed batched ``[R, C, L]`` and the Pallas call is traced once
    with the replica axis handled by vmap's batching rule (no per-chip
    Python loop, no per-chip dispatch).  Each replica still draws fresh
    C2C noise (one read cycle per chip) from its split of ``key``.

    The kernel thresholds against a fixed scalar reference, so the
    per-column CSA offset is NOT modeled — capability selection
    (``repro.api.select_backend``) routes ``csa_offset`` reads to the
    jnp path, which models it.
    """
    from repro.core.imbue import conductances
    from repro.core.variations import VariationConfig
    vcfg = vcfg or VariationConfig.nominal()

    def one(r_mem, k):
        g_on, i_leak = conductances(r_mem, include, icfg, k, vcfg)
        return imbue_class_sums_raw(
            lits, g_on, i_leak, include, icfg.v_read, icfg.r_divider,
            icfg.reference_voltage(), cfg, width=icfg.width,
            bt=bt, ct=ct, kt=kt, interpret=interpret)

    if key is None:
        return jax.vmap(lambda r: one(r, None))(r_stack)
    keys = jax.random.split(key, r_stack.shape[0])
    return jax.vmap(one)(r_stack, keys)


@partial(jax.jit, static_argnames=("cfg", "width", "bt", "ct", "kt",
                                   "interpret"))
def imbue_class_sums_raw_packed(
    litw: jax.Array,          # [B, ceil(L/32)] uint32 packed literals
    g_on: jax.Array,          # [C, L] on-path conductance (S)
    i_leak: jax.Array,        # [C, L] leak currents (A)
    include: jax.Array,       # [C, L] bool (for the empty-clause mask)
    v_read: float,
    r_div: float,
    v_ref: float,
    cfg: TMConfig,
    *,
    width: int = 32,
    bt: int = BT, ct: int = CT, kt: int = KT_ANALOG,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused analog inference from packed literals -> ``[B, M]``.

    The literal operand stays packed from HBM to VMEM (unpacked per K
    tile inside the kernel); the conductance/leak planes are dense f32
    as in :func:`imbue_class_sums_raw`.  Padding the word axis to
    ``kt/32`` words lands on exactly the same padded bit count as
    padding ``L`` to ``kt`` (``ceil(ceil(L/32)/(kt/32)) == ceil(L/kt)``),
    so the two paths see identical zero-padded columns.
    """
    interp = (not _on_tpu()) if interpret is None else interpret
    kw = kt // bitpack.WORD
    b = litw.shape[0]
    litw_p = _pad_to(_pad_to(litw.astype(jnp.uint32), 0, bt), 1, kw)
    g_t = _pad_to(_pad_to(g_on.astype(jnp.float32), 0, ct), 1, kt).T
    leak_t = _pad_to(_pad_to(i_leak.astype(jnp.float32), 0, ct), 1, kt).T
    pol = _pad_to(polarity_matrix(cfg, include), 0, ct)
    out = _ai.imbue_infer_packed_call(litw_p, g_t, leak_t, pol, v_ref,
                                      v_read, width=width, r_div=r_div,
                                      bt=bt, ct=ct, kt=kt, interpret=interp)
    return out[:b, :cfg.n_classes]


@partial(jax.jit, static_argnames=("icfg", "cfg", "vcfg", "bt", "ct", "kt",
                                   "interpret"))
def imbue_class_sums_stack_packed(
    litw: jax.Array,          # [B, ceil(L/32)] uint32 packed literals
    r_stack: jax.Array,       # [R, C, L] per-replica programmed resistance
    include: jax.Array,       # [C, L] bool (shared TA actions)
    icfg,                     # IMBUEConfig (static)
    cfg: TMConfig,
    key: jax.Array | None = None,
    *,
    vcfg=None,
    bt: int = BT, ct: int = CT, kt: int = KT_ANALOG,
    interpret: bool | None = None,
) -> jax.Array:
    """Packed-literal replica-stack inference -> ``[R, B, M]``.

    Same single-vmapped-dispatch property and noise semantics as
    :func:`imbue_class_sums_stack`; only the literal wire format differs.
    """
    from repro.core.imbue import conductances
    from repro.core.variations import VariationConfig
    vcfg = vcfg or VariationConfig.nominal()

    def one(r_mem, k):
        g_on, i_leak = conductances(r_mem, include, icfg, k, vcfg)
        return imbue_class_sums_raw_packed(
            litw, g_on, i_leak, include, icfg.v_read, icfg.r_divider,
            icfg.reference_voltage(), cfg, width=icfg.width,
            bt=bt, ct=ct, kt=kt, interpret=interpret)

    if key is None:
        return jax.vmap(lambda r: one(r, None))(r_stack)
    keys = jax.random.split(key, r_stack.shape[0])
    return jax.vmap(one)(r_stack, keys)


def _read_dev(plane_index: jax.Array, plane_dev: jax.Array | None,
              key: jax.Array | None, vcfg, l_valid: int):
    """The additive deviation plane ``[C, L]`` one read cycle senses:
    the programmed ``plane_dev`` plus, with a ``key`` and C2C on, a fresh
    C2C draw (``apply_c2c(key, r_nom + dev, include, vcfg) - r_nom``).
    None for a nominal chip read without C2C."""
    from repro.core.variations import HRS_MEAN_OHM, LRS_MEAN_OHM, apply_c2c
    if key is None or not vcfg.c2c:
        return plane_dev
    include = bitpack.unpack_bits(plane_index, l_valid).astype(bool)
    r_nom = jnp.where(include, LRS_MEAN_OHM, HRS_MEAN_OHM)
    r = r_nom if plane_dev is None else r_nom + plane_dev
    return apply_c2c(key, r, include, vcfg) - r_nom


def _senses_dev(plane_dev, vcfg, keyed: bool) -> bool:
    """Whether a plane-packed read senses a deviation plane: the
    programmed one, or a fresh C2C draw on a keyed read."""
    return plane_dev is not None or (keyed and vcfg.c2c)


def planes_dot_mode(plane_dev, vcfg, *, keyed: bool) -> str:
    """The column-dot mode (``imbue_infer.dot_mode``) the plane-packed
    kernel runs for a read of this chip, from the predicate the read
    dispatches on."""
    return _ai.dot_mode(_senses_dev(plane_dev, vcfg, keyed))


def _planes_sums(litw, plane_index, devs, icfg, cfg, *, l_valid, bt, ct, kt,
                 interpret):
    """One plane-packed kernel call: ``devs`` ``[R, C, L]`` (or None for
    a nominal chip, ``R = 1``) -> ``[R, B, M]`` class sums."""
    from repro.core.variations import (HRS_MEAN_OHM, I_LEAK_EXCLUDE,
                                       I_LEAK_INCLUDE, LRS_MEAN_OHM)
    interp = (not _on_tpu()) if interpret is None else interpret
    kw = kt // bitpack.WORD
    b = litw.shape[0]
    litw_p = _pad_to(_pad_to(litw.astype(jnp.uint32), 0, bt), 1, kw)
    incw_t = _pad_to(_pad_to(plane_index.astype(jnp.uint32), 0, ct),
                     1, kw).T
    dev_t = (None if devs is None else
             _pad_to(_pad_to(devs.astype(jnp.float32), 1, ct), 2, kt
                     ).transpose(0, 2, 1))
    pol = polarity_matrix(cfg)
    pol = pol * _nonempty_from_packed(
        plane_index)[:, None].astype(jnp.float32)
    pol = _pad_to(pol, 0, ct)
    out = _ai.imbue_infer_planes_call(
        litw_p, incw_t, dev_t, pol, icfg.reference_voltage(), icfg.v_read,
        width=icfg.width, r_div=icfg.r_divider, r_lrs=LRS_MEAN_OHM,
        r_hrs=HRS_MEAN_OHM, leak_inc=I_LEAK_INCLUDE,
        leak_exc=I_LEAK_EXCLUDE, series_factor=icfg.series_factor,
        l_valid=l_valid, bt=bt, ct=ct, kt=kt, interpret=interp)
    return out[:, :b, :cfg.n_classes]


@partial(jax.jit, static_argnames=("icfg", "cfg", "vcfg", "l_valid", "bt",
                                   "ct", "kt", "interpret"))
def imbue_class_sums_planes(
    litw: jax.Array,          # [B, ceil(L/32)] uint32 packed literals
    plane_index: jax.Array,   # [C, ceil(L/32)] uint32 include-index bitplane
    plane_dev: jax.Array | None,  # [C, L] f32 additive r deviation, or None
    icfg,                     # IMBUEConfig (static)
    cfg: TMConfig,
    key: jax.Array | None = None,
    *,
    vcfg=None,
    l_valid: int,
    bt: int = BT, ct: int = CT, kt: int = KT_ANALOG,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused analog inference from a plane-packed chip -> ``[B, M]``.

    The resident operand is the include-index bitplane plus (if any cell
    deviates from its class-nominal resistance) the additive deviation
    plane; the kernel reconstructs ``g``/``leak`` tiles in VMEM with the
    exact ``core.imbue.conductances`` op order, so nominal results are
    bit-identical to :func:`imbue_class_sums_raw_packed` on the dense
    planes.  ``l_valid`` is the true (unpadded) literal count — the
    kernel masks word-padding columns that the dense path zero-pads.

    C2C noise (``key`` + ``vcfg.c2c``) is drawn per read in jnp before
    the kernel (:func:`_read_dev`).  The CSA offset is NOT modeled
    (scalar reference), exactly like the dense analog kernels —
    capability selection routes those reads elsewhere.
    """
    from repro.core.variations import VariationConfig
    vcfg = vcfg or VariationConfig.nominal()
    dev = _read_dev(plane_index, plane_dev, key, vcfg, l_valid)
    return _planes_sums(litw, plane_index, None if dev is None else dev[None],
                        icfg, cfg, l_valid=l_valid, bt=bt, ct=ct, kt=kt,
                        interpret=interpret)[0]


@partial(jax.jit, static_argnames=("icfg", "cfg", "vcfg", "l_valid",
                                   "n_replicas", "bt", "ct", "kt",
                                   "interpret"))
def imbue_class_sums_stack_planes(
    litw: jax.Array,          # [B, ceil(L/32)] uint32 packed literals
    plane_index: jax.Array,   # [C, ceil(L/32)] uint32 (shared TA actions)
    plane_dev: jax.Array | None,  # [R, C, L] f32 deviations, or None
    icfg,                     # IMBUEConfig (static)
    cfg: TMConfig,
    key: jax.Array | None = None,
    *,
    vcfg=None,
    l_valid: int,
    n_replicas: int,
    bt: int = BT, ct: int = CT, kt: int = KT_ANALOG,
    interpret: bool | None = None,
) -> jax.Array:
    """Plane-packed replica-stack inference -> ``[R, B, M]``.

    The index bitplane is shared across the stack (TA actions are); the
    deviation plane is per-replica (each chip drew its own D2D noise /
    carries its own fault overlay) or None for a nominal stack.  Noise
    semantics match :func:`imbue_class_sums_stack_packed`: one fresh C2C
    draw per replica per read from the split of ``key``.  The whole
    stack is ONE kernel call with a replica grid axis; a nominal stack
    with no C2C read is one single-replica call broadcast over R —
    replicas are bit-identical by construction.
    """
    from repro.core.variations import VariationConfig
    vcfg = vcfg or VariationConfig.nominal()
    opts = dict(l_valid=l_valid, bt=bt, ct=ct, kt=kt, interpret=interpret)
    if not _senses_dev(plane_dev, vcfg, key is not None):
        out = _planes_sums(litw, plane_index, None, icfg, cfg, **opts)
        return jnp.broadcast_to(out, (n_replicas,) + out.shape[1:])
    if key is not None and vcfg.c2c:
        keys = jax.random.split(key, n_replicas)
        if plane_dev is None:
            devs = jax.vmap(lambda k: _read_dev(
                plane_index, None, k, vcfg, l_valid))(keys)
        else:
            devs = jax.vmap(lambda d, k: _read_dev(
                plane_index, d, k, vcfg, l_valid))(plane_dev, keys)
    else:
        devs = plane_dev
    return _planes_sums(litw, plane_index, devs, icfg, cfg, **opts)


@partial(jax.jit, static_argnames=("bt", "ct", "kt", "interpret"))
def coalesced_class_sums_planes(litw: jax.Array, include_w: jax.Array,
                                weights: jax.Array, *,
                                bt: int = BT, ct: int = CT, kt: int = KT,
                                interpret: bool | None = None) -> jax.Array:
    """Fused coalesced inference with the include bitplane resident in
    HBM and streamed through the kernel's double-buffered DMA pipeline.

    Same integer AND+popcount arithmetic as
    :func:`coalesced_class_sums_packed` — bit-identical results; the
    difference is purely how the resident operand reaches VMEM (manual
    2-slot prefetch instead of grid-blocked automatic copies).
    """
    interp = (not _on_tpu()) if interpret is None else interpret
    kw = kt // bitpack.WORD
    b, m = litw.shape[0], weights.shape[1]
    litw_p = _pad_to(_pad_to(litw.astype(jnp.uint32), 0, bt), 1, kw)
    incw_t = _pad_to(_pad_to(include_w.astype(jnp.uint32), 0, ct),
                     1, kw).T
    w = _pad_to(coalesced_combine(weights,
                                  _nonempty_from_packed(include_w)), 0, ct)
    out = _ce.tm_infer_planes_call(litw_p, incw_t, w, bt=bt, ct=ct,
                                   kt=kt, interpret=interp)
    return out[:b, :m]


def imbue_class_sums_stacked(
    lits: jax.Array,          # [B, L] uint8
    r_stack: jax.Array,       # [R, C, L] per-replica programmed resistance
    include: jax.Array,       # [C, L] bool (shared TA actions)
    icfg,                     # IMBUEConfig
    cfg: TMConfig,
    *,
    key: jax.Array | None = None,
    vcfg=None,
    **tiles,
) -> jax.Array:
    """DEPRECATED shim: use :func:`imbue_class_sums_stack` (or, better,
    ``repro.api.class_sums`` with a ``ReplicaStackState``).

    The old per-chip host loop is gone; this delegates to the single
    vmapped dispatch.  Noise draws are unchanged (same key split per
    replica), so traces are bit-identical to the loop it replaces.
    """
    import warnings
    warnings.warn(
        "ops.imbue_class_sums_stacked is deprecated; use "
        "repro.api.class_sums(ReplicaStackState(...), lits, key) or "
        "ops.imbue_class_sums_stack", DeprecationWarning, stacklevel=2)
    return imbue_class_sums_stack(lits, r_stack, include, icfg, cfg, key,
                                  vcfg=vcfg, **tiles)
