"""Pallas TPU kernel for the IMBUE *analog* inference pipeline.

Faithful current-domain semantics (paper §II): per 32-cell column KCL
current -> CSA threshold -> AND across a clause's columns -> polarity
matmul.  Unlike the digital kernel, the threshold is applied per column
(the analog architecture cannot see the total violation count, only each
CSA's local comparison), so the K dimension is processed in whole columns.

Per (b, c, k) grid step the block covers ``kt`` literals = ``kt/width``
columns; each column contributes two narrow dots (on-path voltage x
conductance, leak mask x leak current).  A running AND (product of 0/1
partials) lives in VMEM scratch; the last K step folds the finished clause
block into the [bt, M] class-sum output.

The narrow (width=32) contraction underutilizes the 128-wide MXU by design
— it emulates the paper's partial-clause sensing exactly.  The digital
kernel in ``clause_eval.py`` is the full-width variant; the gap has not
been measured on the chip yet.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitpack import (WORD, unpack_words_f32,
                                   unpack_words_f32_cols, word_chunks)


def imbue_infer_kernel(i_ref_ref, v_drive_ref, lit1_ref, g_t_ref, leak_t_ref,
                       pol_ref, out_ref, and_ref, *, width, cols_per_block):
    c = pl.program_id(1)
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        and_ref[...] = jnp.ones_like(and_ref)

    i_ref = i_ref_ref[0]      # reference current = v_ref / r_divider
    for w in range(cols_per_block):
        sl = pl.dslice(w * width, width)
        i_on = jnp.dot(v_drive_ref[:, sl], g_t_ref[sl, :],
                       preferred_element_type=jnp.float32)
        i_leak = jnp.dot(lit1_ref[:, sl], leak_t_ref[sl, :],
                         preferred_element_type=jnp.float32)
        partial_cl = (i_on + i_leak) < i_ref
        and_ref[...] *= partial_cl.astype(jnp.float32)

    @pl.when(jnp.logical_and(k == nk - 1, c == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k == nk - 1)
    def _emit():
        out_ref[...] += jnp.dot(and_ref[...], pol_ref[...],
                                preferred_element_type=jnp.float32)


def imbue_infer_packed_kernel(scal_ref, litw_ref, g_t_ref, leak_t_ref,
                              pol_ref, out_ref, and_ref, *, width,
                              cols_per_block):
    """Packed-literal variant: stream ``[bt, kt/32]`` uint32 words from
    HBM and unpack to drive voltages per K tile, in VMEM, right before
    the column dots.  The conductance/leak planes stay f32 — they are
    programmed once and live on-device; only the per-request literal
    operand crosses the host->device boundary, so that is the plane
    whose wire format matters."""
    c = pl.program_id(1)
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        and_ref[...] = jnp.ones_like(and_ref)

    i_ref = scal_ref[0]       # reference current = v_ref / r_divider
    v_read = scal_ref[1]      # literal '0' drive voltage
    kt = cols_per_block * width
    bits = unpack_words_f32(litw_ref[...], n_bits=kt)     # [bt, kt] 0/1
    # Literal '0' drives v_read onto the on-path; literal '1' leaks.
    # (Word-padding bits unpack to 0 -> v_drive = v_read, but their
    # conductance/leak columns are zero-padded, so they contribute 0 —
    # identical to the unpacked wrapper's padding semantics.)
    v_drive = (1.0 - bits) * v_read
    for w in range(cols_per_block):
        lo, hi = w * width, (w + 1) * width
        sl = pl.dslice(lo, width)
        i_on = jnp.dot(v_drive[:, lo:hi], g_t_ref[sl, :],
                       preferred_element_type=jnp.float32)
        i_leak = jnp.dot(bits[:, lo:hi], leak_t_ref[sl, :],
                         preferred_element_type=jnp.float32)
        partial_cl = (i_on + i_leak) < i_ref
        and_ref[...] *= partial_cl.astype(jnp.float32)

    @pl.when(jnp.logical_and(k == nk - 1, c == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k == nk - 1)
    def _emit():
        out_ref[...] += jnp.dot(and_ref[...], pol_ref[...],
                                preferred_element_type=jnp.float32)


def imbue_infer_planes_kernel(*refs, width, cols_per_block, nk, has_dev):
    """Plane-packed variant: the conductance stack never reaches the
    kernel as f32.  It arrives as (a) the LRS/HRS include-index bitplane
    — ``[Lw, C] uint32``, 32x smaller than either f32 plane — and
    optionally (b) a per-cell additive resistance-deviation plane
    (D2D draws and fault overlays fold into it; it is elided entirely
    for nominal stacks).  Both stay in ANY/HBM memory space; the kernel
    DMAs one K-chunk at a time into a 2-slot VMEM scratch and starts
    chunk ``k+1``'s copy before computing chunk ``k`` — the same
    compute/transfer overlap ``AsyncServeEngine`` plays at the host,
    pushed into the kernel.

    Per chunk the conductance/leak tiles are RECONSTRUCTED in VMEM with
    the exact op order of ``core.imbue.conductances``::

        r_nom = bits * r_lrs + (1 - bits) * r_hrs      # exact 0/1 select
        r     = r_nom + dev                            # dev = r - r_nom
        g     = 1 / (series_factor * r)
        leak  = leak_nom * (r_nom / r)

    so nominal (dev == 0) results are bit-identical to the f32-plane
    kernels.  Word-padded columns past ``l_valid`` would otherwise
    reconstruct as HRS cells (the f32 path zero-pads them away), so an
    in-kernel validity mask zeroes their ``g``/``leak`` contributions.

    Grid is (replica, B block, C block): replica ``r`` reads its own
    ``[L, C]`` slab of the ``[R, L, C]`` deviation stack, so a whole
    replica stack is one kernel call (a vmap would instead batch the
    ANY-space operand, which Mosaic cannot lower).
    """
    if has_dev:
        (scal_ref, litw_ref, incw_hbm, dev_hbm, pol_ref,
         out_ref, and_ref) = refs
    else:
        scal_ref, litw_ref, incw_hbm, pol_ref, out_ref, and_ref = refs
        dev_hbm = None
    r_idx = pl.program_id(0)
    j = pl.program_id(2)

    i_ref = scal_ref[0]
    v_read = scal_ref[1]
    r_lrs = scal_ref[2]
    r_hrs = scal_ref[3]
    leak_inc = scal_ref[4]
    leak_exc = scal_ref[5]
    series_factor = scal_ref[6]
    l_valid = scal_ref[7]

    kt = cols_per_block * width
    kw = kt // WORD
    ct = and_ref.shape[1]

    and_ref[...] = jnp.ones_like(and_ref)

    def compute_chunk(k, inc_words, dev_tile):
        bits_inc = unpack_words_f32_cols(inc_words, n_bits=kt)  # [kt, ct]
        r_nom = bits_inc * r_lrs + (1.0 - bits_inc) * r_hrs
        r = r_nom if dev_tile is None else r_nom + dev_tile
        # Mask word-padding columns (>= l_valid): the f32 path zero-pads
        # their g/leak rows; reconstruction must not resurrect them.
        row = jax.lax.broadcasted_iota(jnp.int32, (kt, ct), 0)
        valid = (k * kt + row).astype(jnp.float32) < l_valid
        g = jnp.where(valid, 1.0 / (series_factor * r), 0.0)
        leak_nom = jnp.where(bits_inc > 0.5, leak_inc, leak_exc)
        leak = jnp.where(valid, leak_nom * (r_nom / r), 0.0)

        bits = unpack_words_f32(litw_ref[k], n_bits=kt)         # [bt, kt]
        v_drive = (1.0 - bits) * v_read
        for w in range(cols_per_block):
            lo, hi = w * width, (w + 1) * width
            i_on = jnp.dot(v_drive[:, lo:hi], g[lo:hi, :],
                           preferred_element_type=jnp.float32)
            i_leak = jnp.dot(bits[:, lo:hi], leak[lo:hi, :],
                             preferred_element_type=jnp.float32)
            partial_cl = (i_on + i_leak) < i_ref
            and_ref[...] *= partial_cl.astype(jnp.float32)

    def body(inc_scr, inc_sem, dev_scr=None, dev_sem=None):
        def copies(slot, k):
            cps = [pltpu.make_async_copy(
                incw_hbm.at[pl.dslice(k * kw, kw), pl.dslice(j * ct, ct)],
                inc_scr.at[slot], inc_sem.at[slot])]
            if has_dev:
                cps.append(pltpu.make_async_copy(
                    dev_hbm.at[r_idx, pl.dslice(k * kt, kt),
                               pl.dslice(j * ct, ct)],
                    dev_scr.at[slot], dev_sem.at[slot]))
            return cps

        for cp in copies(0, 0):
            cp.start()

        def loop(k, carry):
            slot = k % 2
            nxt = k + 1

            @pl.when(nxt < nk)
            def _prefetch():
                for cp in copies(nxt % 2, nxt):
                    cp.start()

            for cp in copies(slot, k):
                cp.wait()
            compute_chunk(k, inc_scr[slot],
                          dev_scr[slot] if has_dev else None)
            return carry

        jax.lax.fori_loop(0, nk, loop, 0)

    if has_dev:
        pl.run_scoped(body,
                      inc_scr=pltpu.VMEM((2, kw, ct), jnp.uint32),
                      inc_sem=pltpu.SemaphoreType.DMA((2,)),
                      dev_scr=pltpu.VMEM((2, kt, ct), jnp.float32),
                      dev_sem=pltpu.SemaphoreType.DMA((2,)))
    else:
        pl.run_scoped(body,
                      inc_scr=pltpu.VMEM((2, kw, ct), jnp.uint32),
                      inc_sem=pltpu.SemaphoreType.DMA((2,)))

    @pl.when(j == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(and_ref[...], pol_ref[...],
                            preferred_element_type=jnp.float32)


def imbue_infer_planes_call(litw, incw_t, dev_t, pol, v_ref, v_read, *,
                            width, r_div, r_lrs, r_hrs, leak_inc, leak_exc,
                            series_factor, l_valid, bt, ct, kt, interpret):
    """``[B, L/32] -> [R, B, M]`` analog class sums from packed literals
    AND a plane-packed conductance stack.

    ``incw_t`` is the transposed include-index bitplane ``[Lw, C]``
    uint32 (bit ``j`` of word row ``w`` = literal ``32*w + j``), shared
    by every replica; ``dev_t`` is the transposed additive deviation
    stack ``[R, L, C]`` f32, or None for a nominal (index-only) chip
    (then ``R == 1``).  ``kt`` counts bits and must be a multiple of
    both ``width`` and 32.  The K dimension is streamed *inside* the
    kernel with double-buffered HBM->VMEM copies, so the grid is
    (R, B, C) blocks.
    """
    if kt % width:
        raise ValueError(f"kt={kt} must be a multiple of width={width}")
    if kt % WORD:
        raise ValueError(f"kt={kt} must be a multiple of {WORD} (packed)")
    kw = kt // WORD
    b, lw = litw.shape
    c = incw_t.shape[1]
    m = pol.shape[1]
    if lw != incw_t.shape[0]:
        raise ValueError(f"literal words cover {lw} word rows but the "
                         f"include bitplane has {incw_t.shape[0]}")
    if lw % kw:
        raise ValueError(f"word rows {lw} not divisible by kt/32={kw}")
    has_dev = dev_t is not None
    if has_dev and dev_t.shape[1:] != (lw * WORD, c):
        raise ValueError(f"dev planes {dev_t.shape[1:]} != "
                         f"{(lw * WORD, c)}")
    n_rep = dev_t.shape[0] if has_dev else 1
    nk = lw // kw
    grid = (n_rep, b // bt, c // ct)
    kern = partial(imbue_infer_planes_kernel, width=width,
                   cols_per_block=kt // width, nk=nk, has_dev=has_dev)
    scal = jnp.asarray([v_ref / r_div, v_read, r_lrs, r_hrs, leak_inc,
                        leak_exc, series_factor, float(l_valid)],
                       dtype=jnp.float32)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                # scalars
        pl.BlockSpec((nk, bt, kw),                            # literal words
                     lambda r, i, j: (0, i, 0)),
        pl.BlockSpec(memory_space=pl.ANY),                    # include plane
    ]
    operands = [scal, word_chunks(litw, kw)]
    if has_dev:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))    # dev planes
        operands += [incw_t, dev_t, pol]
    else:
        operands += [incw_t, pol]
    in_specs.append(pl.BlockSpec((ct, m), lambda r, i, j: (j, 0)))  # pol
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, bt, m), lambda r, i, j: (r, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rep, b, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, ct), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


def imbue_infer_call(v_drive, lit1, g_t, leak_t, pol, v_ref, *,
                     width, r_div, bt, ct, kt, interpret):
    """``[B, L] -> [B, M]`` analog class sums (padded shapes).

    ``g_t``/``leak_t`` are ``[L, C]`` (pre-transposed); ``kt`` must be a
    multiple of ``width``.
    """
    if kt % width:
        raise ValueError(f"kt={kt} must be a multiple of width={width}")
    b, l = v_drive.shape
    c = g_t.shape[1]
    m = pol.shape[1]
    grid = (b // bt, c // ct, l // kt)
    kern = partial(imbue_infer_kernel, width=width,
                   cols_per_block=kt // width)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # v_ref scalar
            pl.BlockSpec((bt, kt), lambda i, j, k: (i, k)),   # v_drive
            pl.BlockSpec((bt, kt), lambda i, j, k: (i, k)),   # lit1
            pl.BlockSpec((kt, ct), lambda i, j, k: (k, j)),   # g_t
            pl.BlockSpec((kt, ct), lambda i, j, k: (k, j)),   # leak_t
            pl.BlockSpec((ct, m), lambda i, j, k: (j, 0)),    # pol
        ],
        out_specs=pl.BlockSpec((bt, m), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, ct), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray([v_ref / r_div], dtype=jnp.float32), v_drive, lit1, g_t,
      leak_t, pol)


def imbue_infer_packed_call(litw, g_t, leak_t, pol, v_ref, v_read, *,
                            width, r_div, bt, ct, kt, interpret):
    """``[B, L/32] -> [B, M]`` analog class sums from packed literals.

    ``kt`` counts BITS and must be a multiple of both ``width`` and 32;
    the literal word blocks are ``kt // 32`` wide.  ``g_t``/``leak_t``
    are dense f32 ``[L, C]`` exactly as in :func:`imbue_infer_call` —
    the packed format applies to the per-request literal operand only.
    """
    if kt % width:
        raise ValueError(f"kt={kt} must be a multiple of width={width}")
    if kt % WORD:
        raise ValueError(f"kt={kt} must be a multiple of {WORD} (packed)")
    kw = kt // WORD
    b, lw = litw.shape
    c = g_t.shape[1]
    m = pol.shape[1]
    if lw * WORD != g_t.shape[0]:
        raise ValueError(f"packed literals cover {lw * WORD} bits but "
                         f"g_t has {g_t.shape[0]} rows")
    grid = (b // bt, c // ct, lw // kw)
    kern = partial(imbue_infer_packed_kernel, width=width,
                   cols_per_block=kt // width)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # [i_ref, v_read]
            pl.BlockSpec((None, bt, kw),                      # literal words
                         lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((kt, ct), lambda i, j, k: (k, j)),   # g_t
            pl.BlockSpec((kt, ct), lambda i, j, k: (k, j)),   # leak_t
            pl.BlockSpec((ct, m), lambda i, j, k: (j, 0)),    # pol
        ],
        out_specs=pl.BlockSpec((bt, m), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, ct), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray([v_ref / r_div, v_read], dtype=jnp.float32),
      word_chunks(litw, kw), g_t, leak_t, pol)
