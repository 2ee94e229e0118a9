"""Pallas TPU kernels for digital TM clause evaluation + fused inference.

The crossbar insight, MXU-shaped (paper §II): clause evaluation is a
binary matmul ``viol[b, c] = sum_i lit0[b, i] * include[c, i]`` followed by
a threshold (``viol == 0``), and class sums are a second (tiny) matmul
against a signed polarity one-hot.  Fusing threshold + polarity matmul into
the violation matmul keeps clause bits in VMEM — they never touch HBM.

Unpacked (f32 operand) kernels:

``clause_eval_kernel``  grid (B/bt, C/ct, L/kt); f32 violation accumulator
                        in VMEM scratch; emits 0/1 clause block on the last
                        K step.
``tm_infer_kernel``     same, plus on the last K step accumulates
                        ``clauses @ pol`` into the [bt, M] output block
                        (revisited across the C grid dimension).

Packed (uint32 bitplane operand) kernels — the Boolean wire format:

``clause_eval_packed_kernel`` / ``tm_infer_packed_kernel`` stream
``[bt, kt/32]`` literal words and ``[kt/32, ct]`` include words from HBM
(32x less traffic than f32, 8x less than uint8) and never expand them:
the violation count for a digital clause is
``popcount(~lit_words & include_words)`` summed over the words of the K
tile — a bitwise AND + population count on the VPU, where the MXU matmul
is pure overhead.  Padding bits are safe by construction: literal pad
bits invert to 1 but include pad bits are 0, so ``AND`` kills them.

Blocks are MXU-aligned (128 multiples) in the unpacked path; packed K
tiles are multiples of 32 bits.  All accumulation stays in VMEM scratch.
Inputs arrive pre-transposed (``include_t [L, C]`` / ``[L/32, C]``) so
the contraction is a plain row-major sweep.  Packed literal words reach
the kernels as ``[K chunks, B, kw]`` (``bitpack.word_chunks``): a K tile
of ``kw`` words is then a full lane dim, which Mosaic accepts, where a
``kw``-lane block of ``[B, L/32]`` is neither full nor 128-aligned.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitpack import WORD, word_chunks


def clause_eval_kernel(lit0_ref, inc_t_ref, out_ref, acc_ref):
    """One (b, c, k) grid step of the violation matmul + threshold."""
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lit0_ref[...], inc_t_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _emit():
        out_ref[...] = (acc_ref[...] == 0.0).astype(out_ref.dtype)


def tm_infer_kernel(lit0_ref, inc_t_ref, pol_ref, out_ref, acc_ref):
    """Fused: violation matmul -> threshold -> polarity matmul."""
    c = pl.program_id(1)
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lit0_ref[...], inc_t_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(k == nk - 1, c == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k == nk - 1)
    def _emit():
        clauses = (acc_ref[...] == 0.0).astype(jnp.float32)
        out_ref[...] += jnp.dot(clauses, pol_ref[...],
                                preferred_element_type=jnp.float32)


def _packed_viol_block(lit_words, inc_words, acc, kw):
    """Violation counts for one packed K tile: AND + popcount per word.

    ``lit_words`` ``[bt, kw]`` holds raw literal words (NOT pre-inverted
    — the packed wire format is the literals themselves); the kernel
    inverts in registers.  Each word contributes
    ``popcount((~lit_w)[bt, 1] & inc_w[1, ct])`` — an outer bitwise AND
    broadcast on the VPU, no MXU pass.
    """
    for w in range(kw):
        l0 = ~lit_words[:, w:w + 1]                      # [bt, 1] uint32
        iw = inc_words[w:w + 1, :]                       # [1, ct] uint32
        acc = acc + jax.lax.population_count(l0 & iw).astype(jnp.int32)
    return acc


def clause_eval_packed_kernel(litw_ref, incw_t_ref, out_ref, acc_ref, *, kw):
    """One (b, c, k) grid step of the packed violation count + threshold."""
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = _packed_viol_block(litw_ref[...], incw_t_ref[...],
                                      acc_ref[...], kw)

    @pl.when(k == nk - 1)
    def _emit():
        out_ref[...] = (acc_ref[...] == 0).astype(out_ref.dtype)


def tm_infer_packed_kernel(litw_ref, incw_t_ref, pol_ref, out_ref, acc_ref,
                           *, kw):
    """Fused packed path: AND+popcount violations -> threshold -> polarity
    matmul (the only MXU pass left in the digital pipeline)."""
    c = pl.program_id(1)
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = _packed_viol_block(litw_ref[...], incw_t_ref[...],
                                      acc_ref[...], kw)

    @pl.when(jnp.logical_and(k == nk - 1, c == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k == nk - 1)
    def _emit():
        clauses = (acc_ref[...] == 0).astype(jnp.float32)
        out_ref[...] += jnp.dot(clauses, pol_ref[...],
                                preferred_element_type=jnp.float32)


def tm_infer_planes_kernel(litw_ref, incw_hbm, pol_ref, out_ref, acc_ref,
                           *, kw, nk):
    """Double-buffered packed path: the resident include bitplane stays
    in ANY/HBM memory space and the kernel DMAs one ``[kw, ct]`` word
    chunk at a time into a 2-slot VMEM scratch, starting chunk ``k+1``'s
    copy before counting chunk ``k``'s violations — kernel-level
    compute/transfer overlap on top of the packed format's 32x traffic
    reduction.  Arithmetic is the integer AND+popcount path of
    :func:`tm_infer_packed_kernel`, so results are identical bit-for-bit.
    """
    j = pl.program_id(1)
    ct = acc_ref.shape[1]

    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(inc_scr, inc_sem):
        def cp(slot, k):
            return pltpu.make_async_copy(
                incw_hbm.at[pl.dslice(k * kw, kw), pl.dslice(j * ct, ct)],
                inc_scr.at[slot], inc_sem.at[slot])

        cp(0, 0).start()

        def loop(k, carry):
            slot = k % 2
            nxt = k + 1

            @pl.when(nxt < nk)
            def _prefetch():
                cp(nxt % 2, nxt).start()

            cp(slot, k).wait()
            acc_ref[...] = _packed_viol_block(litw_ref[k], inc_scr[slot],
                                              acc_ref[...], kw)
            return carry

        jax.lax.fori_loop(0, nk, loop, 0)

    pl.run_scoped(body,
                  inc_scr=pltpu.VMEM((2, kw, ct), jnp.uint32),
                  inc_sem=pltpu.SemaphoreType.DMA((2,)))

    @pl.when(j == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    clauses = (acc_ref[...] == 0).astype(jnp.float32)
    out_ref[...] += jnp.dot(clauses, pol_ref[...],
                            preferred_element_type=jnp.float32)


def clause_eval_call(lit0, inc_t, *, bt, ct, kt, interpret):
    """``[B, L] x [L, C] -> [B, C]`` clause outputs (padded shapes)."""
    b, l = lit0.shape
    c = inc_t.shape[1]
    grid = (b // bt, c // ct, l // kt)
    return pl.pallas_call(
        clause_eval_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, kt), lambda i, j, k: (i, k)),
            pl.BlockSpec((kt, ct), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bt, ct), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, ct), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(lit0, inc_t)


def tm_infer_call(lit0, inc_t, pol, *, bt, ct, kt, interpret):
    """``[B, L] x [L, C] x [C, M] -> [B, M]`` fused class sums (padded)."""
    b, l = lit0.shape
    c = inc_t.shape[1]
    m = pol.shape[1]
    grid = (b // bt, c // ct, l // kt)
    return pl.pallas_call(
        tm_infer_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, kt), lambda i, j, k: (i, k)),
            pl.BlockSpec((kt, ct), lambda i, j, k: (k, j)),
            pl.BlockSpec((ct, m), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bt, m), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, ct), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(lit0, inc_t, pol)


def clause_eval_packed_call(litw, incw_t, *, bt, ct, kt, interpret):
    """``[B, L/32] x [L/32, C] -> [B, C]`` packed clause outputs.

    ``kt`` counts BITS (a multiple of 32); the word blocks are
    ``kt // 32`` wide.
    """
    if kt % WORD:
        raise ValueError(f"kt={kt} must be a multiple of {WORD} (packed)")
    kw = kt // WORD
    b, lw = litw.shape
    c = incw_t.shape[1]
    grid = (b // bt, c // ct, lw // kw)
    return pl.pallas_call(
        partial(clause_eval_packed_kernel, kw=kw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bt, kw), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((kw, ct), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bt, ct), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, ct), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(word_chunks(litw, kw), incw_t)


def tm_infer_planes_call(litw, incw_t, pol, *, bt, ct, kt, interpret):
    """``[B, L/32] x [L/32, C] x [C, M] -> [B, M]`` fused packed sums
    with the include bitplane left resident in HBM and streamed through
    the kernel's own double-buffered DMA pipeline (grid is (B, C) only;
    K is internal)."""
    if kt % WORD:
        raise ValueError(f"kt={kt} must be a multiple of {WORD} (packed)")
    kw = kt // WORD
    b, lw = litw.shape
    c = incw_t.shape[1]
    m = pol.shape[1]
    if lw % kw:
        raise ValueError(f"word rows {lw} not divisible by kt/32={kw}")
    nk = lw // kw
    grid = (b // bt, c // ct)
    return pl.pallas_call(
        partial(tm_infer_planes_kernel, kw=kw, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((nk, bt, kw), lambda i, j: (0, i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((ct, m), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bt, m), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, ct), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(word_chunks(litw, kw), incw_t, pol)


def tm_infer_packed_call(litw, incw_t, pol, *, bt, ct, kt, interpret):
    """``[B, L/32] x [L/32, C] x [C, M] -> [B, M]`` fused packed sums."""
    if kt % WORD:
        raise ValueError(f"kt={kt} must be a multiple of {WORD} (packed)")
    kw = kt // WORD
    b, lw = litw.shape
    c = incw_t.shape[1]
    m = pol.shape[1]
    grid = (b // bt, c // ct, lw // kw)
    return pl.pallas_call(
        partial(tm_infer_packed_kernel, kw=kw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bt, kw), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((kw, ct), lambda i, j, k: (k, j)),
            pl.BlockSpec((ct, m), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bt, m), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, ct), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(word_chunks(litw, kw), incw_t, pol)
