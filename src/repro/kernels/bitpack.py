"""Bit-packed Boolean planes: the native wire format of the inference stack.

IMBUE's premise is that inference stays in the Boolean domain — literals
are digital voltages, include bits are programmed cells — yet shipping
them as float32 (or even uint8) inflates memory traffic 32x (8x) for
data that is one bit wide.  This module is the single source of truth
for the packed representation used end-to-end:

* **layout** — little-endian within each ``uint32`` word: bit ``j`` of
  word ``w`` is Boolean element ``32*w + j``.  Ragged lengths are
  zero-padded up to the word boundary (padding bits are 0, which every
  consumer treats as "excluded literal / excluded cell").
* :func:`pack_bits` / :func:`unpack_bits` — device-side (jnp) pack and
  unpack, shape ``[..., L] <-> [..., ceil(L/32)]``.
* :func:`pack_bits_np` — host-side ``np.packbits`` path (used by the
  serving batcher once per request at submit time, so the queue and the
  host->device transfer carry ``uint32`` words, not bytes).
* :func:`unpack_words_f32` — the in-kernel unpack used by the Pallas
  packed kernels: one ``[bt, kw]`` word block -> ``[bt, 32*kw]`` f32
  bits in VMEM, right before the violation matmul.
* :func:`unpack_words_f32_cols` — the column-axis twin used by the
  plane-packed analog kernels: one ``[kw, ct]`` include-index word
  block -> ``[32*kw, ct]`` f32 bits, i.e. the transposed-plane layout
  the conductance reconstruction consumes.
* :func:`word_chunks` — the ``[K chunks, B, kw]`` literal-word layout
  the packed kernels block over, so that each block's lane dim is full.

The layouts of the np and jnp packers are asserted identical by the
round-trip tests (``tests/test_packed*.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

WORD = 32                      # bits per packed word (uint32 lanes)


def words_for(n_bits: int) -> int:
    """Number of uint32 words holding ``n_bits`` booleans."""
    return -(-n_bits // WORD)


def pack_bits(bits: jax.Array) -> jax.Array:
    """``[..., L]`` 0/1 -> ``[..., ceil(L/32)] uint32`` (little-endian).

    Accepts any integer/bool dtype; values must be 0/1.  Ragged ``L`` is
    zero-padded to the word boundary.
    """
    bits = jnp.asarray(bits)
    l = bits.shape[-1]
    nw = words_for(l)
    pad = nw * WORD - l
    b = bits.astype(jnp.uint32)
    if pad:
        pads = [(0, 0)] * (b.ndim - 1) + [(0, pad)]
        b = jnp.pad(b, pads)
    b = b.reshape(*bits.shape[:-1], nw, WORD)
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    # bits are disjoint across the shift axis, so sum == bitwise OR
    return jnp.sum(b << shifts, axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, n_bits: int) -> jax.Array:
    """``[..., W] uint32`` -> ``[..., n_bits] uint8`` (inverse of pack)."""
    words = jnp.asarray(words, jnp.uint32)
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(*words.shape[:-1], words.shape[-1] * WORD)
    return flat[..., :n_bits].astype(jnp.uint8)


def pack_bits_np(bits: np.ndarray) -> np.ndarray:
    """Host-side pack: ``[..., L]`` 0/1 -> ``[..., ceil(L/32)] uint32``.

    Uses ``np.packbits(bitorder='little')`` + an explicit little-endian
    ``uint32`` view, so the layout matches :func:`pack_bits` bit-for-bit
    on any host.
    """
    bits = np.asarray(bits, dtype=np.uint8)
    nw = words_for(bits.shape[-1])
    by = np.packbits(bits, axis=-1, bitorder="little")   # [..., ceil(L/8)]
    pad = nw * 4 - by.shape[-1]
    if pad:
        pads = [(0, 0)] * (by.ndim - 1) + [(0, pad)]
        by = np.pad(by, pads)
    return np.ascontiguousarray(by).view("<u4")


def unpack_words_f32(words: jax.Array, *, n_bits: int) -> jax.Array:
    """In-kernel unpack: ``[bt, kw] uint32`` -> ``[bt, n_bits] f32``.

    ``n_bits`` must equal ``32 * kw``.  Written with ``jnp.repeat`` +
    ``broadcasted_iota`` (>= 2D, per the TPU iota constraint) so it
    lowers inside a Pallas kernel body; the expansion lives entirely in
    VMEM/registers — HBM only ever sees the words.
    """
    bt, kw = words.shape
    if n_bits != kw * WORD:
        raise ValueError(f"n_bits={n_bits} != {kw}*{WORD}")
    expanded = jnp.repeat(words, WORD, axis=1)                 # [bt, n_bits]
    shift = jax.lax.broadcasted_iota(jnp.uint32, (bt, n_bits), 1) % WORD
    return _bits_f32((expanded >> shift) & jnp.uint32(1))


def unpack_words_f32_cols(words: jax.Array, *, n_bits: int) -> jax.Array:
    """In-kernel unpack along axis 0: ``[kw, ct] uint32`` ->
    ``[n_bits, ct] f32``.

    ``n_bits`` must equal ``32 * kw``.  Bit ``j`` of word row ``w``
    becomes row ``32*w + j`` — the transposed ``[L, C]`` plane layout of
    the analog kernels' conductance operands, so the plane-packed
    kernels can reconstruct ``g``/``leak`` tiles in VMEM from an index
    bitplane that is 32x smaller in HBM.
    """
    kw, ct = words.shape
    if n_bits != kw * WORD:
        raise ValueError(f"n_bits={n_bits} != {kw}*{WORD}")
    expanded = jnp.repeat(words, WORD, axis=0)                 # [n_bits, ct]
    shift = jax.lax.broadcasted_iota(jnp.uint32, (n_bits, ct), 0) % WORD
    return _bits_f32((expanded >> shift) & jnp.uint32(1))


def _bits_f32(bits: jax.Array) -> jax.Array:
    """0/1 ``uint32`` -> f32 through ``int32``: Mosaic has no
    ``uint32 -> f32`` cast, and for 0/1 values the detour is exact."""
    return bits.astype(jnp.int32).astype(jnp.float32)


def word_chunks(words: jax.Array, kw: int) -> jax.Array:
    """``[B, Lw] uint32`` -> ``[Lw/kw, B, kw]``: the K chunks of a word
    matrix moved onto a leading axis.

    A Pallas TPU block's last dim must be a multiple of 128 lanes or the
    array's full dim, and a dynamic lane offset must be provably
    128-aligned.  A ``kw``-word chunk (8 or 16 words at the default
    tiles) is neither on a ``[B, Lw]`` array; as the FULL last dim of
    this layout it is legal, and a chunk is picked by a block index or a
    dynamic index on the untiled leading axis.  ``Lw`` must already be a
    multiple of ``kw``.
    """
    b, lw = words.shape
    if lw % kw:
        raise ValueError(f"word count {lw} not divisible by chunk {kw}")
    return words.reshape(b, lw // kw, kw).transpose(1, 0, 2)
