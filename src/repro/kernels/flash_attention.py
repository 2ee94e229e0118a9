"""Flash (online-softmax) attention as a Pallas TPU kernel.

The §Roofline analysis shows unfused attention is the dominant memory
term for every dense train_4k/prefill_32k cell: the f32 score tensor and
its ~8-op softmax chain round-trip HBM once per op.  Chunked lax.scan
attention does NOT fix this at the XLA level — every fusion boundary is
still HBM (§Perf iter M1).  The fix is keeping the whole
score -> mask -> online-softmax -> weighted-sum pipeline VMEM-resident,
i.e. this kernel.

Design (TPU-native, per DESIGN.md §2 hardware adaptation):
  * grid (B*H, S/bq, S/bk), k innermost; MXU-aligned bq=bk=128 blocks;
  * VMEM scratch carries (m, l, acc) across k steps — scores never leave
    the core;
  * causal/local masks from block indices (iota), softcap optional;
  * supports self-attention layouts [B, S, H, D] with any head count
    (wrapper folds B*H).

Training support: ``flash_attention_trainable`` is a ``jax.custom_vjp``
whose backward is the flash backward — two further Pallas kernels
(dK/dV accumulated over q blocks; dQ over k blocks) that recompute the
probability blocks from the saved (q, k, v, logsumexp) instead of
storing them, exactly like Dao et al.'s Algorithm 2 (§Perf iter M1b:
this is what the chunked-lax.scan attempt could not express).  Gradients
validated against ``jax.grad`` of the unfused oracle across causal /
window / softcap configs in interpret mode.

VMEM budget at (bq, bk, d) = (128, 128, 128), f32 accumulators:
q 64KB + k/v 128KB + acc 64KB + stats 1KB + scores 64KB < 0.5 MB.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_mask(iq, ik, bq, bk, seq_len, causal, window):
    """(mask [bq, bk], run) for the (iq, ik) block pair."""
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_len
    run = True
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
        run = jnp.logical_and(ik * bk <= iq * bq + bq - 1, True)
    if window:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
        run = jnp.logical_and(
            run, (iq * bq) - (ik * bk + bk - 1) < window)
    return mask, run


def _scores(q_blk, k_blk, scale, cap):
    """Raw and capped scores for a block pair: (s, x) where s is what the
    softmax sees and x is the pre-softcap value (for the tanh grad)."""
    x = jax.lax.dot_general(
        q_blk, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    s = cap * jnp.tanh(x / cap) if cap else x
    return s, x


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                  acc_ref, *, scale, causal, window, cap, bq, bk,
                  seq_len):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mask, run = _block_mask(iq, ik, bq, bk, seq_len, causal, window)

    @pl.when(run)
    def _step():
        s, _ = _scores(q_ref[0], k_ref[0], scale, cap)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] +
                      jnp.log(jnp.maximum(l_ref[...], 1e-30)))[:, 0]


def _fold(t, b, s, h, d, blk):
    t = t.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    pad = (-s) % blk
    if pad:
        t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
    return t


def _unfold(t, b, s, h, d):
    return t[:, :s, :].reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _flash_fwd_raw(q, k, v, causal, window, softcap, bq, bk, interp):
    """Returns (out [B,S,H,D], lse [BH, Sp])."""
    b, s, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    blk = max(bq, bk)
    qf = _fold(q, b, s, h, d, blk)
    kf = _fold(k, b, s, h, d, blk)
    vf = _fold(v, b, s, h, d, blk)
    sp = qf.shape[1]
    grid = (b * h, sp // bq, sp // bk)
    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        cap=softcap, bq=bq, bk=bk, seq_len=s)
    out, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bq), lambda bh, iq, ik: (bh, iq)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b * h, sp, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, sp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interp,
    )(qf, kf, vf)
    return _unfold(out, b, s, h, d), lse


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    bq=128, bk=128, interpret=None):
    """q/k/v ``[B, S, H, D]`` (same S) -> ``[B, S, H, D]``.

    Heads must already be expanded (GQA: expand kv first).  Sequence is
    padded to the block size internally.  Forward only — for gradients
    use ``flash_attention_trainable``.
    """
    interp = (jax.default_backend() != "tpu") if interpret is None \
        else interpret
    out, _ = _flash_fwd_raw(q, k, v, causal, window, softcap, bq, bk,
                            interp)
    return out


# ------------------------------------------------------------- backward

def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                      dk_ref, dv_ref, acc_dk, acc_dv, *, scale, causal,
                      window, cap, bq, bk, seq_len):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        acc_dk[...] = jnp.zeros_like(acc_dk)
        acc_dv[...] = jnp.zeros_like(acc_dv)

    mask, run = _block_mask(iq, ik, bq, bk, seq_len, causal, window)

    @pl.when(run)
    def _step():
        s, x = _scores(q_ref[0], k_ref[0], scale, cap)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, None])                 # [bq, bk]
        do = do_ref[0].astype(jnp.float32)
        acc_dv[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, d]
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bq, bk]
        ds = p * (dp - dd_ref[0][:, None])
        if cap:
            ds = ds * (1.0 - jnp.square(jnp.tanh(x / cap)))
        ds = ds * scale
        acc_dk[...] += jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, d]

    @pl.when(iq == nq - 1)
    def _emit():
        dk_ref[0] = acc_dk[...].astype(dk_ref.dtype)
        dv_ref[0] = acc_dv[...].astype(dv_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                     dq_ref, acc_dq, *, scale, causal, window, cap,
                     bq, bk, seq_len):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_dq[...] = jnp.zeros_like(acc_dq)

    mask, run = _block_mask(iq, ik, bq, bk, seq_len, causal, window)

    @pl.when(run)
    def _step():
        s, x = _scores(q_ref[0], k_ref[0], scale, cap)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, None])
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dd_ref[0][:, None])
        if cap:
            ds = ds * (1.0 - jnp.square(jnp.tanh(x / cap)))
        ds = ds * scale
        acc_dq[...] += jax.lax.dot_general(
            ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bq, d]

    @pl.when(ik == nk - 1)
    def _emit():
        dq_ref[0] = acc_dq[...].astype(dq_ref.dtype)


def _flash_bwd_raw(q, k, v, out, lse, dout, causal, window, softcap,
                   bq, bk, interp):
    b, s, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    blk = max(bq, bk)
    qf = _fold(q, b, s, h, d, blk)
    kf = _fold(k, b, s, h, d, blk)
    vf = _fold(v, b, s, h, d, blk)
    dof = _fold(dout, b, s, h, d, blk)
    of = _fold(out, b, s, h, d, blk)
    sp = qf.shape[1]
    # D_i = rowsum(dO ∘ O) (cheap elementwise, jnp)
    dd = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), -1)

    common = dict(scale=scale, causal=causal, window=window, cap=softcap,
                  bq=bq, bk=bk, seq_len=s)
    dkv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, **common),
        grid=(b * h, sp // bk, sp // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, ik, iq: (bh, iq, 0)),  # q
            pl.BlockSpec((1, bk, d), lambda bh, ik, iq: (bh, ik, 0)),  # k
            pl.BlockSpec((1, bk, d), lambda bh, ik, iq: (bh, ik, 0)),  # v
            pl.BlockSpec((1, bq, d), lambda bh, ik, iq: (bh, iq, 0)),  # dO
            pl.BlockSpec((1, bq), lambda bh, ik, iq: (bh, iq)),        # lse
            pl.BlockSpec((1, bq), lambda bh, ik, iq: (bh, iq)),        # D
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, ik, iq: (bh, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ik, iq: (bh, ik, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b * h, sp, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, sp, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interp,
    )(qf, kf, vf, dof, lse, dd)
    dk, dv = dkv

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, **common),
        grid=(b * h, sp // bq, sp // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bq), lambda bh, iq, ik: (bh, iq)),
            pl.BlockSpec((1, bq), lambda bh, iq, ik: (bh, iq)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interp,
    )(qf, kf, vf, dof, lse, dd)

    return (_unfold(dq, b, s, h, d), _unfold(dk, b, s, h, d),
            _unfold(dv, b, s, h, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_trainable(q, k, v, causal=True, window=0,
                              softcap=0.0, bq=128, bk=128,
                              interpret=None):
    """Differentiable flash attention (custom VJP = flash backward)."""
    interp = (jax.default_backend() != "tpu") if interpret is None \
        else interpret
    out, _ = _flash_fwd_raw(q, k, v, causal, window, softcap, bq, bk,
                            interp)
    return out


def _fa_fwd(q, k, v, causal, window, softcap, bq, bk, interpret):
    interp = (jax.default_backend() != "tpu") if interpret is None \
        else interpret
    out, lse = _flash_fwd_raw(q, k, v, causal, window, softcap, bq, bk,
                              interp)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, softcap, bq, bk, interpret, res, dout):
    interp = (jax.default_backend() != "tpu") if interpret is None \
        else interpret
    q, k, v, out, lse = res
    return _flash_bwd_raw(q, k, v, out, lse, dout, causal, window,
                          softcap, bq, bk, interp)


flash_attention_trainable.defvjp(_fa_fwd, _fa_bwd)
