"""Plain references, written from the IMBUE paper and independent of the
program: nothing here imports ``repro`` or takes an array it made.

* ``digital_sums``: the Tsetlin machine's Boolean semantics.  A clause
  fires iff none of its included literals is 0 and it has an include;
  class sums add +1 for even clauses and -1 for odd ones, per class.
* ``analog_margins``: the IMBUE crossbar read (paper section II, Table
  I).  Device-to-device resistances are drawn from the run's key exactly
  as the programming step is specified (a lognormal HRS and a truncated
  normal LRS per cell, one key split per replica); each 32-cell column
  sums ``V/(alpha R)`` over cells driven by a literal 0 and the scaled
  leak over cells at literal 1.  A column senses 1 iff its current is
  below the reference current midway between the all-exclude leak band
  and one include violation.  Per clause it returns the relative margin
  ``mu = max over columns (I / I_ref - 1)``: the clause fires iff
  ``mu < 0``, and ``|mu|`` is how large a relative error would flip it.
* ``kws_rows``: the Boolean rows of keyword windows, thresholding each
  frame channel at the median of the fitting frames.

The references run in float32 at the highest matmul precision; the
``dtype`` switch gives the control (the same computation with bfloat16
operands).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# Published device constants (IMBUE, Table I and section III-C).
LRS_MEAN, LRS_MIN, LRS_MAX = 1.64e3, 1.55e3, 1.67e3
HRS_MEAN, HRS_MIN, HRS_MAX = 65.56e3, 31.0e3, 155.0e3
SERIES = 1.61                   # 1T1R read-path factor
V_READ = 0.2                    # drive of a literal 0 (V)
I_LEAK_INCLUDE, I_LEAK_EXCLUDE = 137e-9, 9.9e-9
WIDTH = 32                      # cells per column
HRS_LOG_SIGMA = (math.log(HRS_MAX / HRS_MEAN)
                 + math.log(HRS_MEAN / HRS_MIN)) / 6.0
LRS_SIGMA = (LRS_MAX - LRS_MIN) / 6.0
I_REF = 0.5 * (WIDTH * V_READ / (SERIES * HRS_MEAN)
               + V_READ / (SERIES * LRS_MEAN))


def polarity(classes: int, per_class: int) -> np.ndarray:
    return np.tile(np.where(np.arange(per_class) % 2 == 0, 1, -1),
                   classes).astype(np.int64)


def class_sums(fired: np.ndarray, classes: int) -> np.ndarray:
    """``[..., C]`` clause outputs -> ``[..., M]`` class sums."""
    per = fired.shape[-1] // classes
    votes = fired.astype(np.int64) * polarity(classes, per)
    return votes.reshape(*fired.shape[:-1], classes, per).sum(-1)


@jax.jit
def _fired(include, x):
    lits = jnp.concatenate([x, 1 - x], axis=-1).astype(jnp.float32)
    viol = jnp.matmul(1.0 - lits, include.astype(jnp.float32).T,
                      precision=jax.lax.Precision.HIGHEST)
    return (viol == 0) & include.any(axis=-1)[None, :]


def digital_sums(include, x: np.ndarray, classes: int,
                 block: int = 4096) -> np.ndarray:
    """Class sums ``[N, M]`` of Boolean requests ``x [N, F]``."""
    out = [class_sums(np.asarray(_fired(include, jnp.asarray(x[i:i + block]))),
                      classes) for i in range(0, len(x), block)]
    return np.concatenate(out) if out else np.zeros((0, classes), np.int64)


def d2d_resistance(engine_key, include, replicas: int) -> jax.Array:
    """``[R, C, L]`` programmed resistances for an engine built with
    ``engine_key``: its first split programs, one split per replica,
    and each replica splits once more into HRS and LRS draws."""
    k_prog = jax.random.split(engine_key)[0]

    def one(k):
        k_h, k_l = jax.random.split(k)
        hrs = HRS_MEAN * jnp.exp(HRS_LOG_SIGMA
                                 * jax.random.normal(k_h, include.shape))
        lrs = LRS_MEAN + LRS_SIGMA * jax.random.normal(k_l, include.shape)
        return jnp.where(include, jnp.clip(lrs, LRS_MIN, LRS_MAX),
                         jnp.clip(hrs, HRS_MIN, HRS_MAX))

    return jax.vmap(one)(jax.random.split(k_prog, replicas))


def nominal_resistance(include, replicas: int) -> jax.Array:
    r = jnp.where(include, LRS_MEAN, HRS_MEAN).astype(jnp.float32)
    return jnp.broadcast_to(r, (replicas,) + include.shape)


@partial(jax.jit, static_argnames=("dtype",))
def _margins(r, include, x, dtype=jnp.float32):
    """``[B, C]`` clause margins of one replica ``r [C, L]``."""
    c, l = include.shape
    k = -(-l // WIDTH)
    pad = k * WIDTH - l
    g = V_READ / (SERIES * r)                                # on current
    r_nom = jnp.where(include, LRS_MEAN, HRS_MEAN)
    leak = jnp.where(include, I_LEAK_INCLUDE, I_LEAK_EXCLUDE) * (r_nom / r)
    lits = jnp.concatenate([x, 1 - x], axis=-1)

    def cols(a, rows):
        return jnp.pad(a, ((0, 0), (0, pad))).reshape(rows, k, WIDTH)

    lit0 = cols((1 - lits).astype(dtype), x.shape[0])
    lit1 = cols(lits.astype(dtype), x.shape[0])
    hi = jax.lax.Precision.HIGHEST
    cur = (jnp.einsum("bkw,ckw->bck", lit0, cols(g.astype(dtype), c),
                      precision=hi, preferred_element_type=jnp.float32)
           + jnp.einsum("bkw,ckw->bck", lit1, cols(leak.astype(dtype), c),
                        precision=hi, preferred_element_type=jnp.float32))
    return jnp.max(cur / I_REF - 1.0, axis=-1)


def analog_margins(r_stack, include, x: np.ndarray, dtype=jnp.float32,
                   block: int = 256) -> np.ndarray:
    """``[N, R, C]`` clause margins of requests ``x [N, F]`` on every
    replica of ``r_stack [R, C, L]`` (empty clauses read +inf: they never
    fire and cannot flip)."""
    empty = ~np.asarray(include.any(axis=-1))
    out = np.empty((len(x), r_stack.shape[0], include.shape[0]), np.float32)
    for i in range(0, len(x), block):
        xb = jnp.asarray(x[i:i + block])
        for j in range(r_stack.shape[0]):
            out[i:i + block, j] = np.asarray(
                _margins(r_stack[j], include, xb, dtype=dtype))
    out[:, :, empty] = np.inf
    return out


def margin_sums(mu: np.ndarray, classes: int) -> np.ndarray:
    """Ensemble class sums ``[N, M]`` of margins ``[N, R, C]``."""
    return class_sums(mu < 0, classes).sum(axis=1)


def sum_gap(served: np.ndarray, rows: np.ndarray, mu: np.ndarray,
            classes: int) -> float:
    """The widest relative margin that some served class sum needs.

    ``served [N, M]`` are ensemble class sums of requests ``rows [N]``
    (indices into ``mu [P, R, C]``).  A served sum that differs from the
    reference by ``d`` needs ``|d|`` clause flips in the direction of
    ``d``; the cheapest are the candidates with the smallest ``|mu|``,
    and the ``|d|``-th of those is the error it takes.  Returns the
    largest such error over all served sums (0 when every sum matches,
    inf when one cannot be explained by flips at all)."""
    ref = margin_sums(mu, classes)
    diff = served.astype(np.int64) - ref[rows]
    bad = np.argwhere(diff != 0)
    worst = 0.0
    seen = set()
    per = mu.shape[-1] // classes
    pol = polarity(classes, per)
    for n, m in bad:
        key = (int(rows[n]), int(m), int(diff[n, m]))
        if key in seen:
            continue
        seen.add(key)
        p, d = key[0], key[2]
        cm = mu[p][:, m * per:(m + 1) * per]                  # [R, per]
        fired = cm < 0
        up = (pol[m * per:(m + 1) * per] > 0)[None, :]
        cand = (~fired & up) | (fired & ~up) if d > 0 else \
            (fired & up) | (~fired & ~up)
        gaps = np.sort(np.abs(cm[cand & np.isfinite(cm)]))
        worst = max(worst, float(gaps[abs(d) - 1]) if abs(d) <= len(gaps)
                    else math.inf)
    return worst


def median_thresholds(frames: np.ndarray) -> np.ndarray:
    """Per-channel median of ``[N, M]`` fitting frames (N odd, so the
    median is one of the frames), as float32."""
    if len(frames) % 2 != 1:
        raise ValueError("fit on an odd number of frames")
    return np.sort(frames.astype(np.float64), axis=0)[len(frames) // 2
                                                      ].astype(np.float32)


def kws_rows(stream: np.ndarray, index: np.ndarray, window: int, hop: int,
             thr: np.ndarray) -> np.ndarray:
    """Boolean rows of windows ``index`` of one frame stream: frames
    ``[i*hop, i*hop + window)``, each channel 1 iff above its
    threshold, frame after frame."""
    bits = (stream > thr[None, :]).astype(np.uint8)
    at = hop * np.asarray(index)[:, None] + np.arange(window)[None, :]
    return bits[at].reshape(len(index), -1)
