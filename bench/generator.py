"""Everything a run draws from its seed: weights, requests, arrivals.

One general generator reads every traffic file (``bench/traffic/*.json``)
and configuration file (``bench/configs/*.json``); a new mix or model is
a new data file, not new code.  The program under test receives only
what is generated here.

Streams are independent: ``rng(seed, stream)`` keys NumPy on the pair,
``seed_key(seed)`` keys JAX on all 64 bits of the seed.  Arrival shapes
(the set of inter-arrival gaps, the set of session phases) come from the
traffic file's own ``shape_seed`` and are only *ordered* by the run's
seed, so every seed offers the same load in another order.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

STREAM_WEIGHTS, STREAM_ENGINE, STREAM_REQUESTS, STREAM_ORDER = 1, 2, 3, 4


def seed_key(seed: int) -> jax.Array:
    """A JAX key from all 64 bits of ``seed`` (``PRNGKey`` keeps 32)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not a 64-bit unsigned integer")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def model_key(seed: int, stream: int) -> jax.Array:
    return jax.random.fold_in(seed_key(seed), stream)


@partial(jax.jit, static_argnames=("clauses", "literals", "includes",
                                   "states"))
def ta_state(key, *, clauses: int, literals: int, includes: int,
             states: int) -> jax.Array:
    """TA states ``[C, L]`` int16 with exactly ``includes`` includes at
    seeded cells (state ``N + 1``; every other cell ``N``), made on the
    device in one call."""
    idx = jax.random.choice(key, clauses * literals, (includes,),
                            replace=False)
    ta = jnp.full((clauses * literals,), states, jnp.int16)
    return ta.at[idx].set(states + 1).reshape(clauses, literals)


def include_mask(config: dict, seed: int) -> jax.Array:
    """The seeded include mask ``[C, L]`` bool of ``config``'s model."""
    m = config["model"]
    ta = ta_state(model_key(seed, STREAM_WEIGHTS),
                  clauses=m["classes"] * m["clauses_per_class"],
                  literals=2 * m["features"], includes=m["includes"],
                  states=m["states"])
    return ta > m["states"]


def bool_images(seed: int, n: int, features: int, density: float
                ) -> np.ndarray:
    """``[n, F]`` uint8 requests, each bit set with ``density``."""
    x = rng(seed, STREAM_REQUESTS).random((n, features)) < density
    return x.astype(np.uint8)


def request_order(seed: int, pool: int, count: int) -> np.ndarray:
    """Which pool entry each of ``count`` requests sends."""
    return rng(seed, STREAM_ORDER).integers(0, pool, count)


def arrivals(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of an open loop.

    A fixed set of exponential gaps (Poisson at ``rate_per_s``), drawn
    from the traffic file's ``shape_seed`` and shuffled by the run's
    seed.  Any other ``process`` is refused, not run as Poisson."""
    a = traffic["arrivals"]
    if a.get("process") != "poisson":
        raise ValueError(f"arrival process {a.get('process')!r} is not "
                         "implemented; bench/generator.py knows 'poisson'")
    rate = float(a["rate_per_s"])
    n = int(np.ceil(rate * seconds * 1.25)) + 16
    gaps = np.random.default_rng(a["shape_seed"]).exponential(1.0, n)
    rng(seed, STREAM_ORDER).shuffle(gaps)
    return np.cumsum(gaps) / rate


def session_phases(traffic: dict, seed: int, period_s: float) -> np.ndarray:
    """Each session's first feed time in ``[0, period_s)``: a fixed set
    from ``shape_seed``, assigned to sessions by the run's seed."""
    n = int(traffic["sessions"])
    ph = np.random.default_rng(traffic["shape_seed"]).uniform(0, period_s, n)
    return rng(seed, STREAM_ORDER).permutation(ph)


def kws_bank(seed: int, n: int, frames: int, channels: int,
             classes: int = 6, noise: float = 0.15) -> np.ndarray:
    """``[n, T, M]`` float32 keyword utterances: per class a spectral
    bump sweeping over the channels with vibrato, plus two fixed
    resonances, with phase and amplitude jitter and white noise."""
    g = rng(seed, STREAM_REQUESTS)
    y = g.integers(0, classes, n)
    t = np.linspace(0.0, 1.0, frames)
    m = np.arange(channels, dtype=np.float64)
    c = np.arange(classes, dtype=np.float64)
    base = 1.0 + (channels - 3.0) * c / max(classes - 1, 1)
    slope = np.where(c % 2 == 0, 1.0, -1.0) * (channels / 6.0)
    vib = 1.0 + (c % 3)
    sig1 = (c + 0.5) * channels / classes
    sig2 = np.mod(sig1 + channels / 2.0 + c % 2, float(channels))
    phase = g.uniform(0.0, 1.0, n)
    amp = 1.0 + 0.2 * g.normal(size=n)
    center = (base[y][:, None] + slope[y][:, None] * t[None, :]
              + 0.8 * np.sin(2 * np.pi * (vib[y][:, None] * t[None, :]
                                          + phase[:, None])))
    center = np.clip(center, 0.0, channels - 1.0)                # [n, T]
    bump = np.exp(-0.5 * ((m[None, None, :] - center[:, :, None]) / 1.2)
                  ** 2)
    res = (np.exp(-0.5 * ((m[None, :] - sig1[y][:, None]) / 0.7) ** 2)
           + np.exp(-0.5 * ((m[None, :] - sig2[y][:, None]) / 0.7) ** 2))
    x = amp[:, None, None] * (bump + 0.8 * res[:, None, :])
    x = x + noise * g.normal(size=x.shape)
    return x.astype(np.float32)


def session_streams(bank: np.ndarray, seed: int, sessions: int,
                    frames: int) -> np.ndarray:
    """``[S, frames, M]``: each session plays bank utterances end to end
    in its own seeded order."""
    n, t, _ = bank.shape
    per = -(-frames // t)
    pick = rng(seed, STREAM_ORDER + 1).integers(0, n, (sessions, per))
    return bank[pick].reshape(sessions, per * t, -1)[:, :frames]
