"""The comparison that decides ``correct``.

Every answer the timed path handed back is compared with the plain
reference (``reference.py``), computed afresh from the seed after the
program's state is freed.  A configuration's ``check`` block names the
kind of comparison and the limit of each number:

* ``analog_gap`` (a pool under device-to-device variation): the widest
  relative error some served class sum needs to be explained by clause
  flips (``reference.sum_gap``); limit from the readings in PERF.md.
* ``digital_exact`` (a nominal pool): the number of answered requests
  whose class sums differ from the Tsetlin machine's; limit 0.

Both also count ``unanswered`` requests, limit 0.  ``control`` computes
the same numbers with the reference in bfloat16 put in the program's
place: it must fail.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import generator
import reference


def _limits(config: dict) -> dict:
    return {k: v for k, v in config["check"].items() if k != "kind"}


def compare(config: dict, seed: int, inputs, answers: dict, rid_key: dict,
            unanswered: int) -> dict:
    """``{number: {"value", "limit"}}`` for the answers of one run.

    ``answers`` maps request id to served class sums, ``rid_key`` maps it
    to what was asked (a pool row, or a ``(session, window)`` pair)."""
    rids = [r for r in rid_key if r in answers]
    served = np.stack([np.asarray(answers[r]) for r in rids]) if rids \
        else np.zeros((0, config["model"]["classes"]), np.int64)
    keys = [rid_key[r] for r in rids]
    out = _numbers(config, seed, inputs, served, keys, jnp.float32)
    out["unanswered"] = unanswered + sum(r not in answers for r in rid_key)
    limits = _limits(config)
    return {k: {"value": v, "limit": limits[k]} for k, v in out.items()}


def control(config: dict, seed: int, inputs, keys: list) -> dict:
    """The numbers with bfloat16 reference answers in the program's
    place, for requests ``keys``."""
    served = _answers(config, seed, inputs, keys, jnp.bfloat16)
    out = _numbers(config, seed, inputs, served, keys, jnp.float32)
    limits = _limits(config)
    return {k: {"value": v, "limit": limits[k]} for k, v in out.items()}


def _numbers(config, seed, inputs, served, keys, dtype) -> dict:
    kind = config["check"]["kind"]
    classes = config["model"]["classes"]
    if kind == "analog_gap":
        rows = np.asarray(keys, np.int64)
        uniq, idx = np.unique(rows, return_inverse=True)
        mu = _margins(config, seed, inputs[uniq], dtype)
        return {"sum_gap": reference.sum_gap(served, idx, mu, classes)}
    if kind == "digital_exact":
        want = _answers(config, seed, inputs, keys, jnp.float32)
        return {"mismatched": int((served != want).any(axis=1).sum())}
    raise ValueError(f"unknown check kind {kind!r}")


def _margins(config, seed, x, dtype):
    include = generator.include_mask(config, seed)
    r = config["pool"]["replicas"]
    if config["pool"]["variation"]["d2d"]:
        stack = reference.d2d_resistance(
            generator.model_key(seed, generator.STREAM_ENGINE), include, r)
    else:
        stack = reference.nominal_resistance(include, r)
    return reference.analog_margins(stack, include, x, dtype=dtype)


def _answers(config, seed, inputs, keys, dtype) -> np.ndarray:
    """Reference class sums ``[N, M]`` for requests ``keys``; bfloat16
    rounds the analog operands, or the raw frames before thresholding."""
    classes = config["model"]["classes"]
    if config["check"]["kind"] == "analog_gap":
        rows = np.asarray(keys, np.int64)
        uniq, idx = np.unique(rows, return_inverse=True)
        mu = _margins(config, seed, inputs[uniq], dtype)
        return reference.margin_sums(mu, classes)[idx]
    include = generator.include_mask(config, seed)
    x = window_rows(config, inputs, keys, dtype)
    return reference.digital_sums(include, x, classes)


def window_rows(config, inputs, keys, dtype) -> np.ndarray:
    """Boolean rows of ``(session, window)`` keys from the session
    streams and fitting frames in ``inputs``."""
    streams, fit = inputs
    s = config["stream"]
    thr = reference.median_thresholds(fit)
    if dtype == jnp.bfloat16:
        streams = np.asarray(jnp.asarray(streams, jnp.bfloat16)
                             .astype(jnp.float32))
    keys = np.asarray(keys, np.int64).reshape(-1, 2)
    rows = np.zeros((len(keys), s["window"] * streams.shape[-1]), np.uint8)
    for sess in np.unique(keys[:, 0]):
        at = np.flatnonzero(keys[:, 0] == sess)
        rows[at] = reference.kws_rows(streams[sess], keys[at, 1],
                                      s["window"], s["hop"], thr)
    return rows
