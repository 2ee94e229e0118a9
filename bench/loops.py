"""The three client loops: closed bulk, open arrivals, keyword sessions.

Each loop drives the engine from one thread for ``seconds``, then keeps
pumping without new arrivals until every request it sent is answered
(or a minute has passed).  Times are ``time.monotonic`` seconds, the
engine's own clock.

* closed: ``outstanding`` requests are always in the system; a new one
  is sent as soon as an answer comes back.
* open: requests are sent when due; a request's latency runs from when
  it was due to when its answer was handed back, so a stall also delays
  every request due during it.
* sessions: every session feeds ``hop`` frames each period at its own
  phase; a window's latency runs from the feed that completed it to its
  decision.

A loop iteration that sends nothing and gets nothing back sleeps
``IDLE_S`` before it polls again, so the client does not spin a core
that the server's own threads share.

The ``span`` argument wraps phases in profiler annotations in a traced
run (``bench.window``, ``bench.intake``, ``bench.pump``, ``bench.take``)
and is a no-op otherwise.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List

import numpy as np

DRAIN_LIMIT_S = 60.0
IDLE_S = 1e-4
now = time.monotonic


@dataclasses.dataclass
class Record:
    """What one run's window produced."""

    t0: float = 0.0
    seconds: float = 0.0
    attempted: int = 0              # requests sent in the window
    done_in_window: int = 0         # answers handed back in the window
    intake_s: float = 0.0           # host time inside submit / feed
    lateness_s: List[float] = dataclasses.field(default_factory=list)
    latency_s: List[float] = dataclasses.field(default_factory=list)
    # request id -> what it asked: a pool row, or (session, window)
    rid_key: Dict[int, object] = dataclasses.field(default_factory=dict)
    unanswered: int = 0


def _busy(engine) -> bool:
    return len(engine.batcher) > 0 or getattr(engine, "in_flight", 0) > 0


def closed(engine, xs, order, outstanding: int, seconds: float, span):
    """Closed loop over pool rows ``xs`` in ``order`` (cycled)."""
    rec, q, i = Record(seconds=seconds), deque(), 0
    rec.t0 = t0 = now()
    t_end = t0 + seconds
    with span("bench.window"):
        while now() < t_end:
            if len(q) < outstanding:
                with span("bench.intake"):
                    ts = now()
                    while len(q) < outstanding:
                        row = int(order[i % len(order)])
                        i += 1
                        q.append((engine.submit(xs[row]), row))
                    rec.intake_s += now() - ts
            with span("bench.pump"):
                engine.pump()
            if not _take(engine, q, rec, t_end, span):
                time.sleep(IDLE_S)
    rec.attempted = i
    _finish(engine, q, rec, t_end, span)
    return rec


def open_loop(engine, xs, order, due, seconds: float, span):
    """Open loop: request ``k`` sends ``xs[order[k]]`` at ``due[k]``."""
    rec, q = Record(seconds=seconds), deque()
    n = int(np.searchsorted(due, seconds))
    i = 0
    rec.t0 = t0 = now()
    t_end = t0 + seconds
    with span("bench.window"):
        while True:
            t = now()
            if t >= t_end:
                break
            sent = i < n and t0 + due[i] <= t
            if sent:
                with span("bench.intake"):
                    ts = now()
                    while i < n and t0 + due[i] <= now():
                        rec.lateness_s.append(now() - (t0 + due[i]))
                        q.append((engine.submit(xs[int(order[i])]),
                                  (int(order[i]), t0 + due[i])))
                        i += 1
                    rec.intake_s += now() - ts
            got = False
            if _busy(engine):
                with span("bench.pump"):
                    engine.pump()
                got = _take(engine, q, rec, t_end, span)
            if not (sent or got):
                time.sleep(IDLE_S)
    rec.attempted = i
    _finish(engine, q, rec, t_end, span)
    return rec


def _take(engine, q, rec, t_end, span) -> bool:
    """Hand back every answer ready at the head of ``q``; False if none
    was."""
    if not q:
        return False
    resp = engine.take(q[0][0])
    if resp is None:
        return False
    with span("bench.take"):
        while resp is not None:
            t = now()
            rid, key = q.popleft()
            _note(rec, rid, key, t, t_end)
            resp = engine.take(q[0][0]) if q else None
    return True


def _note(rec, rid, key, t, t_end) -> None:
    if isinstance(key, tuple):                 # (row, due): open loop
        rec.rid_key[rid] = key[0]
        rec.latency_s.append(t - key[1])
    else:
        rec.rid_key[rid] = key
    if t <= t_end:
        rec.done_in_window += 1


def _finish(engine, q, rec, t_end, span) -> None:
    """Serve what is still outstanding, without new arrivals."""
    limit = now() + DRAIN_LIMIT_S
    while q and now() < limit:
        engine.pump()
        if not _take(engine, q, rec, t_end, span):
            time.sleep(IDLE_S)
    rec.unanswered = len(q)


def sessions(server, streams, phases, period_s: float, hop: int,
             prefill: int, seconds: float, span):
    """Session loop: session ``s`` feeds ``streams[s]`` ``hop`` frames
    at a time, at ``phases[s] + k * period_s``."""
    engine = server.engine
    n_s = len(phases)
    feeds = int(np.ceil(seconds / period_s)) + 1
    t_due = (phases[:, None] + period_s * np.arange(feeds)[None, :])
    sess = np.repeat(np.arange(n_s), feeds)
    k_of = np.tile(np.arange(feeds), n_s)
    t_flat = t_due.reshape(-1)
    keep = t_flat < seconds
    order = np.argsort(t_flat[keep], kind="stable")
    due, sess, k_of = t_flat[keep][order], sess[keep][order], k_of[keep][order]
    sids = [str(s) for s in range(n_s)]
    pending = {}                                # (sid, index) -> due
    rec = Record(seconds=seconds)
    i, n = 0, len(due)
    rec.t0 = t0 = now()
    t_end = t0 + seconds
    with span("bench.window"):
        while True:
            t = now()
            if t >= t_end:
                break
            sent = i < n and t0 + due[i] <= t
            if sent:
                with span("bench.intake"):
                    ts = now()
                    while i < n and t0 + due[i] <= now():
                        s, k = int(sess[i]), int(k_of[i])
                        lo = prefill + hop * k
                        rec.lateness_s.append(now() - (t0 + due[i]))
                        for rid in server.feed(sids[s],
                                               streams[s, lo:lo + hop]):
                            rec.rid_key[rid] = (s, k)
                            pending[(sids[s], k)] = t0 + due[i]
                        i += 1
                    rec.intake_s += now() - ts
            decisions = []
            if _busy(engine):
                with span("bench.pump"):
                    decisions = server.pump()
                _decided(decisions, pending, rec, t_end)
            if not (sent or decisions):
                time.sleep(IDLE_S)
    rec.attempted = i
    limit = now() + DRAIN_LIMIT_S
    while pending and now() < limit:
        decisions = server.pump()
        _decided(decisions, pending, rec, t_end)
        if not decisions:
            time.sleep(IDLE_S)
    rec.unanswered = len(pending)
    return rec


def _decided(decisions, pending, rec, t_end) -> None:
    t = now()
    for d in decisions:
        rec.latency_s.append(t - pending.pop((d.session, d.index)))
        if t <= t_end:
            rec.done_in_window += 1
