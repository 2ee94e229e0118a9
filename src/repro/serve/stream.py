"""Streaming inference front-end: per-session windows over the engine.

The paper's KWS-6 workload is the always-on case for "program once, read
forever": audio frames arrive continuously, every hop completes one
window of recent frames, and each window is one classifier read.  This
module is that front-end, layered on the existing dispatch path — no new
device code:

  session.feed(frames) -> StreamingBooleanizer (the session's ring
                          buffer; emits one Boolean row per completed
                          hop window)
                       -> ServeEngine.submit — the shared engine's
                          dynamic batcher packs/buckets rows from EVERY
                          live session into fused batched dispatches
                          (sync or double-buffered async, single-device
                          or mesh-sharded; nothing stream-specific)
  server.pump()        -> engine.pump + per-session collection
  session decisions    -> per-window argmax, smoothed by majority vote
                          over the session's last ``vote`` windows

Cross-session batching is the entire point of sharing one engine: S
sessions at hop rate h feed the batcher S*h rows/s, so the fused
dispatch runs at real batch sizes even though each session alone would
never fill a bucket.

The invariant that keeps this safe is **bit-exactness**: at
``VariationConfig.nominal()`` the per-window predictions of a streamed
session equal offline batched ``api.predict`` over
``StreamingBooleanizer.transform_offline`` of the same frames — for
sync and async engines, single-device and mesh-sharded
(``tests/test_stream.py``).  Posterior smoothing is deterministic on
top of those windows.

Per-session latency and decisions/s land in ``ServeMetrics``
(``summary()["sessions"]``).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Deque, Dict, Iterable, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.booleanize import Booleanizer, StreamingBooleanizer
from repro.serve.batching import QOS_BULK, QueueFull, validate_qos
from repro.serve.engine import ServeEngine
from repro.serve.metrics import SPANS

DECISION_MODES = ("argmax", "margin")


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Windowing + smoothing knobs shared by a server's sessions."""

    window: int = 8          # frames per classifier read
    hop: int = 4             # frames between successive reads
    vote: int = 5            # majority-vote horizon (windows)
    # Decisions retained per session (oldest dropped first).  Bounded so
    # an always-on session cannot grow host memory forever; the full
    # count/rate survive in ServeMetrics aggregates.
    history: int = 4096
    # QoS class every window of a session submits under (ISSUE 10):
    # "bulk" (default, the pre-QoS behaviour) or "latency".  Per-session
    # override via StreamServer.session(sid, qos=...).
    qos: str = QOS_BULK
    # Per-window decision rule.  "argmax" (default): pred = argmax of
    # the class sums — the KWS workload.  "margin": threshold the
    # class-sum MARGIN of ``margin_class`` over the best other class
    # (TM class sums are calibrated evidence totals, so the margin is a
    # native confidence score) — the anomaly-detection workload: pred =
    # margin_class iff margin >= margin_threshold.  Pure post-dispatch
    # arithmetic on Response.class_sums; the engine path is identical,
    # so nominal bit-exactness extends to margins.
    decision: str = "argmax"
    margin_class: int = 1    # class whose margin is thresholded
    margin_threshold: float = 0.0
    # Admission control: max live sessions a StreamServer accepts (None
    # = unbounded).  Session s max_sessions+1 raises QueueFull.
    max_sessions: Optional[int] = None

    def __post_init__(self):
        if self.window < 1 or self.hop < 1 or self.vote < 1:
            raise ValueError("window, hop and vote must all be >= 1, got "
                             f"{self.window}/{self.hop}/{self.vote}")
        if self.history < 1:
            raise ValueError(f"history must be >= 1, got {self.history}")
        validate_qos(self.qos)
        if self.decision not in DECISION_MODES:
            raise ValueError(f"unknown decision mode {self.decision!r}; "
                             f"expected one of {DECISION_MODES}")
        if self.margin_class < 0:
            raise ValueError(f"margin_class must be >= 0, got "
                             f"{self.margin_class}")
        if self.max_sessions is not None and self.max_sessions < 1:
            raise ValueError(f"max_sessions must be >= 1, got "
                             f"{self.max_sessions}")


def margin_of(class_sums, margin_class: int) -> float:
    """Class-sum margin of ``margin_class`` over the best other class.

    The scalar the anomaly workload thresholds; also the offline
    reference the bit-exactness tests compare streamed margins against
    (computed from ``api.class_sums`` on the same windows).
    """
    sums = np.asarray(class_sums, dtype=np.int64)
    if not 0 <= margin_class < sums.shape[-1]:
        raise ValueError(f"margin_class {margin_class} out of range for "
                         f"{sums.shape[-1]} classes")
    others = np.delete(sums, margin_class, axis=-1)
    return float(sums[margin_class] - others.max())


def majority_vote(preds: Iterable[int]) -> int:
    """Most frequent class among ``preds``; ties break toward the lowest
    class index (same convention as ``replica.ensemble_vote``)."""
    counts = np.bincount(np.asarray(list(preds), dtype=np.int64))
    return int(counts.argmax())


@dataclasses.dataclass
class Decision:
    """One smoothed keyword decision (one completed window)."""

    session: str
    index: int               # window index within the session's stream
    pred: int                # raw per-window argmax
    keyword: int             # majority vote over the last ``votes`` windows
    votes: int               # how many windows voted (<= StreamConfig.vote)
    latency_s: float         # window enqueue -> served (includes queue wait)
    version: int = 0         # pool model generation that served the window
                             # (ISSUE 7: sessions ride through hot-swaps
                             # with zero dropped windows; this is the
                             # per-decision evidence of which model read)
    # Class-sum margin this window's decision thresholded (margin mode
    # only; None under argmax — the KWS summary stays unchanged).
    margin: Optional[float] = None


class StreamSession:
    """One client's keyword stream over a shared serving engine.

    The session owns its ring buffer of recent frames (the
    ``StreamingBooleanizer``) and its posterior state (the vote deque);
    the engine is shared, so windows from many sessions batch together.
    ``feed`` never blocks on the device — rows are queued into the
    engine's batcher; call :meth:`collect` (or ``StreamServer.pump``)
    to turn served windows into decisions.
    """

    def __init__(self, sid: str, engine: ServeEngine,
                 booleanizer: Booleanizer,
                 scfg: StreamConfig = StreamConfig()):
        self.sid = str(sid)
        self.engine = engine
        self.scfg = scfg
        self.windows = StreamingBooleanizer(booleanizer, scfg.window,
                                            scfg.hop)
        self._pending: Deque[int] = deque()      # submitted, undecided rids
        self._votes: Deque[int] = deque(maxlen=scfg.vote)
        self._n_decided = 0                      # lifetime decision count
        self.decisions: Deque[Decision] = deque(maxlen=scfg.history)

    @property
    def backlog(self) -> int:
        """Windows submitted but not yet decided."""
        return len(self._pending)

    @property
    def keyword(self) -> Optional[int]:
        """Latest smoothed keyword (None before the first decision)."""
        return self.decisions[-1].keyword if self.decisions else None

    def feed(self, frames) -> List[int]:
        """Push raw ``[T, F]`` frames; submits every window they complete
        to the shared engine under the session's QoS class.  Returns the
        submitted request ids."""
        with TraceAnnotation(SPANS["push"]):
            rows = self.windows.push(frames)
        rids = [self.engine.submit(row, qos=self.scfg.qos)
                for row in rows]
        self._pending.extend(rids)
        return rids

    def _decide(self, resp) -> tuple:
        """Per-window (pred, margin) under the session's decision mode.

        Margin mode: pred = ``margin_class`` iff its class-sum margin
        clears ``margin_threshold``; otherwise the argmax over the
        REMAINING classes (original indexing).  Derived from
        ``Response.class_sums`` only — no engine/dispatch change, so the
        streamed margin bit-equals the offline ``api.class_sums``
        margin at nominal.
        """
        if self.scfg.decision != "margin" or resp.expired:
            return int(resp.pred), None     # expired: keep the -1 marker
        sums = np.asarray(resp.class_sums, dtype=np.int64)
        mc = self.scfg.margin_class
        margin = margin_of(sums, mc)
        if margin >= self.scfg.margin_threshold:
            return mc, margin
        others = np.delete(np.arange(sums.shape[-1]), mc)
        return int(others[sums[others].argmax()]), margin

    def collect(self) -> List[Decision]:
        """Turn already-served windows into decisions (in stream order).

        Non-blocking: uses ``engine.take`` (poll-and-forget) so an
        async engine's in-flight dispatches are never forced early AND
        the engine's per-request bookkeeping stays bounded over an
        always-on stream.  Stops at the first window still queued or in
        flight (decisions are strictly ordered, so smoothing state
        stays deterministic).
        """
        out = []
        while self._pending:
            resp = self.engine.take(self._pending[0])
            if resp is None:
                break
            self._pending.popleft()
            pred, margin = self._decide(resp)
            self._votes.append(pred)
            d = Decision(session=self.sid, index=self._n_decided,
                         pred=pred,
                         keyword=majority_vote(self._votes),
                         votes=len(self._votes),
                         latency_s=resp.latency_s,
                         version=resp.version,
                         margin=margin)
            self._n_decided += 1
            self.decisions.append(d)
            self.engine.metrics.note_decision(self.sid, resp.latency_s,
                                              self.engine.clock())
            out.append(d)
        return out

    def abandon_pending(self) -> None:
        """Give up on every submitted-but-undecided window: the engine
        still serves (and counts) them, but discards their Responses on
        arrival instead of retaining them forever.  The one place the
        engine-bookkeeping contract for abandoned windows lives — used
        by :meth:`reset` and ``StreamServer.close``."""
        for rid in self._pending:
            self.engine.discard(rid)
        self._pending.clear()

    def reset(self) -> None:
        """Forget stream + posterior state + decision history — a reset
        session reports ``keyword`` None again and restarts its window
        indices at 0.  Pending windows are abandoned
        (:meth:`abandon_pending`)."""
        self.windows.reset()
        self.abandon_pending()
        self._votes.clear()
        self.decisions.clear()
        self._n_decided = 0


class StreamServer:
    """Many keyword sessions multiplexed onto one serving engine.

    Thin session registry + pump loop: ``session(sid)`` lazily creates a
    :class:`StreamSession` (all sharing this server's booleanizer and
    :class:`StreamConfig`), ``pump()`` advances the engine and collects
    every session's newly served windows, ``drain()`` force-serves the
    queue and collects everything outstanding.

    Admission control (ISSUE 10): with ``StreamConfig.max_sessions``
    set, creating a live session beyond the limit raises
    :class:`QueueFull` (metered); a :meth:`close` frees a slot.  A
    session can override the server-wide QoS class at creation:
    ``session(sid, qos="latency")`` — mixed-QoS sessions share one
    engine, which is the standing heavy-traffic bench scenario.
    """

    def __init__(self, engine: ServeEngine, booleanizer: Booleanizer,
                 scfg: StreamConfig = StreamConfig()):
        self.engine = engine
        self.booleanizer = booleanizer
        self.scfg = scfg
        self.sessions: Dict[str, StreamSession] = {}

    def session(self, sid: str, *, qos: Optional[str] = None,
                decision: Optional[str] = None) -> StreamSession:
        """Get or lazily create a session.  ``qos``/``decision``
        override the server-wide :class:`StreamConfig` for a NEW
        session only (an existing sid keeps its config — overrides on a
        live session would corrupt its vote/margin semantics)."""
        sid = str(sid)
        if sid not in self.sessions:
            if (self.scfg.max_sessions is not None
                    and len(self.sessions) >= self.scfg.max_sessions):
                self.engine.metrics.note_rejected(
                    qos=qos if qos is not None else self.scfg.qos)
                raise QueueFull(
                    f"live sessions {len(self.sessions)} at "
                    f"max_sessions={self.scfg.max_sessions}; close() a "
                    "session or raise the limit")
            scfg = self.scfg
            if qos is not None or decision is not None:
                scfg = dataclasses.replace(
                    scfg,
                    qos=qos if qos is not None else scfg.qos,
                    decision=(decision if decision is not None
                              else scfg.decision))
            self.sessions[sid] = StreamSession(sid, self.engine,
                                               self.booleanizer, scfg)
        return self.sessions[sid]

    def feed(self, sid: str, frames) -> List[int]:
        return self.session(sid).feed(frames)

    def close(self, sid: str) -> Optional[StreamSession]:
        """Retire a session: discard its still-pending windows and drop
        its registry and per-session metrics entries.  Always-on servers
        see session churn — nothing may keep accumulating per closed
        id.  Returns the closed session (its decision history intact)
        or None if the id is unknown."""
        sess = self.sessions.pop(str(sid), None)
        if sess is not None:
            sess.abandon_pending()
            self.engine.metrics.session_decisions.pop(str(sid), None)
        return sess

    def _collect(self) -> List[Decision]:
        out: List[Decision] = []
        with TraceAnnotation(SPANS["scan"]):
            for s in self.sessions.values():
                out.extend(s.collect())
        return out

    def pump(self) -> List[Decision]:
        """Cut/dispatch due batches, then collect served windows into
        decisions.  Returns the new decisions (all sessions)."""
        self.engine.pump()
        return self._collect()

    def drain(self) -> List[Decision]:
        """Force-serve everything queued or in flight, then collect."""
        self.engine.drain()
        return self._collect()

    def summary(self) -> Dict:
        """Engine summary (includes the per-session decision block)."""
        return self.engine.summary()
