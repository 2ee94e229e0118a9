"""The serving path's profiler spans and its dispatch log.

A tiny ``AsyncServeEngine`` behind a ``StreamServer`` runs under
``jax.profiler.trace``; the trace is read back with ``ProfileData``.
Every span of ``serve.metrics.SPANS`` must appear, nest as the table
says, and the issue and collect spans of one dispatch must carry the
same ``batch`` as its ``dispatch_log`` entry.  Also: ``host_pack_s``
counts the packing at submit, and ``note_decision`` builds a session's
state once.
"""

import glob
import os
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.booleanize import fit_quantile
from repro.core.tm import TMConfig
from repro.core.variations import VariationConfig
from repro.serve import (AsyncServeEngine, BatcherConfig, EngineConfig,
                         ServeEngine, StreamConfig, StreamServer)
from repro.serve.metrics import SPANS, ServeMetrics

MELS, WINDOW, HOP = 4, 4, 2


def _engine(cls=AsyncServeEngine, **kw):
    cfg = TMConfig(n_classes=3, clauses_per_class=4,
                   n_features=WINDOW * MELS, n_states=100)
    inc = jax.random.bernoulli(jax.random.PRNGKey(5), 0.2,
                               (cfg.n_clauses, cfg.n_literals))
    ta = jnp.where(inc, cfg.n_states + 1, cfg.n_states).astype(
        cfg.state_dtype)
    return cls.from_ta_state(
        ta, cfg, key=jax.random.PRNGKey(3), vcfg=VariationConfig.nominal(),
        ecfg=EngineConfig(batcher=BatcherConfig(max_batch=16,
                                                bucket_sizes=(8, 16))),
        **kw)


def _host_events(trace_dir):
    """``(line, name, start_ns, end_ns, args)`` of every event on the
    host planes of the trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    names, out = set(SPANS.values()), []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out.extend((i, e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats) if e.name in names else {})
                           for e in line.events)
    return out


def _inside(inner, outers):
    return any(o[0] == inner[0] and o[2] <= inner[2] and inner[3] <= o[3]
               for o in outers)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced streaming run: its host events, the dispatch log and
    what ``record_batch`` booked."""
    engine = _engine()
    frames = np.random.default_rng(0).normal(size=(64, MELS)).astype(
        np.float32)
    server = StreamServer(engine, fit_quantile(frames, bits=1),
                          StreamConfig(window=WINDOW, hop=HOP))
    booked = []
    book = engine.metrics.record_batch

    def record_batch(records, bucket, *a, **kw):
        booked.append((bucket, len(records)))
        return book(records, bucket, *a, **kw)

    engine.metrics.record_batch = record_batch
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(trace_dir):
        for step in range(8):
            for sid in range(5):
                server.feed(str(sid), frames[8 * sid + step:][:HOP + 2])
            server.pump()
        server.drain()
    return _host_events(trace_dir), list(engine.metrics.dispatch_log), booked


def test_every_span_appears(traced):
    events, _, _ = traced
    names = {e[1] for e in events}
    assert set(SPANS.values()) <= names


def test_spans_nest_as_specified(traced):
    events, _, _ = traced
    by = {n: [e for e in events if e[1] == n] for n in SPANS.values()}
    for block in by[SPANS["block"]]:
        assert _inside(block, by[SPANS["collect"]])
    for name in ("cut", "issue"):
        for e in by[SPANS[name]]:
            assert _inside(e, by[SPANS["pump"]])
    # The push span sits inside feed, before its submits.
    assert not any(_inside(s, by[SPANS["push"]])
                   for s in by[SPANS["submit"]])


def test_one_batch_id_joins_a_dispatch(traced):
    events, log, _ = traced
    issued = {e[4]["batch"]: e[4] for e in events
              if e[1] == SPANS["issue"]}
    collected = [e[4]["batch"] for e in events
                 if e[1] == SPANS["collect"]]
    assert len(issued) == len(collected) == len(log) > 1
    assert set(issued) == set(collected) == {entry[0] for entry in log}
    for batch, _, bucket, rows, head_wait in log:
        assert (issued[batch]["bucket"], issued[batch]["rows"]) == (
            bucket, rows)
        assert head_wait >= 0


def test_dispatch_log_equals_the_booked_batches(traced):
    _, log, booked = traced
    assert [(bucket, rows) for _, _, bucket, rows, _ in log] == booked
    assert [entry[0] for entry in log] == list(range(1, len(log) + 1))


def test_dispatch_log_head_wait_on_the_engine_clock():
    now = [1.0]
    engine = _engine(ServeEngine, clock=lambda: now[0])
    x = np.zeros(WINDOW * MELS, np.uint8)
    engine.submit(x)
    now[0] = 2.0
    engine.submit(x)
    now[0] = 5.0
    engine.pump(force=True)
    assert list(engine.metrics.dispatch_log) == [(1, 5.0, 8, 2, 4.0)]
    assert len(engine.metrics.dispatch_log) <= ServeMetrics.DISPATCH_WINDOW


def test_host_pack_s_counts_packing_at_submit():
    engine = _engine(ServeEngine)
    x = np.ones(WINDOW * MELS, np.uint8)
    seen = [engine.metrics.host_pack_s]
    for _ in range(3):
        engine.submit(x)
        seen.append(engine.metrics.host_pack_s)
    assert engine.metrics.batches == 0
    assert seen[0] == 0.0 and all(b > a for a, b in zip(seen, seen[1:]))
    assert engine.summary()["host_pack_s"] == seen[-1]


def test_note_decision_builds_session_state_once(monkeypatch):
    from repro.serve import metrics
    built = []

    def counted(*a, **kw):
        built.append(kw.get("maxlen"))
        return deque(*a, **kw)

    m = ServeMetrics()
    monkeypatch.setattr(metrics, "deque", counted)
    m.note_decision("a", 0.001, now=1.0)
    rec = m.session_decisions["a"]
    recent = rec["recent"]
    m.note_decision("a", 0.003, now=2.0)
    assert m.session_decisions["a"] is rec and rec["recent"] is recent
    assert rec["n"] == 2 and rec["t_first"] == 1.0 and rec["t_last"] == 2.0
    assert list(recent) == [0.001, 0.003]
    m.note_decision(7, 0.002, now=3.0)
    m.note_decision("7", 0.002, now=4.0)
    assert m.session_decisions["7"]["n"] == 2
    assert built == [ServeMetrics.SESSION_LATENCY_WINDOW] * 2
