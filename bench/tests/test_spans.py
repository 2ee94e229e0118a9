"""``spans.py`` on the CPU: self times, idle gaps given to the innermost
span, clipping to the window and the longest occurrences, on a synthetic
nested trace; the per-window numbers; the span names, pinned to the
program's table; and the tool's hooks around one tiny traced run."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import spans  # noqa: E402
import tracekit  # noqa: E402

W = tracekit.WINDOW_SPAN

# One host line: the client loop's window (longer than the traced
# window), a pump with the engine's spans inside, an intake, nested
# runtime events inside the issue span, a pump cut by the window's end,
# and a span before the window.
LINE = [
    (W, -1.0, 11.0),
    ("bench.pump", 1.0, 5.0),
    ("serve.pump", 1.5, 4.5),
    ("serve.cut", 1.5, 2.0),
    ("serve.issue", 2.0, 3.0),
    ("PjitFunction(fwd)", 2.1, 2.9),
    ("Execute", 2.2, 2.5),
    ("serve.collect", 3.0, 4.0),
    ("serve.block", 3.2, 3.7),
    ("bench.intake", 6.0, 8.0),
    ("stream.push", 6.0, 6.5),
    ("serve.submit", 6.5, 7.5),
    ("serve.pump", 9.5, 10.5),
    ("bench.pump", -3.0, -2.0),
]
DEVICE = tracekit.DeviceTrace(ops=[("k", 0.5, 2.2), ("k", 3.3, 3.6)],
                              modules=[])
WINDOW = (0.0, 10.0)


def _main_line():
    out = spans.reduce([DEVICE, DEVICE], {"python": LINE,
                                          "runtime": [("Execute", 0, 9)]},
                       WINDOW)
    assert set(out) == {"python"}          # no span on the runtime line
    return out["python"]


def test_self_time_is_duration_less_child_spans():
    s = _main_line()
    assert s.total_s == pytest.approx({
        W: 10.0, "bench.pump": 4.0, "serve.pump": 3.5, "serve.cut": 0.5,
        "serve.issue": 1.0, "serve.collect": 1.0, "serve.block": 0.5,
        "bench.intake": 2.0, "stream.push": 0.5, "serve.submit": 1.0})
    # The runtime's event is part of the issue span, not a child of it.
    assert s.self_s == pytest.approx({
        W: 3.5, "bench.pump": 1.0, "serve.pump": 1.0, "serve.cut": 0.5,
        "serve.issue": 1.0, "serve.collect": 0.5, "serve.block": 0.5,
        "bench.intake": 0.5, "stream.push": 0.5, "serve.submit": 1.0})
    assert sum(s.self_s.values()) == pytest.approx(10.0)
    assert s.count["serve.pump"] == 2 and s.count["bench.pump"] == 1
    # The outermost runtime event counts, within the span it starts in.
    assert list(s.runtime_s) == ["serve.issue"]
    assert s.runtime_s["serve.issue"] == pytest.approx(
        {"PjitFunction(fwd)": 0.8})


def test_idle_gaps_go_to_the_innermost_span():
    s = _main_line()
    # Idle: 0-0.5, 2.2-3.3, 3.6-10 on both devices.
    assert s.gap_s == pytest.approx({
        W: 3.0, "serve.issue": 0.8, "serve.collect": 0.5,
        "serve.block": 0.2, "serve.pump": 1.0, "bench.pump": 0.5,
        "stream.push": 0.5, "serve.submit": 1.0, "bench.intake": 0.5})
    assert sum(s.gap_s.values()) == pytest.approx(8.0)


def test_longest_occurrences_by_self_time():
    s = _main_line()
    # The window's self time counts stretch by stretch; a span's self
    # time counts once per occurrence, however its children split it.
    assert s.longest[0] == pytest.approx((W, 8.0, 1.5))
    ones = {(n, o) for n, o, t in s.longest if t == pytest.approx(1.0)}
    assert ones == {(W, 0.0), (W, 5.0), ("bench.pump", 1.0),
                    ("serve.issue", 2.0), ("serve.submit", 6.5)}
    assert len(s.longest) == spans.LONGEST


def test_stretches_without_a_window_span_and_overrunning_children():
    got = spans.stretches([("a", 1, 3), ("b", 2, 4)], 0, 5)
    assert [(n, a, b) for n, a, b, _ in got] == [
        (tracekit.NO_SPAN, 0, 1), ("a", 1, 2), ("b", 2, 3),
        (tracekit.NO_SPAN, 3, 5)]


def test_per_window_numbers():
    s = _main_line()
    got = spans.per_window({"python": s}, 10, [0.001, 0.002, 0.003])
    assert got["push_us.open"] == pytest.approx(0.5e6 / 10)
    assert got["issue_us.open"] == pytest.approx(1e6 / 10)
    assert got["collect_us.open"] == pytest.approx(0.5e6 / 10)
    assert got["device_wait_us.open"] == pytest.approx(0.5e6 / 10)
    assert got["session_scan_us.open"] is None       # no such span ran
    assert got["queue_wait_ms.open"] == pytest.approx(2.8)
    assert spans.per_window({}, 10, [])["queue_wait_ms.open"] is None
    assert spans.covered_share({"python": s}, "bench.pump") == \
        pytest.approx(0.75)
    log = [(1, 4.0, 8, 3, 0.5), (2, 5.0, 8, 3, 0.25), (3, 9.0, 8, 3, 9.0)]
    assert spans.window_head_waits(log, 4.5, 4.0) == [0.25]


def test_overlapping_runtime_events():
    runtime = spans.runtime_events({"python": LINE,
                                    "pjrt": [("Execute", 2.5, 3.5),
                                             ("serve.cut", 2.0, 4.0)]})
    got = spans.overlapping(runtime, 2.0, 3.0)
    assert [(line, n) for line, n, _ in got] == [
        ("python", "PjitFunction(fwd)"), ("pjrt", "Execute"),
        ("python", "Execute")]
    assert [t for *_, t in got] == pytest.approx([0.8, 0.5, 0.3])


def test_span_names_equal_the_program_table():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.serve.metrics import SPANS
    assert sorted(spans.PROGRAM_SPANS) == sorted(SPANS.values())
    assert all(n.startswith(spans.SPAN_PREFIXES) for n in SPANS.values())


def test_the_tool_reads_a_traced_session_run(tmp_path, monkeypatch,
                                             capsys):
    from test_bench_harness import _run
    with spans.observe() as seen:
        r = _run(tmp_path, monkeypatch, "sess", traced=True)
    assert r["correct"]
    numbers = spans.report(seen)
    out = capsys.readouterr().out
    assert "program idle gaps:" in out and "longest host spans:" in out
    for name in ("push_us.open", "submit_us.open", "session_scan_us.open",
                 "cut_us.open", "queue_wait_ms.open", "issue_us.open",
                 "collect_us.open", "device_wait_us.open"):
        assert numbers[name] > 0, name
    assert 0 < numbers["pump_covered"] <= 1
    assert 0 < numbers["intake_covered"] <= 1
