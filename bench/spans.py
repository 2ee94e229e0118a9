#!/usr/bin/env python3
"""The host thread's time by span: self time, the device idle time each
span holds, and the longest single stretches, from one traced run.

    python bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs the cell once as ``run.py --trace 1`` does (the same result line
first), then prints the tables ``program idle gaps:`` and ``longest host
spans:`` and, last, one JSON line of the host thread's time per window
sent by the program's own spans (``serve/metrics.py`` ``SPANS``) and the
p90 of the dispatches' head-of-queue wait (``ServeMetrics.dispatch_log``).
Not part of a benchmark run: it observes one through hooks that change
nothing the run computes.  On a program without those spans or that log
the numbers it cannot read are null.

The reduction works on plain tuples, so the tests feed it a synthetic
trace.  Only the benchmark's and the program's spans (``SPAN_PREFIXES``)
count as spans; any other event on a host line (the runtime's own) is
part of the span around it.  For each host line that holds such spans,
within the ``bench.window`` span:

* self time: a span's duration less what its child spans cover;
* idle gaps: each stretch of device idle time goes to the innermost span
  that covers it (``NO_SPAN`` where none does), averaged over devices;
* longest: the ten longest occurrences by self time, each with its name
  and offset into the window.  The window's own self time (the client
  loop's code between calls) counts each stretch between two of its
  children as an occurrence of its own;
* runtime: the line's outermost runtime events, each given to the span
  whose self time it starts in (what a span's self time was spent in).

The tool prints, for each of the longest occurrences, the runtime events
on any host line that overlap it most.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import contextlib
import dataclasses
import glob
import json
import os
import sys
import types
from typing import Dict, List, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tracekit  # noqa: E402
from tracekit import NO_SPAN, WINDOW_SPAN  # noqa: E402

SPAN_PREFIXES = ("bench.", "serve.", "stream.")
# The program's spans (``repro.serve.metrics.SPANS``), by what each
# covers; a test pins these names to the program's table.
PUSH, SUBMIT, PUMP, CUT = ("stream.push", "serve.submit", "serve.pump",
                           "serve.cut")
ISSUE, COLLECT, BLOCK, SCAN = ("serve.issue", "serve.collect",
                               "serve.block", "stream.collect")
PROGRAM_SPANS = (PUSH, SUBMIT, PUMP, CUT, ISSUE, COLLECT, BLOCK, SCAN)
LONGEST = 10

Named = tracekit.Named
Interval = tracekit.Interval


@dataclasses.dataclass
class LineSummary:
    """One host line's spans within the window (seconds)."""

    count: Dict[str, int]           # occurrences by span name
    total_s: Dict[str, float]       # summed duration by span name
    self_s: Dict[str, float]        # duration less the child spans
    gap_s: Dict[str, float]         # device idle time by innermost span
    longest: List[Tuple[str, float, float]]   # (name, offset, self_s)
    # span -> runtime event -> time of the line's outermost runtime
    # events, each given to the span whose self time it starts in
    runtime_s: Dict[str, Dict[str, float]]


def stretches(events: Sequence[Named], lo: float, hi: float
              ) -> List[Tuple[str, float, float, int]]:
    """``[lo, hi]`` cut into ``(name, start, end, occurrence)`` stretches,
    each named by the innermost of ``events`` covering it (``NO_SPAN``
    where none does; occurrence -1).  ``events`` are one thread's spans,
    so they nest; one that outlasts its parent is cut at the parent's
    end."""
    evs = sorted(tracekit.clip3(events, lo, hi),
                 key=lambda e: (e[1], -e[2]))
    out: List[Tuple[str, float, float, int]] = []
    stack: List[Tuple[str, float, int]] = []      # (name, end, occurrence)
    t = lo

    def emit(until: float) -> None:
        nonlocal t
        if until > t:
            name, _, k = stack[-1] if stack else (NO_SPAN, hi, -1)
            out.append((name, t, until, k))
            t = until

    for k, (name, a, b) in enumerate(evs):
        while stack and stack[-1][1] <= a:
            emit(stack[-1][1])
            stack.pop()
        emit(a)
        stack.append((name, min(b, stack[-1][1]) if stack else b, k))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    emit(hi)
    return out


def reduce_line(events: Sequence[Named], idle: Sequence[Sequence[Interval]],
                window: Interval, runtime: Sequence[Named] = ()
                ) -> LineSummary:
    """Self time, idle-gap attribution and longest occurrences of one
    host line's span ``events`` within ``window``, and what its other
    ``runtime`` events hold of each span's self time; ``idle`` holds
    each device's idle intervals."""
    lo, hi = window
    total: Dict[str, float] = collections.defaultdict(float)
    count: Dict[str, int] = collections.Counter()
    for name, a, b in tracekit.clip3(events, lo, hi):
        total[name] += b - a
        count[name] += 1
    parts = stretches(events, lo, hi)
    own: Dict[str, float] = collections.defaultdict(float)
    occurrence: Dict[Tuple[str, int], List[float]] = {}
    for j, (name, a, b, k) in enumerate(parts):
        own[name] += b - a
        key = (name, j if name in (WINDOW_SPAN, NO_SPAN) else k)
        occurrence.setdefault(key, [a, 0.0])[1] += b - a
    gap: Dict[str, float] = collections.defaultdict(float)
    for dev_idle in idle:
        for name, t in _attribute(dev_idle, parts).items():
            gap[name] += t / len(idle)
    longest = sorted(((name, a - lo, s) for (name, _), (a, s)
                      in occurrence.items()), key=lambda o: -o[2])
    inner: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    starts = [a for _, a, _, _ in parts]
    end = lo
    for name, a, b in sorted(tracekit.clip3(runtime, lo, hi),
                             key=lambda e: (e[1], -e[2])):
        if a >= end:                    # outermost: not inside another
            end = b
            span = parts[bisect.bisect_right(starts, a) - 1][0]
            inner[span][name] += b - a
    return LineSummary(count=dict(count), total_s=dict(total),
                       self_s=dict(own), gap_s=dict(gap),
                       longest=longest[:LONGEST],
                       runtime_s={k: dict(v) for k, v in inner.items()})


def _attribute(idle: Sequence[Interval], parts) -> Dict[str, float]:
    """Idle intervals split by the stretch that holds each piece."""
    out: Dict[str, float] = collections.defaultdict(float)
    j = 0
    for a, b in idle:
        while j < len(parts) and parts[j][2] <= a:
            j += 1
        i = j
        while i < len(parts) and parts[i][1] < b:
            name, s0, s1, _ = parts[i]
            out[name] += max(0.0, min(b, s1) - max(a, s0))
            i += 1
    return dict(out)


def reduce(devices: Sequence[tracekit.DeviceTrace],
           lines: Dict[str, List[Named]], window: Interval
           ) -> Dict[str, LineSummary]:
    """``reduce_line`` of every host line that holds a span, keyed by
    line."""
    lo, hi = window
    idle = []
    for dev in devices:
        timed = dev.ops or dev.modules
        busy = tracekit.merge(tracekit.clip(((a, b) for _, a, b in timed),
                                            lo, hi))
        idle.append(tracekit.gaps(busy, lo, hi))
    out = {}
    for line, events in lines.items():
        spans = [e for e in events if e[0].startswith(SPAN_PREFIXES)]
        if spans:
            out[line] = reduce_line(
                spans, idle, window,
                [e for e in events if not e[0].startswith(SPAN_PREFIXES)])
    return out


def host_lines(trace_dir: str) -> Dict[str, List[Named]]:
    """Every event of every host line of the newest trace under
    ``trace_dir``, keyed by line name (a repeated name gets ``#i``)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    pd = ProfileData.from_file(paths[-1])
    lines: Dict[str, List[Named]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                name = ln.name if ln.name not in lines \
                    else f"{ln.name}#{len(lines)}"
                lines[name] = tracekit._events(ln)
    return lines


def runtime_events(lines: Dict[str, List[Named]]) -> list:
    """Per host line, its non-span events (the runtime's) as
    ``(line, names, starts, ends)`` arrays, for ``overlapping``."""
    import numpy as np
    out = []
    for line, events in lines.items():
        evs = [e for e in events if not e[0].startswith(SPAN_PREFIXES)]
        if evs:
            names, starts, ends = zip(*evs)
            out.append((line, np.asarray(names, dtype=object),
                        np.asarray(starts), np.asarray(ends)))
    return out


def overlapping(runtime: list, a: float, b: float, n: int = 5
                ) -> List[Tuple[str, str, float]]:
    """The ``n`` runtime events that overlap ``[a, b]`` longest, on any
    host line (``runtime_events``): ``(line, name, overlap_s)``."""
    import numpy as np
    hits: Dict[Tuple[str, str], float] = collections.defaultdict(float)
    for line, names, starts, ends in runtime:
        hit = (starts < b) & (ends > a)
        cover = np.minimum(ends[hit], b) - np.maximum(starts[hit], a)
        for name, t in zip(names[hit], cover):
            hits[(line, name)] += float(t)
    return [(line, name, t) for (line, name), t in
            sorted(hits.items(), key=lambda kv: -kv[1])[:n]]


# ------------------------------------------------------ per-window numbers

def per_window(summaries: Dict[str, LineSummary], attempted: int,
               head_waits: Sequence[float]) -> Dict[str, object]:
    """The host thread's time per window sent (us) in each program span
    (self time; ``serve.block`` is a leaf, so its self time is its
    time), and the p90 head-of-queue wait (ms) of the window's
    dispatches; None where the run holds nothing to read."""
    import numpy as np
    own: Dict[str, float] = collections.defaultdict(float)
    seen = set()
    for s in summaries.values():
        for name, t in s.self_s.items():
            own[name] += t
        seen.update(s.total_s)

    def us(name):
        return (1e6 * own[name] / attempted
                if attempted and name in seen else None)

    return {"push_us.open": us(PUSH), "submit_us.open": us(SUBMIT),
            "session_scan_us.open": us(SCAN), "cut_us.open": us(CUT),
            "queue_wait_ms.open": (float(np.percentile(head_waits, 90)) * 1e3
                                   if len(head_waits) else None),
            "issue_us.open": us(ISSUE), "collect_us.open": us(COLLECT),
            "device_wait_us.open": us(BLOCK)}


def window_head_waits(log, t0: float, seconds: float) -> List[float]:
    """``head_wait_s`` of the dispatch log's entries issued in the
    window ``[t0, t0 + seconds]``."""
    return [w for _, t, _, _, w in log if t0 <= t <= t0 + seconds]


def covered_share(summaries: Dict[str, LineSummary], span: str):
    """Share of ``span``'s time that its child spans cover."""
    total = sum(s.total_s.get(span, 0.0) for s in summaries.values())
    own = sum(s.self_s.get(span, 0.0) for s in summaries.values())
    return 1.0 - own / total if total > 0 else None


# ------------------------------------------------------------- the tool

@contextlib.contextmanager
def observe():
    """Hooks around one ``run.py`` run that keep, for this module, the
    engine's metrics, the loop's record and the trace's host lines and
    summaries; they change nothing the run computes."""
    import run
    import system
    seen = types.SimpleNamespace(metrics=None, rec=None, lines=None,
                                 summaries=None, window=None)
    build, drive, load = system.build, run._drive, tracekit.load

    def build_(*a, **kw):
        engine, tm_cfg = build(*a, **kw)
        seen.metrics = engine.metrics
        return engine, tm_cfg

    def drive_(*a, **kw):
        seen.rec = drive(*a, **kw)
        return seen.rec

    def load_(trace_dir):
        devices, spans, window = load(trace_dir)
        seen.lines = host_lines(trace_dir)
        seen.summaries = reduce(devices, seen.lines, window)
        seen.window = window
        return devices, spans, window

    system.build, run._drive, tracekit.load = build_, drive_, load_
    try:
        yield seen
    finally:
        system.build, run._drive, tracekit.load = build, drive, load


def report(seen) -> dict:
    """Print the two tables for the client thread (the line holding the
    window span); return the per-window numbers."""
    thread = max(seen.summaries.items(),
                 key=lambda kv: kv[1].total_s.get(WINDOW_SPAN, 0.0))[1]
    print("program idle gaps: " + " ".join(
        f"{k} {v}" for k, v in sorted(thread.gap_s.items(),
                                      key=lambda kv: -kv[1])))
    print("longest host spans:")
    lo = seen.window[0]
    runtime = runtime_events(seen.lines)
    for name, offset, own in thread.longest:
        print(f"  {name} at {offset} s: {own * 1e3} ms; runtime: "
              + "; ".join(f"{line}: {n} {t * 1e3} ms" for line, n, t
                          in overlapping(runtime, lo + offset,
                                         lo + offset + own)))
    rec = seen.rec
    log = getattr(seen.metrics, "dispatch_log", ())
    numbers = per_window(seen.summaries, rec.attempted,
                         window_head_waits(log, rec.t0, rec.seconds))
    numbers["self_us"] = {k: 1e6 * v / rec.attempted
                          for k, v in sorted(thread.self_s.items())}
    numbers["per_window"] = {k: v / rec.attempted
                             for k, v in sorted(thread.count.items())}
    numbers["runtime_us"] = {
        span: {n: 1e6 * t / rec.attempted for n, t in sorted(
            table.items(), key=lambda kv: -kv[1])[:3]}
        for span, table in sorted(thread.runtime_s.items())}
    numbers["intake_us"] = 1e6 * rec.intake_s / rec.attempted
    numbers["pump_covered"] = covered_share(seen.summaries, "bench.pump")
    numbers["intake_covered"] = covered_share(seen.summaries,
                                              "bench.intake")
    return numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import run
    with observe() as seen:
        rc = run.main(["--workload", args.workload, "--seed",
                       str(args.seed), "--seconds", str(args.seconds),
                       "--trace", "1"])
    if rc:
        return rc
    print(json.dumps({"spans": report(seen)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
