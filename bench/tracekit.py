"""Reduction of a profiler trace to device busy time, op time and the
host activity behind each idle gap.

``load`` reads the ``.xplane.pb`` the JAX profiler writes; ``reduce``
works on plain tuples, so the tests feed it a synthetic trace.

* busy: the union of the intervals in which an operation ran on a device
  (its "XLA Ops" line), clipped to the traced window and averaged over
  the devices;
* module time: the summed device duration of each compiled program
  (the "XLA Modules" line), averaged over the devices;
* idle gaps: every stretch of the window in which a device ran nothing,
  split among the benchmark's host spans that overlap it, by overlap;
  time that no span covers goes to ``NO_SPAN``.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
NO_SPAN = "no_span"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]          # (start_s, end_s)
Named = Tuple[str, float, float]        # (name, start_s, end_s)


@dataclasses.dataclass
class DeviceTrace:
    """The events of one device, times in seconds on the host timeline."""

    ops: List[Named]
    modules: List[Named]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over devices
    n_devices: int
    module_s: Dict[str, float]          # mean over devices
    op_s: Dict[str, float]              # mean over devices
    gap_s: Dict[str, float]             # idle time by host span, mean

    def top(self, table: Dict[str, float], n: int = 10) -> list:
        return [[k, v] for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])[:n]]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, non-overlapping union of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of merged ``busy`` within ``[lo, hi]``."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(idle: Sequence[Interval], spans: Sequence[Named]
              ) -> Dict[str, float]:
    """Split each idle interval among the host spans overlapping it.

    One sweep over the intervals and the spans, both by start: a span
    joins the active list when it starts before an interval ends and
    leaves it once an interval starts at or after its end, so the cost
    is intervals plus spans plus the overlaps, however long a span is.
    The active list keeps the spans' start order, so each interval's
    overlaps are added in that order, as by a walk over every span.
    """
    table: Dict[str, float] = collections.defaultdict(float)
    spans = sorted(spans, key=lambda s: s[1])
    active: List[Named] = []
    i = 0
    for a, b in sorted(idle):
        while i < len(spans) and spans[i][1] < b:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[2] > a]
        covered = []
        for name, s0, s1 in active:
            lo, hi = max(a, s0), min(b, s1)
            if hi > lo:
                table[name] += hi - lo
                covered.append((lo, hi))
        rest = (b - a) - sum(h - l for l, h in merge(covered))
        if rest > 0:
            table[NO_SPAN] += rest
    return dict(table)


def reduce(devices: Sequence[DeviceTrace], host_spans: Sequence[Named],
           window: Interval) -> TraceSummary:
    """Busy, module, op and gap totals of ``devices`` over ``window``."""
    lo, hi = window
    n = max(len(devices), 1)
    busy_total = 0.0
    module_s: Dict[str, float] = collections.defaultdict(float)
    op_s: Dict[str, float] = collections.defaultdict(float)
    gap_s: Dict[str, float] = collections.defaultdict(float)
    spans = [s for s in host_spans if s[0] != WINDOW_SPAN]
    for dev in devices:
        busy = busy_intervals(dev, lo, hi)
        busy_total += sum(b - a for a, b in busy)
        for name, a, b in clip3(dev.modules, lo, hi):
            module_s[name] += (b - a) / n
        for name, a, b in clip3(dev.ops, lo, hi):
            op_s[name] += (b - a) / n
        for name, t in attribute(gaps(busy, lo, hi), spans).items():
            gap_s[name] += t / n
    return TraceSummary(window_s=hi - lo, busy_s=busy_total / n,
                        n_devices=len(devices), module_s=dict(module_s),
                        op_s=dict(op_s), gap_s=dict(gap_s))


def busy_intervals(dev: DeviceTrace, lo: float, hi: float
                   ) -> List[Interval]:
    """Merged intervals within ``[lo, hi]`` in which ``dev`` ran an
    operation (its programs, where the trace has no op line)."""
    return merge(clip(((a, b) for _, a, b in dev.ops or dev.modules),
                      lo, hi))


def counts(devices: Sequence[DeviceTrace], host_spans: Sequence[Named],
           window: Interval) -> Tuple[int, int]:
    """The idle gaps (over all devices) and host spans that ``reduce``
    attributes: its cost is in proportion to these."""
    lo, hi = window
    n_gaps = sum(len(gaps(busy_intervals(d, lo, hi), lo, hi))
                 for d in devices)
    return n_gaps, sum(s[0] != WINDOW_SPAN for s in host_spans)


def clip3(events: Iterable[Named], lo: float, hi: float) -> List[Named]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if min(b, hi) > max(a, lo)]


def _is_device_plane(name: str) -> bool:
    prefix = "/device:TPU:"
    return name.startswith(prefix) and name[len(prefix):].isdigit()


def load(trace_dir: str) -> Tuple[List[DeviceTrace], List[Named],
                                  Interval]:
    """Device events, host spans and the ``bench.window`` span of the
    newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, spans = [], []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(DeviceTrace(
                ops=_events(lines.get(OPS_LINE)),
                modules=_events(lines.get(MODULES_LINE))))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(e for e in _events(ln)
                             if e[0].startswith("bench."))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    _, a, b = windows[-1]
    return devices, spans, (a, b)


def _events(line) -> List[Named]:
    if line is None:
        return []
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]
