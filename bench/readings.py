"""How each metric is read from a run; the files under ``metrics/``
name these.  ``run`` carries the loop's record (``run.rec``), the set-up
time, the trace summary (``None`` untraced), the dispatches issued in
the window, the work shape, the peak table entry and the chip count.
A reading with nothing to read returns ``None``."""

from __future__ import annotations

import numpy as np

import work

FORWARD_MODULE = "jit_fwd"      # the engine's jitted forward
KERNEL_OP = "%imbue_class_sums"  # the class-sums kernels' custom calls


def decisions_per_s(run):
    return run.rec.done_in_window / run.rec.seconds


def latency_ms(run, q: float):
    lat = run.rec.latency_s
    return float(np.percentile(lat, q)) * 1e3 if lat else None


def setup_s(run):
    return run.setup_s


def idle_pct(run):
    t = run.trace
    if t is None or t.n_devices == 0 or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def _window_rows(run) -> int:
    return sum(r for _, _, r in run.dispatches)


def class_sums_roofline_pct(run):
    """Least time of the window's dispatches over the device time of the
    class-sums kernel's own ops; None where no such kernel ran (a path
    without it, as the sharded XLA forward)."""
    t = run.trace
    if t is None or not run.dispatches:
        return None
    device_s = sum(v for k, v in t.op_s.items() if k.startswith(KERNEL_OP))
    if device_s <= 0:
        return None
    least = sum(work.least_time_s(run.shape, rows, run.peak, run.chips)
                for _, _, rows in run.dispatches)
    return 100.0 * least / device_s


def step_mfu_pct(run):
    """Operations of the decisions dispatched in the window over what the
    chips' peak does in the device time of the whole jitted forward
    (every op of the step: the kernel, padding, the vote)."""
    t = run.trace
    if t is None or not run.dispatches:
        return None
    device_s = sum(v for k, v in t.module_s.items()
                   if k.startswith(FORWARD_MODULE))
    if device_s <= 0:
        return None
    ops = _window_rows(run) * work.ops_per_decision(run.shape)
    return 100.0 * ops / (run.chips * run.peak["flops_per_s"] * device_s)


def intake_us(run):
    return (1e6 * run.rec.intake_s / run.rec.attempted
            if run.rec.attempted else None)


def occupancy_pct(run):
    slots = sum(b for _, b, _ in run.dispatches)
    return 100.0 * _window_rows(run) / slots if slots else None


def host_stall_ms(run):
    late = run.rec.lateness_s
    return 1e3 * max(late) if late else None
