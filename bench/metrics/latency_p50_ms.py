"""Median latency of every request due in the window (ms)."""

from readings import latency_ms


def read(run):
    return latency_ms(run, 50)
