"""Decisions handed back in the window, per second of it."""

from readings import decisions_per_s


def read(run):
    return decisions_per_s(run)
