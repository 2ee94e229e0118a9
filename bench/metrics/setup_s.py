"""Process start to the first timed request (s)."""

from readings import setup_s


def read(run):
    return setup_s(run)
