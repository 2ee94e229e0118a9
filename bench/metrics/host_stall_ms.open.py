"""Longest time the client thread sent a request after it was due (ms):
the longest stall of the host thread that runs the front end."""

from readings import host_stall_ms


def read(run):
    return host_stall_ms(run)
