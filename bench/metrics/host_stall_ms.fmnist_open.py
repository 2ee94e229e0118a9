"""Longest time the client thread sent an image request after it was
due (ms): the longest stall of the host thread that drives the engine."""

from readings import host_stall_ms


def read(run):
    return host_stall_ms(run)
