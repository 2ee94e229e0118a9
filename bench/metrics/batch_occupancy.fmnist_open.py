"""Valid rows over dispatched rows of the window's dispatches (%)."""

from readings import occupancy_pct


def read(run):
    return occupancy_pct(run)
