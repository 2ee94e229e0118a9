"""Least time of the dispatched work over the class-sums kernel's device
time (%): the ensemble kernel over four deviation planes."""

from readings import class_sums_roofline_pct


def read(run):
    return class_sums_roofline_pct(run)
