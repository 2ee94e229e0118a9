"""Host time in submit per image request sent (us)."""

from readings import intake_us


def read(run):
    return intake_us(run)
