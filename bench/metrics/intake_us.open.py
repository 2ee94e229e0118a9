"""Host time in submit or the stream front end's feed, per request sent (us)."""

from readings import intake_us


def read(run):
    return intake_us(run)
