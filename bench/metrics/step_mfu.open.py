"""Operations of the window's decisions over the chips' peak in the
device time of the whole forward step (%)."""

from readings import step_mfu_pct


def read(run):
    return step_mfu_pct(run)
