"""Operations of the window's decisions over the chip's peak in the
device time of the whole forward step (%): the ensemble kernel, the
deviation stack's re-pad and the vote."""

from readings import step_mfu_pct


def read(run):
    return step_mfu_pct(run)
