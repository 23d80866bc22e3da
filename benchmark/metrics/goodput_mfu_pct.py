"""As ``step_mfu_pct``, on the rate of a window that holds the saves: the share
of the chip's peak that bounds any later claim in the cell."""


def read(run):
    return run.mfu_pct(run.rate)
