"""``memory_stats()["peak_bytes_in_use"]`` of the fullest device."""


def read(run):
    return run.hbm_peak_bytes / 2**30 if run.hbm_peak_bytes else None
