"""Sum of the saves' blocking seconds / the window."""


def read(run):
    ev = run.events("ckpt_saved")
    if not ev:
        return None
    return 100.0 * sum(e["blocking_s"] for e in ev) / run.seconds
