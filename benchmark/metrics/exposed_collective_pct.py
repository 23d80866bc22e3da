"""Collective time on a device that no compute on it covers / the traced
window, mean over the devices."""


def read(run):
    t = run.trace
    if not t or t["collective_s"] <= 0:
        return None
    return 100.0 * t["exposed_collective_s"] / t["window_s"]
