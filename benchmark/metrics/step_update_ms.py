"""Device self time a step in phase ``update``: the ``optimizer`` scope (the
gradient's norm and clip, the moments, the new weights, the rng's fold; the
explicit gradient sync where one is built) (benchmark/lib/scope_trace.py)."""

from benchmark.lib import scope_trace


def read(run):
    return scope_trace.ms_a_step(run, "phase", "update")
