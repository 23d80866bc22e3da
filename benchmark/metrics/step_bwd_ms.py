"""Device self time a step in phase ``bwd``: ``transpose(`` on the path and
not recomputed forward (benchmark/lib/scope_trace.py)."""

from benchmark.lib import scope_trace


def read(run):
    return scope_trace.ms_a_step(run, "phase", "bwd")
