"""Device self time a step in phase ``fwd``: the operations the compiled
step's metadata places in the differentiated forward (``jvp(`` on the path,
no ``transpose(``, no ``rematted_computation``), joined to the trace by
instruction name (benchmark/lib/scope_trace.py)."""

from benchmark.lib import scope_trace


def read(run):
    return scope_trace.ms_a_step(run, "phase", "fwd")
