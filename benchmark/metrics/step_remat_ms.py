"""Device self time a step in phase ``remat``: the forward recomputed
inside the backward sweep (``rematted_computation`` on the path: the layer
scan's ``jax.checkpoint`` and the loss head's per chunk), which a remat
rung that keeps more makes smaller (benchmark/lib/scope_trace.py)."""

from benchmark.lib import scope_trace


def read(run):
    return scope_trace.ms_a_step(run, "phase", "remat")
