"""Device self time a step under a group scope (``layers``, ``loop_pass``)
and no sublayer: the layer scan's own plumbing: slices of stacked weights,
bare writes of stacked gradients, the loops themselves, the compiler's
copies; in a looped model each pass's closing norm and gate too
(benchmark/lib/scope_trace.py)."""

from benchmark.lib import scope_trace


def read(run):
    return scope_trace.ms_a_step(run, "sublayer", scope_trace.LAYER_SCAN)
