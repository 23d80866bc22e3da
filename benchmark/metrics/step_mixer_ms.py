"""Device self time a step in sublayer ``mamba_mixer``, every phase: a Mamba
layer's norm, its four products, convolution, inner norms, gate and the
selective scan inside (scope ``ssm_scan``) (benchmark/lib/scope_trace.py)."""

from benchmark.lib import scope_trace


def read(run):
    return scope_trace.ms_a_step(run, "sublayer", "mamba_mixer")
