"""Device self time a step in sublayer ``ffn``, every phase: the norm, the
SwiGLU's three products with the weight-gradient products that write the
stacked gradient, the elementwise passes, the residual
(benchmark/lib/scope_trace.py)."""

from benchmark.lib import scope_trace


def read(run):
    return scope_trace.ms_a_step(run, "sublayer", "ffn")
