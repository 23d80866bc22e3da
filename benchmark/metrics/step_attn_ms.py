"""Device self time a step in sublayer ``attn``, every phase: the norm, the
q/k/v products, rope, the attention call (the flash kernels inside, scope
``flash_attention``), the output product and residual
(benchmark/lib/scope_trace.py)."""

from benchmark.lib import scope_trace


def read(run):
    return scope_trace.ms_a_step(run, "sublayer", "attn")
