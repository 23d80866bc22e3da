"""Device self time a step in the loss head, every phase: sublayer
``loss_head`` (the final norm where it is applied once, the head's product
and the cross-entropy, chunk by chunk) or, in a looped model,
``exit_head_loss`` (the head and loss of every pass with the exit
distribution) (benchmark/lib/scope_trace.py)."""

from benchmark.lib import scope_trace


def read(run):
    return scope_trace.ms_a_step(run, "sublayer", *scope_trace.HEAD)
