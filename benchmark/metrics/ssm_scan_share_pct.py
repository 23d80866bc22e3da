"""Share of the traced busy time in the selective scan's operations: forward,
the forward a rematerialised layer repeats, and backward, of every Mamba
layer (benchmark/lib/ssm_trace.py tells them: the kernels by their names, an
XLA formulation by the state's shape). The mixer's products, convolution,
norms and gate are NOT in the share; by count the mixers are a third of the
step's operations and the recurrence itself 0.2 %
(benchmark/lib/counts_hybrid.py)."""

from benchmark.lib import ssm_trace


def read(run):
    if not run.trace or "attn_layer_period" not in run.cfg:
        return None
    took = ssm_trace.scan_events(run)[0]
    busy = run.trace["busy_s"]
    return 100.0 * took / busy if took > 0 and busy > 0 else None
