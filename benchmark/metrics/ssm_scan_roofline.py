"""The selective scan's share of its roofline: the least seconds the chip
could take for the scans of the traced window over the seconds the trace
shows for them. The least seconds of one layer's call are the larger of its
bytes over the HBM peak and its operations over the bf16 peak
(benchmark/lib/counts_hybrid.py ``scan_bytes`` / ``scan_flops``: the bytes
bind, 0.41 ms forward and 0.72 ms backward a layer at 8,192 tokens: what the
recurrence moves, the gate's z not among it, as the gate is not among the
timed operations). The calls are COUNTED in the trace, one event of a named
kernel a call, as ``flash_roofline`` counts its own: a layer that runs the
forward once more under remat is credited with two, one whose policy keeps
the scan's output with one. A formulation without named kernels has no calls
to count and is held to the work the step requires: one forward and one
backward a Mamba layer and traced step, so recomputing reads lower."""

from benchmark.lib import counts, counts_hybrid, ssm_trace


def read(run):
    steps = run.traced_steps()
    if not (run.trace and steps and "attn_layer_period" in run.cfg):
        return None
    took, fwd, bwd = ssm_trace.scan_events(run)
    if took <= 0:
        return None
    cfg, cell = run.cfg, run.cell
    tokens = cell["batch_size"] // cell["chips"] * cell["sequence_length"]
    if not fwd + bwd:
        fwd = bwd = counts_hybrid.mamba_layers(cfg) * steps
    least = 0.0
    for call, times in (("fwd", fwd), ("bwd", bwd)):
        t, _ = counts.roofline_seconds(
            counts_hybrid.scan_flops(call, tokens, cfg),
            counts_hybrid.scan_bytes(call, tokens, cfg), run.peaks)
        least += times * t
    return 100.0 * least / took
