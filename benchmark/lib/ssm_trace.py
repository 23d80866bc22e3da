"""The selective scan's operations in a device trace, whatever implements it.

The kernel pair is named (``ssm_scan_fwd`` / ``ssm_scan_bwd`` on the
``pallas_call``s; the name reaches the event's HLO line through the custom
call's metadata or its call target's name), one event a call. An XLA
formulation carries no name of its own and is told by the state's shape,
``[rows, d_state, d_inner]`` per device, which nothing else in the step has:
its token-step fusions hold that shape as an operand or a result, many events
a call. Only leaf operations count (``xplane.leaf_events``), so a loop is read
through what runs inside it."""

from benchmark.lib import counts_hybrid, xplane

FWD, BWD = "ssm_scan_fwd", "ssm_scan_bwd"


def scan_events(run):
    """``(seconds, forward calls, backward calls)`` of the traced window's
    scan operations. The calls are those of the named kernels; a formulation
    told by the state's shape has seconds and no calls to count (0, 0)."""
    cfg, cell = run.cfg, run.cell
    rows = cell["batch_size"] // cell["chips"]
    state = f"[{rows},{cfg['mamba_d_state']},{counts_hybrid.d_inner(cfg)}]"
    events = next(iter(run.trace["events"].values()))
    ns, fwd, bwd = 0.0, 0, 0
    for name, _, dur in xplane.leaf_events(events):
        if FWD in name:
            fwd += 1
        elif BWD in name:
            bwd += 1
        elif state not in name:
            continue
        ns += dur
    return ns / 1e9, fwd, bwd
