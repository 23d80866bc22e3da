"""Share of the traced busy time in a looped model's head-and-cross-entropy
evaluations (``total_ut_steps`` of them a step, each in chunks of the cell's
``loss_chunk_size`` tokens): the operations whose HLO line holds the logits
chunk's shape ``[rows, chunk, vocab]`` — the head's product with its
log-softmax, the cross-entropy's backward, and the two products of the head's
backward, which read the float32 chunk as an operand. Told by shape, as
``flash_roofline`` tells its calls: the fusions carry no name of their own.
Only operations that hold no other are counted (``xplane.leaf_events``), so
a loop is read through what runs inside it, not twice. Not in
the share: the copies that stack and slice the (pass, chunk) states, which
carry no logits shape. By count the head is 12.3 % of the step
(benchmark/lib/counts_looped.py:head_share)."""

from benchmark.lib import xplane


def read(run):
    if not run.trace or "total_ut_steps" not in run.cfg:
        return None
    cell = run.cell
    chunk = cell.get("trainer", {}).get("loss_chunk_size") or cell["sequence_length"]
    rows = cell["batch_size"] // cell["chips"]  # rows a device holds
    shape = f"[{rows},{chunk},{run.cfg['vocab_size']}]"
    events = next(iter(run.trace["events"].values()))
    took = sum(dur for name, _, dur in xplane.leaf_events(events)
               if shape in name) / 1e9
    busy = run.trace["busy_s"]
    return 100.0 * took / busy if took > 0 and busy > 0 else None
