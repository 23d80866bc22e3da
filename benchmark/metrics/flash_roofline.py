"""The flash-attention kernel's share of its roofline, over its three Mosaic
calls (forward — also run again under remat —, dq, dk/dv): the least seconds
the chip could take for the calls in the traced window (operations / peak or
bytes / peak, whichever is larger, from benchmark/lib/counts.py) over the
seconds the trace shows for them.

The ``pallas_call``s carry no name today; the trace shows them as
``custom-call`` events named after the jax call they sit in
(``closed_call.14``, ``rematted_computation.11``, ``checkpoint.22``...). They
are told from each other, and from any other custom call, by their result
shapes: forward gives (q-shaped, float32 row statistics), dq one q-shaped
array, dk/dv two kv-shaped arrays, at the cell's own per-device shapes."""

from benchmark.lib import xplane


def classify(name, batch, heads, kv_heads, seq, head_dim):
    res = xplane.custom_call_results(name)
    if not res:
        return None
    q = f"[{batch},{heads},{seq},{head_dim}]"
    kv = f"[{batch},{kv_heads},{seq},{head_dim}]"
    shapes = [r[r.index("["):] for r in res]
    if len(res) == 2 and shapes[0] == q and res[1].startswith("f32[") and \
            shapes[1].startswith(f"[{batch},{heads},{seq},"):
        return "fwd"
    if len(res) == 1 and shapes[0] == q:
        return "dq"
    if len(res) == 2 and shapes == [kv, kv]:
        return "dkv"
    return None


def read(run):
    if not run.trace:
        return None
    cfg, cell, c = run.cfg, run.cell, run.counts
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", cfg["hidden_size"] // heads)
    seq = cell["sequence_length"]
    batch = cell["batch_size"] // cell["chips"]  # rows a device holds
    least = took = 0.0
    events = next(iter(run.trace["events"].values()))
    for name, _, dur in events:
        kind = classify(name, batch, heads, kv, seq, hd)
        if kind is None:
            continue
        t, _ = c.roofline_seconds(
            c.flash_flops(kind, batch, heads, seq, hd),
            c.flash_bytes(kind, batch, heads, kv, seq, hd), run.peaks)
        least += t
        took += dur / 1e9
    return 100.0 * least / took if took > 0 else None
