"""From a run's readings to the result line the driver reads."""

import json

from benchmark.lib import counts, xplane


class RunView:
    """What a per-layer reader may read: the window, the trainer's events in
    it, the reduced trace (``None`` without ``--trace 1``), the configuration,
    the peaks. A reader that finds nothing to read returns ``None``."""

    def __init__(self, ctx, res, trace):
        self.cell, self.cfg, self.peaks = ctx["cell"], ctx["cfg"], ctx["peaks"]
        self.name = ctx["name"]
        self.res, self.trace, self.counts = res, trace, counts
        self.rate = res["rate"]  # tokens/s/chip over the whole window
        self.seconds, self.steps = res["seconds"], res["steps"]
        self.hbm_peak_bytes = res["hbm_peak_bytes"]

    def events(self, name):
        return self.res["sink"].in_window(name)

    def mfu_pct(self, rate):
        """Share of the chip's bf16 peak that ``rate`` tokens/s/chip is, by
        the operations forward and backward require (benchmark/lib/counts.py)."""
        per_token = counts.train_flops_per_token(
            self.cfg, self.cell["sequence_length"])
        return 100.0 * per_token * rate / self.peaks["bf16_flops_per_s"]

    def traced_steps(self):
        """Steps whose dispatch fell inside the traced window."""
        if not self.trace:
            return 0
        return self.res["sink"].spec["trace_steps"]


def reduce_trace(res):
    sink = res["sink"]
    if not (res["trace_file"] and sink.trace and sink.trace["t1"]):
        return None
    return xplane.reduce(
        res["trace_file"], sink.trace["anchor_ns"], sink.trace["t0"],
        sink.trace["t1"], sink.host_spans(), res["chips"])


def result_line(man, ctx, res, device, check, ok):
    cell = ctx["name"]
    device = dict(device, memory_peak_bytes=int(res["hbm_peak_bytes"]))
    line = {"correct": ok, "attempted": int(res["steps"]),
            "failed": int(res["failed"]), "metrics": {}, "device": device}
    if not ctx["trace"]:
        have = {ctx["cell"]["rate_metric"]: res["rate"],
                "setup_s": res["setup_s"]}
        for m in man.metrics_of(cell, "end_to_end"):
            line["metrics"][m["name"]] = {
                "value": have[m["name"]], "unit": m["unit"]}
    else:
        if ctx.get("describe_trace") and res["trace_file"]:
            from pathlib import Path

            out = Path(ctx["describe_trace"])
            out.parent.mkdir(parents=True, exist_ok=True)
            out.write_text(xplane.describe(res["trace_file"]))
        trace = reduce_trace(res)
        view = RunView(ctx, res, trace)
        for m in man.metrics_of(cell, "per_layer"):
            value = man.reader(m["name"])(view)
            if value is not None:
                line["metrics"][m["name"]] = {
                    "value": float(value), "unit": m["unit"]}
        if trace:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            line["breakdown"] = {
                "device_ops": [[n, s] for n, s in trace["device_ops"][:10]],
                "idle_gaps": [[n, s] for n, s in trace["idle_gaps"][:10]],
            }
    line["window"] = {
        "steps": res["steps"], "seconds": res["seconds"],
        "tokens": res["tokens"], "after_window_s": res["post"],
        "compiles_in_window": res["compiles_in_window"],
        # where a run reads far off, these say whether one interval stalled
        "sync_interval_s": [r["interval_s"] for r in
                            res["sink"].in_window("train_sync")]}
    line["check"] = check
    json.dumps(line)  # fail here, not in the last print
    return line
