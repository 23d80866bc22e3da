"""The share of the traced busy self time in operations no scope names: an
event whose instruction the program's table lacks (another module's), or
whose entry has neither phase nor scope. Large means a part of the step has
no scope yet, as ``ckpt_unspanned_pct`` says of a save
(benchmark/lib/scope_trace.py)."""

from benchmark.lib import scope_trace


def read(run):
    got = scope_trace.by(run)
    if not got or not got["total_s"]:
        return None
    return 100.0 * got["unscoped_s"] / got["total_s"]
