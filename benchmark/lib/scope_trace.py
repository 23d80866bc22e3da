"""A traced window's device time by the program's own scopes.

The program writes, when it holds its compiled step, a table from
instruction name to ``[phase, scopes, root opcode, product]``
(``pyrecover_tpu/telemetry/stepscopes.py``; the event ``step_scopes`` names
the file). A trace's events carry the same instruction names
(``xplane.short``), so the join is by name: self seconds (an operation's
duration less what nests in it, ``xplane.self_times``) by phase (``fwd``,
``remat``: the forward recomputed inside the backward sweep, ``bwd``,
``update``), by sublayer (the last of the table's ``sublayers`` on an
operation's path; ``layer_scan`` under a group alone: the layer scan's own
slices, writes of stacked gradients and copies) and by kernel scope. An
event whose name the table lacks, or whose entry has neither phase nor
scope, is *unscoped*. The steady cells run no other program in the traced
window, and a name no table holds shows in ``step_unscoped_pct``.

Everything the split needs to know of the vocabulary is in the table's own
``vocabulary``: this file imports nothing of the program, and where a
program writes no table (every commit before PR 38) ``by`` returns ``None``
and the readers built on it report nothing.
"""

import json
import sys
import time
from collections import defaultdict
from pathlib import Path

from benchmark.lib import xplane

LAYER_SCAN, NONE = "layer_scan", "none"
HEAD = ("loss_head", "exit_head_loss")


def load_table(path):
    """The table as written, or ``None`` where the file is not there."""
    path = Path(path)
    if not path.is_file():
        return None
    with open(path) as f:
        return json.load(f)


def part(scopes, vocabulary):
    """The sublayer a path of scopes lies in: the last sublayer on it,
    ``layer_scan`` under a group alone, ``none`` under no scope."""
    inner = [s for s in scopes if s in vocabulary["sublayers"]]
    if inner:
        return inner[-1]
    return LAYER_SCAN if any(
        s in vocabulary["groups"] for s in scopes) else NONE


def split(events, table):
    """Self seconds of one device line's ``events`` ((name, start_ns,
    dur_ns), sorted by start) by phase, sublayer, (phase, sublayer) and
    kernel scope; ``total_s`` is their sum, ``unscoped_s`` what no entry
    of ``table`` names; ``ops`` every operation ``(name, seconds, entry or
    None)``, heaviest first."""
    vocabulary, rows = table["vocabulary"], table["instructions"]
    selfs = defaultdict(float)
    for name, secs in xplane.self_times(events).items():
        selfs[xplane.short(name)] += secs
    out = {"phase": defaultdict(float), "sublayer": defaultdict(float),
           "cross": defaultdict(float), "kernel": defaultdict(float),
           "unscoped_s": 0.0, "total_s": sum(selfs.values())}
    for name, secs in selfs.items():
        entry = rows.get(name)
        if entry is None or not (entry[0] or entry[1]):
            out["unscoped_s"] += secs
            continue
        scopes = entry[1].split("/") if entry[1] else []
        phase, sub = entry[0] or NONE, part(scopes, vocabulary)
        out["phase"][phase] += secs
        out["sublayer"][sub] += secs
        out["cross"][phase, sub] += secs
        for kernel in vocabulary["kernels"]:
            if kernel in scopes:
                out["kernel"][kernel] += secs
    out["ops"] = sorted(
        ((n, s, rows.get(n)) for n, s in selfs.items()), key=lambda r: -r[1])
    return out


def by(run):
    """``split`` of the traced window's first device under the table the
    run's ``step_scopes`` event names, once a run; ``None`` without a
    trace, an event or a file."""
    if hasattr(run, "_scope_trace"):
        return run._scope_trace
    run._scope_trace = None
    if not run.trace:
        return None
    named = [r for _, r in run.res["sink"].records
             if r.get("event") == "step_scopes"]
    table = load_table(named[-1]["path"]) if named else None
    if table is None:
        return None
    t0 = time.monotonic()
    events = next(iter(run.trace["events"].values()))
    run._scope_trace = out = split(events, table)
    print(f"step_scopes: module {table['module']}, "
          f"{len(table['instructions'])} instructions "
          f"({named[-1].get('unscoped')} without phase and scope), "
          f"built in {named[-1].get('build_s')} s; {len(events)} events "
          f"split in {time.monotonic() - t0:.3f} s, "
          f"{100 * out['unscoped_s'] / max(out['total_s'], 1e-30):.3f} % "
          "of their time unscoped", file=sys.stderr)
    return out


def ms_a_step(run, kind, *names):
    """Milliseconds a traced step under ``names`` of ``kind`` ('phase' |
    'sublayer' | 'kernel'); ``None`` where there is nothing to read."""
    got, steps = by(run), run.traced_steps()
    if not got or not steps:
        return None
    secs = sum(got[kind].get(n, 0.0) for n in names)
    return 1e3 * secs / steps if secs > 0 else None
