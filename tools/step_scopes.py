#!/usr/bin/env python3
"""A profile of the jitted step, read by the program's own scopes.

    python3 tools/step_scopes.py <trace.xplane.pb> <step_scopes.json> \
        [--steps N] [--device 0] [--top 10] [--scope NAME] [--json out.json]

The trainer writes ``step_scopes.json`` under its experiment directory when
it holds the compiled step (``telemetry/stepscopes.py``: instruction name ->
phase, scopes, the opcode at a fusion's root and the product inside it), and
a ``--profile`` run writes the profile under ``--profile-dir``
(``plugins/profile/<time>/*.xplane.pb``). This tool joins the two by
instruction name (``benchmark/lib/scope_trace.py``, the benchmark's own
reader) and prints milliseconds a step by phase x sublayer, the kernel
scopes, the heaviest operations with their scope beside their name, and
the weight-gradient products that write a stacked gradient apart from the
bare writes (ROADMAP S8 d). ``--scope NAME`` lists the heaviest operations
under that scope alone (what a kernel scope holds beside its kernels). Self
time: an operation's duration less what nests in it, so a loop is read
through its body. ``--steps`` defaults to the runs of the table's module
the profile holds. Needs no chip; the numbers are the profile's.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

PHASES = ("fwd", "remat", "bwd", "update", "none")


def module_runs(planes, device, module):  # jaxlint: host-only
    """How often the table's module ran on ``device`` in the profile."""
    from benchmark.lib import xplane

    for p in planes:
        m = xplane.DEVICE_PLANE.match(p["name"])
        if not (m and int(m.group(1)) == device):
            continue
        return sum(
            1 for ln in p["lines"] if ln["name"] == "XLA Modules"
            for name, _, _ in ln["events"] if name.startswith(module + "("))
    return 0


def report(got, steps, top, scope=""):  # jaxlint: host-only
    """The split as rows of text and as a dictionary (ms a step); the
    heaviest operations are those under ``scope`` where one is given."""
    ms = lambda secs: 1e3 * secs / steps
    subs = sorted(got["sublayer"], key=lambda s: -got["sublayer"][s])
    out = {
        "steps": steps, "step_ms": ms(got["total_s"]),
        "unscoped_pct": 100.0 * got["unscoped_s"] / max(got["total_s"], 1e-30),
        "phase_ms": {p: ms(s) for p, s in got["phase"].items()},
        "sublayer_ms": {s: ms(got["sublayer"][s]) for s in subs},
        "kernel_ms": {k: ms(s) for k, s in got["kernel"].items()},
        "phase_x_sublayer_ms": {
            f"{p} {s}": ms(v) for (p, s), v in sorted(got["cross"].items())},
    }
    lines = [f"{out['step_ms']:10.3f} ms a step over {steps} steps, "
             f"{out['unscoped_pct']:.3f} % of it unscoped", ""]
    phases = [p for p in PHASES if p in got["phase"]]
    lines.append(f"{'ms a step':>14}" + "".join(f"{p:>10}" for p in phases)
                 + f"{'all':>10}{'share':>8}")
    for s in subs:
        row = [got["cross"].get((p, s), 0.0) for p in phases]
        lines.append(
            f"{s:>14}" + "".join(f"{ms(v):10.2f}" for v in row)
            + f"{ms(got['sublayer'][s]):10.2f}"
            + f"{100 * got['sublayer'][s] / got['total_s']:7.2f}%")
    lines.append(
        f"{'all':>14}" + "".join(f"{ms(got['phase'][p]):10.2f}" for p in phases)
        + f"{ms(got['total_s'] - got['unscoped_s']):10.2f}")
    lines.append("")
    for k, s in sorted(got["kernel"].items()):
        lines.append(f"kernel scope {k}: {ms(s):.3f} ms a step, "
                     f"{100 * s / got['total_s']:.2f} % of the step")
    # a product fused with its write into a stacked tensor, and bare writes
    writes = {"product+write": 0.0, "bare write": 0.0}
    for _, secs, entry in got["ops"]:
        if entry and entry[2] == "dynamic-update-slice":
            writes["product+write" if entry[3] else "bare write"] += secs
    out["stacked_write_ms"] = {k: ms(v) for k, v in writes.items()}
    lines.append(
        "operations whose root is a dynamic-update-slice: "
        f"{ms(writes['product+write']):.3f} ms a step hold a product "
        f"(a product that writes), {ms(writes['bare write']):.3f} ms none")
    ops = [r for r in got["ops"] if not scope or (
        r[2] and scope in r[2][1].split("/"))]
    under = f" under scope {scope}" if scope else ""
    lines += ["", f"the {top} heaviest of {len(ops)} operations{under}, "
                  f"{ms(sum(r[1] for r in ops)):.3f} ms a step together:"]
    out["heaviest"] = []
    for name, secs, entry in ops[:top]:
        phase, scopes, root, product = entry or ("?", "not in the table", "", "")
        what = f"{product}>{root}" if product and product != root else root
        lines.append(f"{ms(secs):10.3f}  {name:<44} {phase or '-':<6} "
                     f"{scopes or '-':<34} {what}")
        out["heaviest"].append([name, ms(secs), phase, scopes, root, product])
    return "\n".join(lines), out


def main(argv=None):  # jaxlint: host-only
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a profile (*.xplane.pb)")
    ap.add_argument("table", help="the run's step_scopes.json")
    ap.add_argument("--steps", type=int, default=0,
                    help="steps the profile holds (default: the runs of the "
                         "table's module in it)")
    ap.add_argument("--device", type=int, default=0)
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--scope", default="",
                    help="list the heaviest operations under this scope only")
    ap.add_argument("--json", default="", help="also write the split here")
    args = ap.parse_args(argv)

    from benchmark.lib import scope_trace, xplane

    table = scope_trace.load_table(args.table)
    if table is None:
        print(f"step_scopes: no table at {args.table}", file=sys.stderr)
        return 2
    planes = xplane.load(args.trace)
    events = xplane.device_ops(planes).get(args.device)
    if not events:
        print(f"step_scopes: the profile holds no operations of TPU device "
              f"{args.device}", file=sys.stderr)
        return 2
    steps = args.steps or module_runs(planes, args.device, table["module"])
    if steps < 1:
        print(f"step_scopes: the profile holds no run of module "
              f"{table['module']}; give --steps", file=sys.stderr)
        return 2
    text, out = report(
        scope_trace.split(events, table), steps, args.top, args.scope)
    print(text)
    if args.json:
        Path(args.json).parent.mkdir(parents=True, exist_ok=True)
        # jaxlint: disable-next=torn-write -- a report, regenerated by a rerun
        Path(args.json).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
