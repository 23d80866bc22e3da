#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the numbers `correct` compares.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--faults half_batch,label_shift --fault-seeds 1,2,3] \\
        [--control fp8 --control-seeds 1,2,3] --out <file.json>

Not part of a benchmark run. For each seed: the program's first steps against
the float32 reference (the lower readings); for the fault seeds, the same with
the timed path broken underneath (``benchmark/lib/faults.py``), against that
seed's reference, computed once; for the control seeds, the reference in the
precision below the configuration's, put in the program's place (the upper
readings). One process, so the set-up is paid once. Training's readings need
no measured window: each run's window is one sync interval.
"""

import json
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import run as bench  # noqa: E402


def ints(text):
    return [int(x) for x in text.split(",") if x]


def main(argv=None):
    ap = bench.parser()
    ap.add_argument("--seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(
        (argv or sys.argv[1:]) + ["--seed", "0", "--seconds", "1"])
    out = {"cell": args.workload, "sound": {}, "faults": {}, "control": {},
           "seconds": {}}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    fault_seeds, control_seeds = ints(args.fault_seeds), ints(args.control_seeds)
    for seed in dict.fromkeys(ints(args.seeds) + fault_seeds + control_seeds):
        args.seed, args.fault = seed, ""
        res = one(args)
        out["sound"][seed] = res["numbers"]
        out["seconds"][seed] = res["post"]
        ref_out, rows = res["readings"]["reference"], res["rows"]
        if seed in fault_seeds:
            for fault in args.faults.split(","):
                args.fault = fault
                out["faults"].setdefault(fault, {})[seed] = one(
                    args, reference_out=ref_out)["numbers"]
        if seed in control_seeds:
            out["control"][seed] = control(args, res, ref_out, rows)
        Path(args.out).write_text(json.dumps(out, indent=1))
        print(f"seed {seed} done", file=sys.stderr)
    return 0


def one(args, **extra):
    man, ctx, _ = bench.prepare(args, time.monotonic())
    ctx.update(extra)
    try:
        return man.runner(ctx["cell"]["runner"]).run(ctx)
    finally:
        shutil.rmtree(ctx["work"], ignore_errors=True)


def control(args, res, ref_out, rows):
    """The reference in the lower precision, in the program's place."""
    import gc

    import jax

    from benchmark.runners import train_window as tw

    man, ctx, _ = bench.prepare(args, time.monotonic())
    shutil.rmtree(ctx["work"], ignore_errors=True)
    cfg, config = ctx["cfg"], res["config"]
    low = man.reference(cfg["reference"]).Reference(
        cfg, tw.optimizer_facts(config), jax.devices()[:ctx["cell"]["chips"]],
        precision=args.control)
    low_out = tw.follow(low, config.seed, rows)
    del low
    gc.collect()
    return tw.compare(low_out, ref_out)


if __name__ == "__main__":
    sys.exit(main())
