#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Measures on a TPU or not at all: without one (or with fewer chips than the
cell asks for, or a ``device_kind`` the peaks table does not know) it exits
non-zero and prints no result. ``--rehearse-cpu`` runs the same control flow at
a toy width on the CPU, says so, and prints no metric.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``check``: every number compared beside its limit.
"""

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))  # the checkout: pyrecover_tpu, benchmark


def process_age():
    """Seconds this process had lived when the module started to load."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        up = float(Path("/proc/uptime").read_text().split()[0])
        return max(up - ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except Exception:
        return 0.0


REHEARSAL = {  # a toy width for the CPU pass; never measured
    "cfg": {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "intermediate_size": 224,
            "vocab_size": 256, "num_hidden_layers": 2,
            "trainer_model": {"multiple_of": 32}},
    "cell": {"sequence_length": 64},
}


def refusal(platform, kind, count, chips, table):
    """Why this machine cannot measure the cell, or None."""
    if platform != "tpu":
        return (f"the benchmark measures on a TPU only, and JAX found "
                f"{platform!r}")
    if count < chips:
        return f"the cell needs {chips} chips, JAX found {count}"
    if kind not in table:
        return (f"no peaks for device_kind {kind!r} in "
                "benchmark/lib/peaks.json (an unknown kind is an error, "
                "never a default)")
    return None


def parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--reference-precision", default="f32",
                    help="f32 is the reference; bf16/fp8 put the control in "
                         "its place (then `correct` must come out false)")
    ap.add_argument("--fault", default="",
                    help="tests and calibration: break the timed path "
                         "(benchmark/lib/faults.py); `correct` must read false")
    ap.add_argument("--describe-trace", default="",
                    help="with --trace 1: write a by-hand look at the trace "
                         "(planes, lines, heaviest names) to this file")
    ap.add_argument("--dump", default="",
                    help="write the readings compared to this JSON file")
    return ap


def prepare(args, t_start):
    """Files by name, the device gate, and the context a runner takes."""

    from benchmark.lib.manifest import Manifest

    man = Manifest()
    cell = man.cell(args.workload)
    cfg = man.config(cell["config"])
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("PYRECOVER_PALLAS_INTERPRET", "1")
        if cell["chips"] > 1:
            os.environ.setdefault(
                "XLA_FLAGS",
                f"--xla_force_host_platform_device_count={cell['chips']}")
        tm = {**cfg.get("trainer_model", {}), **REHEARSAL["cfg"]["trainer_model"]}
        cfg = {**cfg, **REHEARSAL["cfg"], "trainer_model": tm}
        cell = {**cell, **REHEARSAL["cell"]}

    marks = {"process": t_start, "main": _T0}
    import threading

    import jax

    # the TPU runtime takes ~10 s to come up and the program ~20 s to import:
    # let the one wait on the other's time (set-up is paid by every run)
    up = threading.Thread(target=jax.devices, daemon=True)
    up.start()
    import pyrecover_tpu.train  # noqa: F401  (places the compile cache too)

    marks["program_imported"] = time.monotonic()
    up.join()
    dev = jax.devices()[0]
    marks["devices_up"] = time.monotonic()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    peaks = None
    if not args.rehearse_cpu:
        table = json.loads((HERE / "lib" / "peaks.json").read_text())
        reason = refusal(dev.platform, dev.device_kind, jax.device_count(),
                         cell["chips"], table)
        if reason:
            sys.exit("refused: " + reason)
        peaks = table[dev.device_kind]

    ctx = {
        "manifest": man, "cell": cell, "cfg": cfg, "name": args.workload,
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "t_start": t_start, "peaks": peaks,
        "reference_precision": args.reference_precision,
        "describe_trace": args.describe_trace, "marks": marks,
        "fault": args.fault,
        # checkpoints and traces: outside the checkout, gone when the run ends
        "work": tempfile.mkdtemp(prefix="pyrecover-bench-"),
    }
    return man, ctx, device


def main(argv=None):
    args = parser().parse_args(argv)
    man, ctx, device = prepare(args, _T0 - process_age())
    try:
        return finish(args, man, ctx, device)
    finally:
        shutil.rmtree(ctx["work"], ignore_errors=True)


def finish(args, man, ctx, device):
    cell = ctx["cell"]
    res = man.runner(cell["runner"]).run(ctx)
    marks = ctx["marks"]
    marks["window_open"] = res["sink"].t_open
    order = sorted(marks.items(), key=lambda kv: kv[1])
    print("set-up, seconds since the process started: " + ", ".join(
        f"{k} {v - marks['process']:.1f}" for k, v in order), file=sys.stderr)

    limits = cell["check"]["limits"]
    check, ok = {}, res["failed"] == 0
    for name, value in res["numbers"].items():
        if name not in limits:
            continue  # read and printed, not compared (PERF.md says why)
        check[name] = {"value": value, "limit": limits[name]}
        ok = ok and value <= limits[name]
    missing = [n for n in limits if n not in res["numbers"]]
    ok = ok and not missing
    extra = {k: v for k, v in res["numbers"].items() if k not in limits}

    if args.dump:
        Path(args.dump).parent.mkdir(parents=True, exist_ok=True)
        Path(args.dump).write_text(json.dumps(
            {"numbers": res["numbers"], "readings": res["readings"],
             "post": res["post"], "seed": args.seed, "cell": args.workload},
            default=lambda a: a.tolist()))

    if args.rehearse_cpu:
        extra["host_spans_seen"] = len(res["sink"].host_spans())
        print(json.dumps({
            "rehearsal": "CPU, toy width: control flow only, nothing measured",
            "correct": bool(ok), "steps": res["steps"], "device": device,
            "check": check, "not_compared": extra}))
        return 0

    from benchmark.lib import report

    line = report.result_line(man, ctx, res, device, check, bool(ok))
    for name, c in check.items():
        print(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    for name, v in extra.items():
        print(f"read  {name}: {v:.6g} (not compared)", file=sys.stderr)
    if missing:
        print(f"check: not read: {missing}", file=sys.stderr)
    print(f"correct: {bool(ok)}", file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
