"""shardcheck command line (the engine behind ``tools/shardcheck.py``).

Exit codes mirror jaxlint: 0 clean (or report-only mode), 1 findings
under ``--strict``, 2 usage/engine error.
"""

import argparse
import json
import sys
from pathlib import Path

JSON_SCHEMA_VERSION = 1


def _build_parser():
    p = argparse.ArgumentParser(
        prog="shardcheck",
        description=(
            "Abstract SPMD preflight: partition-spec consistency, per-"
            "device memory budget, collective census, and checkpoint "
            "schema diffs — no devices, no HBM, milliseconds per config."
        ),
    )
    p.add_argument(
        "--preset", action="append", default=None, metavar="NAME",
        help="model preset to check (repeatable; models/presets.py)",
    )
    p.add_argument(
        "--all-presets", action="store_true",
        help="check every shipped preset (the CI gate)",
    )
    p.add_argument(
        "--devices", default="1,2,4,8", metavar="N,N,...",
        help="virtual device counts for the mesh matrix (default 1,2,4,8)",
    )
    p.add_argument("--dp", type=int, default=None, help="explicit mesh: data")
    p.add_argument("--fsdp", type=int, default=None)
    p.add_argument("--tp", type=int, default=None, help="explicit mesh: tensor")
    p.add_argument("--sp", type=int, default=None, help="explicit mesh: sequence")
    p.add_argument("--pp", type=int, default=None, help="explicit mesh: pipeline")
    p.add_argument("--ep", type=int, default=None, help="explicit mesh: expert")
    p.add_argument(
        "--batch-size", type=int, default=None,
        help="global batch to check divisibility/memory against "
        "(default: one row per batch shard)",
    )
    p.add_argument("--seq-len", type=int, default=None)
    p.add_argument(
        "--device-kind", default=None, metavar="KIND",
        help="budget HBM against this device kind (v4/v5e/v5p/v6e); "
        "omitted = report the table without judging it",
    )
    p.add_argument(
        "--threshold-mb", type=int, default=64,
        help="SC04 replicated-leaf size threshold in MiB (default 64)",
    )
    p.add_argument(
        "--hbm-fraction", type=float, default=0.9,
        help="SC05 budget as a fraction of device HBM (default 0.9)",
    )
    p.add_argument(
        "--no-census", action="store_true",
        help="skip the (train-step tracing) collective census",
    )
    p.add_argument(
        "--optimizer-sharding", default="none", choices=("none", "zero1"),
        help="check the bandwidth-lean update path: zero1 shards AdamW "
        "moments over the data axis (specs, HBM table and census all "
        "reflect it; SC12 fires when nothing actually shards)",
    )
    p.add_argument(
        "--grad-allreduce", default="fp32", choices=("fp32", "bf16", "int8"),
        help="gradient-sync wire format to check: the census traces the "
        "step built in this mode (SC12 fires when the quantized "
        "collective is configured but absent from the trace) and the "
        "traffic model prices the wire against the fp32/none baseline",
    )
    p.add_argument(
        "--grad-quant-block", type=int, default=256,
        help="int8 quantization block size for the traffic model and the "
        "traced step (default 256)",
    )
    p.add_argument(
        "--grad-bucket-mb", type=float, default=0,
        help="check the comm/compute overlap path: resolve the gradient "
        "bucket layout at this MiB cap, assert the traced step issues "
        "one data-axis collective per bucket (SC13 fires when the sync "
        "collapsed back into a single tail collective), and price each "
        "bucket's wire legs with the modelled exposed-vs-hidden split",
    )
    p.add_argument(
        "--diff-checkpoint", metavar="PATH", default=None,
        help="diff a saved checkpoint's schema manifest against the "
        "(single) --preset instead of running the mesh matrix",
    )
    p.add_argument(
        "--check-specs", action="store_true",
        help="with --diff-checkpoint: also diff partition specs (SC10). "
        "Off by default — specs saved on a different mesh size are "
        "normalized differently without being wrong, and restore "
        "reshards freely",
    )
    p.add_argument(
        "--select", default=None, metavar="CHECKS",
        help="comma-separated check ids/names to run (default: all)",
    )
    p.add_argument(
        "--ignore", default=None, metavar="CHECKS",
        help="comma-separated check ids/names to skip (the suppression "
        "surface; e.g. --ignore SC04)",
    )
    p.add_argument(
        "--strict", action="store_true",
        help="exit non-zero on any finding (the CI gate)",
    )
    p.add_argument(
        "--format", choices=("text", "json"), default="text",
        help="stdout format",
    )
    p.add_argument(
        "--json", metavar="PATH", default=None,
        help="also write the JSON report to PATH (works with --format text)",
    )
    p.add_argument(
        "--list-checks", action="store_true",
        help="print the check catalog and exit",
    )
    return p


def _csv_set(raw):
    return frozenset(x.strip() for x in raw.split(",") if x.strip())


def _human(n):
    if n is None:
        return "?"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024:
            return f"{n:.1f}{unit}"
        n /= 1024
    return f"{n:.1f}PiB"


def _finding_line(f):
    return f"{f.path}: {f.severity} {f.rule_id}({f.rule}) {f.message}"


def render_text(reports):
    lines = []
    total = 0
    for r in reports:
        lines.append(f"== {r['preset']} " + "=" * max(1, 56 - len(r["preset"])))
        mesh_summary = {}
        for m in r["meshes"]:
            mesh_summary.setdefault(m["devices"], []).append(
                m["mesh"] + ("!" if m["findings"] else "")
            )
        for n, descs in mesh_summary.items():
            lines.append(f"  {n} device(s): {', '.join(descs)}")
        mem = r.get("memory")
        if mem:
            cap = ""
            if mem.get("hbm_capacity_bytes"):
                cap = (
                    f" vs {mem['device_kind']} HBM "
                    f"{_human(mem['hbm_capacity_bytes'])}"
                )
            lines.append(
                f"  memory/device @ {mem['mesh']} (batch {mem['batch_size']}"
                f" × seq {mem['seq_len']}): params {_human(mem['params_bytes'])}"
                f" | optimizer {_human(mem['optimizer_bytes'])}"
                f" | grads {_human(mem['gradients_bytes'])}"
                f" | saved activations ~{_human(mem['activations_bytes'])}"
                f" | working set ~{_human(mem['working_bytes'])}"
                f" (logits ~{_human(mem['logits_bytes'])} inside it)"
                f" | total ~{_human(mem['total_bytes'])}{cap}"
            )
        cen = r.get("census")
        if cen:
            traced = ", ".join(
                f"{k}={v}" for k, v in cen.get("traced", {}).items()
            ) or "none"
            lines.append(
                f"  census @ {cen['mesh']}"
                f"{'' if cen.get('mesh_context') else ' (no mesh context)'}: "
                f"{traced}"
            )
            ana = cen.get("analytic", {})
            parts = [
                f"{k.replace('_bytes', '')} {_human(v)}"
                for k, v in ana.items()
                if k.endswith("_bytes") and k != "param_bytes_total"
            ]
            if parts:
                lines.append("  modelled/step: " + " | ".join(parts))
        traffic = r.get("traffic")
        if traffic and traffic["configured"]["mode"] != "fp32/none":
            cfg_t = traffic["configured"]
            legs = ", ".join(
                f"{k} {_human(v)}" for k, v in cfg_t["legs_bytes"].items()
            )
            lines.append(
                f"  wire/step ({traffic['data_replicas']} data replicas): "
                f"{cfg_t['mode']} {_human(cfg_t['bytes_on_wire_per_step'])}"
                f" [{legs}] vs fp32/none "
                f"{_human(traffic['baseline']['bytes_on_wire_per_step'])}"
                f" ({traffic['reduction_pct']:+.1f}% saved)"
            )
        ov = (traffic or {}).get("overlap")
        if ov:
            if ov["buckets"]:
                per = ov["per_bucket_wire_bytes"]
                lines.append(
                    f"  overlap @ {ov['bucket_mb']:g} MiB buckets: "
                    f"{ov['buckets']} buckets "
                    f"({_human(min(per))}..{_human(max(per))} wire each), "
                    f"modelled hidden {_human(ov['hidden_wire_bytes'])} / "
                    f"exposed {_human(ov['exposed_wire_bytes'])} "
                    f"({ov['hidden_pct']:.1f}% hideable ceiling)"
                )
            else:
                lines.append(
                    f"  overlap @ {ov['bucket_mb']:g} MiB buckets: layout "
                    "degenerate (one bucket) — unbucketed single "
                    "collective, all wire exposed"
                )
        for f in r["findings"]:
            lines.append("  " + _finding_line(f))
        total += len(r["findings"])
    lines.append(
        f"{total} finding(s) across {len(reports)} configuration(s)"
    )
    return "\n".join(lines)


def summarize(reports):
    by_check = {}
    errors = warnings = 0
    for r in reports:
        for f in r["findings"]:
            by_check[f.rule] = by_check.get(f.rule, 0) + 1
            if f.severity == "error":
                errors += 1
            else:
                warnings += 1
    return {
        "presets": len(reports),
        "findings": errors + warnings,
        "errors": errors,
        "warnings": warnings,
        "by_check": by_check,
    }


def render_json(reports, strict=False):
    docs = []
    for r in reports:
        d = dict(r)
        d["findings"] = [f.as_dict() for f in r["findings"]]
        docs.append(d)
    return json.dumps(
        {
            "tool": "shardcheck",
            "schema_version": JSON_SCHEMA_VERSION,
            "strict": bool(strict),
            "summary": summarize(reports),
            "reports": docs,
        },
        indent=2,
        sort_keys=False,
    )


def _explicit_mesh(args):
    axes = dict(dp=args.dp, fsdp=args.fsdp, tp=args.tp, sp=args.sp,
                pp=args.pp, ep=args.ep)
    if all(v is None for v in axes.values()):
        return None
    from pyrecover_tpu.parallel.mesh import MeshConfig

    return MeshConfig(
        data=args.dp if args.dp is not None else -1,
        fsdp=args.fsdp or 1, tensor=args.tp or 1, sequence=args.sp or 1,
        pipeline=args.pp or 1, expert=args.ep or 1,
    )


def main(argv=None):
    args = _build_parser().parse_args(argv)

    from pyrecover_tpu.analysis.shardcheck.checks import CHECKS, ShardcheckConfig

    if args.list_checks:
        for cid, (name, severity, summary) in CHECKS.items():
            print(f"{cid}  {name:<28} {severity:<7} {summary}")
        return 0

    from pyrecover_tpu.models.presets import PRESETS

    if args.all_presets:
        names = list(PRESETS)
    else:
        names = args.preset or []
    if not names:
        print("shardcheck: give --preset NAME (repeatable) or --all-presets",
              file=sys.stderr)
        return 2
    unknown = [n for n in names if n not in PRESETS]
    if unknown:
        print(
            f"shardcheck: unknown preset(s) {', '.join(unknown)}; "
            f"known: {', '.join(PRESETS)}", file=sys.stderr,
        )
        return 2

    config = ShardcheckConfig(
        select=_csv_set(args.select) if args.select else None,
        ignore=_csv_set(args.ignore) if args.ignore else frozenset(),
        replicated_threshold_bytes=args.threshold_mb * 2**20,
        hbm_budget_fraction=args.hbm_fraction,
        device_kind=args.device_kind,
    )

    if args.diff_checkpoint:
        if len(names) != 1:
            print("shardcheck: --diff-checkpoint needs exactly one --preset",
                  file=sys.stderr)
            return 2
        return _diff_mode(args, names[0], config)

    try:
        device_counts = tuple(
            int(x) for x in args.devices.split(",") if x.strip()
        )
    except ValueError:
        print(f"shardcheck: bad --devices {args.devices!r}", file=sys.stderr)
        return 2

    from pyrecover_tpu.analysis.shardcheck.runner import check_preset

    explicit = _explicit_mesh(args)
    reports = []
    for name in names:
        reports.append(check_preset(
            name, PRESETS[name](), device_counts=device_counts,
            config=config, batch_size=args.batch_size, seq_len=args.seq_len,
            run_census=not args.no_census,
            mesh_configs=[explicit] if explicit is not None else None,
            optimizer_sharding=args.optimizer_sharding,
            grad_allreduce=args.grad_allreduce,
            quant_block=args.grad_quant_block,
            grad_bucket_mb=args.grad_bucket_mb,
        ))

    if args.json:
        # jaxlint: disable-next=torn-write -- CI report artifact, regenerated
        # every run; a torn report fails its consumer loudly and is simply
        # re-produced
        Path(args.json).write_text(
            render_json(reports, strict=args.strict) + "\n", encoding="utf-8"
        )
    if args.format == "json":
        print(render_json(reports, strict=args.strict))
    else:
        print(render_text(reports))

    n_findings = sum(len(r["findings"]) for r in reports)
    if args.strict and n_findings:
        return 1
    return 0


def _diff_mode(args, preset_name, config):
    """--diff-checkpoint: saved manifest vs the preset's current schema."""
    from pyrecover_tpu.analysis.shardcheck.manifest import (
        diff_manifests,
        read_ckpt_manifest,
        state_manifest,
    )
    from pyrecover_tpu.models.presets import PRESETS

    path = Path(args.diff_checkpoint)
    if not path.exists():
        print(f"shardcheck: no such checkpoint: {path}", file=sys.stderr)
        return 2
    saved = read_ckpt_manifest(path)

    import jax

    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.train import state_pspecs
    from pyrecover_tpu.train_state import create_train_state

    model_config = PRESETS[preset_name]()
    optimizer, _ = build_optimizer(TrainConfig())
    abstract = jax.eval_shape(
        lambda key: create_train_state(key, model_config, optimizer),
        jax.random.key(0),
    )
    current = state_manifest(abstract, specs=state_pspecs(abstract))
    findings = [
        f for f in diff_manifests(
            saved, current, locus=str(path), check_specs=args.check_specs,
        )
        if config.check_enabled(f.rule_id)
    ]
    # reuse the report plumbing: one pseudo-report, no meshes/memory/census
    reports = [{
        "preset": preset_name, "findings": findings, "meshes": [],
        "memory": None, "census": None,
    }]
    if args.json:
        # jaxlint: disable-next=torn-write -- CI report artifact, regenerated
        # every run; a torn report fails its consumer loudly and is simply
        # re-produced
        Path(args.json).write_text(
            render_json(reports, strict=args.strict) + "\n", encoding="utf-8"
        )
    if args.format == "json":
        print(render_json(reports, strict=args.strict))
    else:
        for f in findings:
            print(_finding_line(f))
        print(f"{len(findings)} finding(s); checkpoint "
              f"{'does NOT fit' if findings else 'fits'} preset {preset_name}")
    if args.strict and findings:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
