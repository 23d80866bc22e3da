#!/usr/bin/env python3
"""The remat ladder of one training shape, rung by rung (ISSUE 31): what the
layer scan keeps for the backward sweep, what that costs the chip's memory
by the COMPILER's own count, and what it buys in milliseconds a step.

Without a chip (this is how the table of compiler peaks in PERF.md is
reproduced; ~1 min a rung):

    JAX_PLATFORMS=cpu python3 tools/remat_ladder.py --cell mistral-7b.steady \
        --topology v5e:2x2

compiles the train step of every rung of `utils/remat.py`'s `LADDER` for one
device of the described topology and prints the compiler's peak, whether it
compiles at all (its refusal names the limit: "Used 22.69G of 15.75G hbm"),
the Mosaic calls in the program, how many instructions the compiler
rematerialized BY ITSELF to get there (`.remat` clones: a rung that makes it
do that recomputes more than it saved), and beside them the bytes
`memory_budget` models and the rung `--remat-policy auto` picks. `--sets a+b,c+d` adds
save-sets that are no rung (names of `utils/remat.py`, joined by `+`).

On a chip it compiles for the chip it holds and times each rung:

    chiprun --timeout 1800 -- python3 tools/remat_ladder.py \
        --cell mistral-7b.steady --steps 5

(`--rungs a,b` picks rungs). Timings are wall seconds on the host clock
round `--steps` steps that end in `block_until_ready`, after one warm-up
step a rung. `--rehearse-cpu` walks the same control flow on the CPU at a
toy width and prints no time. Shapes come from a benchmark cell (`--cell`)
or from the trainer's own flags after `--` (`-- --model-dim 2048 ...`).
Results: stdout (a table, one JSON line) and `chiprun_out/remat_ladder.
<cell>.json` when run on a chip. One chip only: a sharded mesh is not sized
here.
"""

import argparse
import dataclasses
import json
import os
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

GIB = 2**30


def cell_config(name, rehearse):  # jaxlint: host-only
    """The trainer's configuration of a benchmark cell."""
    from benchmark.runners.train_window import train_config

    bench = ROOT / "benchmark"
    cell = json.loads((bench / "workloads" / f"{name}.json").read_text())
    cfg = json.loads((bench / "configs" / f"{cell['config']}.json").read_text())
    if cell["chips"] != 1:
        raise SystemExit(f"remat_ladder: {name} needs {cell['chips']} chips; "
                         "this tool sizes one chip")
    if rehearse:  # a toy of the same structure
        from benchmark.run import REHEARSAL

        toy = dict(REHEARSAL["cfg"])
        toy["trainer_model"] = {
            **cfg.get("trainer_model", {}), **toy["trainer_model"]}
        cfg = {**cfg, **toy}
        if "head_dim" in cfg:  # a published head size (Ouro's widths)
            cfg.update(intermediate_size=192, head_dim=16)
        cell = {**cell, **REHEARSAL["cell"]}
    return train_config(cell, cfg, 31, "/nonexistent")


def abstract_inputs(config, model_config, optimizer, mesh):
    """Shapes, dtypes and shardings of the step's state and batch."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from pyrecover_tpu.parallel.sharding import batch_pspec
    from pyrecover_tpu.train import state_pspecs
    from pyrecover_tpu.train_state import create_train_state

    # (the key is made inside the trace: nothing runs on a described device)
    state = jax.eval_shape(
        lambda: create_train_state(jax.random.key(0), model_config, optimizer)
    )
    specs = state_pspecs(state)
    state = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
        state, specs,
        is_leaf=lambda x: isinstance(x, (P, jax.ShapeDtypeStruct)),
    )
    rows = jax.ShapeDtypeStruct(
        (config.batch_size, config.sequence_length), "int32",
        sharding=NamedSharding(mesh, batch_pspec()),
    )
    return state, {"inputs": rows, "labels": rows}


def parse_sets(args, ladder):
    """[(label, names)] to walk: rungs, then ad-hoc sets."""
    keep = args.rungs.split(",") if args.rungs else list(ladder)
    unknown = set(keep) - set(ladder)
    if unknown:
        raise SystemExit(f"remat_ladder: no such rung: {sorted(unknown)}")
    sets = [(rung, names) for rung, names in ladder.items() if rung in keep]
    for spec in filter(None, (args.sets or "").split(",")):
        sets.append((spec, tuple(spec.split("+"))))
    return sets


def refusal(err):
    """The compiler's own sentence about memory, from its error."""
    text = str(err)
    found = re.search(r"Used [\d.]+[GMK] of [\d.]+[GMK] hbm", text)
    return found.group(0) if found else text.strip().splitlines()[0][:200]


def main(argv=None):  # jaxlint: host-only
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default=None,
                    help="a benchmark cell's shapes (benchmark/workloads)")
    ap.add_argument("--topology", default=None,
                    help="compile for one device of this described topology "
                         "(v5e:2x2) instead of the device held")
    ap.add_argument("--rungs", default=None, help="comma-separated rungs")
    ap.add_argument("--sets", default=None,
                    help="further save-sets: names joined by +, sets by ,")
    ap.add_argument("--steps", type=int, default=5,
                    help="timed steps a rung (on a chip)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="walk the control flow on the CPU at a toy width; "
                         "prints no time")
    ap.add_argument("trainer_flags", nargs="*",
                    help="after --: the trainer's flags, in place of --cell")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from pyrecover_tpu.config import get_args
    from pyrecover_tpu.ops.flash_attention import default_blocks
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.parallel.mesh import MeshConfig, create_mesh
    from pyrecover_tpu.train_state import make_train_step
    from pyrecover_tpu.utils import remat

    if args.cell:
        config = cell_config(args.cell, args.rehearse_cpu)
    else:
        config = get_args(args.trainer_flags)
    label = args.cell or "flags"

    platform = jax.devices()[0].platform
    on_chip = platform == "tpu" and not args.topology
    if not (on_chip or args.topology or args.rehearse_cpu):
        print(f"remat_ladder: resolved platform is {platform}: give "
              "--topology (compile only) or --rehearse-cpu", file=sys.stderr)
        return 3
    if args.rehearse_cpu:
        os.environ.setdefault("PYRECOVER_PALLAS_INTERPRET", "1")

    if args.topology:
        from jax.experimental import topologies

        device = topologies.get_topology_desc(
            platform="tpu", topology_name=args.topology).devices[0]
    else:
        device = jax.devices()[0]
    kind = device.device_kind
    mesh = create_mesh(MeshConfig(), devices=[device])
    optimizer, _ = build_optimizer(config)
    # the tile the trainer would resolve ON that device (here the CPU's
    # would be taken, and another program compiled)
    bq, bk = default_blocks(kind)
    base = dataclasses.replace(
        config.model, remat=True,
        flash_block_q=config.model.flash_block_q or bq,
        flash_block_kv=config.model.flash_block_kv or bk,
    )
    sizing = dict(
        batch_size=config.batch_size, seq_len=config.sequence_length,
        loss_chunk_size=config.loss_chunk_size,
    )
    decision = remat.resolve_remat_policy(
        dataclasses.replace(base, remat_policy="auto"), {},
        device_kind=kind, **sizing)
    limit = decision.limit_bytes

    def build(label, names):
        model = dataclasses.replace(
            base, remat=label != "none", remat_save=names)
        return model, make_train_step(
            model, optimizer, loss_chunk_size=config.loss_chunk_size)

    state = batch = None
    if not args.topology:
        from pyrecover_tpu.train import init_sharded_state

        state = init_sharded_state(
            jax.random.key(31), base, optimizer, mesh)
        tokens = jax.random.randint(
            jax.random.key(32), (config.batch_size, config.sequence_length),
            0, base.vocab_size, "int32")
        batch = {"inputs": tokens, "labels": jax.numpy.roll(tokens, -1, 1)}

    # (sized outside the mesh: nothing may run on a described device)
    sets = [(label, names, *build(label, names))
            for label, names in parse_sets(args, remat.LADDER)]
    sizes = {
        label: decision.table.get(label) or remat.modelled_total_bytes(
            model, {}, **sizing)
        for label, _, model, _ in sets
    }
    rows = []
    with jax.sharding.set_mesh(mesh):
        for label, names, model, step in sets:
            row = {"set": label, "names": list(names),
                   "modelled_bytes": sizes[label]}
            inputs = (state, batch) if state is not None else abstract_inputs(
                config, model, optimizer, mesh)
            t0 = time.monotonic()
            try:
                compiled = step.lower(*inputs).compile()
            except jax.errors.JaxRuntimeError as err:
                row.update(compiles=False, refusal=refusal(err))
                rows.append(row)
                print(json.dumps(row), file=sys.stderr, flush=True)
                continue
            mem = compiled.memory_analysis()
            text = compiled.as_text()
            row.update(
                compiles=True, compile_s=round(time.monotonic() - t0, 1),
                peak_bytes=int(mem.peak_memory_in_bytes),
                temp_bytes=int(mem.temp_size_in_bytes),
                argument_bytes=int(mem.argument_size_in_bytes),
                mosaic_calls=text.count("tpu_custom_call"),
                # instructions the compiler's OWN rematerialization cloned
                # to get under its budget: it is recomputing by itself
                xla_remat=len(set(re.findall(
                    r"%([\w.\-]+\.remat\d*) =", text))),
            )
            del text
            if state is not None:
                try:
                    state, _ = step(state, batch)  # warm-up: no compile left
                    jax.block_until_ready(state)
                    t0 = time.monotonic()
                    for _ in range(args.steps):
                        state, metrics = step(state, batch)
                    jax.block_until_ready(state)
                    elapsed = time.monotonic() - t0
                except jax.errors.JaxRuntimeError as err:
                    # compiled, and the chip had no room to run it: the
                    # donated state is gone with the failed call
                    row["run_refusal"] = refusal(err)
                    del state
                    state = init_sharded_state(
                        jax.random.key(31), base, optimizer, mesh)
                else:
                    if on_chip:
                        row["step_ms"] = elapsed / args.steps * 1e3
                    row["loss"] = float(metrics["loss"])
            rows.append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)
            del compiled

    out = {
        "cell": label, "device_kind": kind, "platform": platform,
        "compiled_for": args.topology or "the device held",
        "limit_bytes": limit, "margin_bytes": decision.margin_bytes,
        "auto_rung": decision.rung, "rows": rows,
    }
    gib = lambda n: "" if n is None else f"{n / GIB:.2f}"  # noqa: E731
    full_ms = next((r.get("step_ms") for r in rows if r["set"] == "full"),
                   None)
    print(f"{label} on {kind} (compiled for {out['compiled_for']}); "
          f"compiler's limit {gib(limit) or 'unknown'} GiB, auto picks "
          f"{decision.rung}")
    print("| what the layer scan saves | compiler's peak GiB | modelled GiB "
          "| Mosaic calls | rematerialized by XLA itself | ms a step "
          "| ms saved a GiB kept |")
    print("| --- | --- | --- | --- | --- | --- | --- |")
    full_peak = next((r.get("peak_bytes") for r in rows
                      if r["set"] == "full"), None)
    for r in rows:
        peak = (gib(r["peak_bytes"]) if r["compiles"]
                else f"does not compile: {r['refusal']}")
        ms = r.get("step_ms")
        gain = ""
        if ms and full_ms and full_peak and r["peak_bytes"] > full_peak:
            gain = f"{(full_ms - ms) / ((r['peak_bytes'] - full_peak) / GIB):.1f}"
        print(f"| {r['set']} | {peak} | {gib(r['modelled_bytes'])} "
              f"| {r.get('mosaic_calls', '')} | {r.get('xla_remat', '')} "
              f"| {'' if ms is None else f'{ms:.2f}'} | {gain} |")
    print(json.dumps(out))
    if on_chip:
        dest = ROOT / "chiprun_out"
        dest.mkdir(exist_ok=True)
        # jaxlint: disable-next=torn-write -- a report, regenerated by a rerun
        (dest / f"remat_ladder.{label}.json").write_text(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
