"""Checkpoint naming, latest-discovery, and retention pruning.

Layout parity with the reference (train.py:135-141, 309-315, 348-353):

    <checkpoint_dir>/<experiment_name>/ckpt_<step>[_final][.ckpt]

Vanilla checkpoints are single *files* (`.ckpt`); sharded checkpoints are
*directories* — exactly the reference's file/dir split (checkpoint.py:
371-404); zerostall checkpoints are manifest files (`.zs.json`) whose
tensor data lives in the content-addressed ``chunks/`` store beside them
(checkpoint/zerostall/). Engines can coexist in one experiment
directory: discovery, `latest`, and retention are engine-scoped via
``engine_of`` so one engine's pruning can never eat another's
checkpoints. Two deliberate fixes over the reference (SURVEY §2.3):

  * defect #6 — vanilla retention pruned by lexicographic name sort, so
    `ckpt_1000.pt` sorted before `ckpt_200.pt` and the wrong checkpoint was
    deleted. Here ordering is ALWAYS by parsed step number (mtime as
    tiebreak), for both strategies.
  * `latest` discovery likewise uses step numbers, not mtime, so a restored
    + re-touched old checkpoint can't shadow a newer one.
"""

import re
import shutil
from pathlib import Path

from pyrecover_tpu.resilience.quarantine import QUARANTINE_DIRNAME

_CKPT_RE = re.compile(r"^ckpt_(\d+)(_final)?(\.ckpt|\.zs\.json)?$")

VANILLA_SUFFIX = ".ckpt"
ZEROSTALL_SUFFIX = ".zs.json"

# what each engine's checkpoint names end in (sharded: a bare directory)
SUFFIXES = {"vanilla": VANILLA_SUFFIX, "sharded": "",
            "zerostall": ZEROSTALL_SUFFIX}
ENGINES = tuple(SUFFIXES)


def engine_of(path):
    """Which engine owns a checkpoint path: directories are sharded
    (Orbax), ``.zs.json`` manifests are zerostall, everything else is a
    vanilla single file."""
    path = Path(path)
    if path.is_dir():
        return "sharded"
    if path.name.endswith(ZEROSTALL_SUFFIX):
        return "zerostall"
    return "vanilla"


def _known(engine):
    if engine is not None and engine not in SUFFIXES:
        raise ValueError(f"unknown checkpoint engine {engine!r}")
    return engine


def checkpoint_path(checkpoint_dir, experiment_name, step, *, final=False,
                    engine="vanilla"):
    name = f"ckpt_{int(step)}"
    if final:
        name += "_final"
    name += SUFFIXES[_known(engine)]
    return Path(checkpoint_dir) / experiment_name / name


def parse_step(path):
    """Step number of a checkpoint path, or None if not a checkpoint name."""
    m = _CKPT_RE.match(Path(path).name)
    return int(m.group(1)) if m else None


def list_checkpoints(exp_dir, *, engine=None):
    """All checkpoints in ``exp_dir``, ordered oldest→newest by step.

    ``engine`` ("vanilla" | "sharded" | "zerostall") restricts to one
    engine's checkpoints; without it, every engine's checkpoints are
    returned.
    """
    exp_dir = Path(exp_dir)
    want = _known(engine)
    if not exp_dir.is_dir():
        return []
    out = []
    for p in exp_dir.iterdir():
        # quarantined entries live under .corrupt/ and are invisible to
        # discovery AND retention — a failed checkpoint must never count
        # against max_keep or shadow `latest` (its name can't match the
        # pattern either, but the guard keeps the contract explicit)
        if p.name == QUARANTINE_DIRNAME:
            continue
        step = parse_step(p)
        if step is None:
            continue
        if want is not None and engine_of(p) != want:
            continue
        out.append((step, p.stat().st_mtime, p))
    out.sort(key=lambda t: (t[0], t[1]))
    return [p for _, _, p in out]


def get_latest_checkpoint(exp_dir, *, engine=None):
    """Newest checkpoint by step number (reference checkpoint.py:371-404,
    which used mtime — step numbers are the actual intent)."""
    ckpts = list_checkpoints(exp_dir, engine=engine)
    return ckpts[-1] if ckpts else None


def prune_checkpoints(exp_dir, max_keep, *, engine=None):
    """Delete oldest checkpoints beyond ``max_keep`` (plus checksum
    sidecars). Returns the deleted paths.

    Engine-scoped: with ``engine`` only that engine's checkpoints
    count against ``max_keep`` — retention on
    one engine never deletes another's. For zerostall, removing a
    manifest only drops references; the chunk bytes are reclaimed by
    ``zerostall.chunkstore.collect_garbage`` (refcounted — a chunk any
    live manifest still names is never collected)."""
    if max_keep is None or max_keep <= 0:
        return []
    ckpts = list_checkpoints(exp_dir, engine=engine)
    doomed = ckpts[:-max_keep] if len(ckpts) > max_keep else []
    engine_label = engine or "any"
    from pyrecover_tpu.resilience import faults

    for p in doomed:
        # seam BEFORE the deletion: retention destroys durable state, so
        # a drill must be able to kill between victim selection and the
        # rmtree/unlink to prove a half-finished prune stays restorable
        faults.check("ckpt_prune", path=p.name, step=parse_step(p))
        if p.is_dir():
            shutil.rmtree(p, ignore_errors=True)
        else:
            p.unlink(missing_ok=True)
            for sidecar in (p.with_suffix(p.suffix + ".sha256"),
                            p.with_suffix(p.suffix + ".md5")):
                sidecar.unlink(missing_ok=True)
        from pyrecover_tpu import telemetry

        # one event per removal: retention is destroying durable state, so
        # every deletion must be individually attributable in the stream
        telemetry.emit(
            "ckpt_pruned", engine=engine_label, path=p.name,
            step=parse_step(p),
        )
    if doomed:
        from pyrecover_tpu import telemetry

        telemetry.emit(
            "ckpt_prune", engine=engine_label,
            count=len(doomed), removed=[p.name for p in doomed],
        )
    return doomed
