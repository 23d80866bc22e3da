#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of ``llama-1b`` (and ``moe-4x1b`` for the MoE step), with depth
as the presets have it and weights random from the trainer's seed:

  A  straight run    ``python -m pyrecover_tpu.train`` for 6 steps, final save
     (``--checkpoint-engine sharded``: the 7.6 GB state in files of at
     most ~93 MB — the vanilla engine's single file met EFBIG, a file-size
     ceiling, on the machine that checks this script)
  B  the paper's workload: the same command for 3 steps (final save at 3),
     then a NEW process ``--resume-from-checkpoint latest`` to step 6;
     ``tools/check_equality.py --all-state`` and ``tools/compare_loss_csv.py``
     must find B identical to A (bit-exact resume, on the chip)
  C  serve what was trained: A's step-6 checkpoint through
     ``serving.restore.load_serving_params`` into a ``ServingEngine``
     answering mixed-length requests; the serving program's logits must
     agree with the training forward on a small input
  D  the MoE step: ``moe-4x1b`` for 3 steps (``moe_dispatch=auto``)

One process per chip: this parent NEVER imports jax or pyrecover_tpu (a
parent that touched JAX would hold the chip and every child would fail or
hang). Each leg is a child process, one at a time, each exiting before the
next starts — the shape ``launch/run_resilient.sh`` has. Evidence comes
from each run's own artefacts (telemetry JSONL, loss CSV, checkpoints),
never from an exit code alone.

It FAILS — non-zero exit, no result line — when the resolved platform is
not ``tpu``: there is no CPU fallback and no shrink. ``--rehearse-cpu`` is
a test aid, not a fallback: the same legs at a tiny width on the CPU with
the kernel interpreted, to make the script run here before a chip call;
its result line says ``"rehearsal": true``.

Last stdout line on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

import argparse
import json
import math
import os
import shutil
import resource
import signal
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
# the package's default persistent-compile-cache directory
# (pyrecover_tpu/__init__.py places it; tests/test_chip_smoke.py pins that
# the two agree) — only COUNTED here, never set
DEFAULT_COMPILE_CACHE = REPO / ".jax_cache"
ARTEFACTS = REPO / "chiprun_out" / "chip_smoke"
DEADLINE_S = 1150.0  # the contract allows 1200 s, compilation included
# no file this script or its children write may pass this: the sharded
# engine keeps checkpoint files near 92 MiB whatever the model's size
FILE_CEILING_BYTES = 128 * 1024 * 1024

# The sizes the legs run at. ``width``: model widths as trainer flags —
# depth and the rest are the presets' (models/presets.py llama_1b /
# moe_4x1b over ModelConfig's default ffn multiplier 1.3 / multiple 1024;
# the serve child asserts that). ``shape``: (sequence, global batch, loss
# chunk) — the old bench point and the S1 MoE cell; batch 8 divides 1, 2,
# 4 and 8 devices on the data axis. ``serve``: sized for the chip (not the
# 4-slot default) — 8 decode slots, 256-token prefill chunks under a
# 512-token per-pass budget, so the 700+ token prompts take several
# scheduler passes.
CHIP = dict(
    width={
        "llama-1b": dict(dim=2048, n_layers=20, n_heads=16, n_kv_heads=8,
                         vocab_size=32768),
        "moe-4x1b": dict(dim=2048, n_layers=8, n_heads=16, n_kv_heads=8,
                         vocab_size=32768, n_experts=4, moe_top_k=2),
    },
    shape={"llama-1b": (2048, 8, 512), "moe-4x1b": (1024, 4, 512)},
    serve=dict(
        block_size=16, max_seqs=8, prefill_chunk=256,
        prefill_token_budget=512,
        prompt_lens=(24, 1200, 100, 700, 300, 64, 900, 512, 40, 1500),
        max_new=(16, 8, 32, 12, 24, 32, 8, 16, 32, 6),
    ),
)
REHEARSAL = dict(  # --rehearse-cpu: same flags, toy numbers
    width={
        "llama-1b": dict(dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                         vocab_size=128),
        "moe-4x1b": dict(dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                         vocab_size=128, n_experts=4, moe_top_k=2),
    },
    shape={"llama-1b": (64, 8, 32), "moe-4x1b": (64, 4, 32)},
    serve=dict(
        block_size=8, max_seqs=4, prefill_chunk=8, prefill_token_budget=16,
        prompt_lens=(3, 40, 10, 24, 17, 5, 30, 16, 4, 50),
        max_new=(6, 4, 8, 5, 7, 8, 3, 6, 8, 2),
    ),
)


class SmokeFailure(Exception):
    """A leg failed its checks (message says which evidence)."""


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def need(cond, why):
    if not cond:
        raise SmokeFailure(why)


def needs_for(name):
    """``need`` with the leg's name on every message."""
    return lambda cond, why: need(cond, f"{name}: {why}")


# ---- child processes --------------------------------------------------------

def child_env(rehearse):
    """The children's environment: never interpret mode, never a forced
    CPU platform (``main`` refuses up front when either was set) — except
    in the explicit rehearsal, which sets both."""
    env = dict(os.environ)
    env.pop("PYRECOVER_PALLAS_INTERPRET", None)
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        env.pop("JAX_PLATFORMS")
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["PYRECOVER_PALLAS_INTERPRET"] = "1"
    else:
        # a trainer child that resolves to CPU must die, not grind on
        env["PYRECOVER_EXPECT_ACCELERATOR"] = "1"
    return env


def run_child(name, cmd, log_path, env, deadline, timeout_s):
    """Run one child to completion in its own process group, output to
    ``log_path``; kill the whole group on timeout. Returns wall seconds.
    Raises SmokeFailure on a non-zero exit or a timeout."""
    budget = min(timeout_s, deadline - time.monotonic())
    if budget <= 0:
        raise SmokeFailure(f"{name}: no time left before the deadline")
    shown = " ".join(str(c) for c in cmd[1:4]).split("\n")[0][:70]
    say(f"{name}: python {shown} ... (log {log_path.name}, "
        f"<= {budget:.0f}s)")
    t0 = time.monotonic()
    # jaxlint: disable-next=torn-write -- a child's log: diagnostic only,
    # rewritten by every run
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [str(c) for c in cmd], cwd=REPO, env=env, stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        try:
            rc = proc.wait(timeout=budget)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            # the child and anything it started: none may outlive the leg
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    wall = time.monotonic() - t0
    if rc is None:
        raise SmokeFailure(f"{name}: killed after {budget:.0f}s\n"
                           + tail(log_path))
    if rc != 0:
        raise SmokeFailure(f"{name}: exit code {rc}\n" + tail(log_path))
    say(f"{name}: exit 0 in {wall:.1f}s")
    return wall


def tail(path, n=40):
    try:
        lines = Path(path).read_text(errors="replace").splitlines()
    except OSError as e:
        return f"(no log: {e})"
    return "\n".join(f"    | {line[:400]}" for line in lines[-n:])


# ---- the trainer legs -------------------------------------------------------

def train_cmd(model, sizes, n_devices, workdir, exp, steps, *, save,
              extra=()):
    width = sizes["width"][model]
    seq, batch, chunk = sizes["shape"][model]
    # all visible devices sit on the data axis: the MoE cell's batch 4
    # grows to one row per device on an 8-chip host
    batch = max(batch, n_devices)
    cmd = [
        sys.executable, "-m", "pyrecover_tpu.train",
        "--model-dim", width["dim"], "--model-layers", width["n_layers"],
        "--model-heads", width["n_heads"],
        "--model-kv-heads", width["n_kv_heads"],
        "--vocab-size", width["vocab_size"],
        "--sequence-length", seq, "--batch-size", batch,
        # pin the dataset size: runs of different --training-steps must
        # draw the same sample order or B can never equal A
        "--training-samples", 8 * batch,
        # bf16 params and compute: fp32 masters do not fit 16 GB at 1B
        "--model-dtype", "bf16", "--param-dtype", "bf16",
        "--use-flash-attention", "--remat", "--loss-chunk-size", chunk,
        "--training-steps", steps, "--logging-frequency", 1,
        "--log-loss-to-csv", "--telemetry", "--timeaware-checkpointing",
        # final save only (any frequency beyond the run), or none at all
        "--checkpoint-frequency", 1000 if save else -1,
        "--checkpoint-dir", workdir, "--experiment-name", exp,
    ]
    if save:
        # many bounded files, not one the size of the state (see leg A)
        cmd += ["--checkpoint-engine", "sharded"]
    if "n_experts" in width:
        cmd += ["--moe-experts", width["n_experts"],
                "--moe-top-k", width["moe_top_k"]]
    return cmd + list(extra)


def read_events(path):
    events = []
    for line in Path(path).read_text().splitlines():
        try:
            events.append(json.loads(line))
        except ValueError:
            continue  # a torn tail line is not evidence of anything
    return events


def segments(events):
    """Split one experiment's (appended) telemetry stream into one event
    list per process, at each ``run_start``."""
    out = []
    for e in events:
        if e.get("event") == "run_start":
            out.append([])
        if out:
            out[-1].append(e)
    return out


def check_train_segment(name, seg, device, *, first_step, last_step,
                        resumed_at=None):
    """The evidence one trainer process must have left in its telemetry."""
    def of(kind):
        return [e for e in seg if e.get("event") == kind]

    need = needs_for(name)
    start = seg[0]
    kind = str(start.get("device_kind", ""))
    need(start.get("devices") == device["count"],
         f"run_start.devices={start.get('devices')} != {device['count']}")
    need(kind == device["kind"],
         f"run_start.device_kind={kind!r} != probed {device['kind']!r}")
    if device["platform"] == "tpu":
        need("tpu" in kind.lower(), f"device_kind {kind!r} is not a TPU")
    resumes = of("resume")
    if resumed_at is None:
        need(not resumes, f"unexpected resume event {resumes}")
    else:
        # resume walks PAST a checkpoint that fails to restore (a
        # feature), so exit 0 proves nothing: read the event's step
        need(len(resumes) == 1 and resumes[0].get("step") == resumed_at,
             f"resume event(s) {resumes}, wanted exactly one at step "
             f"{resumed_at}")
        bad = of("ckpt_restore_fallback") + of("ckpt_precheck_failed")
        need(not bad, f"resume fell back past a checkpoint: {bad}")
    need(not of("recompile"), f"recompile event(s): {of('recompile')}")
    losses = {e["step"]: e["loss"] for e in of("train_sync")}
    want = list(range(first_step, last_step + 1))
    need(sorted(losses) == want,
         f"train_sync steps {sorted(losses)} != {want}")
    need(all(math.isfinite(v) for v in losses.values()),
         f"non-finite loss: {losses}")
    summary = of("run_summary")
    need(len(summary) == 1 and summary[0].get("status") == "finished"
         and summary[0].get("step") == last_step,
         f"run_summary {summary} is not 'finished' at step {last_step}")
    dispatch = {e["step"]: e["dispatch_s"] for e in of("step_time")}
    return {
        "device_kind": kind,
        "loss_first": losses[first_step], "loss_last": losses[last_step],
        "setup_s": summary[0].get("setup_s"),
        # the first call of the jitted step: trace + lower + compile (or
        # load from the persistent cache) — the child's compile seconds
        "compile_s": dispatch.get(first_step),
        "steady_iter_s": min(
            (e["iter_s"] for e in of("train_sync")
             if e["step"] != first_step), default=None,
        ),
        "hbm_peak_bytes": summary[0].get("hbm_peak_bytes"),
        "watcher_retired": bool(of("maintenance_watcher_retired")),
    }


def disk_facts(*roots):
    """What the machine allows a file to be and what was written so far:
    the report's ``disk`` row, and the last line of a failure."""
    sizes = [p.stat().st_size for root in roots
             for p in Path(root).rglob("*") if p.is_file()]
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    free = shutil.disk_usage(roots[0]).free
    return {
        "rlimit_fsize": [None if v == resource.RLIM_INFINITY else v
                         for v in (soft, hard)],
        "free_bytes": free, "files": len(sizes),
        "largest_file_bytes": max(sizes, default=0),
        "total_bytes": sum(sizes),
    }


def file_size_allowed(where):
    """The largest file ``where`` accepts, found with sparse truncates that
    write nothing (None: no ceiling below 1 TiB). A ceiling set outside
    this process's rlimits shows up only this way; a failure reports it."""
    lo, hi = 0, 1 << 40
    with tempfile.TemporaryFile(dir=where) as f:
        while hi - lo > 4096:
            mid = (lo + hi) // 2
            try:
                os.ftruncate(f.fileno(), mid)
                lo = mid
            except OSError:
                hi = mid
    return None if hi == 1 << 40 else lo


def cache_entries(cache_dir):
    try:
        return sum(1 for p in Path(cache_dir).iterdir() if p.is_file())
    except OSError:
        return 0


def run_train(name, cmd, workdir, exp, env, deadline, device, cache_dir,
              **check):
    """One trainer child + its evidence; returns the report row."""
    before = cache_entries(cache_dir)
    wall = run_child(name, cmd, workdir / f"{name}.log", env, deadline, 480)
    seg = segments(
        read_events(workdir / exp / f"{exp}_telemetry.jsonl")
    )[-1]
    row = check_train_segment(name, seg, device, **check)
    row["wall_s"] = round(wall, 1)
    row["compile_share"] = (
        round(row["compile_s"] / wall, 3) if row["compile_s"] else None
    )
    row["cache_entries_added"] = cache_entries(cache_dir) - before
    say(f"{name}: loss {row['loss_first']:.4f} -> {row['loss_last']:.4f}, "
        f"first-step compile {row['compile_s']}s "
        f"({row['cache_entries_added']} cache entries added), steady "
        f"{row['steady_iter_s']}s/step (smoke observation, not a benchmark)")
    return row


# ---- leg C: the serving child (this file, re-entered with jax) --------------

def serve_child(ckpt, out_path, rehearse):
    """Runs in its OWN process (it imports jax): restore the trained
    checkpoint for serving, answer the requests, check the result."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pyrecover_tpu.models import presets
    from pyrecover_tpu.models.llama import ModelConfig, forward
    from pyrecover_tpu.serving.engine import ServingConfig, ServingEngine
    from pyrecover_tpu.serving.kvpool import blocks_for, make_block_table
    from pyrecover_tpu.serving.restore import load_serving_params

    t_start = time.monotonic()
    compiles = []  # (program, seconds) of every backend compile/cache load
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append((kw.get("fun_name"), secs))
        if event == "/jax/core/compile/backend_compile_duration" else None
    )
    sizes = REHEARSAL if rehearse else CHIP
    seq = sizes["shape"]["llama-1b"][0]
    cfg = ModelConfig(
        **sizes["width"]["llama-1b"], max_seq_len=seq,
        param_dtype="bfloat16", compute_dtype="bfloat16",
    )
    if not rehearse:
        preset = dataclasses.replace(
            presets.PRESETS["llama-1b"](max_seq_len=seq),
            param_dtype="bfloat16", compute_dtype="bfloat16",
        )
        need(cfg == preset, f"CHIP width drifted from the preset: {cfg} vs {preset}")
    sv = sizes["serve"]
    params, info = load_serving_params(ckpt, cfg)
    engine = ServingEngine(params, cfg, ServingConfig(
        block_size=sv["block_size"], max_seqs=sv["max_seqs"],
        prefill_chunk=sv["prefill_chunk"],
        prefill_token_budget=sv["prefill_token_budget"],
    ))
    # warm-up request: compiles prefill AND decode (2 new tokens)
    warm = engine.submit([1, 2, 3], 2)
    engine.run_until_drained()
    need(len(engine.result(warm)) == 5, "warm-up request did not finish")
    n_warm = len(compiles)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).tolist()
               for n in sv["prompt_lens"]]
    t0 = time.monotonic()
    rids = [engine.submit(p, n) for p, n in zip(prompts, sv["max_new"])]
    engine.run_until_drained()
    serve_s = time.monotonic() - t0
    for rid, prompt, n_new in zip(rids, prompts, sv["max_new"]):
        got = engine.result(rid)
        need(got is not None, f"request {rid} never finished")
        need(got[:len(prompt)] == prompt, f"request {rid}: prompt altered")
        need(len(got) == len(prompt) + n_new,
             f"request {rid}: {len(got) - len(prompt)} new tokens, "
             f"wanted {n_new}")
        need(all(0 <= t < cfg.vocab_size for t in got),
             f"request {rid}: token id outside the vocabulary")
    engine.pool.check_drained()

    # finite logits that agree with the training forward on a small
    # input — through the engine's OWN compiled prefill program (no
    # token equality vs models/decode.py on the chip: near-uniform logits
    # after 6 steps make bf16 argmax ties; that equality is a CPU test)
    chunk = sv["prefill_chunk"]
    toks = jnp.asarray(
        [rng.integers(0, cfg.vocab_size, (chunk,)).tolist()], jnp.int32
    )
    blocks = engine.pool.alloc("refcheck", blocks_for(chunk, sv["block_size"]))
    table = make_block_table(engine.table_width, blocks)
    logits, engine._arrays = engine._prefill_fn(
        engine.params, engine._arrays, toks, jnp.asarray([0], jnp.int32),
        jnp.asarray(table[None]),
    )
    logits = np.asarray(logits, np.float32)[0]  # host-side index: no program
    engine.pool.release("refcheck")
    engine.pool.check_drained()
    # prefill and decode are the only two serving programs: everything
    # the engine runs was built by the warm-up request
    after_warm = [name for name, _ in compiles[n_warm:]]
    need(not after_warm,
         f"compile(s) after the warm-up request: {after_warm}")
    ref = np.asarray(
        jax.jit(lambda p, t: forward(p, t, cfg))(params, toks), np.float32
    )[0]
    need(logits.shape == ref.shape == (chunk, cfg.vocab_size),
         f"logits shape {logits.shape} vs forward {ref.shape}")
    need(bool(np.isfinite(logits).all()), "non-finite serving logits")
    err = float(np.max(np.abs(logits - ref)))
    scale = float(np.max(np.abs(ref)))
    # bf16 compute on both sides: agreement to a few bf16 ulps of the
    # largest logit; a wrong mask/position/table is O(scale) off
    need(err <= 0.05 * scale,
         f"serving logits differ from the training forward: max |diff| "
         f"{err:.4g} vs logit scale {scale:.4g}")
    new_tokens = sum(sv["max_new"])
    # jaxlint: disable-next=torn-write -- read by the parent only after
    # this child exits 0; a tear means a dead child, which already failed
    Path(out_path).write_text(json.dumps({
        "device_kind": jax.devices()[0].device_kind,
        "restore": info, "requests": len(rids),
        "prompt_tokens": sum(sv["prompt_lens"]), "new_tokens": new_tokens,
        "serve_s": round(serve_s, 3),
        "compile_s": round(sum(secs for _, secs in compiles[:n_warm]), 2),
        "programs_built_by_warmup": sorted(
            {name for name, _ in compiles[:n_warm]}
        ),
        "compiles_after_warmup": len(after_warm),
        "logits_max_abs_diff_vs_forward": err, "logit_scale": scale,
        "wall_in_process_s": round(time.monotonic() - t_start, 1),
    }))


# ---- the parent -------------------------------------------------------------

PROBE = (
    "import json, jax, jaxlib\n"
    "d = jax.devices()\n"
    "try:\n"
    "    import libtpu; lt = getattr(libtpu, '__version__', 'unknown')\n"
    "except ImportError:\n"
    "    lt = None\n"
    "print(json.dumps({'platform': d[0].platform, 'kind': d[0].device_kind,"
    " 'count': len(d), 'jax': jax.__version__, 'jaxlib': jaxlib.__version__,"
    " 'libtpu': lt}))\n"
)


def probe_device(env, workdir, deadline):
    """What jax resolves to, asked of a CHILD (the parent stays off jax)."""
    log = workdir / "probe.log"
    run_child("probe", [sys.executable, "-c", PROBE], log, env, deadline, 180)
    for line in reversed(log.read_text().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise SmokeFailure("probe: no device line\n" + tail(log))


def run_legs(workdir, rehearse, deadline):
    env = child_env(rehearse)
    probed = probe_device(env, workdir, deadline)
    device = {k: probed[k] for k in ("platform", "kind", "count")}
    versions = {k: probed[k] for k in ("jax", "jaxlib", "libtpu")}
    say(f"device: {device}  versions: {versions}"
        + ("  [REHEARSAL on CPU, kernel interpreted — not a chip run]"
           if rehearse else ""))
    want = "cpu" if rehearse else "tpu"
    if device["platform"] != want:
        raise SmokeFailure(
            f"resolved platform is {device['platform']!r}, not {want!r}: "
            "this smoke measures the chip or nothing (no CPU fallback; "
            "--rehearse-cpu is the explicit test aid)"
        )
    cache_dir = Path(
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_COMPILE_CACHE
    )
    report = {"rehearsal": rehearse, "device": device, "versions": versions,
              "compile_cache_dir": str(cache_dir), "legs": {}}
    legs = report["legs"]

    sizes = REHEARSAL if rehearse else CHIP

    def train(name, exp, steps, *, model="llama-1b", save=True, extra=(),
              **check):
        legs[name] = run_train(
            name, train_cmd(model, sizes, device["count"], workdir, exp,
                            steps, save=save, extra=extra),
            workdir, exp, env, deadline, device, cache_dir, **check,
        )

    # A: straight; B: interrupted + resumed in a new process
    train("A", "legA", 6, first_step=1, last_step=6)
    train("B1", "legB", 3, first_step=1, last_step=3)
    train("B2", "legB", 6, first_step=4, last_step=6, resumed_at=3,
          extra=("--resume-from-checkpoint", "latest"))
    cold, warm = legs["A"]["compile_s"], legs["B1"]["compile_s"]
    report["compile_cold_s"], report["compile_warm_s"] = cold, warm
    # B1 compiles exactly A's programs: every one the cache keeps must
    # hit. (A itself is warm when $JAX_COMPILATION_CACHE_DIR came
    # pre-filled, so "warm < cold" is only asserted when A wrote entries.)
    if not rehearse:
        if cache_entries(cache_dir) == 0:
            raise SmokeFailure(f"compile cache {cache_dir} is empty after A")
        if legs["B1"]["cache_entries_added"]:
            raise SmokeFailure(
                f"B1 added {legs['B1']['cache_entries_added']} compile-cache "
                "entries: it missed programs A already compiled"
            )
        if legs["A"]["cache_entries_added"] and not warm < cold:
            raise SmokeFailure(
                f"warm first-step compile {warm}s is not below cold {cold}s"
            )
    say(f"compile cache {cache_dir}: cold {cold}s -> warm {warm}s "
        f"(B2 {legs['B2']['compile_s']}s)")

    ckpt_a = workdir / "legA" / "ckpt_6_final"
    ckpt_b = workdir / "legB" / "ckpt_6_final"
    # all three checkpoints are on disk now: the most this run ever holds
    report["disk"] = disk_facts(workdir, cache_dir)
    say(f"disk: {report['disk']}")
    need(report["disk"]["largest_file_bytes"] <= FILE_CEILING_BYTES,
         f"a file of {report['disk']['largest_file_bytes']} bytes was "
         f"written, over the {FILE_CEILING_BYTES} this smoke allows itself")
    t = run_child(
        "check_equality",
        [sys.executable, "tools/check_equality.py", ckpt_a, ckpt_b,
         "--all-state"],
        workdir / "check_equality.log", env, deadline, 300,
    )
    t += run_child(
        "compare_loss_csv",
        [sys.executable, "tools/compare_loss_csv.py",
         workdir / "legA" / "legA_loss_log.csv",
         workdir / "legB" / "legB_loss_log.csv"],
        workdir / "compare_loss_csv.log", env, deadline, 60,
    )
    legs["A_vs_B"] = {"check_equality_all_state": "equal",
                      "compare_loss_csv_steps_1_6": "equal",
                      "wall_s": round(t, 1)}
    # the resumed run is done with its checkpoints; leg C needs only A's
    drop_checkpoints(workdir / "legB")

    serve_out = workdir / "serve.json"
    wall = run_child(
        "C", [sys.executable, Path(__file__).resolve(), "--serve-child",
              ckpt_a, serve_out] + (["--rehearse-cpu"] if rehearse else []),
        workdir / "C.log", env, deadline, 420,
    )
    legs["C"] = json.loads(serve_out.read_text())
    legs["C"]["wall_s"] = round(wall, 1)
    legs["C"]["compile_share"] = round(legs["C"]["compile_s"] / wall, 3)
    if legs["C"].pop("device_kind") != device["kind"]:
        raise SmokeFailure("C: served on a different device kind")
    say(f"C: {legs['C']['requests']} requests, {legs['C']['new_tokens']} new "
        f"tokens in {legs['C']['serve_s']}s, drained clean, "
        f"{legs['C']['compiles_after_warmup']} compiles after warm-up, "
        f"logits within {legs['C']['logits_max_abs_diff_vs_forward']:.3g} "
        f"of the training forward (scale {legs['C']['logit_scale']:.3g})")
    drop_checkpoints(workdir / "legA")

    train("D", "legD", 3, model="moe-4x1b", save=False,
          first_step=1, last_step=3)
    return report


def drop_checkpoints(exp_dir):  # faultcheck: tear-ok -- scratch cleanup
    """Free the disk an experiment's checkpoints hold (7.6 GB each at
    llama-1b) as soon as no later leg reads them; the small evidence
    files stay for ``keep_artefacts``."""
    for p in exp_dir.glob("ckpt_*"):
        if p.is_dir():
            shutil.rmtree(p, ignore_errors=True)
        else:
            p.unlink(missing_ok=True)


def keep_artefacts(workdir):
    """Small evidence files only (never checkpoints) for the caller to
    read after a chip call."""
    ARTEFACTS.mkdir(parents=True, exist_ok=True)
    for pattern in ("*.log", "*.json", "*/*_telemetry.jsonl", "*/*.csv"):
        for p in workdir.glob(pattern):
            shutil.copy2(p, ARTEFACTS / p.name)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="test aid: the same legs at a tiny width on the "
                    "CPU with the kernel interpreted (NOT a chip result)")
    ap.add_argument("--workdir", default=None,
                    help="keep checkpoints/logs here instead of a temp "
                    "directory that is removed at exit")
    ap.add_argument("--serve-child", nargs=2, metavar=("CKPT", "OUT"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.serve_child:
        serve_child(*args.serve_child, rehearse=args.rehearse_cpu)
        return 0

    t0 = time.monotonic()
    if not args.rehearse_cpu:
        forced = []
        if os.environ.get("PYRECOVER_PALLAS_INTERPRET", "0") == "1":
            forced.append("PYRECOVER_PALLAS_INTERPRET=1 (interpreted kernel)")
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
            forced.append("JAX_PLATFORMS=cpu (no accelerator)")
        if forced:
            print("chip_smoke: FAILED — the environment would have kept "
                  "the run off the chip: " + "; ".join(forced)
                  + ". Run it on the TPU machine, or pass --rehearse-cpu "
                  "for the CPU rehearsal (a test aid, not a result).",
                  file=sys.stderr)
            return 2
    if not (REPO / "pyrecover_tpu").is_dir():
        print("chip_smoke: FAILED — pyrecover_tpu/ is not beside this "
              "script; it drives the repository, it is not the program.",
              file=sys.stderr)
        return 2

    workdir = Path(args.workdir or tempfile.mkdtemp(prefix="chip_smoke_"))
    workdir.mkdir(parents=True, exist_ok=True)
    try:
        report = run_legs(workdir, args.rehearse_cpu,
                          deadline=t0 + DEADLINE_S)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        print(f"chip_smoke: disk at failure: {disk_facts(workdir)}, "
              f"largest file allowed {file_size_allowed(workdir)}",
              file=sys.stderr)
        return 1
    finally:
        if not args.workdir:
            keep_artefacts(workdir)
            shutil.rmtree(workdir, ignore_errors=True)
    report["wall_s"] = round(time.monotonic() - t0, 1)
    if not args.workdir:
        # jaxlint: disable-next=torn-write -- report artefact, regenerated
        # by every run; the result line on stdout is the verdict
        (ARTEFACTS / "report.json").write_text(json.dumps(report, indent=1))
    print(json.dumps(report))
    result = {"ok": True, "device": report["device"]}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
