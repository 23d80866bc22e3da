"""Chaos soak harness: kill/corrupt/resume a real trainer, prove continuity.

The recovery paths (SIGTERM mid-run, SIGKILL mid-save, flipped bytes in a
committed checkpoint, transient EIO under the writer) are only trustworthy
if a machine exercises them the way production does: against a real
training process, across real restarts, judged by the artifact that
matters — the stitched per-step loss curve. This harness runs the tiny
model trainer as a subprocess under a seeded fault plan
(``resilience.faults`` via ``$PYRECOVER_FAULT_PLAN``), cycles through
kill→resume, and diffs the surviving loss CSV row-for-row against an
uninterrupted golden run with the same seed. Bit-exact or it fails.

A smoke soak is four trainer runs over one experiment directory::

    golden   : fresh, no faults, steps 1..N           -> reference CSV
    cycle 1  : fresh, SIGTERM as step s1 begins       -> final ckpt @ s1
    cycle 2  : resume, SIGKILL mid-checkpoint-write   -> torn tmp, rc -9
    cycle 3  : resume, transient EIO absorbed by the retry path, SIGTERM
               at s2, then the *final* checkpoint's bytes flipped
    cycle 4  : resume, no faults: quarantines the corrupt checkpoint,
               falls back to the newest good one, finishes, DONE marker
    cycle 5  : hang drill in its own exp dir — a seeded loader_stall wedges
               the prefetch pipeline past the run-health watchdog window;
               the run survives, but hang_detected + a postmortem bundle
               must appear and `doctor` must classify a hang wedged in
               the loader_wait phase
    cycles 6-9: elastic_shrink drill in its own exp dirs — a 4-device
               golden run, then kill at 4 devices → resume on a 2-device
               mesh (the topology-elastic reshard path) → grow back to 4
               and finish; gated on loss continuity vs the golden
               (bit-exact before the shrink, tolerance-aware after) and
               the elastic_resume/sampler_rescaled telemetry trail
    cycles 10-15: zerostall drill in its own exp dirs — async-zerostall
               golden + SIGTERM seed run, then SIGKILL at each pipeline
               stage (device→host snapshot, chunk-store write, between
               durable chunks and the manifest rename) and a recovery
               run; gated on bit-exact stitched loss vs the zerostall
               golden, every kill site fired, a torn save leaving the
               previous manifest restorable (no quarantines), and zero
               chunks leaked after GC
    cycles 16-18: zero1 flag-flip drill in its own exp dirs, pinned to a
               2-device mesh — a --optimizer-sharding zero1 golden, a
               zero1 run SIGTERM'd at s1, then a resume with the flag
               flipped to none; gated on the stitched CSV matching the
               zero1 golden BIT-EXACTLY (zero1 is semantically the
               replicated update) and the spec-drifted checkpoint
               restoring without quarantine
    cycles 19-24: gradient-bucket flag-flip drills (own exp dirs, 2-device
               mesh). (a) int8+buckets: a bucketed-int8 golden, a
               bucketed-int8 run SIGTERM'd at s1, then a resume with
               buckets OFF — the residual's layout-independent shape
               must restore cleanly (no quarantine) and the stitched
               CSV must track the golden bit-exactly before the flip
               and within tolerance after (re-blocked quantization
               groups change the low bits, never the trajectory).
               (b) fp32 layout flip: a bucketed-fp32 golden, a kill at
               s1, then a resume with a DIFFERENT bucket cap — gated
               BIT-EXACT end to end: a per-bucket fp32 psum is an exact
               elementwise sum, so the bucket layout can change across
               a resume without touching the trajectory at all.
    cycles 25+: goodput-autopilot drill (own exp dirs) — a golden run with
               --checkpoint-frequency auto and no faults (must hold the
               bounded prior: constant ceiling interval, saves never
               disabled), then a run under a seeded random_sigkill hazard
               whose rate SHIFTS mid-run (AP_RATE until step AP_SHIFT,
               zero after), resumed until it finishes; gated on the
               adapted interval landing within 2x of the analytic
               Young-Daly optimum on both sides of the shift, the
               ckpt_policy decision trail appearing in every run segment,
               the failure-history sidecar counting exactly the observed
               kills, and zero quarantines.

Verdicts: per-cycle exit codes, stitched CSV == golden CSV, exactly the
injected corruption quarantined (zero non-injected losses), and the
``ckpt_io_retry`` / ``ckpt_quarantined`` / ``fault_injected`` telemetry
trail present. The JSON report (``--json`` / ``$CHAOS_JSON``) carries the
seed — rerunning with the same seed reproduces the same schedule.
"""

import argparse
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

from pyrecover_tpu.resilience.quarantine import list_quarantined
from pyrecover_tpu.telemetry import flight, read_events
from pyrecover_tpu.telemetry import doctor as doctor_mod

CHAOS_JSON_ENV = "CHAOS_JSON"

_TINY_MODEL_ARGS = (
    "--model-dim", "64", "--model-layers", "2", "--model-heads", "4",
    "--model-kv-heads", "2", "--vocab-size", "128",
)

PRESETS = {
    # CI-speed: 2 fault kinds per kill cycle, tiny model, CPU, ~10 runs
    # (golden + 4 kill/corrupt/resume cycles + the hang drill + the
    # 4-run elastic_shrink drill)
    "smoke": dict(
        training_steps=10, checkpoint_frequency=3, batch_size=8,
        sequence_length=32, training_samples=64, run_timeout_s=240,
    ),
    # longer soak for local qualification: more steps, same protocol
    "soak": dict(
        training_steps=30, checkpoint_frequency=5, batch_size=8,
        sequence_length=32, training_samples=64, run_timeout_s=600,
    ),
}


def _trainer_cmd(preset, exp, seed, workdir, *, resume=False,
                 extra_args=(), sync_ckpt=True):
    cmd = [
        sys.executable, "-m", "pyrecover_tpu.train",
        "--training-steps", str(preset["training_steps"]),
        "--batch-size", str(preset["batch_size"]),
        "--sequence-length", str(preset["sequence_length"]),
        "--training-samples", str(preset["training_samples"]),
        "--learning-rate", "1e-3", "--lr-warmup-steps", "2",
        "--seed", str(seed),
        "--checkpoint-dir", str(workdir),
        "--experiment_name", exp,
        "--checkpoint-frequency", str(preset["checkpoint_frequency"]),
        # sync (and flush the loss CSV) every step: the post-kill CSV must
        # carry every completed step, that is the artifact under test
        "--logging-frequency", "1000000",
        "--preempt-check-interval", "1",
        "--timeaware-checkpointing",
        "--log-loss-to-csv", "--telemetry",
        "--verify-checkpoints",  # checksum sidecars make corruption visible
        *_TINY_MODEL_ARGS,
    ]
    if sync_ckpt:
        # the classic drills save synchronously; the zerostall drill keeps
        # async saves ON — the overlapped pipeline IS the thing under test
        cmd += ["--no-async-checkpoint"]
    if resume:
        cmd += ["--resume-from-checkpoint", "latest"]
    cmd += list(extra_args)
    return cmd


def _run_trainer(cmd, *, fault_plan, log_path, timeout_s, device_count=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if device_count is not None:
        # the elastic drill pins each cycle's VIRTUAL device count (kill at
        # 4, resume at 2, grow back to 4); any inherited forced count (e.g.
        # pytest's 8) must not leak through
        flags = [
            f for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")
        ]
        flags.append(
            f"--xla_force_host_platform_device_count={int(device_count)}"
        )
        env["XLA_FLAGS"] = " ".join(flags)
    # exercise telemetry JSONL rotation under real kill/resume cycles: a
    # tiny byte cap forces several rotations per run, and the keep depth is
    # raised so the merged read-back (and the event-trail gates below)
    # still see the whole stream
    env.setdefault("PYRECOVER_TELEMETRY_MAX_BYTES", "16384")
    env.setdefault("PYRECOVER_TELEMETRY_KEEP", "50")
    if fault_plan is not None:
        env["PYRECOVER_FAULT_PLAN"] = json.dumps(fault_plan)
    else:
        env.pop("PYRECOVER_FAULT_PLAN", None)
    t0 = time.monotonic()
    # jaxlint: disable-next=torn-write -- append-only subprocess log for
    # humans; a torn tail is harmless
    with open(log_path, "ab") as logf:
        logf.write(("\n==== " + " ".join(cmd) + "\n").encode())
        logf.flush()
        proc = subprocess.run(
            cmd, env=env, stdout=logf, stderr=subprocess.STDOUT,
            timeout=timeout_s,
        )
    return proc.returncode, round(time.monotonic() - t0, 2)


def _read_csv_rows(path):
    path = Path(path)
    if not path.exists():
        return []
    return [ln for ln in path.read_text().splitlines() if ln.strip()]


def _schedule(preset, seed):
    """The seeded fault schedule: (s1, s2) SIGTERM steps. Reproducing a
    soak failure = rerunning with the seed printed in its report."""
    rng = random.Random(seed)
    freq = preset["checkpoint_frequency"]
    steps = preset["training_steps"]
    # s1 lands around the first periodic save; s2 after the second one but
    # before the third, so cycle 3's final save is save #2 of that run
    s1 = rng.randint(freq, freq + 2)
    s2 = rng.randint(2 * freq + 1, min(3 * freq - 1, steps - 2))
    return s1, s2


# goodput-autopilot drill shape: AP_STEPS total steps, the seeded
# random_sigkill hazard active on global steps [0, AP_SHIFT) at AP_RATE
# per eligible step (then zero — the mid-run rate shift), AP_GRACE
# hazard-free steps after every process start (> AP_CEILING, the
# liveness-by-construction bound), and the controller clamped to
# [1, AP_CEILING] so the analytic optimum sits interior to the bounds at
# tiny-model CPU timings. The drill typically runs: golden + kill at
# ~step 14-16 + kill at ~step 26-27 + a clean finish.
AP_STEPS = 44
AP_SHIFT = 32
AP_RATE = 0.7
AP_GRACE = 13
AP_CEILING = 12
AP_MAX_ATTEMPTS = 12
# convergence gate: the chosen interval must land within this factor of
# the bound-clamped analytic Young–Daly optimum recomputed from the
# decision's own reported inputs (cost, MTTI, step time)
AP_CONVERGENCE_FACTOR = 2.0

# relative per-step loss tolerance for the post-shrink segment of the
# elastic drill: a changed replica count changes the cross-device
# reduction order (and per-replica batch composition), so the float
# trajectory drifts in the low-order bits — measured ~1e-5 on the smoke
# preset; the gate leaves headroom without ever accepting a divergence
ELASTIC_RTOL = 0.05


def _elastic_continuity(golden_rows, rows, steps, shrink_step,
                        rtol=ELASTIC_RTOL, label="elastic drill"):
    """Gate a drill's stitched loss CSV against its same-seed golden:
    bit-exact through the last step before the configuration first
    changed (``shrink_step``), within ``rtol`` relative after it, exact
    step sequence throughout. Returns ``(info, violations)``. Shared by
    the elastic topology drill and the bucket flag-flip drill — both
    change a trajectory-preserving knob mid-run and owe the same
    exact-then-tolerance continuity shape."""
    violations = []
    info = {"rows": len(rows), "bitexact_rows": 0, "max_rel_diff": 0.0,
            "shrink_step": shrink_step, "rtol": rtol}
    if len(rows) != steps + 1 or len(golden_rows) != steps + 1:
        violations.append(
            f"{label}: {len(rows)} stitched rows vs "
            f"{len(golden_rows)} golden (want {steps + 1})"
        )
        return info, violations
    if rows[0] != golden_rows[0]:
        violations.append(f"{label}: CSV headers differ")
        return info, violations
    for i, (g, r) in enumerate(zip(golden_rows[1:], rows[1:]), start=1):
        try:
            gs, gl = g.split(",")
            rs, rl = r.split(",")
            gs, rs, gl, rl = int(gs), int(rs), float(gl), float(rl)
        except ValueError:
            violations.append(
                f"{label}: unparseable CSV row {i}: {g!r} vs {r!r}"
            )
            return info, violations
        if gs != i or rs != i:
            violations.append(
                f"{label}: step sequence broken at row {i}: "
                f"golden step {gs}, stitched step {rs}"
            )
            return info, violations
        if i <= shrink_step:
            # same configuration, same seed, deterministic CPU: any
            # drift here means the resume machinery, not float noise
            if g != r:
                violations.append(
                    f"{label}: pre-flip row {i} not bit-exact: "
                    f"{g!r} vs {r!r}"
                )
                return info, violations
            info["bitexact_rows"] = i
        else:
            rel = abs(rl - gl) / max(abs(gl), 1e-12)
            info["max_rel_diff"] = max(info["max_rel_diff"], rel)
            if rel > rtol:
                violations.append(
                    f"{label}: loss diverged at step {i}: golden "
                    f"{gl} vs stitched {rl} (rel {rel:.5f} > {rtol})"
                )
                return info, violations
    info["max_rel_diff"] = round(info["max_rel_diff"], 8)
    return info, violations


def run_soak(preset_name="smoke", seed=0, workdir=None, json_out=None):
    """Run the kill/corrupt/resume soak. Returns the report dict
    (``report["ok"]`` is the gate verdict)."""
    preset = PRESETS[preset_name]
    owns_workdir = workdir is None
    workdir = Path(workdir or tempfile.mkdtemp(prefix="pyrecover_chaos_"))
    workdir.mkdir(parents=True, exist_ok=True)
    log_path = workdir / "chaos_runs.log"
    s1, s2 = _schedule(preset, seed)
    steps = preset["training_steps"]
    timeout = preset["run_timeout_s"]
    violations = []
    cycles = []

    def cycle(name, *, fault_plan, resume, expect_rc, exp="chaos",
              extra_args=(), device_count=None, sync_ckpt=True,
              preset_over=None):
        cmd = _trainer_cmd(preset_over or preset, exp, seed, workdir,
                           resume=resume, extra_args=extra_args,
                           sync_ckpt=sync_ckpt)
        try:
            rc, secs = _run_trainer(
                cmd, fault_plan=fault_plan, log_path=log_path,
                timeout_s=timeout, device_count=device_count,
            )
        except subprocess.TimeoutExpired:
            rc, secs = "timeout", timeout
        ok = rc in expect_rc
        if not ok:
            violations.append(
                f"cycle {name}: exit code {rc}, expected one of {expect_rc}"
            )
        cycles.append({"name": name, "rc": rc, "seconds": secs, "ok": ok,
                       "faults": (fault_plan or {}).get("faults", [])})
        return ok

    # golden: the uninterrupted reference curve, same seed, own exp dir
    cycle("golden", fault_plan=None, resume=False, expect_rc=(0,),
          exp="golden")

    # cycle 1 — graceful preemption drill: SIGTERM as step s1 begins
    cycle("sigterm", resume=False, expect_rc=(0,), fault_plan={
        "seed": seed,
        "faults": [{"type": "sigterm_at_step", "step": s1}],
    })

    # cycle 2 — hard kill mid-save: SIGKILL inside the first periodic
    # checkpoint write of the resumed run (rc is -SIGKILL)
    cycle("kill9_during_save", resume=True, expect_rc=(-9, 137),
          fault_plan={
              "seed": seed,
              "faults": [{"type": "kill9_during_save", "save_index": 1}],
          })

    # cycle 3 — transient EIO under the writer (absorbed by retry), then
    # SIGTERM at s2 and the final checkpoint's committed bytes flipped
    cycle("transient_io+corrupt", resume=True, expect_rc=(0,), fault_plan={
        "seed": seed,
        "faults": [
            {"type": "transient_io_error", "op": "write", "fail_count": 2},
            {"type": "sigterm_at_step", "step": s2},
            {"type": "corrupt_ckpt_bytes", "save_index": 2, "count": 64},
        ],
    })

    # cycle 4 — recovery run: must quarantine the corrupt checkpoint,
    # fall back to the newest good one, and finish the full step budget
    cycle("recover_and_finish", resume=True, expect_rc=(0,),
          fault_plan=None)

    # cycle 5 — hang drill (own exp dir; continuity gates untouched): a
    # seeded loader_stall wedges one producer worker long past the
    # run-health watchdog's window. The run must NOT die — the watchdog's
    # contract is forensics, never a kill — but hang_detected must fire, a
    # postmortem bundle must land in .postmortem/, and doctor must read
    # the artifacts as a hang wedged in the loader_wait phase. The stall
    # hits producer batch 9: the prefetch pipeline materializes ~6 batches
    # ahead, so the sleep starts AFTER first-step compile (the watchdog
    # only arms post-compile) and the window has stall time to measure.
    cycle("hang_watchdog", resume=False, expect_rc=(0,), exp="hang",
          extra_args=("--hang-watchdog-timeout", "5"),
          fault_plan={
              "seed": seed,
              "faults": [
                  {"type": "loader_stall", "seconds": 20.0, "batch": 9},
              ],
          })

    # cycles 6-9 — elastic_shrink drill (own exp dirs; the main continuity
    # gates are untouched): a golden run on a 4-device virtual mesh, then
    # kill at 4 devices → resume on 2 (the elastic reshard path) → grow
    # back to 4 and finish. The stitched loss CSV is gated against the
    # 4-device golden: BIT-EXACT up to the first kill (same topology, same
    # seed), tolerance-aware after it (a different replica count changes
    # the cross-device reduction order and per-replica batch composition,
    # which perturbs the float trajectory without breaking continuity).
    cycle("elastic_golden", resume=False, expect_rc=(0,),
          exp="elastic_golden", fault_plan=None, device_count=4)
    cycle("elastic_kill@4dev", resume=False, expect_rc=(0,), exp="elastic",
          device_count=4, fault_plan={
              "seed": seed,
              "faults": [{"type": "sigterm_at_step", "step": s1}],
          })
    cycle("elastic_shrink@2dev", resume=True, expect_rc=(0,), exp="elastic",
          device_count=2, fault_plan={
              "seed": seed,
              "faults": [{"type": "sigterm_at_step", "step": s2}],
          })
    cycle("elastic_regrow@4dev", resume=True, expect_rc=(0,), exp="elastic",
          device_count=4, fault_plan=None)

    # cycles 10-15 — zerostall drill (own exp dirs): the async snapshot
    # pipeline killed at EVERY stage. A golden async-zerostall run, a
    # SIGTERM at s1 to seed a resumable manifest, then SIGKILL during the
    # device→host snapshot, during a chunk-store write, and in the gap
    # between durable chunks and the manifest rename — each torn save must
    # leave the previous manifest as the newest restorable checkpoint —
    # and a recovery run that finishes. Gated below on bit-exact stitched
    # loss vs the zerostall golden, zero quarantines (a torn zerostall
    # save never publishes anything to quarantine), and zero leaked
    # chunks after the final GC.
    zs_args = ("--checkpoint-engine", "zerostall")
    cycle("zs_golden", resume=False, expect_rc=(0,), exp="zs_golden",
          fault_plan=None, extra_args=zs_args, sync_ckpt=False)
    cycle("zs_sigterm", resume=False, expect_rc=(0,), exp="zs",
          extra_args=zs_args, sync_ckpt=False, fault_plan={
              "seed": seed,
              "faults": [{"type": "sigterm_at_step", "step": s1}],
          })
    for stage in ("ckpt_snapshot", "ckpt_chunk_write",
                  "ckpt_manifest_commit"):
        cycle(f"zs_kill@{stage}", resume=True, expect_rc=(-9, 137),
              exp="zs", extra_args=zs_args, sync_ckpt=False, fault_plan={
                  "seed": seed,
                  "faults": [{"type": "kill9_during_save",
                              "save_index": 1, "site": stage}],
              })
    cycle("zs_recover", resume=True, expect_rc=(0,), exp="zs",
          extra_args=zs_args, sync_ckpt=False, fault_plan=None)

    # cycles 16-18 — zero1 flag-flip drill (own exp dirs, pinned to a
    # 2-device virtual mesh so the data axis is real): a golden run with
    # --optimizer-sharding zero1 throughout, a zero1 run killed at s1,
    # then a resume with the flag FLIPPED back to none. Because zero1 is
    # bit-exact vs none at the same topology (the decomposed update is
    # semantically the replicated update), the stitched CSV must match
    # the zero1 golden BIT-EXACTLY even across the flag flip — proving
    # both the numerics claim and that a zero1 checkpoint restores onto
    # a none run (spec-only drift) without quarantine.
    z1_args = ("--optimizer-sharding", "zero1")
    cycle("z1_golden", resume=False, expect_rc=(0,), exp="z1_golden",
          fault_plan=None, extra_args=z1_args, device_count=2)
    cycle("z1_kill@zero1", resume=False, expect_rc=(0,), exp="z1",
          device_count=2, extra_args=z1_args, fault_plan={
              "seed": seed,
              "faults": [{"type": "sigterm_at_step", "step": s1}],
          })
    cycle("z1_flip_resume@none", resume=True, expect_rc=(0,), exp="z1",
          device_count=2, fault_plan=None)

    # cycles 19-24 — gradient-bucket flag-flip drills (own exp dirs,
    # 2-device mesh so the data axis — and the per-bucket collectives —
    # are real). (a) int8+buckets killed at s1, resumed with buckets
    # OFF: the error-feedback residual's shape is layout-independent,
    # so the flip is spec-only drift and must restore without
    # quarantine; the post-flip curve re-blocks the quantization
    # groups, so the gate is bit-exact-then-tolerance (like elastic).
    # (b) bucketed fp32 killed at s1, resumed with a DIFFERENT bucket
    # cap: per-bucket fp32 psums are exact elementwise sums, so the
    # whole stitched curve must match the bucketed golden BIT-EXACTLY.
    bk_args = ("--grad-allreduce", "int8", "--grad-bucket-mb", "0.05")
    cycle("bk_golden", resume=False, expect_rc=(0,), exp="bk_golden",
          fault_plan=None, extra_args=bk_args, device_count=2)
    cycle("bk_kill@int8+buckets", resume=False, expect_rc=(0,), exp="bk",
          device_count=2, extra_args=bk_args, fault_plan={
              "seed": seed,
              "faults": [{"type": "sigterm_at_step", "step": s1}],
          })
    cycle("bk_flip_resume@nobuckets", resume=True, expect_rc=(0,),
          exp="bk", device_count=2,
          extra_args=("--grad-allreduce", "int8"), fault_plan=None)
    bkf_args = ("--grad-bucket-mb", "0.05")
    cycle("bkf_golden", resume=False, expect_rc=(0,), exp="bkf_golden",
          fault_plan=None, extra_args=bkf_args, device_count=2)
    cycle("bkf_kill@fp32+buckets", resume=False, expect_rc=(0,), exp="bkf",
          device_count=2, extra_args=bkf_args, fault_plan={
              "seed": seed,
              "faults": [{"type": "sigterm_at_step", "step": s1}],
          })
    cycle("bkf_flip_resume@newlayout", resume=True, expect_rc=(0,),
          exp="bkf", device_count=2,
          extra_args=("--grad-bucket-mb", "0.2"), fault_plan=None)

    # cycles 25+ — goodput-autopilot drill (own exp dirs): the closed loop
    # measurement → failure model → Young–Daly policy → actuation, proven
    # against a seeded hazard-rate kill schedule whose rate SHIFTS mid-run
    # (rate AP_RATE for global steps < AP_SHIFT, zero after — maintenance
    # ended). A golden run with --checkpoint-frequency auto and no faults
    # pins the graceful zero-failure posture (bounded prior, never
    # thrashes, never disables saves); the faulted run is resumed until it
    # finishes, and the gates below assert the ckpt_policy decision trail
    # survives every kill via the failure-history sidecar and lands within
    # 2× of the analytic optimum on both sides of the shift. Liveness is
    # by construction: grace_steps (13) > the interval ceiling (12), so
    # every cycle commits at least one new save before it can die and the
    # resume point advances monotonically.
    ap_preset = dict(preset, training_steps=AP_STEPS,
                     checkpoint_frequency="auto")
    ap_flags = (
        "--ckpt-auto-floor", "1", "--ckpt-auto-ceiling", str(AP_CEILING),
        "--ckpt-auto-window", "4",
    )
    ap_plan = {
        "seed": seed,
        "faults": [{
            "type": "random_sigkill", "rate_per_step": AP_RATE,
            "seed": seed * 1000 + 17, "grace_steps": AP_GRACE,
            "start_step": 0, "end_step": AP_SHIFT,
        }],
    }
    cycle("ap_golden", resume=False, expect_rc=(0,), exp="ap_golden",
          fault_plan=None, extra_args=ap_flags, preset_over=ap_preset)
    ap_kills = 0
    ap_done = False
    for attempt in range(AP_MAX_ATTEMPTS):
        cycle(f"ap_run{attempt + 1}", resume=attempt > 0,
              expect_rc=(0, -9, 137), exp="ap", extra_args=ap_flags,
              fault_plan=ap_plan, preset_over=ap_preset)
        rc = cycles[-1]["rc"]
        if rc == 0:
            ap_done = True
            break
        if rc in (-9, 137):
            ap_kills += 1
        else:
            break  # the unexpected rc is already a cycle violation
    if not ap_done:
        violations.append(
            f"autopilot drill: no clean finish within {AP_MAX_ATTEMPTS} "
            f"resume attempts ({ap_kills} kills observed)"
        )

    exp_dir = workdir / "chaos"
    golden_rows = _read_csv_rows(
        workdir / "golden" / "golden_loss_log.csv"
    )
    stitched_rows = _read_csv_rows(exp_dir / "chaos_loss_log.csv")
    first_divergence = None
    for i, (a, b) in enumerate(zip(golden_rows, stitched_rows)):
        if a != b:
            first_divergence = {"row": i, "golden": a, "stitched": b}
            break
    continuity_ok = (
        first_divergence is None
        and len(golden_rows) == len(stitched_rows)
        and len(golden_rows) == steps + 1  # header + every step
    )
    if not continuity_ok:
        violations.append(
            "loss continuity broken: "
            + (json.dumps(first_divergence) if first_divergence else
               f"{len(stitched_rows)} stitched rows vs "
               f"{len(golden_rows)} golden (want {steps + 1})")
        )

    if not (exp_dir / "DONE").exists():
        violations.append("no DONE marker after the recovery cycle")

    quarantined = [p.name for p in list_quarantined(exp_dir)]
    # zero lost checkpoints: exactly the one injected corruption is
    # quarantined — anything else means recovery ate a good checkpoint
    if len(quarantined) != 1:
        violations.append(
            f"expected exactly the injected corruption quarantined, got "
            f"{quarantined}"
        )
    elif not quarantined[0].startswith(f"ckpt_{s2}_final"):
        violations.append(
            f"quarantined {quarantined[0]}, expected ckpt_{s2}_final*"
        )

    # read_events merges rotated shards; the fault/recovery trail must
    # survive rotation intact
    events = read_events(exp_dir / "chaos_telemetry.jsonl")
    counts = {}
    for e in events:
        counts[e["event"]] = counts.get(e["event"], 0) + 1
    for required in ("ckpt_io_retry", "ckpt_quarantined", "fault_injected",
                     "ckpt_precheck_failed"):
        if not counts.get(required):
            violations.append(f"no {required} telemetry event recorded")

    # rotation gate: the byte cap set in _run_trainer must actually have
    # rotated the live shard at least once across the kill/resume cycles —
    # otherwise the soak stopped exercising the rotation path
    rotated = len(list(exp_dir.glob("chaos_telemetry.jsonl.*")))
    if os.environ.get("PYRECOVER_TELEMETRY_MAX_BYTES") is None and not rotated:
        violations.append(
            "telemetry JSONL never rotated despite the soak's byte cap"
        )

    # hang drill verdicts: watchdog fired, bundle landed, doctor reads it
    hang_dir = workdir / "hang"
    hang_events = read_events(hang_dir / "hang_telemetry.jsonl")
    hang_hits = [e for e in hang_events if e["event"] == "hang_detected"]
    if not hang_hits:
        violations.append(
            "hang drill: no hang_detected event despite a 20s loader stall "
            "against a 5s watchdog window"
        )
    hang_bundles = flight.list_bundles(hang_dir)
    if not hang_bundles:
        violations.append("hang drill: no postmortem bundle in .postmortem/")
    hang_doctor = doctor_mod.diagnose(hang_dir)
    if hang_doctor["classification"] != "hang":
        violations.append(
            "hang drill: doctor classified "
            f"{hang_doctor['classification']!r}, expected 'hang'"
        )
    elif hang_doctor.get("phase") != "loader_wait":
        violations.append(
            "hang drill: doctor named phase "
            f"{hang_doctor.get('phase')!r}, expected 'loader_wait'"
        )
    if not any(e["event"] == "flight_dump" for e in hang_events):
        violations.append(
            "hang drill: no flight_dump event in the telemetry stream"
        )

    # elastic drill verdicts: stitched-vs-golden continuity (bit-exact
    # before the shrink, tolerance-aware after), the 4→2 and 2→4
    # elastic_resume transitions with their sampler rescales in the
    # telemetry trail, a DONE marker, and a healthy doctor verdict
    elastic_dir = workdir / "elastic"
    elastic_info, e_viol = _elastic_continuity(
        _read_csv_rows(
            workdir / "elastic_golden" / "elastic_golden_loss_log.csv"
        ),
        _read_csv_rows(elastic_dir / "elastic_loss_log.csv"),
        steps, s1,
    )
    violations += e_viol
    if not (elastic_dir / "DONE").exists():
        violations.append(
            "elastic drill: no DONE marker after the regrow cycle"
        )
    e_events = read_events(elastic_dir / "elastic_telemetry.jsonl")
    transitions = [
        ((e.get("saved_topology") or {}).get("devices"),
         (e.get("target_topology") or {}).get("devices"))
        for e in e_events if e["event"] == "elastic_resume"
    ]
    elastic_info["transitions"] = transitions
    if (4, 2) not in transitions or (2, 4) not in transitions:
        violations.append(
            "elastic drill: expected 4→2 and 2→4 elastic_resume "
            f"transitions in telemetry, got {transitions}"
        )
    if not any(e["event"] == "sampler_rescaled" for e in e_events):
        violations.append(
            "elastic drill: no sampler_rescaled telemetry event"
        )
    e_doctor = doctor_mod.diagnose(elastic_dir)
    elastic_info["doctor_classification"] = e_doctor["classification"]
    if e_doctor["classification"] != "healthy":
        violations.append(
            "elastic drill: doctor classified "
            f"{e_doctor['classification']!r}, expected 'healthy'"
        )

    # zerostall drill verdicts: stitched-vs-golden bit-exactness, DONE
    # marker, the kill trail at every pipeline stage, no quarantines (a
    # torn zerostall save publishes nothing), and ZERO chunk leakage —
    # after the recovery run's GC the chunk store holds exactly the
    # chunks the live manifests reference
    from pyrecover_tpu.checkpoint.zerostall import chunkstore as zs_chunks

    zs_dir = workdir / "zs"
    zs_golden_rows = _read_csv_rows(
        workdir / "zs_golden" / "zs_golden_loss_log.csv"
    )
    zs_rows = _read_csv_rows(zs_dir / "zs_loss_log.csv")
    zs_divergence = None
    for i, (a, b) in enumerate(zip(zs_golden_rows, zs_rows)):
        if a != b:
            zs_divergence = {"row": i, "golden": a, "stitched": b}
            break
    zs_continuity = (
        zs_divergence is None
        and len(zs_rows) == len(zs_golden_rows) == steps + 1
    )
    if not zs_continuity:
        violations.append(
            "zerostall drill: loss continuity broken: "
            + (json.dumps(zs_divergence) if zs_divergence else
               f"{len(zs_rows)} stitched rows vs {len(zs_golden_rows)} "
               f"golden (want {steps + 1})")
        )
    if not (zs_dir / "DONE").exists():
        violations.append(
            "zerostall drill: no DONE marker after the recovery cycle"
        )
    zs_quarantined = [p.name for p in list_quarantined(zs_dir)]
    if zs_quarantined:
        violations.append(
            "zerostall drill: a torn save must publish nothing, but "
            f"{zs_quarantined} got quarantined"
        )
    zs_events = read_events(zs_dir / "zs_telemetry.jsonl")
    zs_kill_sites = {
        e.get("site") for e in zs_events
        if e["event"] == "fault_injected"
        and e.get("type") == "kill9_during_save"
    }
    for stage in ("ckpt_snapshot", "ckpt_chunk_write",
                  "ckpt_manifest_commit"):
        if stage not in zs_kill_sites:
            violations.append(
                f"zerostall drill: no kill9_during_save fired at {stage}"
            )
    zs_resumes = [e for e in zs_events if e["event"] == "resume"]
    if len(zs_resumes) < 4:
        violations.append(
            f"zerostall drill: expected >=4 resume events (one per kill "
            f"cycle + recovery), got {len(zs_resumes)}"
        )
    referenced = zs_chunks.referenced_digests(zs_dir)
    on_disk = {
        p.name for p in zs_chunks.chunks_root(zs_dir).rglob("*")
        if p.is_file()
    }
    leaked = sorted(on_disk - referenced)
    missing = sorted(referenced - on_disk)
    if leaked:
        violations.append(
            f"zerostall drill: {len(leaked)} chunk(s) leaked past GC "
            f"(e.g. {leaked[:3]})"
        )
    if missing:
        violations.append(
            f"zerostall drill: {len(missing)} referenced chunk(s) missing "
            f"from the store (e.g. {missing[:3]}) — live manifests are "
            "not restorable"
        )
    # zero1 flag-flip drill verdicts: the stitched CSV (zero1 segment +
    # post-flip none segment) must be BIT-EXACT against the zero1 golden
    # — the convergence-parity contract of the bandwidth-lean update
    # path — and the flip must restore without quarantining (the zero1
    # checkpoint differs from the none run's schema only in partition
    # specs, SC10, a warning)
    z1_dir = workdir / "z1"
    z1_golden_rows = _read_csv_rows(
        workdir / "z1_golden" / "z1_golden_loss_log.csv"
    )
    z1_rows = _read_csv_rows(z1_dir / "z1_loss_log.csv")
    z1_divergence = None
    for i, (a, b) in enumerate(zip(z1_golden_rows, z1_rows)):
        if a != b:
            z1_divergence = {"row": i, "golden": a, "stitched": b}
            break
    z1_continuity = (
        z1_divergence is None
        and len(z1_rows) == len(z1_golden_rows) == steps + 1
    )
    if not z1_continuity:
        violations.append(
            "zero1 drill: flag-flip loss continuity broken: "
            + (json.dumps(z1_divergence) if z1_divergence else
               f"{len(z1_rows)} stitched rows vs {len(z1_golden_rows)} "
               f"golden (want {steps + 1})")
        )
    if not (z1_dir / "DONE").exists():
        violations.append(
            "zero1 drill: no DONE marker after the flag-flip resume"
        )
    z1_quarantined = [p.name for p in list_quarantined(z1_dir)]
    if z1_quarantined:
        violations.append(
            "zero1 drill: the flag flip must restore the zero1 checkpoint "
            f"intact, but {z1_quarantined} got quarantined"
        )
    z1_events = read_events(z1_dir / "z1_telemetry.jsonl")
    if not any(e["event"] == "resume" for e in z1_events):
        violations.append("zero1 drill: no resume event after the kill")
    z1_info = {
        "rows": len(z1_rows),
        "continuity_ok": z1_continuity,
        "bitexact": z1_divergence is None,
        "quarantined": z1_quarantined,
        "resumes": sum(1 for e in z1_events if e["event"] == "resume"),
    }

    # bucket flag-flip drill verdicts. (a) int8: bit-exact before the
    # flip, tolerance after (the re-blocked quantization groups change
    # low bits), residual restores without quarantine, the grad_bucket
    # telemetry record shows the bucketed layout. (b) fp32 layout flip:
    # BIT-EXACT stitched CSV against the bucketed golden end to end.
    bk_dir = workdir / "bk"
    bk_info, bk_viol = _elastic_continuity(
        _read_csv_rows(workdir / "bk_golden" / "bk_golden_loss_log.csv"),
        _read_csv_rows(bk_dir / "bk_loss_log.csv"),
        steps, s1, label="bucket drill (int8)",
    )
    violations += bk_viol
    if not (bk_dir / "DONE").exists():
        violations.append(
            "bucket drill (int8): no DONE marker after the flip resume"
        )
    bk_quarantined = [p.name for p in list_quarantined(bk_dir)]
    if bk_quarantined:
        violations.append(
            "bucket drill (int8): the buckets-off flip must restore the "
            f"bucketed-int8 checkpoint intact, but {bk_quarantined} got "
            "quarantined"
        )
    bk_events = read_events(bk_dir / "bk_telemetry.jsonl")
    if not any(e["event"] == "resume" for e in bk_events):
        violations.append("bucket drill (int8): no resume event")
    bk_buckets = [e for e in bk_events if e["event"] == "grad_bucket"]
    if not any(e.get("buckets", 0) >= 2 for e in bk_buckets):
        violations.append(
            "bucket drill (int8): no grad_bucket record with a real "
            "(>= 2 bucket) layout — the drill never ran bucketed"
        )
    bk_info["quarantined"] = bk_quarantined
    bk_info["grad_bucket_events"] = len(bk_buckets)

    bkf_dir = workdir / "bkf"
    bkf_golden_rows = _read_csv_rows(
        workdir / "bkf_golden" / "bkf_golden_loss_log.csv"
    )
    bkf_rows = _read_csv_rows(bkf_dir / "bkf_loss_log.csv")
    bkf_divergence = None
    for i, (a, b) in enumerate(zip(bkf_golden_rows, bkf_rows)):
        if a != b:
            bkf_divergence = {"row": i, "golden": a, "stitched": b}
            break
    bkf_continuity = (
        bkf_divergence is None
        and len(bkf_rows) == len(bkf_golden_rows) == steps + 1
    )
    if not bkf_continuity:
        violations.append(
            "bucket drill (fp32): layout-flip loss continuity broken "
            "(per-bucket fp32 psums are exact sums — any drift is a "
            "bug): "
            + (json.dumps(bkf_divergence) if bkf_divergence else
               f"{len(bkf_rows)} stitched rows vs {len(bkf_golden_rows)} "
               f"golden (want {steps + 1})")
        )
    if not (bkf_dir / "DONE").exists():
        violations.append(
            "bucket drill (fp32): no DONE marker after the layout-flip "
            "resume"
        )
    bkf_quarantined = [p.name for p in list_quarantined(bkf_dir)]
    if bkf_quarantined:
        violations.append(
            "bucket drill (fp32): the layout flip must restore intact, "
            f"but {bkf_quarantined} got quarantined"
        )
    bucket_info = {
        "int8": bk_info,
        "fp32_layout_flip": {
            "rows": len(bkf_rows),
            "bitexact": bkf_divergence is None,
            "continuity_ok": bkf_continuity,
            "quarantined": bkf_quarantined,
        },
    }

    # autopilot drill verdicts: (a) the golden auto run degrades to the
    # bounded prior with zero failures — every decision at the ceiling,
    # one constant interval (never thrashes), periodic saves actually
    # taken (never disables); (b) the faulted run's decision trail spans
    # the kill/resume chain, the failure-history sidecar counts EXACTLY
    # the observed kills, and the adapted interval lands within
    # AP_CONVERGENCE_FACTOR of the clamp-bounded analytic Young–Daly
    # optimum recomputed from each decision's own reported inputs on BOTH
    # sides of the rate shift; (c) no checkpoints were quarantined (a
    # hazard kill must never eat a committed save).
    import math as _math

    ap_dir = workdir / "ap"
    ap_golden_events = read_events(
        workdir / "ap_golden" / "ap_golden_telemetry.jsonl"
    )
    ap_g_policies = [
        e for e in ap_golden_events if e["event"] == "ckpt_policy"
    ]
    ap_g_intervals = sorted({e.get("interval_steps") for e in ap_g_policies})
    ap_g_saves = [
        e["step"] for e in ap_golden_events
        if e["event"] == "ckpt_saved" and not e.get("final")
    ]
    if not ap_g_policies:
        violations.append("autopilot drill: golden auto run emitted no "
                          "ckpt_policy decisions")
    else:
        if any(e.get("failures_observed") for e in ap_g_policies):
            violations.append(
                "autopilot drill: golden run reported nonzero failures"
            )
        if ap_g_intervals != [AP_CEILING]:
            violations.append(
                "autopilot drill: zero-failure run must hold the bounded "
                f"prior (one constant interval {AP_CEILING}), got "
                f"{ap_g_intervals}"
            )
        expected_saves = list(range(AP_CEILING, AP_STEPS, AP_CEILING))
        if ap_g_saves != expected_saves:
            violations.append(
                "autopilot drill: zero-failure run must keep saving at "
                f"the prior cadence {expected_saves}, got {ap_g_saves}"
            )

    ap_events = read_events(ap_dir / "ap_telemetry.jsonl")
    ap_policies = [e for e in ap_events if e["event"] == "ckpt_policy"]
    ap_fault_kills = sum(
        1 for e in ap_events
        if e["event"] == "fault_injected" and e.get("type") == "random_sigkill"
    )
    ap_segments = 0
    ap_segments_with_policy = 0
    seg_has = False
    for e in ap_events:
        if e["event"] == "run_start":
            ap_segments += 1
            if seg_has:
                ap_segments_with_policy += 1
            seg_has = False
        elif e["event"] == "ckpt_policy":
            seg_has = True
    if seg_has:
        ap_segments_with_policy += 1
    if ap_kills < 2:
        violations.append(
            f"autopilot drill: expected >= 2 seeded kills before the rate "
            f"shift, got {ap_kills}"
        )
    if ap_fault_kills != ap_kills:
        violations.append(
            f"autopilot drill: {ap_kills} kill exits but {ap_fault_kills} "
            "random_sigkill fault_injected events — the announce-then-kill "
            "trail is torn"
        )
    if ap_segments_with_policy < ap_kills + 1:
        violations.append(
            "autopilot drill: ckpt_policy decisions must appear in every "
            f"run segment ({ap_segments} segments, only "
            f"{ap_segments_with_policy} carried decisions)"
        )
    sidecar_path = ap_dir / "failure_history.json"
    sidecar_interruptions = None
    try:
        sidecar = json.loads(sidecar_path.read_text())
        sidecar_interruptions = [
            r.get("kind") for r in sidecar.get("interruptions", [])
        ]
    except (OSError, ValueError):
        violations.append(
            "autopilot drill: failure-history sidecar missing/unreadable "
            f"at {sidecar_path}"
        )
    if sidecar_interruptions is not None and (
        len(sidecar_interruptions) != ap_kills
        or any(k != "hard_kill" for k in sidecar_interruptions)
    ):
        violations.append(
            f"autopilot drill: sidecar recorded {sidecar_interruptions}, "
            f"expected exactly {ap_kills} hard_kill interruption(s) — the "
            "resume-chain reconstruction lost or double-counted a death"
        )

    def _ap_convergence(decision, label):
        cost = decision.get("cost_s")
        mtti = decision.get("mtti_s")
        iter_s = decision.get("step_iter_s")
        chosen = decision.get("interval_steps")
        if not all(
            isinstance(v, (int, float)) and v > 0
            for v in (cost, mtti, iter_s, chosen)
        ):
            violations.append(
                f"autopilot drill: {label} decision carries unusable "
                f"inputs: {decision}"
            )
            return None
        analytic = _math.sqrt(2.0 * cost * mtti) / iter_s
        clamped = min(max(analytic, 1.0), float(AP_CEILING))
        ratio = chosen / clamped
        if not (1.0 / AP_CONVERGENCE_FACTOR <= ratio <= AP_CONVERGENCE_FACTOR):
            violations.append(
                f"autopilot drill: {label} interval {chosen} is {ratio:.2f}x "
                f"the bound-clamped analytic optimum {clamped:.2f} "
                f"(raw {analytic:.2f}; cost {cost}s, MTTI {mtti}s, "
                f"step {iter_s}s) — outside {AP_CONVERGENCE_FACTOR}x"
            )
        return {"chosen": chosen, "analytic": round(analytic, 3),
                "clamped": round(clamped, 3), "ratio": round(ratio, 3)}

    pre_shift = [e for e in ap_policies if e.get("step", 0) < AP_SHIFT
                 and e.get("failures_observed", 0) > 0]
    post_shift = [e for e in ap_policies if e.get("step", 0) >= AP_SHIFT]
    ap_pre = ap_post = None
    if not pre_shift:
        violations.append(
            "autopilot drill: no failure-informed ckpt_policy decision "
            "before the rate shift"
        )
    else:
        ap_pre = _ap_convergence(pre_shift[-1], "pre-shift")
    if not post_shift:
        violations.append(
            "autopilot drill: no ckpt_policy decision after the rate shift"
        )
    else:
        ap_post = _ap_convergence(post_shift[-1], "post-shift")
    if pre_shift and post_shift:
        # the hazard dropped to zero at the shift: the windowed MTTI can
        # only grow from there, so the adapted interval must never come
        # back DOWN after the last pre-shift decision
        if post_shift[-1].get("interval_steps", 0) < pre_shift[-1].get(
            "interval_steps", 0
        ):
            violations.append(
                "autopilot drill: interval shrank after the failure rate "
                f"dropped to zero ({pre_shift[-1].get('interval_steps')} "
                f"-> {post_shift[-1].get('interval_steps')})"
            )
    if not (ap_dir / "DONE").exists():
        violations.append("autopilot drill: no DONE marker after recovery")
    ap_quarantined = [p.name for p in list_quarantined(ap_dir)]
    if ap_quarantined:
        violations.append(
            "autopilot drill: a hazard kill must never eat a committed "
            f"save, but {ap_quarantined} got quarantined"
        )
    ap_info = {
        "kills": ap_kills,
        "attempts": sum(1 for c in cycles if c["name"].startswith("ap_run")),
        "decisions": len(ap_policies),
        "segments": ap_segments,
        "segments_with_decisions": ap_segments_with_policy,
        "sidecar_interruptions": sidecar_interruptions,
        "pre_shift": ap_pre,
        "post_shift": ap_post,
        "golden_intervals": ap_g_intervals,
        "golden_saves": ap_g_saves,
        "interval_trajectory": [
            e.get("interval_steps") for e in ap_policies
        ],
        "quarantined": ap_quarantined,
    }

    zs_info = {
        "rows": len(zs_rows),
        "continuity_ok": zs_continuity,
        "kill_sites": sorted(s for s in zs_kill_sites if s),
        "resumes": len(zs_resumes),
        "chunks_on_disk": len(on_disk),
        "chunks_referenced": len(referenced),
        "chunks_leaked": len(leaked),
        "backpressure_events": sum(
            1 for e in zs_events if e["event"] == "ckpt_backpressure"
        ),
    }

    report = {
        "preset": preset_name,
        "seed": seed,
        "schedule": {"sigterm_step_1": s1, "sigterm_step_2": s2},
        "workdir": str(workdir),
        "cycles": cycles,
        "kill_resume_cycles": sum(
            1 for c in cycles if any(
                f["type"] in ("sigterm_at_step", "kill9_during_save")
                for f in c["faults"]
            )
        ),
        "continuity_ok": continuity_ok,
        "first_divergence": first_divergence,
        "rows": len(stitched_rows),
        "quarantined": quarantined,
        "hang": {
            "hang_detected": len(hang_hits),
            "bundles": [Path(b).name for b in hang_bundles],
            "doctor_classification": hang_doctor["classification"],
            "doctor_phase": hang_doctor.get("phase"),
        },
        "elastic": elastic_info,
        "zerostall": zs_info,
        "zero1": z1_info,
        "bucket": bucket_info,
        "autopilot": ap_info,
        "telemetry_rotated_shards": rotated,
        "telemetry_counts": {
            k: counts.get(k, 0)
            for k in ("fault_injected", "ckpt_io_retry", "ckpt_quarantined",
                      "ckpt_precheck_failed", "ckpt_pruned", "ckpt_saved",
                      "resume")
        },
        "violations": violations,
        "ok": not violations,
    }
    if json_out:
        Path(json_out).parent.mkdir(parents=True, exist_ok=True)
        # jaxlint: disable-next=torn-write -- CI report artifact, regenerated
        # every run; a torn report fails its consumer loudly and is simply
        # re-produced
        Path(json_out).write_text(json.dumps(report, indent=2))
    if report["ok"] and owns_workdir:
        shutil.rmtree(workdir, ignore_errors=True)
        report["workdir"] = None  # removed; the log died with it
    return report


def main(argv=None):
    p = argparse.ArgumentParser(
        description="pyrecover chaos soak: kill/corrupt/resume a real "
                    "trainer under a seeded fault plan and verify "
                    "bit-exact loss continuity",
    )
    p.add_argument("--preset", choices=sorted(PRESETS), default="smoke")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--workdir", default=None,
                   help="experiment directory (kept); default: a temp dir, "
                        "removed on success, kept on failure")
    p.add_argument("--json", default=os.environ.get(CHAOS_JSON_ENV) or None,
                   help=f"JSON report path (default ${CHAOS_JSON_ENV})")
    args = p.parse_args(argv)

    report = run_soak(
        args.preset, seed=args.seed, workdir=args.workdir,
        json_out=args.json,
    )
    for c in report["cycles"]:
        print(f"  cycle {c['name']:<22} rc={c['rc']!s:>4}  "
              f"{c['seconds']}s  {'ok' if c['ok'] else 'FAIL'}")
    print(f"  continuity: {'bit-exact' if report['continuity_ok'] else 'BROKEN'}"
          f" ({report['rows']} rows) | quarantined: {report['quarantined']}"
          f" | retries: {report['telemetry_counts']['ckpt_io_retry']}")
    el = report.get("elastic") or {}
    print(f"  elastic: transitions {el.get('transitions')} | "
          f"{el.get('bitexact_rows')} bit-exact rows, max rel diff "
          f"{el.get('max_rel_diff')} (tol {el.get('rtol')}) | doctor "
          f"{el.get('doctor_classification')}")
    zs = report.get("zerostall") or {}
    print(f"  zerostall: kills at {zs.get('kill_sites')} | "
          f"{zs.get('resumes')} resumes | chunks "
          f"{zs.get('chunks_on_disk')} on disk = "
          f"{zs.get('chunks_referenced')} referenced "
          f"({zs.get('chunks_leaked')} leaked)")
    z1 = report.get("zero1") or {}
    print(f"  zero1 flag-flip: "
          f"{'bit-exact' if z1.get('bitexact') else 'DIVERGED'} "
          f"({z1.get('rows')} rows) | {z1.get('resumes')} resumes | "
          f"quarantined: {z1.get('quarantined')}")
    bk = report.get("bucket") or {}
    bki, bkf = bk.get("int8") or {}, bk.get("fp32_layout_flip") or {}
    print(f"  bucket flag-flip: int8 {bki.get('bitexact_rows')} bit-exact "
          f"rows then max rel {bki.get('max_rel_diff')} "
          f"(tol {bki.get('rtol')}) | fp32 layout flip "
          f"{'bit-exact' if bkf.get('bitexact') else 'DIVERGED'} "
          f"({bkf.get('rows')} rows)")
    ap = report.get("autopilot") or {}
    pre, post = ap.get("pre_shift") or {}, ap.get("post_shift") or {}
    print(f"  autopilot: {ap.get('kills')} seeded kills over "
          f"{ap.get('attempts')} attempts | {ap.get('decisions')} decisions "
          f"across {ap.get('segments_with_decisions')} segments | interval "
          f"pre-shift {pre.get('chosen')} vs optimum {pre.get('clamped')} | "
          f"post-shift {post.get('chosen')} vs {post.get('clamped')} | "
          f"golden prior {ap.get('golden_intervals')}")
    if report["violations"]:
        for v in report["violations"]:
            print(f"  VIOLATION: {v}")
        print(f"chaos: FAIL (seed {report['seed']}, workdir kept at "
              f"{report['workdir']})")
        return 1
    print(f"chaos: OK — {report['kill_resume_cycles']} kill/resume cycles, "
          f"losses bit-exact vs golden (seed {report['seed']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
