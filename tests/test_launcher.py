"""L6 launcher integration: run_resilient.sh must finish a normal run (DONE)
and must survive a preemption → requeue → resume cycle driven by the
preemption-notice file. (The reference's launcher was only ever testable on
a real SLURM cluster; the marker-file protocol makes ours testable anywhere.)"""

import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow  # driver/cluster-scale suite; fast tier skips it

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "launch" / "run_resilient.sh"

BASE_FLAGS = [
    "--sequence-length", "32", "--batch-size", "8", "--training-samples", "64",
    "--model-dim", "64", "--model-layers", "2", "--model-heads", "4",
    "--model-kv-heads", "2", "--vocab-size", "128", "--logging-frequency", "100",
    "--checkpoint-frequency", "4", "--learning-rate", "1e-3",
]


def run_env(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHON"] = sys.executable
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["MAX_RESTARTS"] = "5"
    return env


def test_resilient_normal_completion(tmp_path):
    proc = subprocess.run(
        ["bash", str(SCRIPT), "--checkpoint-dir", str(tmp_path),
         "--experiment_name", "launch", "--training-steps", "4", *BASE_FLAGS],
        env=run_env(tmp_path), capture_output=True, text=True, timeout=300,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert (tmp_path / "launch" / "DONE").exists()


def test_resilient_preempt_resume_cycle(tmp_path):
    """Notice file present → run 1 stops early with a _final ckpt + REQUEUE;
    wrapper restarts with --resume-from-checkpoint=latest; once the notice
    clears, the resumed run completes to DONE."""
    notice = tmp_path / "preempt-notice"
    notice.write_text("evict")  # preemption already signalled at launch
    env = run_env(tmp_path)
    env["PYRECOVER_PREEMPT_FILE"] = str(notice)

    proc = subprocess.Popen(
        ["bash", str(SCRIPT), "--checkpoint-dir", str(tmp_path),
         "--experiment_name", "launch", "--training-steps", "8",
         "--timeaware-checkpointing", *BASE_FLAGS],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO,
    )
    exp = tmp_path / "launch"
    try:
        # wait for the first graceful stop
        deadline = time.time() + 180
        while time.time() < deadline and not (exp / "REQUEUE").exists():
            if proc.poll() is not None:
                break
            time.sleep(0.5)
        assert (exp / "REQUEUE").exists(), "first run never wrote REQUEUE"
        assert list(exp.glob("ckpt_*_final.ckpt")), "no final checkpoint saved"
        notice.unlink()  # platform says: eviction over
        out, _ = proc.communicate(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, out[-2000:]
    assert (exp / "DONE").exists()
    assert "resuming from latest" in out or "resume" in out.lower()
