"""chip_smoke.py: the CPU rehearsal runs end to end, and without the
rehearsal switch a CPU-only host gets a non-zero exit and the reason.

The rehearsal is the same legs (straight run, interrupted + resumed run,
the two equality tools, serving, the MoE step) at a toy width with the
kernel interpreted — it proves the script's control flow and checks here,
so a chip call is never spent debugging them.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"

sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402  (jax-free by contract: safe to import)


def run_smoke(args, env, cwd=REPO, script=SMOKE, timeout=840):
    return subprocess.run(
        [sys.executable, str(script), *args], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=timeout,
    )


def test_rehearsal_runs_every_leg_end_to_end(tmp_path):
    env = dict(os.environ)
    # two virtual devices: the legs shard their batch over a real mesh
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    proc = run_smoke(
        ["--rehearse-cpu", "--workdir", str(tmp_path / "work")], env
    )
    assert proc.returncode == 0, proc.stderr[-4000:] + proc.stdout[-2000:]
    lines = proc.stdout.strip().splitlines()
    # last line: the contract's result object, marked as a rehearsal
    assert json.loads(lines[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 2},
    }
    assert "REHEARSAL on CPU" in proc.stdout
    report = json.loads(lines[-2])
    legs = report["legs"]
    assert set(legs) == {"A", "B1", "B2", "A_vs_B", "C", "D"}
    assert report["rehearsal"] is True
    assert set(report["versions"]) == {"jax", "jaxlib", "libtpu"}
    assert legs["A_vs_B"]["check_equality_all_state"] == "equal"
    # the sharded engine's many bounded files, never one state-sized file
    assert 0 < report["disk"]["largest_file_bytes"] <= 128 * 1024 * 1024
    assert report["disk"]["files"] > 20
    assert legs["A"]["loss_last"] == legs["B2"]["loss_last"]
    assert legs["C"]["compiles_after_warmup"] == 0
    assert legs["C"]["requests"] == 10
    for name in ("A", "B1", "B2", "D"):
        assert legs[name]["compile_s"] > 0 and legs[name]["wall_s"] > 0
    # checkpoints are dropped as the legs finish; the evidence stays
    assert not list((tmp_path / "work").glob("*/ckpt_*"))
    assert (tmp_path / "work" / "legB" / "legB_telemetry.jsonl").exists()


def test_without_the_switch_a_cpu_host_fails_and_says_why():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYRECOVER_PALLAS_INTERPRET"] = "1"
    proc = run_smoke([], env, timeout=120)
    assert proc.returncode != 0
    assert "JAX_PLATFORMS=cpu" in proc.stderr
    assert "PYRECOVER_PALLAS_INTERPRET=1" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line on failure


def test_a_resolved_platform_other_than_tpu_fails(tmp_path, monkeypatch):
    """Past the environment gate: whatever the probe child reports, only
    ``tpu`` runs the legs — no CPU fallback, no shrink."""
    monkeypatch.setattr(
        chip_smoke, "probe_device",
        lambda env, workdir, deadline: {
            "platform": "cpu", "kind": "cpu", "count": 1, "jax": "x",
            "jaxlib": "x", "libtpu": None,
        },
    )
    monkeypatch.setattr(
        chip_smoke, "run_child",
        lambda *a, **k: pytest.fail("a leg ran on a non-TPU platform"),
    )
    with pytest.raises(chip_smoke.SmokeFailure, match="not 'tpu'"):
        chip_smoke.run_legs(
            tmp_path, rehearse=False, deadline=time.monotonic() + 60
        )


def test_children_never_see_interpret_mode_or_a_forced_cpu(monkeypatch):
    monkeypatch.setenv("PYRECOVER_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("JAX_PLATFORMS", " CPU ")
    env = chip_smoke.child_env(rehearse=False)
    assert "PYRECOVER_PALLAS_INTERPRET" not in env
    assert "JAX_PLATFORMS" not in env
    assert env["PYRECOVER_EXPECT_ACCELERATOR"] == "1"
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # the chip machine's
    assert chip_smoke.child_env(rehearse=False)["JAX_PLATFORMS"] == "tpu,cpu"


def test_the_script_alone_is_not_the_program(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy2(SMOKE, alone)
    # past the environment gate (other test modules export interpret mode)
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "PYRECOVER_PALLAS_INTERPRET")
    }
    proc = run_smoke([], env, cwd=tmp_path, script=alone, timeout=120)
    assert proc.returncode != 0
    assert "pyrecover_tpu/ is not beside this script" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_widths_are_the_presets_and_the_cache_dir_is_the_packages():
    from pyrecover_tpu.models import presets

    for name, width in chip_smoke.CHIP["width"].items():
        preset = presets.PRESETS[name]()
        assert {k: getattr(preset, k) for k in width} == width
    assert chip_smoke.DEFAULT_COMPILE_CACHE == REPO / ".jax_cache"
    for sizes in (chip_smoke.CHIP, chip_smoke.REHEARSAL):
        assert set(sizes["width"]) == set(sizes["shape"])
        for seq, batch, chunk in sizes["shape"].values():
            assert seq % chunk == 0 and batch in (4, 8)
        serve = sizes["serve"]
        assert len(serve["prompt_lens"]) == len(serve["max_new"])
        # some prompts outgrow one scheduler pass; all fit the context
        assert max(serve["prompt_lens"]) > serve["prefill_token_budget"]
        assert all(
            p + n <= sizes["shape"]["llama-1b"][0]
            for p, n in zip(serve["prompt_lens"], serve["max_new"])
        )


@pytest.mark.parametrize("script", [
    ["bench.py"],
    ["tools/bench_decode.py"],
    ["tools/bench_decode.py", "--serving"],
    ["tools/bench_flash_blocks.py"],
    ["tools/ckpt_d2h_probe.py"],
])
def test_timed_scripts_refuse_a_cpu(script):
    """A timed script measures the chip or nothing: on a CPU-only host it
    exits non-zero and prints no metric line (no fallback, no shrink)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, *script], env=env, cwd=REPO, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "resolved platform is cpu" in proc.stderr
    assert proc.stdout.strip() == ""
