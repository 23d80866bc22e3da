"""The harness measures on a TPU or not at all, and leaves nothing behind."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from benchmark import run as bench
from benchmark.lib.manifest import ROOT

CMD = [sys.executable, "benchmark/run.py", "--seed", "1", "--seconds", "1",
       "--trace", "0", "--workload"]


def test_refuses_off_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(CMD + ["mistral-7b.steady"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refused" in p.stderr and "TPU" in p.stderr


def test_refusal_reasons():
    table = json.loads((ROOT / "benchmark/lib/peaks.json").read_text())
    assert bench.refusal("tpu", "TPU v5 lite", 1, 1, table) is None
    assert bench.refusal("tpu", "TPU v5 lite", 4, 4, table) is None
    assert "TPU only" in bench.refusal("cpu", "cpu", 8, 1, table)
    assert "needs 4 chips" in bench.refusal("tpu", "TPU v5 lite", 1, 4, table)
    # matched by the exact kind, not by a substring of it
    assert "no peaks" in bench.refusal("tpu", "TPU v5", 1, 1, table)
    assert "no peaks" in bench.refusal("tpu", "TPU v7x", 1, 1, table)


def test_refuses_where_only_the_benchmark_is(tmp_path):
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(CMD + ["mistral-7b.steady"], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def listing(root):
    skip = ("__pycache__", ".jax_cache", ".git", "chiprun_out", ".scratch",
            ".pytest_cache", "native/build")
    return sorted(
        str(p.relative_to(root)) for p in Path(root).rglob("*")
        if p.is_file() and not any(s in str(p) for s in skip))


def test_the_save_cell_leaves_nothing_behind(tmp_path):
    before = listing(ROOT)
    tmp = tmp_path / "tmp"
    tmp.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp))
    p = subprocess.run(
        CMD + ["mistral-7b.save-every-8", "--rehearse-cpu"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert "rehearsal" in line and "metrics" not in line
    assert line["correct"] is True
    assert line["check"]["readback_mismatch"]["value"] == 0
    assert list(tmp.iterdir()) == []      # checkpoints and traces are gone
    assert listing(ROOT) == before        # the checkout is what it was
