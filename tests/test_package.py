"""Package-level pins: what importing ``pyrecover_tpu`` does before any
entry point's first compile — placing jax's persistent compilation cache.

Checked in fresh interpreters (the placement runs at import; this process
imported the package long ago). Importing the package creates no backend
client, so these are safe on any host.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_PRINT = (
    "import json, pyrecover_tpu, jax; "
    "print(json.dumps(jax.config.jax_compilation_cache_dir))"
)


def cache_dir_in_fresh_process(**overrides):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")
    }
    env.update(overrides)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", _PRINT], env=env, capture_output=True,
        text=True, timeout=120, check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_default_cache_is_one_fixed_ignored_directory_in_the_checkout():
    placed = cache_dir_in_fresh_process()
    assert placed == str(REPO / ".jax_cache")
    # fixed (the path is part of the cache key) and never committed
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_cache_placed_from_outside_is_left_alone(tmp_path):
    """With $JAX_COMPILATION_CACHE_DIR set the program sets NOTHING: jax
    reads the variable itself and the cache is there."""
    placed = cache_dir_in_fresh_process(
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "outside")
    )
    assert placed == str(tmp_path / "outside")


def test_cpu_held_process_gets_no_default_cache():
    assert cache_dir_in_fresh_process(JAX_PLATFORMS="cpu") is None


def test_only_one_place_sets_the_cache_dir():
    setters = [
        str(p.relative_to(REPO))
        for root in ("pyrecover_tpu", "tools")
        for p in (REPO / root).rglob("*.py")
        if "compilation_cache_dir" in p.read_text()
    ] + [
        name for name in ("bench.py", "__graft_entry__.py", "chip_smoke.py")
        if "jax_compilation_cache_dir" in (REPO / name).read_text()
    ]
    assert setters == ["pyrecover_tpu/__init__.py"]
