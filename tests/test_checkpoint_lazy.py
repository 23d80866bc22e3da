"""The checkpoint package loads an engine's module, and Orbax or Flax
behind it, only when a run opens that engine: importing the trainer and a
run that never saves load none of them, and ``open_engine`` loads what its
engine's saves need before it returns, so no module is first imported
inside the step loop."""

import importlib
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from pyrecover_tpu import checkpoint

ROOT = Path(__file__).resolve().parent.parent

# what the package's names resolved to when it imported every engine
SOURCE = {
    "checkpoint_path": "registry",
    "engine_of": "registry",
    "get_latest_checkpoint": "registry",
    "list_checkpoints": "registry",
    "prune_checkpoints": "registry",
    "save_ckpt_vanilla": "vanilla",
    "load_ckpt_vanilla": "vanilla",
    "ShardedCheckpointer": "sharded",
    "save_ckpt_sharded": "sharded",
    "load_ckpt_sharded": "sharded",
    "precheck_ckpt_sharded": "sharded",
    "TopologyMismatchError": "elastic",
    "compute_reshard_plan": "elastic",
    "preflight_elastic": "elastic",
    "read_saved_meta": "elastic",
    "topologies_differ": "elastic",
    "save_ckpt_zerostall": "zerostall",
    "load_ckpt_zerostall": "zerostall",
    "precheck_ckpt_zerostall": "zerostall",
}

HEAVY = (
    "orbax",
    "orbax.checkpoint",
    "flax",
    "flax.serialization",
    "google.cloud.logging",
    "pyrecover_tpu.checkpoint.sharded",
    "pyrecover_tpu.checkpoint.vanilla",
    "pyrecover_tpu.checkpoint.zerostall",
)

# a fresh process: the trainer's import, a run that never saves, then the
# sharded engine opened; the modules loaded after each
_PROBE = r"""
import json, sys

def loaded():
    return sorted(sys.modules)

import pyrecover_tpu.train as trainer
out = {"imported": loaded(), "import_s": trainer.IMPORT_S,
       "ckpt_modules": trainer.CKPT_MODULES}

from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint.engine import open_engine
from pyrecover_tpu.config import TrainConfig
from pyrecover_tpu.models import ModelConfig

sink = telemetry.add_sink(telemetry.MemorySink())
trainer.train(TrainConfig(
    sequence_length=16, batch_size=8, training_samples=16, training_steps=2,
    logging_frequency=1, checkpoint_frequency=-1, checkpoint_engine="vanilla",
    checkpoint_dir=sys.argv[1], experiment_name="lazy", model_dtype="fp32",
    model=ModelConfig().tiny(max_seq_len=16, vocab_size=128)))
telemetry.remove_sink(sink)
out["run_start"] = next(r for r in sink.events if r["event"] == "run_start")
out["after_run"] = loaded()

engine = open_engine(TrainConfig(checkpoint_engine="vanilla"))
out["vanilla_opened"] = [type(engine).__name__, loaded()]
engine = open_engine(TrainConfig(checkpoint_engine="sharded"))
out["sharded_opened"] = [type(engine).__name__, loaded()]
print("PROBE " + json.dumps(out, default=str))
"""


@pytest.fixture(scope="module")
def probe(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE, str(tmp_path_factory.mktemp("lazy"))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("PROBE "))
    return json.loads(line[len("PROBE "):])


@pytest.mark.parametrize("module", HEAVY)
def test_importing_the_trainer_loads_no_engine(probe, module):
    assert module not in probe["imported"]


@pytest.mark.parametrize("module", HEAVY)
def test_a_run_that_never_saves_loads_no_engine_but_its_own(probe, module):
    # the vanilla engine is opened by the run; nothing it saves with or
    # any other engine comes in
    if module == "pyrecover_tpu.checkpoint.vanilla":
        assert module in probe["after_run"]
    else:
        assert module not in probe["after_run"]


def test_run_start_reports_the_import(probe):
    start = probe["run_start"]
    assert start["import_s"] == probe["import_s"]
    assert isinstance(start["import_s"], float) and start["import_s"] > 0
    assert start["ckpt_modules"] == probe["ckpt_modules"] == []


def test_open_engine_vanilla_loads_no_flax(probe):
    name, loaded = probe["vanilla_opened"]
    assert name == "VanillaEngine"
    assert "pyrecover_tpu.checkpoint.vanilla" in loaded
    assert "flax" not in loaded and "orbax.checkpoint" not in loaded


def test_open_engine_sharded_loads_what_its_save_needs(probe):
    # Orbax and the digest the commit files come in at open_engine, in
    # set-up: the first save imports nothing
    name, loaded = probe["sharded_opened"]
    assert name == "ShardedCheckpointer"
    for module in ("orbax.checkpoint", "pyrecover_tpu.checkpoint.sharded",
                   "pyrecover_tpu.checkpoint.zerostall.chunkstore"):
        assert module in loaded


@pytest.mark.parametrize("name", checkpoint.__all__)
def test_each_name_is_its_modules_object(name):
    module = importlib.import_module(f"pyrecover_tpu.checkpoint.{SOURCE[name]}")
    assert getattr(checkpoint, name) is getattr(module, name)
    assert name in dir(checkpoint)


def test_the_package_exports_what_it_always_did():
    assert sorted(checkpoint.__all__) == sorted(SOURCE)


def test_an_unknown_name_is_an_attribute_error():
    with pytest.raises(AttributeError, match="no_such_engine"):
        checkpoint.no_such_engine  # noqa: B018


def _reader():
    spec = importlib.util.spec_from_file_location(
        "setup_import_s", ROOT / "benchmark" / "metrics" / "setup_import_s.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Run:
    def __init__(self, *records):
        sink = type("Sink", (), {"records": [(0.0, r) for r in records]})
        self.res = {"sink": sink}


@pytest.mark.parametrize("start, want", [
    ({"event": "run_start", "import_s": 3.5, "ckpt_modules": []}, 3.5),
    ({"event": "run_start", "devices": 1}, None),  # a trainer without it
    (None, None),
])
def test_the_benchmark_reads_the_import_from_run_start(start, want):
    records = [{"event": "train_sync", "step": 5}] + ([start] if start else [])
    assert _reader()(_Run(*records)) == want
