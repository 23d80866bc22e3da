"""The contract of ``checkpoint/engine.py``: what ``train.py`` and the
serving restore ask of an engine holds for each of the three, and those
two modules name none."""

import ast
import re
from pathlib import Path

import jax
import numpy as np
import pytest

import pyrecover_tpu
from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint import checkpoint_path
from pyrecover_tpu.checkpoint.engine import (
    ENGINES,
    CheckpointIntegrityError,
    engine_for_path,
    open_engine,
)
from pyrecover_tpu.checkpoint.zerostall import emergency
from pyrecover_tpu.config import TrainConfig
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.optim import build_optimizer
from pyrecover_tpu.train_state import create_train_state

MODEL_CFG = ModelConfig().tiny(max_seq_len=32)
EACH_ENGINE = pytest.mark.parametrize("name", ENGINES)


def config(name, **kw):
    return TrainConfig(sequence_length=32, checkpoint_engine=name,
                       verify_checkpoints=True, max_kept_checkpoints=2, **kw)


def make_state(seed=0):
    optimizer, _ = build_optimizer(config("vanilla"))
    return create_train_state(jax.random.key(seed), MODEL_CFG, optimizer)


def assert_trees_equal(a, b):
    a, b = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(autouse=True)
def no_ram_records():
    yield
    emergency.drop()


@pytest.fixture()
def sink():
    s = telemetry.add_sink(telemetry.MemorySink())
    yield s
    telemetry.remove_sink(s)


def flip_byte(path, frac=0.5):
    data = bytearray(path.read_bytes())
    data[int(len(data) * frac)] ^= 0xFF
    path.write_bytes(bytes(data))


def largest_file(root):
    return max((p for p in root.rglob("*") if p.is_file()),
               key=lambda p: p.stat().st_size)


@EACH_ENGINE
def test_save_join_precheck_load_read_params(tmp_ckpt_dir, name):
    state = make_state()
    with open_engine(config(name)) as engine:
        assert engine.name == name
        path = checkpoint_path(tmp_ckpt_dir, "exp", 3, engine=engine.name)
        assert path.name == "ckpt_3" + engine.suffix
        secs = engine.save(path, state, {"consumed": 3},
                           extra_meta={"step": 3})
        assert secs >= 0.0
        assert engine.join() >= 0.0
        assert engine.join() == 0.0  # nothing left in flight
        assert engine.precheck(path, make_state(seed=1)) == (True, "")
        loaded, sampler_meta, meta = engine.load(
            path, make_state(seed=1), prechecked=True)
        assert_trees_equal(state, loaded)
        assert sampler_meta == {"consumed": 3} and meta["step"] == 3
        assert_trees_equal(state.params, engine.read_params(path))
        tier = engine.ram_tier(path.parent)
        if name == "zerostall":
            assert tier.peek()[0] == 3
        else:
            assert tier is None
    with engine_for_path(path) as reader:
        assert reader.name == name and type(reader) is type(engine)
        assert_trees_equal(state.params, reader.read_params(path))


@EACH_ENGINE
def test_damage_fails_in_the_engines_words(tmp_ckpt_dir, name):
    state = make_state()
    with open_engine(config(name, async_checkpoint=False)) as engine:
        path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine=engine.name)
        engine.save(path, state, {}, extra_meta={"step": 1})
        if name == "vanilla":
            flip_byte(path, 0.75)  # inside a tensor frame
            assert engine.precheck(path, state) == (
                False, "checksum mismatch")
            words = "fails its checksum sidecar"
        elif name == "sharded":
            flip_byte(largest_file(path / "state"))
            words = "fails its recorded content digest"
        else:
            flip_byte(largest_file(path.parent / "chunks"))
            ok, why = engine.precheck(path, state)
            assert not ok and "digest" in why
            words = "digest"
        with pytest.raises(
            ValueError if name == "zerostall" else CheckpointIntegrityError,
            match=words,
        ):
            engine.read_params(path)
        if name == "sharded":
            # a save torn before its rename: the pre-check's own words
            (path / "_CHECKPOINT_METADATA").unlink()
            ok, why = engine.precheck(path, state)
            assert not ok and "missing commit marker" in why


@EACH_ENGINE
def test_one_save_in_flight_and_none_after_a_final_one(tmp_ckpt_dir, name,
                                                       sink):
    state = make_state()
    handles = name != "sharded"  # the engines that count shadow seconds
    with open_engine(config(name)) as engine:
        for step in (1, 2):  # the second serialises behind the first
            engine.save(
                checkpoint_path(tmp_ckpt_dir, "exp", step, engine=name),
                state, {}, extra_meta={"step": step})
        final = checkpoint_path(tmp_ckpt_dir, "exp", 3, final=True,
                                engine=name)
        engine.save(final, state, {}, extra_meta={"step": 3}, final=True)
        assert engine_for_path(final).precheck(final, state) == (True, "")
        joins = [e for e in sink.events if e["event"] == "ckpt_bg_join"]
        assert len(joins) == (2 if handles else 0)
        assert all(e["engine"] == name and e["ok"] and not e["bounded"]
                   for e in joins)
        assert (engine.shadow_s > 0.0) == handles
        before = len(sink.events)
        assert engine.join(timeout_s=5.0) == 0.0
        assert not [e for e in sink.events[before:]
                    if e["event"] == "ckpt_bg_join"]
    # max_kept_checkpoints reached the engine; the sharded prune runs at
    # the dispatch, before the save it started can be seen
    kept = [p.name for p in final.parent.iterdir()
            if p.name.startswith("ckpt_") and not p.name.endswith(".sha256")]
    assert len(kept) == (2 if handles else 3), kept


def test_the_two_callers_name_no_engine():
    """``train.py`` and ``serving/restore.py`` hold an engine object: no
    engine's name in a string outside docstrings, and nothing private
    imported from the checkpoint package."""
    root = Path(pyrecover_tpu.__file__).parent
    named = re.compile(r"\b(%s)\b" % "|".join(ENGINES))
    for rel in ("train.py", "serving/restore.py"):
        tree = ast.parse((root / rel).read_text())
        docstrings = {
            id(node.body[0].value) for node in ast.walk(tree)
            if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef))
            and node.body and isinstance(node.body[0], ast.Expr)
            and isinstance(node.body[0].value, ast.Constant)
        }
        for node in ast.walk(tree):
            if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                    and id(node) not in docstrings):
                assert not named.search(node.value), (rel, node.lineno)
            if (isinstance(node, ast.ImportFrom) and node.module
                    and node.module.startswith("pyrecover_tpu.checkpoint")):
                private = [a.name for a in node.names
                           if a.name.startswith("_")]
                assert not private, (rel, node.lineno, private)
