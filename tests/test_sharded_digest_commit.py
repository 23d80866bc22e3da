"""The tamper gate's digests under the async sharded save: the save call
snapshots the state into host memory once, the BLAKE2b hash of the
snapshot's ``.params`` leaves runs in a commit future of the same Orbax save
(off the caller's thread), and the checkpoint commits only when the digests
are in ``meta/metadata``. Same algorithm, same leaves, the state as it was
at the call."""

import dataclasses
import json
import threading
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint import checkpoint_path
from pyrecover_tpu.checkpoint.sharded import ShardedCheckpointer
from pyrecover_tpu.checkpoint.zerostall import chunkstore
from pyrecover_tpu.checkpoint.zerostall.chunkstore import leaf_digest
from pyrecover_tpu.config import TrainConfig
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.optim import build_optimizer
from pyrecover_tpu.telemetry import metrics

ENGINES = pytest.mark.parametrize(
    "use_async", [True, False], ids=["async", "sync"])


@pytest.fixture(autouse=True)
def clean_bus():
    telemetry.close()
    metrics.reset()
    yield
    telemetry.close()
    metrics.reset()


@pytest.fixture()
def state():
    """A state of the test's own: some cases delete its arrays."""
    from pyrecover_tpu.train_state import create_train_state

    optimizer, _ = build_optimizer(TrainConfig(sequence_length=32))
    return create_train_state(
        jax.random.key(0), ModelConfig().tiny(max_seq_len=32), optimizer)


def want_digests(state):
    """What the gate must hold for this state, computed from host copies
    taken now (``np.array`` copies: nothing here aliases a device buffer)."""
    return {
        ".params" + jax.tree_util.keystr(p): leaf_digest(np.array(leaf))
        for p, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]
    }


def written_digests(path):
    return json.loads((path / "meta" / "metadata").read_text())["leaf_digests"]


def uncommitted(path):
    """Orbax's temporary directories of a save to ``path``."""
    return list(path.parent.glob(path.name + ".orbax-checkpoint-tmp*"))


@pytest.fixture()
def held_hash(monkeypatch):
    """``leaf_digest`` waits for ``release`` before it hashes; ``entered``
    says that the hash has begun (on whatever thread runs it)."""
    entered, release = threading.Event(), threading.Event()
    callers = []

    def held(arr):
        callers.append(threading.get_ident())
        entered.set()
        assert release.wait(30), "the test never released the hash"
        return leaf_digest(arr)

    monkeypatch.setattr(chunkstore, "leaf_digest", held)
    yield entered, release, callers
    release.set()


@pytest.mark.parametrize("placement", ["one_device", "sharded_over_8"])
def test_async_save_records_a_digest_per_params_leaf(tmp_ckpt_dir, state,
                                                     placement):
    if placement == "sharded_over_8":
        # fully addressable and split: the copy gathers it on the host, and
        # the digest is that of the whole leaf's byte stream, as before
        mesh = Mesh(np.array(jax.devices()[:8]), ("x",))
        split = NamedSharding(mesh, PartitionSpec("x"))
        whole = NamedSharding(mesh, PartitionSpec())
        state = dataclasses.replace(state, params=jax.tree_util.tree_map(
            lambda x: jax.device_put(
                x, split if x.ndim and x.shape[0] % 8 == 0 else whole),
            state.params))
        assert any(len(x.sharding.device_set) == 8 and
                   not x.sharding.is_fully_replicated
                   for x in jax.tree_util.tree_leaves(state.params))
    want = want_digests(state)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="sharded")
    with ShardedCheckpointer(use_async=True) as ckptr:
        ckptr.save(path, state, extra_meta={"step": 1})
        ckptr.wait()
        assert written_digests(path) == want
        assert len(want) == len(jax.tree_util.tree_leaves(state.params)) > 0
        # and the directory restores through the same checkpointer
        _, _, meta = ckptr.restore(path, state)
    assert meta["leaf_digests"] == want and meta["step"] == 1


def test_digests_are_of_the_state_at_the_call(tmp_ckpt_dir, state, held_hash):
    """What donation does to the saved arrays right after ``save`` returns:
    they are gone before the hash has read a byte. A hash that went back to
    the device would fail here."""
    entered, release, _ = held_hash
    want = want_digests(state)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="sharded")
    with ShardedCheckpointer(use_async=True) as ckptr:
        ckptr.save(path, state, extra_meta={"step": 1})
        for leaf in jax.tree_util.tree_leaves(state):
            leaf.delete()
        # new arrays take the freed memory before the hash runs
        junk = [jax.numpy.full((64, 64), 7.0) + i for i in range(32)]
        jax.block_until_ready(junk)
        assert entered.wait(30)
        release.set()
        ckptr.wait()
    assert written_digests(path) == want


def test_no_commit_while_the_hash_is_open(tmp_ckpt_dir, state, held_hash):
    entered, release, callers = held_hash
    want = want_digests(state)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="sharded")
    with ShardedCheckpointer(use_async=True) as ckptr:
        ckptr.save(path, state, extra_meta={"step": 1})
        assert entered.wait(30)
        # the writes of the state have long ended at this toy size; the
        # rename still waits for the digests
        time.sleep(0.5)
        assert not path.exists()
        assert len(uncommitted(path)) == 1
        assert ckptr._write_in_flight() is True
        release.set()
        ckptr.wait()
        assert path.is_dir() and uncommitted(path) == []
        assert (path / "_CHECKPOINT_METADATA").exists()
        assert written_digests(path) == want
    assert callers and threading.get_ident() not in callers


def broken_hash(arr):
    raise OSError("hash failed")


def test_a_failed_hash_fails_the_save_and_commits_nothing(tmp_ckpt_dir, state,
                                                          monkeypatch):
    monkeypatch.setattr(chunkstore, "leaf_digest", broken_hash)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="sharded")
    ckptr = ShardedCheckpointer(use_async=True)
    ckptr.save(path, state, extra_meta={"step": 1})  # the dispatch succeeds
    with pytest.raises(OSError, match="hash failed"):
        ckptr.wait()
    assert not path.exists()
    # the engine goes on: the next save, with a sound hash, commits
    monkeypatch.setattr(chunkstore, "leaf_digest", leaf_digest)
    path2 = checkpoint_path(tmp_ckpt_dir, "exp", 2, engine="sharded")
    ckptr.save(path2, state, extra_meta={"step": 2})
    ckptr.close()
    assert not path.exists()
    assert written_digests(path2) == want_digests(state)


def test_a_failed_hash_fails_a_sync_save_at_the_call(tmp_ckpt_dir, state,
                                                     monkeypatch):
    monkeypatch.setattr(chunkstore, "leaf_digest", broken_hash)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="sharded")
    with ShardedCheckpointer(use_async=False) as ckptr:
        with pytest.raises(OSError, match="hash failed"):
            ckptr.save(path, state, extra_meta={"step": 1})
    assert not path.exists() and uncommitted(path) == []


def test_sync_and_async_write_the_same_meta(tmp_ckpt_dir, state):
    paths = {}
    for use_async in (True, False):
        paths[use_async] = checkpoint_path(
            tmp_ckpt_dir, "async" if use_async else "sync", 1,
            engine="sharded")
        with ShardedCheckpointer(use_async=use_async) as ckptr:
            ckptr.save(paths[use_async], state, {"epoch": 3},
                       extra_meta={"step": 1})
    a, b = (json.loads((paths[k] / "meta" / "metadata").read_text())
            for k in (True, False))
    assert a == b
    assert a["leaf_digests"] == want_digests(state)
    # the commit marker names Orbax's own JSON item in both: the form on
    # disk is the one every earlier checkpoint has
    for path in paths.values():
        marker = json.loads((path / "_CHECKPOINT_METADATA").read_text())
        assert marker["item_handlers"]["meta"].endswith(
            "json_checkpoint_handler.JsonCheckpointHandler")


@ENGINES
def test_spans_say_where_the_hash_ran(tmp_ckpt_dir, state, use_async):
    sink = telemetry.add_sink(telemetry.MemorySink())
    params = jax.tree_util.tree_leaves(state.params)
    with ShardedCheckpointer(use_async=use_async) as ckptr:
        for step in (1, 2, 3):
            ckptr.save(checkpoint_path(tmp_ckpt_dir, "exp", step, engine="sharded"),
                       state, max_keep=2, extra_meta={"step": step})
    digest = [e for e in sink.events
              if e["event"] == "span_end" and e["name"] == "ckpt_digest"]
    assert [e["deferred"] for e in digest] == (
        [len(params)] * 3 if use_async else [0] * 3)
    assert all(e["leaves"] == len(params) for e in digest)
    bg = [e for e in sink.events
          if e["event"] == "span" and e["name"] == "ckpt_digest_background"]
    if not use_async:
        assert bg == []
        assert metrics.histogram("ckpt_sharded_digest_background_s").count == 0
        return
    assert sorted(e["step"] for e in bg) == [1, 2, 3]
    me = threading.get_ident()
    for e in bg:
        assert e["tid"] != me and e["parent"] is None
        assert e["engine"] == "sharded" and e["leaves"] == len(params)
        assert e["bytes"] == sum(x.nbytes for x in params)
        # it starts after the copy that feeds it has ended
        copied = next(d for d in digest if d["step"] == e["step"])
        assert e["mono"] >= copied["mono"] - 1e-6
    assert metrics.histogram("ckpt_sharded_digest_background_s").count == 3
    # and the commit thread's life holds the hash it waited for
    write = [e for e in sink.events
             if e["event"] == "span" and e["name"] == "ckpt_write_background"]
    assert len(write) == 3
    for e, w in zip(sorted(bg, key=lambda e: e["mono"]),
                    sorted(write, key=lambda e: e["mono"])):
        assert e["mono"] + e["dur_s"] <= w["mono"] + w["dur_s"] + 1e-3
