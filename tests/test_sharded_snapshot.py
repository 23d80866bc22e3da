"""One snapshot of the state into host memory a save: every shard array the
save's readers ask for crosses the host link once, in ``_snapshot``; the
digests and Orbax's serialization then read the copies the runtime keeps on
those array objects, and nothing reads the device after ``save`` returns."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from orbax.checkpoint._src.serialization import replica_slices

from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint import checkpoint_path, sharded
from pyrecover_tpu.checkpoint.sharded import ShardedCheckpointer
from pyrecover_tpu.checkpoint.zerostall.chunkstore import leaf_digest
from pyrecover_tpu.config import TrainConfig
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.optim import build_optimizer
from pyrecover_tpu.telemetry import metrics
from pyrecover_tpu.train_state import create_train_state

ENGINES = pytest.mark.parametrize(
    "use_async", [True, False], ids=["async", "sync"])


@pytest.fixture(autouse=True)
def clean_bus():
    telemetry.close()
    metrics.reset()
    yield
    telemetry.close()
    metrics.reset()


def tiny_state():
    optimizer, _ = build_optimizer(TrainConfig(sequence_length=32))
    return create_train_state(
        jax.random.key(0), ModelConfig().tiny(max_seq_len=32), optimizer)


@pytest.fixture()
def state():
    """A state of the test's own: some cases delete its arrays."""
    return tiny_state()


def over_8(state):
    """The state laid over 8 devices: leaves whose first dimension divides
    are split over it, the others are replicated."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("x",))
    split = NamedSharding(mesh, PartitionSpec("x"))
    whole = NamedSharding(mesh, PartitionSpec())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(
            x, split if x.ndim and x.shape[0] % 8 == 0 else whole),
        state)


def host_values(state):
    """``{key: bytes}`` of every leaf, taken now (``np.array`` copies)."""
    return {
        jax.tree_util.keystr(p): np.array(leaf)
        for p, leaf in jax.tree_util.tree_flatten_with_path(state)[0]
    }


def want_digests(values):
    return {k: leaf_digest(v) for k, v in values.items()
            if k.startswith(".params")}


def written_digests(path):
    return json.loads((path / "meta" / "metadata").read_text())["leaf_digests"]


@pytest.fixture()
def transfers(monkeypatch):
    """Counts at the engine's two seams, and what Orbax's serialization
    asks for afterwards: ``started`` holds the id of every array a transfer
    was started on, ``orbax`` the ids of the shard arrays Orbax reads whole
    (it cuts a replicated leaf up on its devices itself)."""
    seen = {"started": [], "most_in_flight": 0, "orbax": [], "orbax_cut": 0,
            "keep": []}
    start, wait = sharded._start_host_copy, sharded._await_host_copy
    flying = {}

    def counted_start(arr):
        seen["started"].append(id(arr))
        seen["keep"].append(arr)  # ids stay unique while the test looks
        flying[id(arr)] = arr.nbytes
        seen["most_in_flight"] = max(
            seen["most_in_flight"], sum(flying.values()))
        start(arr)

    def counted_wait(arr):
        flying.pop(id(arr), None)
        return wait(arr)

    real_slices = replica_slices.get_replica_slices

    def watched_slices(arr, *a, **kw):
        out = real_slices(arr, *a, **kw)
        for rslice in out.replica_slices:
            if rslice.slice_args is None:
                seen["orbax"].append(id(rslice.unsliced_data))
            else:
                seen["orbax_cut"] += 1
        return out

    monkeypatch.setattr(sharded, "_start_host_copy", counted_start)
    monkeypatch.setattr(sharded, "_await_host_copy", counted_wait)
    monkeypatch.setattr(replica_slices, "get_replica_slices", watched_slices)
    return seen


def serialize_spans(sink):
    return [e for e in sink.events
            if e["event"] == "span_end" and e["name"] == "ckpt_serialize"]


@ENGINES
def test_every_leaf_crosses_the_host_link_once(tmp_ckpt_dir, state, transfers,
                                               use_async):
    leaves = jax.tree_util.tree_leaves(state)
    shards = [s.data for x in leaves for s in x.addressable_shards]
    assert len(shards) == len(leaves)  # one device: one shard a leaf
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="sharded")
    with ShardedCheckpointer(use_async=use_async) as ckptr:
        ckptr.save(path, state, extra_meta={"step": 1})
    # one transfer a leaf, scalars and rank-1 leaves like the rest
    assert sorted(transfers["started"]) == sorted(id(a) for a in shards)
    assert any(x.ndim == 0 for x in leaves) and any(x.ndim == 1 for x in leaves)
    # and Orbax read those very arrays, whole (it asks for each more than
    # once: to size the write, then to write): it started no other copy
    assert set(transfers["orbax"]) == {id(a) for a in shards}
    assert transfers["orbax_cut"] == 0
    assert written_digests(path) == want_digests(host_values(state))


@ENGINES
def test_the_count_is_per_save(tmp_ckpt_dir, state, transfers, use_async):
    """Two saves of the same arrays ask the runtime twice for each (the
    second finds the copy it kept)."""
    with ShardedCheckpointer(use_async=use_async) as ckptr:
        for step in (1, 2):
            ckptr.save(checkpoint_path(tmp_ckpt_dir, "exp", step, engine="sharded"),
                       state, extra_meta={"step": step})
    n = len(jax.tree_util.tree_leaves(state))
    assert len(transfers["started"]) == 2 * n
    assert len(set(transfers["started"])) == n


def test_the_state_may_go_the_moment_save_returns(tmp_ckpt_dir, state):
    values = host_values(state)
    target = tiny_state()
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="sharded")
    with ShardedCheckpointer(use_async=True) as ckptr:
        ckptr.save(path, state, extra_meta={"step": 1})
        for leaf in jax.tree_util.tree_leaves(state):
            leaf.delete()
        # new arrays take the freed memory while the write is under way
        junk = [jnp.full((64, 64), 7.0) + i for i in range(32)]
        jax.block_until_ready(junk)
        ckptr.wait()
        restored, _, meta = ckptr.restore(path, target)
    assert meta["leaf_digests"] == want_digests(values)
    got = host_values(restored)
    assert got.keys() == values.keys()
    for key, want in values.items():
        assert got[key].dtype == want.dtype
        assert got[key].tobytes() == want.tobytes(), key


@ENGINES
def test_a_state_over_8_devices_keeps_its_layout_and_restores_under_another(
        tmp_ckpt_dir, state, transfers, use_async):
    values = host_values(state)
    spread = over_8(state)
    leaves = jax.tree_util.tree_leaves(spread)
    params = {id(x) for x in jax.tree_util.tree_leaves(spread.params)}
    split = [x for x in leaves if not x.sharding.is_fully_replicated]
    assert split and all(len(x.addressable_shards) == 8 for x in split)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="sharded")
    sink = telemetry.add_sink(telemetry.MemorySink())
    with ShardedCheckpointer(use_async=use_async) as ckptr:
        ckptr.save(path, spread, extra_meta={"step": 1})
        # nothing was gathered: every shard of a split leaf went by itself,
        # a replicated leaf sent one replica where it needs a digest and
        # was left to Orbax's cut otherwise
        want = [id(s.data) for x in leaves for s in x.addressable_shards
                if s.replica_id == 0
                and (id(x) in params or not x.sharding.is_fully_replicated)]
        assert sorted(transfers["started"]) == sorted(want)
        whole = {id(s.data) for x in split for s in x.addressable_shards}
        assert whole <= set(transfers["orbax"])
        assert transfers["orbax_cut"] > 0
        span = serialize_spans(sink)[0]
        left = [x for x in leaves
                if x.sharding.is_fully_replicated and id(x) not in params]
        # what Orbax read besides the snapshot's copies: one replica of
        # the replicated leaves that no axis lets it cut (scalars, the key)
        own = set(transfers["orbax"]) - set(transfers["started"])
        assert own <= {id(x.addressable_shards[0].data) for x in left}
        assert span["fallback_leaves"] == len(left) > 0
        assert span["snapshot_bytes"] + sum(x.nbytes for x in left) == (
            span["bytes"])
        # the digests are of each whole leaf's byte stream, as on one device
        ckptr.wait()
        assert written_digests(path) == want_digests(values)
        # the shardings on disk are the state's own
        names = json.loads((path / "state" / "_sharding").read_text())
        assert len(names) == len(leaves)
        assert all("NamedSharding" in v for v in names.values())
        # another layout: four devices, split over the second dimension
        mesh = Mesh(np.array(jax.devices()[4:]), ("y",))
        target = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, PartitionSpec(
                *([None, "y"] if x.ndim > 1 and x.shape[1] % 4 == 0 else [])
            ))), tiny_state())
        restored, _, _ = ckptr.restore(path, target)
    for (p, leaf), t in zip(
            jax.tree_util.tree_flatten_with_path(restored)[0],
            jax.tree_util.tree_leaves(target)):
        key = jax.tree_util.keystr(p)
        assert leaf.sharding == t.sharding
        assert np.asarray(leaf).tobytes() == values[key].tobytes(), key


@ENGINES
def test_a_leaf_already_on_the_host_is_left_as_it_is(tmp_ckpt_dir, state,
                                                     transfers, use_async):
    rng_on_host = np.asarray(state.rng).copy()
    mixed = dataclasses.replace(state, rng=rng_on_host)
    values = host_values(mixed)
    sink = telemetry.add_sink(telemetry.MemorySink())
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="sharded")
    with ShardedCheckpointer(use_async=use_async) as ckptr:
        ckptr.save(path, mixed, extra_meta={"step": 1})
        restored, _, _ = ckptr.restore(path, tiny_state())
    span = serialize_spans(sink)[0]
    assert span["fallback_leaves"] == 1
    assert span["snapshot_bytes"] + rng_on_host.nbytes == span["bytes"]
    assert len(transfers["started"]) == len(
        jax.tree_util.tree_leaves(mixed)) - 1
    for key, got in host_values(restored).items():
        assert got.tobytes() == values[key].tobytes(), key


@ENGINES
def test_the_span_says_what_the_snapshot_moved(tmp_ckpt_dir, state, use_async):
    sink = telemetry.add_sink(telemetry.MemorySink())
    with ShardedCheckpointer(use_async=use_async) as ckptr:
        for step in (1, 2):
            ckptr.save(checkpoint_path(tmp_ckpt_dir, "exp", step, engine="sharded"),
                       state, extra_meta={"step": step})
    total = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
    spans = serialize_spans(sink)
    assert len(spans) == 2
    for span in spans:
        assert span["snapshot_bytes"] == span["bytes"] == total
        assert span["fallback_leaves"] == 0
        assert 0 <= span["snapshot_s"] <= span["dur_s"]


@pytest.mark.parametrize("cap", [1, 4096, 1 << 40],
                         ids=["one_at_a_time", "4KiB", "all"])
def test_no_more_in_flight_than_the_bound(tmp_ckpt_dir, state, transfers,
                                          monkeypatch, cap):
    monkeypatch.setattr(sharded, "IN_FLIGHT_BYTES", cap)
    leaves = jax.tree_util.tree_leaves(state)
    largest = max(x.nbytes for x in leaves)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="sharded")
    with ShardedCheckpointer(use_async=False) as ckptr:
        ckptr.save(path, state, extra_meta={"step": 1})
    assert len(transfers["started"]) == len(leaves)
    # a transfer starts only while the others leave it room (one goes alone)
    assert transfers["most_in_flight"] <= max(cap, largest)
    if cap == 1:
        assert transfers["most_in_flight"] == largest
    if cap == 1 << 40:
        assert transfers["most_in_flight"] == sum(x.nbytes for x in leaves)
    assert written_digests(path) == want_digests(host_values(state))


def test_the_form_on_disk_is_what_it_was(tmp_ckpt_dir, state):
    """Every leaf is filed as a ``jax.Array`` with its sharding, chunks and
    data files stay bounded, and the commit marker names the same items: a
    checkpoint of the parent commit reads the same, and the reverse."""
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="sharded")
    with ShardedCheckpointer(use_async=True) as ckptr:
        ckptr.save(path, state, {"epoch": 3}, extra_meta={"step": 1})
    assert sorted(p.name for p in path.iterdir()) == [
        "_CHECKPOINT_METADATA", "meta", "state"]
    tree = json.loads((path / "state" / "_METADATA").read_text())
    kinds = [v["value_metadata"]["value_type"]
             for v in tree["tree_metadata"].values()]
    n = len(jax.tree_util.tree_leaves(state))
    # (the empty ``grad_residual`` node is filed as "None")
    assert kinds.count("jax.Array") == n == len(kinds) - kinds.count("None")
    assert len(json.loads((path / "state" / "_sharding").read_text())) == n
    marker = json.loads((path / "_CHECKPOINT_METADATA").read_text())
    assert sorted(marker["item_handlers"]) == ["meta", "state"]
    meta = json.loads((path / "meta" / "metadata").read_text())
    assert sorted(meta) == ["leaf_digests", "manifest", "sampler", "step",
                            "topology"]
    assert meta["sampler"] == {"epoch": 3}
    # and it serves: the tamper gate reads the digests and passes
    from pyrecover_tpu.serving.restore import _read_params_sharded

    served = _read_params_sharded(path)
    assert len(jax.tree_util.tree_leaves(served)) == len(
        jax.tree_util.tree_leaves(state.params))
