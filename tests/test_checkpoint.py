"""Checkpoint engine tests: registry ordering, vanilla roundtrip + checksum,
sharded (Orbax) roundtrip, retention pruning."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyrecover_tpu.checkpoint import (
    checkpoint_path,
    get_latest_checkpoint,
    load_ckpt_vanilla,
    save_ckpt_vanilla,
    load_ckpt_sharded,
    save_ckpt_sharded,
    prune_checkpoints,
)
from pyrecover_tpu.checkpoint.registry import parse_step, VANILLA_SUFFIX
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.optim import build_optimizer
from pyrecover_tpu.config import TrainConfig
from pyrecover_tpu.train_state import create_train_state

CFG = TrainConfig(sequence_length=32)
MODEL_CFG = ModelConfig().tiny(max_seq_len=32)


def make_state(seed=0):
    optimizer, _ = build_optimizer(CFG)
    return create_train_state(jax.random.key(seed), MODEL_CFG, optimizer)


def test_registry_orders_by_step_not_name(tmp_ckpt_dir):
    """Reference defect #6: lexicographic sort put ckpt_1000 before ckpt_200
    and pruned the newest. Our registry must order numerically."""
    exp = tmp_ckpt_dir / "exp"
    exp.mkdir()
    for step in (200, 1000, 30):
        (exp / f"ckpt_{step}{VANILLA_SUFFIX}").write_bytes(b"x")
        time.sleep(0.01)
    latest = get_latest_checkpoint(exp)
    assert parse_step(latest) == 1000
    prune_checkpoints(exp, max_keep=2)
    remaining = sorted(parse_step(p) for p in exp.iterdir())
    assert remaining == [200, 1000]


def test_checkpoint_path_naming(tmp_ckpt_dir):
    p = checkpoint_path(tmp_ckpt_dir, "exp", 42)
    assert p.name == f"ckpt_42{VANILLA_SUFFIX}"
    p = checkpoint_path(tmp_ckpt_dir, "exp", 42, final=True)
    assert p.name == f"ckpt_42_final{VANILLA_SUFFIX}"
    p = checkpoint_path(tmp_ckpt_dir, "exp", 7, engine="sharded")
    assert p.name == "ckpt_7"
    assert parse_step(p) == 7


def test_vanilla_roundtrip_bitexact(tmp_ckpt_dir):
    state = make_state(seed=1)
    sampler_state = {"epoch": 2, "cursor": 8, "seed": 5,
                     "global_batch_size": 4, "num_samples": 100, "shuffle": True}
    path = checkpoint_path(tmp_ckpt_dir, "exp", 3)
    save_ckpt_vanilla(path, state, sampler_state, verify=True,
                      extra_meta={"step": 3, "epoch": 2})
    assert path.exists()

    target = make_state(seed=99)  # different values, same structure
    restored, restored_sampler, meta = load_ckpt_vanilla(path, target, verify=True)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert restored_sampler["cursor"] == 8
    assert meta["step"] == 3


def test_vanilla_checksum_detects_corruption(tmp_ckpt_dir):
    state = make_state()
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1)
    save_ckpt_vanilla(path, state, verify=True)
    # corrupt one byte mid-file
    data = bytearray(path.read_bytes())
    data[len(data) // 2] ^= 0xFF
    path.write_bytes(bytes(data))
    target = make_state(seed=2)
    with pytest.raises(Exception):
        load_ckpt_vanilla(path, target, verify=True)


def test_vanilla_shape_mismatch_rejected(tmp_ckpt_dir):
    state = make_state()
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1)
    save_ckpt_vanilla(path, state)
    other_cfg = MODEL_CFG.tiny(dim=32)
    optimizer, _ = build_optimizer(CFG)
    target = create_train_state(jax.random.key(0), other_cfg, optimizer)
    with pytest.raises(ValueError):
        load_ckpt_vanilla(path, target)


def test_vanilla_retention_prunes_with_sidecars(tmp_ckpt_dir):
    state = make_state()
    for step in (1, 2, 3, 4):
        save_ckpt_vanilla(
            checkpoint_path(tmp_ckpt_dir, "exp", step), state,
            verify=True, max_keep=2,
        )
    exp = tmp_ckpt_dir / "exp"
    steps = sorted(parse_step(p) for p in exp.iterdir() if parse_step(p) is not None)
    assert steps == [3, 4]
    sidecars = list(exp.glob("*.sha256"))
    assert len(sidecars) == 2


def test_sharded_roundtrip_bitexact(tmp_ckpt_dir):
    state = make_state(seed=3)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 5, engine="sharded")
    save_ckpt_sharded(path, state, {"epoch": 0, "cursor": 4}, extra_meta={"step": 5})
    assert path.is_dir()
    assert get_latest_checkpoint(path.parent, engine="sharded") == path

    target = make_state(seed=77)
    restored, sampler_state, meta = load_ckpt_sharded(path, target)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert sampler_state["cursor"] == 4
    assert meta["step"] == 5


def test_sharded_files_stay_bounded(tmp_ckpt_dir, monkeypatch):
    """No file of a sharded checkpoint grows with the model: leaves are cut
    into CHUNK_BYTES chunks and data files roll over at DATA_FILE_BYTES, so
    a host with a file-size ceiling (EFBIG) can still save. Pinned with
    the two sizes shrunk, on leaves many times larger than both, under a
    real RLIMIT_FSIZE in a child process."""
    import subprocess
    import sys
    import textwrap

    from pyrecover_tpu.checkpoint import sharded

    monkeypatch.setattr(sharded, "DATA_FILE_BYTES", 64 * 1024)
    monkeypatch.setattr(sharded, "CHUNK_BYTES", 32 * 1024)
    rng = np.random.default_rng(0)  # incompressible: zstd cannot hide size
    tree = {
        "big": jnp.asarray(rng.integers(0, 2**31, (512, 512), dtype=np.int32)),
        "stack": jnp.asarray(rng.integers(0, 2**31, (4, 64, 256), dtype=np.int32)),
        "scalar": jnp.int32(3),
    }
    path = tmp_ckpt_dir / "exp" / "ckpt_1"
    save_ckpt_sharded(path, tree)
    sizes = [f.stat().st_size for f in path.rglob("*") if f.is_file()]
    assert sum(sizes) > 1024 * 1024  # the data really is there, uncompressed
    bound = sharded.DATA_FILE_BYTES + sharded.CHUNK_BYTES
    assert max(sizes) <= bound, sorted(sizes)[-3:]
    restored, _, _ = load_ckpt_sharded(path, tree)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # a save larger than a kernel-enforced ceiling, at the shipped sizes
    child = textwrap.dedent(f"""
        import resource, sys
        import numpy as np, jax.numpy as jnp
        from pyrecover_tpu.checkpoint import sharded
        cap = 2 * (sharded.DATA_FILE_BYTES + sharded.CHUNK_BYTES)
        resource.setrlimit(resource.RLIMIT_FSIZE, (cap, cap))
        rng = np.random.default_rng(0)
        tree = {{"w": jnp.asarray(rng.integers(0, 2**31, (5 * cap // 16,),
                                               dtype=np.int32))}}
        sharded.save_ckpt_sharded(sys.argv[1], tree)
        back, _, _ = sharded.load_ckpt_sharded(sys.argv[1], tree)
        assert bool((back["w"] == tree["w"]).all())
    """)
    subprocess.run(
        [sys.executable, "-c", child, str(tmp_ckpt_dir / "exp" / "ckpt_2")],
        check=True, timeout=300,
    )


def test_sharded_restore_onto_mesh(tmp_ckpt_dir, devices8):
    """Save from single-device state, restore onto a sharded 8-device mesh —
    the resharded-restore capability (SURVEY hard-part #2)."""
    from pyrecover_tpu.parallel.mesh import MeshConfig, create_mesh
    from pyrecover_tpu.parallel.sharding import shard_params

    state = make_state(seed=4)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 9, engine="sharded")
    save_ckpt_sharded(path, state)

    mesh = create_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    target = make_state(seed=88)
    target_sharded = jax.tree_util.tree_map(lambda x: x, target)
    target_sharded.params = shard_params(target.params, mesh)
    restored, _, _ = load_ckpt_sharded(path, target_sharded)
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_vanilla_background_save(tmp_ckpt_dir):
    """Background save: returns quickly with a handle; after wait() the file
    is complete, verified, and loadable; write errors surface at wait()."""
    from pyrecover_tpu.checkpoint.vanilla import save_ckpt_vanilla as save

    state = make_state(seed=6)
    path = checkpoint_path(tmp_ckpt_dir, "bg", 1)
    secs, handle = save(path, state, {"consumed": 1}, verify=True,
                        background=True)
    handle.wait()
    assert handle.done
    target = make_state(seed=7)
    restored, sampler_state, _ = load_ckpt_vanilla(path, target, verify=True)
    assert sampler_state["consumed"] == 1
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # unwritable destination → error surfaces at wait(), not silently lost
    bad = checkpoint_path("/proc/definitely-not-writable", "bg", 2)
    _, bad_handle = save(bad, state, background=True)
    with pytest.raises(BaseException):
        bad_handle.wait()


def test_legacy_v1_checkpoint_still_loads(tmp_ckpt_dir):
    """Checkpoints written by the v1 msgpack format (rounds 1-3) must keep
    restoring after the v2 streaming-format upgrade."""
    import json

    from flax.serialization import msgpack_serialize

    from pyrecover_tpu.checkpoint.vanilla import read_ckpt_raw

    state = make_state(seed=11)
    path_leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
    np_leaves = [np.asarray(x) for _, x in path_leaves]
    meta = {
        "format": 1,
        "num_leaves": len(np_leaves),
        "treedef": str(treedef),
        "paths": [jax.tree_util.keystr(p) for p, _ in path_leaves],
        "sampler": {"consumed": 5},
        "step": 5,
    }
    payload = msgpack_serialize({
        "meta": json.dumps(meta),
        "leaves": {str(i): leaf for i, leaf in enumerate(np_leaves)},
    })
    path = checkpoint_path(tmp_ckpt_dir, "v1", 5)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(payload)

    got_meta, _, got_leaves = read_ckpt_raw(path)
    assert got_meta["format"] == 1
    restored, sampler_state, meta2 = load_ckpt_vanilla(path, make_state(seed=12))
    assert sampler_state["consumed"] == 5 and meta2["step"] == 5
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_streaming_save_memory_bounded(tmp_ckpt_dir):
    """The v2 serializer must never build a whole-state payload copy: peak
    python-level allocation during a save of a ~192 MB state stays around
    one leaf (~48 MB) + chunk buffers, nowhere near the v1 msgpack path's
    >= 1x-state payload (round-3 verdict weak #5)."""
    import tracemalloc

    leaf_bytes = 48 * 1024 * 1024
    state = {
        f"leaf{i}": np.full(leaf_bytes // 4, float(i), dtype=np.float32)
        for i in range(4)
    }
    path = checkpoint_path(tmp_ckpt_dir, "mem", 1)
    tracemalloc.start()
    tracemalloc.reset_peak()
    save_ckpt_vanilla(path, state, verify=True)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # one leaf copy (48M) + hash chunk buffers (~32M) + slack; the old
    # payload path peaked >= 192M here
    assert peak < 140 * 1024 * 1024, f"peak {peak/1e6:.0f} MB"
    restored, _, _ = load_ckpt_vanilla(path, {
        f"leaf{i}": np.zeros(leaf_bytes // 4, dtype=np.float32)
        for i in range(4)
    }, verify=True)
    for i in range(4):
        assert (restored[f"leaf{i}"] == float(i)).all()
