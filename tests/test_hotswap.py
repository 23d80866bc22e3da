"""Zero-downtime weight hot-swap (pyrecover_tpu/serving/hotswap/).

The contract under test: a live serving engine tracks the checkpoint
registry and swaps weights between decode steps — incremental fetch
moves only changed-digest chunks (every byte re-verified), the flip is
atomic at a step boundary with zero retraces, any failure rejects the
manifest loudly and keeps the old weights serving, and the pin-lease
machinery closes the fetch-during-GC race. Plus the satellites: the
manifest chunk-diff tool, the open-loop load generator, and tamper
rejection in the serving restore across all three engines.
"""

import dataclasses
import io
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint.zerostall import pins, save_ckpt_zerostall
from pyrecover_tpu.checkpoint.zerostall.chunkstore import (
    chunk_path,
    chunks_root,
    collect_garbage,
    read_manifest,
    referenced_digests,
)
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.serving import (
    HotSwapper,
    ServingConfig,
    ServingEngine,
    ServingRestoreError,
    load_serving_params,
    open_loop_workload,
)
from pyrecover_tpu.serving.hotswap.fetch import (
    diff_manifest_chunks,
    fetch_params_incremental,
)
from pyrecover_tpu.telemetry import metrics

REPO = Path(__file__).resolve().parent.parent

CFG = ModelConfig().tiny(
    max_seq_len=96, vocab_size=64, compute_dtype="float32",
    param_dtype="float32",
)

SCFG = ServingConfig(
    block_size=8, max_seqs=4, prefill_chunk=16, prefill_token_budget=32,
)


@pytest.fixture()
def mem_sink():
    sink = telemetry.MemorySink()
    telemetry.add_sink(sink)
    metrics.reset()
    yield sink
    telemetry.remove_sink(sink)


def _train_state(seed=0):
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.train_state import create_train_state

    optimizer, _ = build_optimizer(TrainConfig())
    return create_train_state(jax.random.key(seed), CFG, optimizer)


def _perturb(state, i, keys=("output", "final_norm")):
    params = dict(state.params)
    for key in keys:
        params[key] = jax.tree_util.tree_map(
            lambda x: (x + jnp.asarray(1e-3 * i, x.dtype)).astype(x.dtype),
            params[key],
        )
    return dataclasses.replace(state, params=params)


def _save_zs(exp, step, state):
    path = Path(exp) / f"ckpt_{step}.zs.json"
    save_ckpt_zerostall(path, state, {}, background=False,
                        emergency_tier=False, extra_meta={"step": step})
    return path


def _probe(engine, prompts=((1, 2, 3, 4), (9, 8, 7), (5, 5, 5, 5, 5))):
    rids = [engine.submit(list(p), 6) for p in prompts]
    engine.run_until_drained()
    return [engine.result(r) for r in rids]


# ---- pin leases + the fetch-during-GC race (satellite 1) ----------------


def test_pin_lease_lifecycle(tmp_path):
    state = _train_state()
    path = _save_zs(tmp_path, 1, state)
    lease = pins.pin_manifest(tmp_path, path, owner="t1")
    assert lease.path.exists()
    assert [p.name for p in pins.live_pins(tmp_path)] == [lease.path.name]
    # fresh leases survive expiry at the default TTL, die at ttl 0
    assert pins.expire_stale_pins(tmp_path) == []
    lease.refresh()
    assert pins.expire_stale_pins(tmp_path, ttl_s=0.0) == [lease.path.name]
    assert pins.live_pins(tmp_path) == []
    lease.release()  # idempotent after expiry


def test_pinned_manifest_counts_as_live_for_gc(tmp_path):
    """THE race regression: retention prunes the manifest a reader is
    mid-fetch on, GC runs — with the pin held every chunk survives and
    the fetch completes; once the lease expires, GC reclaims them."""
    from pyrecover_tpu.checkpoint.registry import prune_checkpoints

    state = _train_state()
    path1 = _save_zs(tmp_path, 1, state)
    doc1 = read_manifest(path1)
    path2 = _save_zs(tmp_path, 2, _perturb(state, 2))
    doc2_refs = set()
    for e in read_manifest(path2)["leaves"]:
        doc2_refs.update(e["chunks"])
    only_in_1 = {
        d for e in doc1["leaves"] for d in e["chunks"]
    } - doc2_refs
    assert only_in_1  # the perturbed leaves' old chunks

    # reader pins manifest 1 mid-"fetch"; trainer retention prunes it
    lease = pins.pin_manifest(tmp_path, path1, doc1, owner="reader")
    prune_checkpoints(tmp_path, 1, engine="zerostall")
    assert not path1.exists()
    collect_garbage(tmp_path)
    root = chunks_root(tmp_path)
    for d in only_in_1:
        assert chunk_path(root, d).exists(), (
            "GC collected a pinned manifest's chunk mid-fetch"
        )
    # the reader can still assemble every leaf, digests verified
    flat, stats = fetch_params_incremental(
        tmp_path, doc1, None, None, manifest_path=path1,
    )
    assert stats["fetched_bytes"] > 0 and stats["reused_bytes"] == 0

    # lease expires (crashed reader) -> the chunks are reclaimable
    lease.release()
    collect_garbage(tmp_path)
    for d in only_in_1:
        assert not chunk_path(root, d).exists(), "stale chunks leaked"
    # store now holds exactly what the live manifest references
    on_disk = {p.name for p in root.rglob("*") if p.is_file()}
    assert on_disk == referenced_digests(tmp_path)


def test_stale_pin_expires_instead_of_blocking_gc(tmp_path, monkeypatch):
    state = _train_state()
    path1 = _save_zs(tmp_path, 1, state)
    pins.pin_manifest(tmp_path, path1, owner="dead-reader")
    path1.unlink()  # manifest gone, only the stale pin references chunks
    monkeypatch.setenv(pins.PIN_TTL_ENV, "0")
    collect_garbage(tmp_path)
    assert pins.live_pins(tmp_path) == []
    assert not any(chunks_root(tmp_path).rglob("*"))


# ---- chunk-digest diff + incremental fetch ------------------------------


def test_diff_manifest_chunks_accounting(tmp_path, monkeypatch):
    # tiny chunks so single leaves split into several chunks and the
    # diff is sub-leaf, not all-or-nothing
    monkeypatch.setenv("PYRECOVER_ZS_CHUNK_BYTES", "4096")
    state = _train_state()
    doc1 = read_manifest(_save_zs(tmp_path, 1, state))
    doc2 = read_manifest(_save_zs(tmp_path, 2, _perturb(state, 2)))
    diff = diff_manifest_chunks(doc1, doc2)
    assert diff["num_leaves"] == len(doc2["leaves"])
    assert 0 < diff["changed_leaves"] < diff["num_leaves"]
    assert diff["fetch_bytes"] + diff["reused_bytes"] == sum(
        int(e["nbytes"]) for e in doc2["leaves"]
    )
    by_path = {r["path"]: r for r in diff["leaves"]}
    assert by_path[".params['output']"]["changed"]
    assert not by_path[".params['tok_embed']"]["changed"]
    # identical docs: nothing to fetch
    same = diff_manifest_chunks(doc1, doc1)
    assert same["fetch_bytes"] == 0 and same["changed_leaves"] == 0
    # prefix restriction
    only_params = diff_manifest_chunks(doc1, doc2, prefix=".params")
    assert all(r["path"].startswith(".params")
               for r in only_params["leaves"])
    # incomparable chunk sizes -> all changed
    doc1_alt = json.loads(json.dumps(doc1))
    for e in doc1_alt["leaves"]:
        e["chunk_bytes"] = int(e["chunk_bytes"]) * 2
    alien = diff_manifest_chunks(doc1_alt, doc2)
    assert alien["reused_bytes"] == 0
    # a leaf absent from the old manifest is NEW (all fetched)
    doc1_missing = json.loads(json.dumps(doc1))
    doc1_missing["leaves"] = [
        e for e in doc1_missing["leaves"] if e["path"] != ".params['output']"
    ]
    miss = diff_manifest_chunks(doc1_missing, doc2)
    assert {r["path"]: r["new_leaf"] for r in miss["leaves"]}[
        ".params['output']"
    ]


def test_incremental_fetch_moves_only_changed_chunks(tmp_path, monkeypatch):
    monkeypatch.setenv("PYRECOVER_ZS_CHUNK_BYTES", "4096")
    state = _train_state()
    path1 = _save_zs(tmp_path, 1, state)
    doc1 = read_manifest(path1)
    flat1, stats1 = fetch_params_incremental(
        tmp_path, doc1, None, None, manifest_path=path1,
    )
    assert stats1["reused_bytes"] == 0  # cold: everything fetched
    host1 = dict(flat1)
    state2 = _perturb(state, 2)
    path2 = _save_zs(tmp_path, 2, state2)
    doc2 = read_manifest(path2)
    flat2, stats2 = fetch_params_incremental(
        tmp_path, doc2, doc1, host1, manifest_path=path2,
    )
    assert stats2["reused_bytes"] > 0
    diff = diff_manifest_chunks(doc1, doc2, prefix=".params")
    assert stats2["fetched_bytes"] == diff["fetch_bytes"]
    assert stats2["chunks_fetched"] == diff["chunks_changed"]
    # assembled leaves equal the saved state bit-for-bit
    want = {
        f".params['{k}']": v for k, v in state2.params.items()
        if not isinstance(v, dict)
    }
    got = dict(flat2)
    np.testing.assert_array_equal(
        got[".params['output']"], np.asarray(state2.params["output"])
    )
    for key in want:
        np.testing.assert_array_equal(got[key], np.asarray(want[key]))


def test_incremental_fetch_rejects_corrupt_cache_and_chunks(
        tmp_path, monkeypatch):
    """Every byte is digest-verified: a corrupted HOST cache entry falls
    back to a store fetch (never laundered into the swap), and a
    corrupted STORE chunk raises."""
    monkeypatch.setenv("PYRECOVER_ZS_CHUNK_BYTES", "4096")
    state = _train_state()
    path1 = _save_zs(tmp_path, 1, state)
    doc1 = read_manifest(path1)
    flat1, _ = fetch_params_incremental(
        tmp_path, doc1, None, None, manifest_path=path1,
    )
    host1 = dict(flat1)
    # corrupt the cached copy of an UNCHANGED leaf: the fetcher must
    # detect the digest mismatch and re-fetch from the store
    bad = np.array(host1[".params['tok_embed']"], copy=True)
    bad.reshape(-1)[0] += 1
    host1[".params['tok_embed']"] = bad
    path2 = _save_zs(tmp_path, 2, _perturb(state, 2))
    doc2 = read_manifest(path2)
    flat2, stats = fetch_params_incremental(
        tmp_path, doc2, doc1, host1, manifest_path=path2,
    )
    np.testing.assert_array_equal(
        dict(flat2)[".params['tok_embed']"],
        np.asarray(state.params["tok_embed"]),
    )
    # corrupt a store chunk a changed leaf needs -> hard failure
    entry = next(e for e in doc2["leaves"]
                 if e["path"] == ".params['output']")
    victim = chunk_path(chunks_root(tmp_path), entry["chunks"][0])
    data = bytearray(victim.read_bytes())
    data[0] ^= 0xFF
    victim.write_bytes(bytes(data))
    with pytest.raises(ValueError, match="digest|corrupt"):
        fetch_params_incremental(
            tmp_path, doc2, None, None, manifest_path=path2,
        )


# ---- the swapper --------------------------------------------------------


def test_swapper_polls_and_swaps_with_token_equality(tmp_path, mem_sink):
    state = _train_state()
    path1 = _save_zs(tmp_path, 1, state)
    params, _ = load_serving_params(path1, CFG)
    engine = ServingEngine(params, CFG, SCFG)
    before = _probe(engine)
    swapper = HotSwapper(engine, tmp_path, CFG, loaded_path=path1,
                         poll_interval_s=0.01)
    assert swapper.poll_once() is False  # nothing newer: no-op
    assert engine.weights_step == 1

    decode_cache = getattr(engine._decode_fn, "_cache_size", None)
    compiled_before = decode_cache() if decode_cache else None

    state2 = _perturb(state, 2)
    path2 = _save_zs(tmp_path, 2, state2)
    assert swapper.poll_once() is True
    assert swapper.loaded_step == 2
    after = _probe(engine)  # manual pump applies the staged flip first
    assert engine.weights_step == 2
    # the weights genuinely moved: the served params now carry the NEW
    # state's perturbed leaves bit-for-bit (token diffs are not a
    # reliable witness — a tiny perturbation can keep every argmax)
    np.testing.assert_array_equal(
        np.asarray(engine.params["output"]),
        np.asarray(state2.params["output"]),
    )
    assert not np.array_equal(
        np.asarray(engine.params["output"]),
        np.asarray(state.params["output"]),
    )
    del before  # the probes before/after may legitimately coincide

    # cold restore of the new manifest serves identically (token-level)
    cold = ServingEngine(load_serving_params(path2, CFG)[0], CFG, SCFG)
    assert _probe(cold) == after

    # zero retraces: the swapped params are shape-stable, so the decode
    # program is reused (cache-size pin where this jax exposes it)
    if compiled_before is not None:
        assert decode_cache() == compiled_before

    events = {e["event"] for e in mem_sink.events}
    assert {"weights_swap_begin", "swap_fetch_bytes",
            "weights_swap_done"} <= events
    done = [e for e in mem_sink.events
            if e["event"] == "weights_swap_done"][0]
    assert done["step"] == 2 and done["from_step"] == 1
    fetch = [e for e in mem_sink.events
             if e["event"] == "swap_fetch_bytes"][0]
    assert fetch["incremental"] and fetch["reused_bytes"] > 0
    params_bytes = sum(
        int(e["nbytes"]) for e in read_manifest(path2)["leaves"]
        if e["path"].startswith(".params")
    )
    assert fetch["fetched_bytes"] + fetch["reused_bytes"] == params_bytes
    assert fetch["fetched_bytes"] < params_bytes


def test_swap_applies_at_step_boundary_midflight_untouched(tmp_path):
    """A request in flight across the flip completes correctly: the pump
    applies the staged swap BEFORE a pass, never inside one, and the
    finished tokens match an engine that served the same request with
    the flip staged at the same boundary."""
    state = _train_state()
    path1 = _save_zs(tmp_path, 1, state)
    params, _ = load_serving_params(path1, CFG)
    engine = ServingEngine(params, CFG, SCFG)
    rid = engine.submit([3, 1, 4, 1, 5], 8)
    # partial progress on the old weights
    for _ in range(3):
        engine.step()
    assert engine.result(rid) is None  # genuinely mid-flight
    state2 = _perturb(state, 5)
    path2 = _save_zs(tmp_path, 2, state2)
    swapper = HotSwapper(engine, tmp_path, CFG, loaded_path=path1)
    assert swapper.poll_once()
    engine.run_until_drained()
    got = engine.result(rid)
    assert got is not None and len(got) == 5 + 8
    # in-flight requests are untouched in the sense that they complete
    # and release cleanly across the flip
    engine.pool.check_drained()


def test_swapper_rejects_tampered_manifest_and_keeps_serving(
        tmp_path, mem_sink):
    state = _train_state()
    path1 = _save_zs(tmp_path, 1, state)
    params, _ = load_serving_params(path1, CFG)
    engine = ServingEngine(params, CFG, SCFG)
    before = _probe(engine)
    path2 = _save_zs(tmp_path, 2, _perturb(state, 2))
    # flip a byte in a chunk the new manifest needs
    entry = next(e for e in read_manifest(path2)["leaves"]
                 if e["path"] == ".params['output']")
    victim = chunk_path(chunks_root(tmp_path), entry["chunks"][0])
    data = bytearray(victim.read_bytes())
    data[10] ^= 0xFF
    victim.write_bytes(bytes(data))

    swapper = HotSwapper(engine, tmp_path, CFG, loaded_path=path1)
    assert swapper.poll_once() is False
    rejected = [e for e in mem_sink.events
                if e["event"] == "weights_swap_rejected"]
    assert rejected and rejected[0]["to_step"] == 2
    assert "digest" in rejected[0]["reason"] or "corrupt" in (
        rejected[0]["reason"]
    )
    assert swapper.loaded_step == 1 and engine.weights_step == 1
    assert _probe(engine) == before  # old weights still serving
    # no retry loop against the bad artifact...
    assert swapper.poll_once() is False
    assert len([e for e in mem_sink.events
                if e["event"] == "weights_swap_rejected"]) == 1
    # ...but a NEWER good manifest swaps normally
    _save_zs(tmp_path, 3, _perturb(state, 3))
    assert swapper.poll_once() is True
    assert swapper.loaded_step == 3
    # the fetch rebuilt its reuse cache from the engine's own leaves
    # (lazily, digest-checked) rather than fetching everything
    fetch = [e for e in mem_sink.events
             if e["event"] == "swap_fetch_bytes"][-1]
    assert fetch["reused_bytes"] > 0


def test_swapper_rejects_shape_unstable_checkpoint(tmp_path, mem_sink):
    """A checkpoint from a different model config must be rejected
    BEFORE staging (the zero-retrace contract)."""
    state = _train_state()
    path1 = _save_zs(tmp_path, 1, state)
    params, _ = load_serving_params(path1, CFG)
    engine = ServingEngine(params, CFG, SCFG)

    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.train_state import create_train_state

    other_cfg = ModelConfig().tiny(
        max_seq_len=96, vocab_size=32, compute_dtype="float32",
        param_dtype="float32",
    )
    optimizer, _ = build_optimizer(TrainConfig())
    other = create_train_state(jax.random.key(1), other_cfg, optimizer)
    _save_zs(tmp_path, 2, other)
    swapper = HotSwapper(engine, tmp_path, CFG, loaded_path=path1)
    assert swapper.poll_once() is False
    rejected = [e for e in mem_sink.events
                if e["event"] == "weights_swap_rejected"]
    assert rejected and "shape" in rejected[0]["reason"].lower()
    assert engine.weights_step == 1


def test_swapper_full_load_fallback_for_vanilla(tmp_path, mem_sink):
    """Non-zerostall checkpoints hot-swap through the full serving
    restore — same API, reused_bytes 0."""
    from pyrecover_tpu.checkpoint.vanilla import save_ckpt_vanilla

    state = _train_state()
    path1 = tmp_path / "ckpt_1.ckpt"
    save_ckpt_vanilla(path1, state, {})
    params, _ = load_serving_params(path1, CFG)
    engine = ServingEngine(params, CFG, SCFG)
    swapper = HotSwapper(engine, tmp_path, CFG, loaded_path=path1)
    state2 = _perturb(state, 4)
    path2 = tmp_path / "ckpt_2.ckpt"
    save_ckpt_vanilla(path2, state2, {})
    assert swapper.poll_once() is True
    after = _probe(engine)
    cold = ServingEngine(load_serving_params(path2, CFG)[0], CFG, SCFG)
    assert _probe(cold) == after
    fetch = [e for e in mem_sink.events
             if e["event"] == "swap_fetch_bytes"][0]
    assert not fetch["incremental"] and fetch["reused_bytes"] == 0


def test_swapper_watcher_thread_bounded_lifecycle(tmp_path):
    state = _train_state()
    path1 = _save_zs(tmp_path, 1, state)
    params, _ = load_serving_params(path1, CFG)
    engine = ServingEngine(params, CFG, SCFG)
    swapper = HotSwapper(engine, tmp_path, CFG, loaded_path=path1,
                         poll_interval_s=0.01)
    swapper.start()
    with pytest.raises(RuntimeError, match="already running"):
        swapper.start()
    engine.start()
    try:
        _save_zs(tmp_path, 2, _perturb(state, 2))
        deadline = __import__("time").monotonic() + 30.0
        while swapper.loaded_step < 2:
            assert __import__("time").monotonic() < deadline, (
                "watcher never picked up the new manifest"
            )
            __import__("time").sleep(0.01)
    finally:
        engine.stop()
        swapper.stop()
    assert swapper._thread is None  # joined, not leaked
    swapper.stop()  # idempotent


# ---- open-loop load generator (satellite 3) -----------------------------


def test_open_loop_workload_fixed_duration_deterministic():
    w1 = open_loop_workload(2.0, vocab_size=64, max_model_len=96, seed=3,
                            arrival_rate=100.0)
    w2 = open_loop_workload(2.0, vocab_size=64, max_model_len=96, seed=3,
                            arrival_rate=100.0)
    assert w1 == w2  # deterministic in seed
    assert w1 != open_loop_workload(2.0, vocab_size=64, max_model_len=96,
                                    seed=4, arrival_rate=100.0)
    assert all(r["arrival_s"] < 2.0 for r in w1)
    arrivals = [r["arrival_s"] for r in w1]
    assert arrivals == sorted(arrivals)
    # ~rate*duration requests (Poisson: loose 3-sigma-ish band)
    assert 140 <= len(w1) <= 260
    assert all(
        len(r["prompt"]) + r["max_new_tokens"] <= 96 for r in w1
    )
    # longer window, same seed: strictly more offered load
    w3 = open_loop_workload(4.0, vocab_size=64, max_model_len=96, seed=3,
                            arrival_rate=100.0)
    assert len(w3) > len(w1)


# ---- serving restore tamper rejection (satellite 4) ---------------------


def _flip_byte(path, offset_frac=0.75):
    data = bytearray(Path(path).read_bytes())
    idx = int(len(data) * offset_frac)
    data[idx] ^= 0xFF
    Path(path).write_bytes(bytes(data))


def test_restore_rejects_tampered_vanilla_before_placement(tmp_path):
    from pyrecover_tpu.checkpoint.vanilla import save_ckpt_vanilla

    state = _train_state()
    path = tmp_path / "ckpt_1.ckpt"
    save_ckpt_vanilla(path, state, {}, verify=True)  # checksum sidecar
    load_serving_params(path, CFG)  # intact: loads
    _flip_byte(path)  # a tensor-frame byte: decodes silently without gate
    with pytest.raises(ServingRestoreError, match="checksum"):
        load_serving_params(path, CFG)


def _save_sharded_sync(path, state):
    from pyrecover_tpu.checkpoint.sharded import save_ckpt_sharded

    save_ckpt_sharded(path, state, {})


def _save_sharded_async(path, state):
    """The trainer's engine: the digests are hashed by the save's commit."""
    from pyrecover_tpu.checkpoint.sharded import ShardedCheckpointer

    with ShardedCheckpointer(use_async=True) as ckptr:
        ckptr.save(path, state, {})
        ckptr.wait()


def _save_sharded_parent_layout(path, state):
    """A directory as the engine wrote it before the hash moved to the
    commit: Orbax's own JSON item, the digests hashed inline beforehand."""
    import numpy as np
    import orbax.checkpoint as ocp

    from pyrecover_tpu.analysis.shardcheck.manifest import state_manifest
    from pyrecover_tpu.checkpoint.zerostall.chunkstore import leaf_digest
    from pyrecover_tpu.parallel.mesh import state_topology

    digests = {
        ".params" + jax.tree_util.keystr(p): leaf_digest(np.asarray(leaf))
        for p, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]
    }
    with ocp.Checkpointer(ocp.CompositeCheckpointHandler()) as ckptr:
        ckptr.save(path.absolute(), args=ocp.args.Composite(
            state=ocp.args.PyTreeSave(state),
            meta=ocp.args.JsonSave({
                "sampler": {}, "manifest": state_manifest(state),
                "topology": state_topology(state), "leaf_digests": digests,
            }),
        ))


@pytest.mark.parametrize(
    "save", [_save_sharded_sync, _save_sharded_async,
             _save_sharded_parent_layout],
    ids=["sync", "async", "parent_layout"])
def test_restore_rejects_tampered_sharded_before_placement(tmp_path, save):
    state = _train_state()
    path = tmp_path / "ckpt_1"
    save(path, state)
    load_serving_params(path, CFG)  # intact: loads
    # flip a byte in the largest tensorstore data file (Orbax's raw read
    # verifies nothing — the recorded leaf digests must catch it)
    victim = max(
        (p for p in path.rglob("*") if p.is_file() and "d" in p.parts),
        key=lambda p: p.stat().st_size,
    )
    _flip_byte(victim, 0.5)
    with pytest.raises(ServingRestoreError, match="digest"):
        load_serving_params(path, CFG)


def test_restore_rejects_tampered_zerostall_before_placement(tmp_path):
    state = _train_state()
    path = _save_zs(tmp_path, 1, state)
    load_serving_params(path, CFG)  # intact: loads
    entry = next(e for e in read_manifest(path)["leaves"]
                 if e["path"] == ".params['output']")
    _flip_byte(chunk_path(chunks_root(tmp_path), entry["chunks"][0]), 0.5)
    with pytest.raises(Exception, match="digest|corrupt"):
        load_serving_params(path, CFG)


# ---- tools: --diff-manifests ------------------------------------------


def test_inspect_checkpoint_diff_manifests_cli(tmp_path, capsys):
    sys.path.insert(0, str(REPO / "tools"))
    import inspect_checkpoint as ic

    state = _train_state()
    p1 = _save_zs(tmp_path, 1, state)
    p2 = _save_zs(tmp_path, 2, _perturb(state, 2))
    assert ic.main(["--diff-manifests", str(p1), str(p2)]) == 0
    out = capsys.readouterr().out
    assert "bytes to fetch" in out and "changed" in out
    assert ".params['output']" in out
    assert ic.main(["--diff-manifests", str(p1), str(p2), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["changed_leaves"] >= 1 and doc["reused_bytes"] > 0
    # non-zerostall inputs are refused, not mis-diffed
    other = tmp_path / "ckpt_3.ckpt"
    other.write_bytes(b"not a manifest")
    assert ic.main(["--diff-manifests", str(p1), str(other)]) == 2


# ---- summarizer: the hot-swap section ----------------------------------


def test_summarizer_renders_hotswap_section():
    sys.path.insert(0, str(REPO / "tools"))
    import summarize_telemetry as st

    events = [{"ts": 0.0, "event": "run_start", "host": 0}]
    events.append({"ts": 5.0, "event": "weights_swap_begin", "host": 0,
                   "path": "ckpt_2.zs.json", "engine": "zerostall",
                   "from_step": 1, "to_step": 2})
    events.append({"ts": 5.2, "event": "swap_fetch_bytes", "host": 0,
                   "path": "ckpt_2.zs.json", "incremental": True,
                   "fetched_bytes": 1000, "reused_bytes": 9000,
                   "chunks_fetched": 1, "chunks_reused": 9,
                   "changed_leaves": 1, "leaves": 10})
    events.append({"ts": 5.3, "event": "weights_swap_done", "host": 0,
                   "step": 2, "swap_s": 0.3, "in_flight": 2,
                   "fetched_bytes": 1000, "reused_bytes": 9000,
                   "path": "ckpt_2.zs.json", "from_step": 1})
    for i in range(8):
        events.append({"ts": 5.0 + 0.1 * i, "event": "request_done",
                       "host": 0, "rid": i, "prompt_tokens": 4,
                       "new_tokens": 4, "blocks_released": 1,
                       "ttft_s": 0.01, "tpot_s": 0.002,
                       "e2e_s": 0.02 * (i + 1)})
    events.append({"ts": 9.0, "event": "weights_swap_rejected", "host": 0,
                   "path": "ckpt_3.zs.json", "engine": "zerostall",
                   "from_step": 2, "to_step": 3,
                   "reason": "ValueError: chunk digest mismatch"})
    agg = st.aggregate(events)
    hs = agg["hotswap"]
    assert hs["swaps"] == 1 and hs["rejected"] == 1
    assert hs["fetched_bytes"] == 1000 and hs["reused_bytes"] == 9000
    assert hs["last_step"] == 2
    assert hs["swap_window_requests"] == 8  # all inside begin..done+1s
    assert hs["swap_window_e2e_p99"] == pytest.approx(0.16, abs=0.021)
    out = io.StringIO()
    st.render(agg, out)
    text = out.getvalue()
    assert "hot-swap (train→serve weights)" in text
    assert "bytes fetched" in text and "p99 across swaps" in text
    assert "REJECTED" in text and "digest mismatch" in text
    # an empty stream renders no hot-swap section
    quiet = st.aggregate([{"ts": 0.0, "event": "run_start", "host": 0}])
    assert quiet["hotswap"] == {}


# ---- catalog + hygiene pins --------------------------------------------


def test_hotswap_events_documented_in_both_catalogs():
    from conftest import assert_observed

    assert_observed(
        events=("weights_swap_begin", "weights_swap_done",
                "weights_swap_rejected", "swap_fetch_bytes"),
    )
    readme = (REPO / "README.md").read_text()
    assert "## Zero-downtime hot-swap" in readme
    # cross-links the satellite demands
    assert "#zero-downtime-hot-swap" in readme


def test_hotswap_host_apis_are_host_only_marked():
    import ast

    from pyrecover_tpu.analysis.engine import ModuleInfo

    expected = {
        "swap.py": {"start", "stop", "poll_once", "swap_to"},
        "fetch.py": {"fetch_leaf_incremental", "fetch_params_incremental"},
        "drill.py": {"hotswap_smoke", "hotswap_chaos_drill"},
    }
    pkg = REPO / "pyrecover_tpu" / "serving" / "hotswap"
    for rel, names in expected.items():
        p = pkg / rel
        mi = ModuleInfo(p, p.read_text(), relpath=p)
        marked = set()
        for node in ast.walk(mi.tree):
            if isinstance(node, ast.FunctionDef) and (
                "host-only" in mi.function_markers(node)
            ):
                marked.add(node.name)
        missing = names - marked
        assert not missing, f"{rel}: unmarked host APIs {sorted(missing)}"


# ---- the format.sh gates (slow) ----------------------------------------


@pytest.mark.slow
def test_hotswap_smoke_gate(tmp_path):
    from pyrecover_tpu.serving.hotswap import hotswap_smoke

    report = hotswap_smoke(tmp_path, duration_s=2.0, n_saves=2, seed=0)
    assert report["swaps"] >= 1 and report["rejected"] == 0
    assert report["token_equal"]
    assert report["reused_bytes"] > 0
    assert report["fetched_bytes"] < report["swaps"] * report["params_bytes"]
    assert report["p99_e2e_s"] <= report["p99_gate_s"]
    shard = tmp_path / "hotswap_telemetry.jsonl"
    events = {e["event"] for e in telemetry.read_events(shard)}
    assert {"weights_swap_begin", "weights_swap_done",
            "swap_fetch_bytes", "request_done"} <= events


@pytest.mark.slow
def test_hotswap_chaos_drill(tmp_path):
    from pyrecover_tpu.serving.hotswap import hotswap_chaos_drill

    report = hotswap_chaos_drill(tmp_path, seed=0)
    assert report["kill_rc"] == -9
    assert report["old_manifest_probe_equal"]
    assert report["resumed_swap_step"] == 2
    assert report["quarantined"] == [] and report["chunks_leaked"] == 0
    assert report["pin_after_kill"]
