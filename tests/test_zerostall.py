"""Zero-stall checkpoint engine: content-addressed chunk store (dedup +
refcounted GC), async snapshot pipeline (backpressure, fault seams, torn
saves), in-RAM emergency tier (strict digest gate), mixed-engine registry
discovery, goodput blocking/shadow split, and the committed traceview
baseline that pins the >=5x blocking-save win."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint import (
    checkpoint_path,
    engine_of,
    get_latest_checkpoint,
    list_checkpoints,
    load_ckpt_zerostall,
    precheck_ckpt_zerostall,
    prune_checkpoints,
    save_ckpt_vanilla,
    save_ckpt_zerostall,
)
from pyrecover_tpu.checkpoint.registry import (
    VANILLA_SUFFIX,
    ZEROSTALL_SUFFIX,
    parse_step,
)
from pyrecover_tpu.checkpoint.vanilla import CheckpointStructureError
from pyrecover_tpu.checkpoint.zerostall import chunkstore, emergency
from pyrecover_tpu.config import TrainConfig
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.optim import build_optimizer
from pyrecover_tpu.resilience import faults
from pyrecover_tpu.train_state import create_train_state

CFG = TrainConfig(sequence_length=32)
MODEL_CFG = ModelConfig().tiny(max_seq_len=32)


def make_state(seed=0):
    optimizer, _ = build_optimizer(CFG)
    return create_train_state(jax.random.key(seed), MODEL_CFG, optimizer)


def leaves_np(state):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(state)]


@pytest.fixture(autouse=True)
def _clean_engine_state(monkeypatch):
    """Small chunks (so tiny leaves split into several), a clean
    emergency store, and no leftover fault plan — per test."""
    monkeypatch.setenv(chunkstore.CHUNK_BYTES_ENV, "4096")
    emergency.drop()
    faults.clear()
    yield
    emergency.drop()
    faults.clear()


@pytest.fixture()
def sink():
    s = telemetry.MemorySink()
    telemetry.add_sink(s)
    yield s
    telemetry.remove_sink(s)


def events(sink, name):
    return [e for e in sink.events if e["event"] == name]


# ---------------------------------------------------------------------------
# chunk store
# ---------------------------------------------------------------------------


def test_chunk_digest_is_content_addressed(tmp_path):
    store = chunkstore.ChunkStore(tmp_path)
    d1 = store.put(b"hello world")
    d2 = store.put(b"hello world")
    d3 = store.put(b"hello worle")
    assert d1 == d2 != d3
    assert store.written_chunks == 2 and store.reused_chunks == 1
    # the address IS the checksum: reads verify it
    assert store.get(d1) == b"hello world"
    p = chunkstore.chunk_path(store.root, d1)
    p.write_bytes(b"hello wOrld")
    with pytest.raises(ValueError, match="does not match its address"):
        store.get(d1)


def test_expected_chunk_sizes_layout():
    assert chunkstore.expected_chunk_sizes(0, 4) == [0]
    assert chunkstore.expected_chunk_sizes(4, 4) == [4]
    assert chunkstore.expected_chunk_sizes(9, 4) == [4, 4, 1]


def test_roundtrip_bitexact(tmp_ckpt_dir):
    state = make_state(seed=1)
    sampler_state = {"epoch": 2, "cursor": 8, "seed": 5,
                     "global_batch_size": 4, "num_samples": 100,
                     "shuffle": True}
    path = checkpoint_path(tmp_ckpt_dir, "exp", 3, engine="zerostall")
    assert path.name == f"ckpt_3{ZEROSTALL_SUFFIX}"
    secs = save_ckpt_zerostall(path, state, sampler_state,
                               extra_meta={"step": 3, "epoch": 2},
                               background=False)
    assert secs >= 0 and path.exists()
    target = make_state(seed=99)  # different values, same structure
    restored, restored_sampler, meta = load_ckpt_zerostall(path, target)
    for a, b in zip(leaves_np(state), leaves_np(restored)):
        np.testing.assert_array_equal(a, b)
    assert restored_sampler["cursor"] == 8
    assert meta["step"] == 3
    # shardings land on the TARGET's (restore reshards like vanilla)
    for t, r in zip(jax.tree_util.tree_leaves(target),
                    jax.tree_util.tree_leaves(restored)):
        if isinstance(t, jax.Array) and hasattr(t, "sharding"):
            assert r.sharding.is_equivalent_to(t.sharding, t.ndim)


def test_second_save_dedups_unchanged_leaves(tmp_ckpt_dir, sink):
    """Acceptance: a second consecutive save of an unchanged-except-hot-
    leaves state writes measurably fewer bytes, provable from the
    manifest's per-leaf chunk reuse counts."""
    state = make_state(seed=2)
    p1 = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    save_ckpt_zerostall(p1, state, extra_meta={"step": 1}, background=False)
    doc1 = chunkstore.read_manifest(p1)

    # touch ONE leaf (the "hot" one); everything else stays cold
    leaves, treedef = jax.tree_util.tree_flatten(state)
    leaves = list(leaves)
    leaves[0] = leaves[0] + jnp.ones_like(leaves[0])
    state2 = jax.tree_util.tree_unflatten(treedef, leaves)
    p2 = checkpoint_path(tmp_ckpt_dir, "exp", 2, engine="zerostall")
    save_ckpt_zerostall(p2, state2, extra_meta={"step": 2}, background=False)
    doc2 = chunkstore.read_manifest(p2)

    assert doc2["reuse"]["bytes_written"] < doc1["reuse"]["bytes_written"]
    # per-leaf reuse counts: every untouched leaf reuses ALL its chunks
    hot = doc2["leaves"][0]
    cold = doc2["leaves"][1:]
    assert hot["reused"] < len(hot["chunks"])
    for entry in cold:
        assert entry["reused"] == len(entry["chunks"]), entry["path"]
    # the ledger also rides the ckpt_commit event
    commits = events(sink, "ckpt_commit")
    assert commits and commits[-1]["reused_bytes"] > 0


def test_gc_collects_orphans_keeps_referenced(tmp_ckpt_dir, sink):
    state = make_state(seed=3)
    exp = tmp_ckpt_dir / "exp"
    p1 = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    save_ckpt_zerostall(p1, state, extra_meta={"step": 1}, background=False)
    # orphan chunks: a torn save that died before its manifest commit
    store = chunkstore.ChunkStore(exp)
    orphan = store.put(b"\x01" * 5000)
    orphan_path = chunkstore.chunk_path(store.root, orphan)
    assert orphan_path.exists()
    removed, removed_bytes = chunkstore.collect_garbage(exp)
    assert removed == 1 and removed_bytes == 5000
    assert not orphan_path.exists()
    # every chunk the live manifest references survived
    ok, why = precheck_ckpt_zerostall(p1, verify=True)
    assert ok, why
    assert events(sink, "ckpt_gc")


def test_gc_respects_quarantined_manifests(tmp_ckpt_dir):
    """A quarantined manifest is forensic evidence: its chunks must stay
    restorable until the corpse is deleted deliberately."""
    from pyrecover_tpu.resilience.quarantine import quarantine_checkpoint

    state = make_state(seed=4)
    exp = tmp_ckpt_dir / "exp"
    p1 = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    save_ckpt_zerostall(p1, state, extra_meta={"step": 1}, background=False)
    n_chunks = sum(
        1 for p in chunkstore.chunks_root(exp).rglob("*") if p.is_file()
    )
    quarantine_checkpoint(p1, reason="test")
    removed, _ = chunkstore.collect_garbage(exp)
    assert removed == 0
    assert sum(
        1 for p in chunkstore.chunks_root(exp).rglob("*") if p.is_file()
    ) == n_chunks


def test_prune_triggers_refcounted_gc_through_save(tmp_ckpt_dir):
    """max_keep retention on the zerostall engine prunes manifests AND
    reclaims the chunk bytes only they referenced — while chunks shared
    with surviving manifests stay put."""
    state = make_state(seed=5)
    exp = tmp_ckpt_dir / "exp"
    for step in (1, 2, 3):
        # vary the state each step so each save writes some unique chunks
        leaves, treedef = jax.tree_util.tree_flatten(state)
        leaves = [x + step for x in leaves]
        state = jax.tree_util.tree_unflatten(treedef, leaves)
        save_ckpt_zerostall(
            checkpoint_path(tmp_ckpt_dir, "exp", step, engine="zerostall"),
            state, max_keep=2, extra_meta={"step": step}, background=False,
        )
    manifests = list_checkpoints(exp, engine="zerostall")
    assert [parse_step(p) for p in manifests] == [2, 3]
    on_disk = {
        p.name for p in chunkstore.chunks_root(exp).rglob("*") if p.is_file()
    }
    assert on_disk == chunkstore.referenced_digests(exp)


# ---------------------------------------------------------------------------
# snapshot pipeline: background saves, backpressure, fault seams
# ---------------------------------------------------------------------------


def test_background_save_handle_and_shadow(tmp_ckpt_dir, sink):
    state = make_state(seed=6)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    blocking_s, handle = save_ckpt_zerostall(
        path, state, extra_meta={"step": 1}, background=True,
    )
    handle.wait()
    assert handle.error is None and handle.shadow_s > 0
    assert path.exists()
    blk = events(sink, "ckpt_save_blocking")
    shd = events(sink, "ckpt_save_shadow")
    assert blk and blk[-1]["engine"] == "zerostall" and blk[-1]["background"]
    assert shd and shd[-1]["ok"] and shd[-1]["shadow_s"] >= 0


def test_backpressure_is_bounded_and_loud(tmp_ckpt_dir, sink, monkeypatch):
    """Depth-1 in-flight queue: a save arriving while the previous one is
    still writing WAITS and emits ckpt_backpressure — never a silent
    stall, never an unbounded queue."""
    real_commit = chunkstore.commit_manifest

    def slow_commit(path, doc):
        time.sleep(0.3)
        return real_commit(path, doc)

    monkeypatch.setattr(chunkstore, "commit_manifest", slow_commit)
    state = make_state(seed=7)
    p1 = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    p2 = checkpoint_path(tmp_ckpt_dir, "exp", 2, engine="zerostall")
    _, h1 = save_ckpt_zerostall(p1, state, extra_meta={"step": 1},
                                background=True)
    _, h2 = save_ckpt_zerostall(p2, state, extra_meta={"step": 2},
                                background=True)
    h2.wait()
    assert h1.done  # the queue forced save 2 behind save 1
    bp = events(sink, "ckpt_backpressure")
    assert bp and bp[-1]["wait_s"] > 0.1


def test_background_save_error_surfaces_at_wait(tmp_ckpt_dir, monkeypatch):
    def exploding_commit(path, doc):
        raise RuntimeError("injected commit failure")

    monkeypatch.setattr(chunkstore, "commit_manifest", exploding_commit)
    state = make_state(seed=8)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    _, handle = save_ckpt_zerostall(path, state, extra_meta={"step": 1},
                                    background=True)
    with pytest.raises(RuntimeError, match="injected commit failure"):
        handle.wait()
    assert not path.exists()  # nothing published


def test_transient_chunk_write_error_heals_via_retry(tmp_ckpt_dir, sink):
    faults.install({"seed": 0, "faults": [
        {"type": "transient_io_error", "op": "chunk_write", "fail_count": 2},
    ]})
    state = make_state(seed=9)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    save_ckpt_zerostall(path, state, extra_meta={"step": 1},
                        background=False)
    assert path.exists()
    retries = events(sink, "ckpt_io_retry")
    assert retries and all(r["op"] == "chunk_write" for r in retries)
    ok, why = precheck_ckpt_zerostall(path, verify=True)
    assert ok, why


def test_kill9_site_validation():
    with pytest.raises(faults.FaultPlanError, match="unknown site"):
        faults.FaultEngine({"faults": [
            {"type": "kill9_during_save", "site": "ckpt_nonsense"},
        ]})
    # the zerostall seams are legal kill sites
    eng = faults.FaultEngine({"faults": [
        {"type": "kill9_during_save", "site": s}
        for s in ("ckpt_snapshot", "ckpt_chunk_write",
                  "ckpt_manifest_commit")
    ]})
    assert len(eng.faults) == 3


def test_torn_save_leaves_previous_manifest_restorable(tmp_ckpt_dir):
    """The commit-point property, in-process: chunks written but no
    manifest published == the previous checkpoint is still `latest`, and
    GC reclaims the orphans."""
    state = make_state(seed=10)
    exp = tmp_ckpt_dir / "exp"
    p1 = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    save_ckpt_zerostall(p1, state, extra_meta={"step": 1}, background=False)

    # a "save" that dies between chunk writes and the manifest commit
    store = chunkstore.ChunkStore(exp)
    for arr in leaves_np(make_state(seed=11)):
        chunkstore.write_leaf(store, arr, 4096)
    assert store.written_bytes > 0  # the torn save really wrote chunks

    assert get_latest_checkpoint(exp, engine="zerostall") == p1
    removed, _ = chunkstore.collect_garbage(exp)
    assert removed > 0
    restored, _, _ = load_ckpt_zerostall(p1, make_state(seed=12))
    for a, b in zip(leaves_np(state), leaves_np(restored)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# precheck
# ---------------------------------------------------------------------------


def test_precheck_rejects_torn_manifest_and_missing_chunks(tmp_ckpt_dir):
    state = make_state(seed=13)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    save_ckpt_zerostall(path, state, extra_meta={"step": 1},
                        background=False)
    ok, _ = precheck_ckpt_zerostall(path, verify=True)
    assert ok

    # torn manifest (truncated JSON)
    torn = path.read_text()[: len(path.read_text()) // 2]
    p_torn = path.parent / f"ckpt_2{ZEROSTALL_SUFFIX}"
    p_torn.write_text(torn)
    ok, why = precheck_ckpt_zerostall(p_torn)
    assert not ok and why

    # missing chunk
    doc = chunkstore.read_manifest(path)
    victim = doc["leaves"][0]["chunks"][0]
    chunkstore.chunk_path(chunkstore.chunks_root(path.parent), victim).unlink()
    ok, why = precheck_ckpt_zerostall(path)
    assert not ok and "missing chunk" in why


def test_precheck_digest_rehash_catches_bitflips(tmp_ckpt_dir):
    state = make_state(seed=14)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    save_ckpt_zerostall(path, state, extra_meta={"step": 1},
                        background=False)
    doc = chunkstore.read_manifest(path)
    victim = chunkstore.chunk_path(
        chunkstore.chunks_root(path.parent), doc["leaves"][0]["chunks"][0]
    )
    data = bytearray(victim.read_bytes())
    data[0] ^= 0xFF
    victim.write_bytes(bytes(data))
    # size-only walk passes (same length), the digest rehash does not
    ok, _ = precheck_ckpt_zerostall(path)
    assert ok
    ok, why = precheck_ckpt_zerostall(path, verify=True)
    assert not ok and "digest" in why
    with pytest.raises(ValueError, match="digest"):
        load_ckpt_zerostall(path, make_state(seed=15))


def test_precheck_wrong_model_raises_structure_error(tmp_ckpt_dir):
    state = make_state(seed=16)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    save_ckpt_zerostall(path, state, extra_meta={"step": 1},
                        background=False)
    other_cfg = MODEL_CFG.tiny(dim=32)
    optimizer, _ = build_optimizer(CFG)
    target = create_train_state(jax.random.key(0), other_cfg, optimizer)
    with pytest.raises(CheckpointStructureError):
        precheck_ckpt_zerostall(path, target_state=target)


# ---------------------------------------------------------------------------
# emergency tier
# ---------------------------------------------------------------------------


def test_emergency_publish_and_restore(tmp_ckpt_dir, sink):
    state = make_state(seed=17)
    exp = tmp_ckpt_dir / "exp"
    path = checkpoint_path(tmp_ckpt_dir, "exp", 5, engine="zerostall")
    save_ckpt_zerostall(path, state, {"consumed": 5},
                        extra_meta={"step": 5}, background=False)
    assert events(sink, "emergency_publish")
    step, record = emergency.peek(exp)
    assert step == 5
    ok, why = emergency.verify(record)
    assert ok, why
    restored, sampler, doc = emergency.restore(exp, make_state(seed=18))
    for a, b in zip(leaves_np(state), leaves_np(restored)):
        np.testing.assert_array_equal(a, b)
    assert sampler["consumed"] == 5 and doc["step"] == 5
    assert events(sink, "emergency_restore")


def test_emergency_strict_digest_gate_rejects_tampered_record(tmp_ckpt_dir):
    state = make_state(seed=19)
    exp = tmp_ckpt_dir / "exp"
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    save_ckpt_zerostall(path, state, extra_meta={"step": 1},
                        background=False)
    _, record = emergency.peek(exp)
    record["leaves"][0] = np.array(record["leaves"][0], copy=True)
    record["leaves"][0].reshape(-1)[0] += 1  # RAM rot
    ok, why = emergency.verify(record)
    assert not ok and "digests" in why
    with pytest.raises(ValueError, match="rejected"):
        emergency.restore(exp, make_state(seed=20))


def test_emergency_usable_gate(tmp_ckpt_dir):
    from pyrecover_tpu.parallel.mesh import state_topology

    state = make_state(seed=21)
    exp = tmp_ckpt_dir / "exp"
    path = checkpoint_path(tmp_ckpt_dir, "exp", 3, engine="zerostall")
    save_ckpt_zerostall(path, state, extra_meta={"step": 3},
                        background=False)
    topo = state_topology(state)
    assert emergency.usable(exp, topo, min_step=3) is not None
    # staler than the disk tier: never preferred
    assert emergency.usable(exp, topo, min_step=4) is None
    # different topology: the elastic disk path owns that restore
    other = dict(topo, devices=int(topo.get("devices", 1)) * 2,
                 mesh={"data": int(topo.get("devices", 1)) * 2})
    assert emergency.usable(exp, other, min_step=0) is None


# ---------------------------------------------------------------------------
# registry: mixed engines in one experiment dir
# ---------------------------------------------------------------------------


def _touch_mixed_exp(exp):
    exp.mkdir(parents=True, exist_ok=True)
    (exp / f"ckpt_10{VANILLA_SUFFIX}").write_bytes(b"v")
    (exp / f"ckpt_30{VANILLA_SUFFIX}").write_bytes(b"v")
    (exp / "ckpt_20").mkdir()  # sharded dir
    (exp / "ckpt_40").mkdir()
    (exp / f"ckpt_15{ZEROSTALL_SUFFIX}").write_text("{}")
    (exp / f"ckpt_25{ZEROSTALL_SUFFIX}").write_text("{}")


def test_mixed_engine_discovery_and_latest(tmp_path):
    exp = tmp_path / "exp"
    _touch_mixed_exp(exp)
    assert engine_of(exp / "ckpt_20") == "sharded"
    assert engine_of(exp / f"ckpt_10{VANILLA_SUFFIX}") == "vanilla"
    assert engine_of(exp / f"ckpt_15{ZEROSTALL_SUFFIX}") == "zerostall"

    assert [parse_step(p) for p in list_checkpoints(exp)] == \
        [10, 15, 20, 25, 30, 40]
    assert [parse_step(p) for p in list_checkpoints(exp, engine="vanilla")] \
        == [10, 30]
    assert [parse_step(p) for p in list_checkpoints(exp, engine="sharded")] \
        == [20, 40]
    assert [parse_step(p)
            for p in list_checkpoints(exp, engine="zerostall")] == [15, 25]
    # legacy tristate keeps its meaning — and zerostall manifests are
    # FILES, yet must never leak into the vanilla engine's view
    assert [parse_step(p) for p in list_checkpoints(exp, engine="vanilla")] \
        == [10, 30]
    assert parse_step(get_latest_checkpoint(exp, engine="vanilla")) == 30
    assert parse_step(get_latest_checkpoint(exp, engine="zerostall")) == 25
    assert parse_step(get_latest_checkpoint(exp)) == 40


def test_mixed_engine_prune_isolation(tmp_path):
    """Retention on one engine must never count or delete another
    engine's checkpoints (the pruning/GC isolation the mixed-engine
    layout depends on)."""
    exp = tmp_path / "exp"
    _touch_mixed_exp(exp)
    doomed = prune_checkpoints(exp, max_keep=1, engine="vanilla")
    assert [p.name for p in doomed] == [f"ckpt_10{VANILLA_SUFFIX}"]
    # zerostall + sharded untouched
    assert [parse_step(p)
            for p in list_checkpoints(exp, engine="zerostall")] == [15, 25]
    assert [parse_step(p) for p in list_checkpoints(exp, engine="sharded")] \
        == [20, 40]
    doomed = prune_checkpoints(exp, max_keep=1, engine="zerostall")
    assert [p.name for p in doomed] == [f"ckpt_15{ZEROSTALL_SUFFIX}"]
    assert [parse_step(p) for p in list_checkpoints(exp, engine="vanilla")] \
        == [30]


# ---------------------------------------------------------------------------
# elastic gate + goodput split + committed baseline
# ---------------------------------------------------------------------------


def test_elastic_gate_reads_zerostall_manifests(tmp_ckpt_dir):
    """The .zs.json manifest carries topology + the PR 3 schema manifest,
    so the elastic machinery (read_saved_meta → resume_gate) works on
    this engine unchanged."""
    from pyrecover_tpu.checkpoint import elastic

    state = make_state(seed=22)
    path = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    save_ckpt_zerostall(path, state, {"consumed": 1, "replicas": 8,
                                      "global_batch_size": 8},
                        extra_meta={"step": 1}, background=False)
    meta = elastic.read_saved_meta(path)
    # the unsharded test state spans 1 device; what matters is that the
    # topology record exists and round-trips through the manifest file
    assert meta["topology"]["devices"] >= 1
    assert meta["manifest"]["num_leaves"] > 0
    gate, reason, plan = elastic.resume_gate("auto", path, state)
    assert gate == elastic.GATE_OK, reason


def test_walltime_totals_blocking_shadow_split():
    from pyrecover_tpu.metrics import WallTimeTotals

    t = WallTimeTotals()
    t.wall_s, t.step_s = 100.0, 80.0
    t.ckpt_save_s = t.ckpt_blocking_s = 2.0
    t.ckpt_shadow_s = 30.0  # overlapped: must NOT count as lost
    d = t.as_dict()
    assert d["ckpt_blocking_s"] == 2.0 and d["ckpt_shadow_s"] == 30.0
    assert t.lost_s() == 2.0
    assert "shadow" in t.summary()


def test_summarizer_renders_blocking_vs_shadow(tmp_path, capsys):
    import summarize_telemetry as st

    stream = [
        {"ts": 1.0, "event": "run_start", "host": 0},
        {"ts": 2.0, "event": "ckpt_save_blocking", "host": 0,
         "engine": "zerostall", "path": "ckpt_3.zs.json",
         "blocking_s": 0.01, "background": True},
        {"ts": 2.5, "event": "ckpt_save_shadow", "host": 0,
         "engine": "zerostall", "path": "ckpt_3.zs.json",
         "shadow_s": 4.2, "ok": True},
        {"ts": 2.6, "event": "ckpt_backpressure", "host": 0,
         "engine": "zerostall", "path": "ckpt_6.zs.json", "wait_s": 0.4},
        {"ts": 2.7, "event": "emergency_publish", "host": 0,
         "engine": "zerostall", "step": 3, "leaves": 4, "bytes": 100},
        {"ts": 2.8, "event": "emergency_restore", "host": 0,
         "engine": "zerostall", "step": 3, "seconds": 0.004},
        {"ts": 3.0, "event": "run_summary", "host": 0, "status": "finished",
         "step": 8, "wall_s": 10.0, "step_s": 8.0, "productive_s": 8.0,
         "ckpt_save_s": 0.01, "ckpt_blocking_s": 0.01, "ckpt_shadow_s": 4.2,
         "ckpt_load_s": 0.0, "setup_s": 1.0, "eval_s": 0.0, "lost_s": 1.01,
         "replayed_s": 0.0, "replayed_steps": 0, "goodput_pct": 80.0},
    ]
    p = tmp_path / "t.jsonl"
    p.write_text("".join(json.dumps(e) + "\n" for e in stream))
    out_json = tmp_path / "out.json"
    assert st.main([str(p), "--json", str(out_json)]) == 0
    out = capsys.readouterr().out
    assert "recovered: shadow" in out
    assert "shadow 4.2s overlapped" in out
    assert "BACKPRESSURE" in out
    assert "emergency tier: 1 publishes, 1 RAM restores" in out
    blob = json.loads(out_json.read_text())
    assert blob["extra"]["totals"]["ckpt_shadow_s"] == 4.2
    assert blob["extra"]["ckpt"]["zerostall"]["shadow_s"] == 4.2
    assert blob["extra"]["ckpt_backpressure"]["count"] == 1
    assert blob["extra"]["emergency"]["restores"] == 1


def test_committed_baselines_pin_blocking_win():
    """Acceptance: on the bench tiny-model config (llama-150m state, the
    same state for both engines — bench.py --write-ckpt-baseline), the
    zerostall engine's blocking save time is >=5x lower than the vanilla
    engine's full save — pinned by the traceview-format baseline
    committed in the repo. The chaos-scale phase baseline (which
    format.sh gates regressions against) must carry the zerostall
    pipeline phases so a blocking-time regression fails the build."""
    from pathlib import Path

    basedir = Path(__file__).resolve().parent.parent / "baselines"
    bench = json.loads(
        (basedir / "ckpt_phase_bench_baseline.json").read_text()
    )
    zs_blocking = bench["zerostall:ckpt_blocking"]
    vanilla_save = bench["vanilla:ckpt_save"]
    assert zs_blocking > 0
    assert vanilla_save >= 5 * zs_blocking, (
        f"zerostall blocking p50 {zs_blocking}s must be >=5x below the "
        f"vanilla full-save p50 {vanilla_save}s"
    )
    chaos = json.loads((basedir / "ckpt_phase_baseline.json").read_text())
    for key in ("zerostall:ckpt_blocking", "zerostall:ckpt_snapshot",
                "zerostall:ckpt_chunk_write",
                "zerostall:ckpt_manifest_commit", "vanilla:ckpt_save"):
        assert key in chaos, f"regression-gate baseline lost {key}"


# ---------------------------------------------------------------------------
# driver-level coverage (slow tier, like the rest of the e2e suite)
# ---------------------------------------------------------------------------


def _tiny_config(tmp_path, **overrides):
    base = dict(
        sequence_length=32, batch_size=8, training_samples=64,
        training_steps=8, learning_rate=1e-3, lr_warmup_steps=2, seed=13,
        checkpoint_dir=str(tmp_path), checkpoint_frequency=4,
        experiment_name="zs", logging_frequency=100,
        verify_checkpoints=True, checkpoint_engine="zerostall",
        log_loss_to_csv=True,
    )
    base.update(overrides)
    cfg = TrainConfig(**base)
    cfg.model = ModelConfig().tiny(max_seq_len=32, vocab_size=128)
    cfg.__post_init__()
    return cfg


@pytest.mark.slow
def test_driver_zerostall_resume_bitexact(tmp_path):
    from pyrecover_tpu.train import train

    straight, _, _ = train(_tiny_config(tmp_path / "straight"))
    train(_tiny_config(tmp_path / "res", training_steps=4))
    emergency.drop()  # force the DISK tier path for this resume
    resumed, end, stopped = train(_tiny_config(
        tmp_path / "res", resume_from_checkpoint="latest",
    ))
    assert end == 8 and not stopped
    for a, b in zip(leaves_np(straight), leaves_np(resumed)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_driver_emergency_restore_with_disk_tier_deleted(tmp_path):
    """Acceptance: with the disk tier deleted, _resume restores the
    latest state from the in-memory tier and training continues with
    loss continuity (the stitched CSV equals the straight run's)."""
    import csv as csvlib
    import shutil

    from pyrecover_tpu.train import train

    straight_dir = tmp_path / "straight"
    straight, _, _ = train(_tiny_config(straight_dir))
    straight_rows = list(csvlib.reader(
        open(straight_dir / "zs" / "zs_loss_log.csv")
    ))

    res_dir = tmp_path / "res"
    train(_tiny_config(res_dir, training_steps=4))
    exp = res_dir / "zs"
    for p in list(exp.iterdir()):  # delete the ENTIRE disk tier
        if p.name.endswith(ZEROSTALL_SUFFIX):
            p.unlink()
    shutil.rmtree(exp / "chunks")
    assert list_checkpoints(exp, engine="zerostall") == []

    resumed, end, stopped = train(_tiny_config(
        res_dir, resume_from_checkpoint="latest",
    ))
    assert end == 8 and not stopped
    for a, b in zip(leaves_np(straight), leaves_np(resumed)):
        np.testing.assert_array_equal(a, b)
    rows = list(csvlib.reader(open(exp / "zs_loss_log.csv")))
    assert rows == straight_rows


@pytest.mark.slow
def test_driver_resume_falls_back_past_corrupt_manifest(tmp_path):
    """_resume fallback order on this engine: a corrupt newest manifest
    is quarantined and the walk falls back to the previous one."""
    from pyrecover_tpu.resilience.quarantine import list_quarantined
    from pyrecover_tpu.train import train

    train(_tiny_config(tmp_path, training_steps=8))
    exp = tmp_path / "zs"
    newest = get_latest_checkpoint(exp, engine="zerostall")
    assert parse_step(newest) == 8
    newest.write_text(newest.read_text()[:40])  # torn manifest
    emergency.drop()  # the RAM tier would mask the disk fallback

    _, end, _ = train(_tiny_config(
        tmp_path, training_steps=8, resume_from_checkpoint="latest",
    ))
    assert end == 8
    quarantined = [p.name for p in list_quarantined(exp)]
    assert any(p.startswith("ckpt_8") for p in quarantined)


@pytest.mark.slow
def test_driver_mixed_engines_resume_their_own(tmp_path):
    """vanilla and zerostall runs sharing one experiment dir stay
    isolated: each engine's `latest` resume finds its OWN newest
    checkpoint even when the other engine's is newer."""
    from pyrecover_tpu.train import train

    # vanilla run to step 4, then a LONGER zerostall run to step 8
    train(_tiny_config(tmp_path, training_steps=4,
                       checkpoint_engine="vanilla"))
    train(_tiny_config(tmp_path, training_steps=8))
    emergency.drop()
    # the vanilla resume must pick its own step-4 final, not the newer
    # zerostall manifests — and run 4 more steps to 8
    _, end, _ = train(_tiny_config(
        tmp_path, training_steps=8, checkpoint_engine="vanilla",
        resume_from_checkpoint="latest",
    ))
    assert end == 8
    # both engines' checkpoints coexist
    assert list_checkpoints(tmp_path / "zs", engine="vanilla")
    assert list_checkpoints(tmp_path / "zs", engine="zerostall")


# ---------------------------------------------------------------------------
# pin-lease error paths + the GC/prune fault seams (faultcheck FT02/FT05)
# ---------------------------------------------------------------------------


def test_pin_publish_failure_leaves_no_orphan_lease(tmp_path, monkeypatch):
    """A pin writer that dies at the rename must leave NOTHING behind:
    no half-published lease (GC would count phantom references) and no
    staging litter (the finally sweeps its own tmp)."""
    import errno
    import os

    from pyrecover_tpu.checkpoint.zerostall import pins

    mpath = tmp_path / "ckpt_1.zs.json"
    mpath.write_text(json.dumps({"leaves": []}))

    def no_publish(src, dst):
        raise OSError(errno.EIO, "injected publish failure")

    monkeypatch.setattr(os, "replace", no_publish)
    with pytest.raises(OSError):
        pins.pin_manifest(tmp_path, mpath, owner="t")
    pdir = pins.pins_dir(tmp_path)
    assert list(pdir.glob(f"*{pins.PIN_SUFFIX}")) == []
    assert list(pdir.glob("*.tmp")) == []


def test_pin_write_failure_mid_copy_cleans_staging(tmp_path, monkeypatch):
    import errno
    import os

    from pyrecover_tpu.checkpoint.zerostall import pins

    mpath = tmp_path / "ckpt_1.zs.json"
    mpath.write_text(json.dumps({"leaves": []}))

    def no_fsync(fd):
        raise OSError(errno.EIO, "injected fsync failure")

    monkeypatch.setattr(os, "fsync", no_fsync)
    with pytest.raises(OSError):
        pins.pin_manifest(tmp_path, mpath, owner="t")
    assert list(pins.pins_dir(tmp_path).iterdir()) == []


def test_pin_release_idempotent_after_expiry(tmp_path):
    import os

    from pyrecover_tpu.checkpoint.zerostall import pins

    mpath = tmp_path / "ckpt_1.zs.json"
    mpath.write_text(json.dumps({"leaves": []}))
    lease = pins.pin_manifest(tmp_path, mpath, owner="t")
    old = time.time() - 1000
    os.utime(lease.path, (old, old))
    removed = pins.expire_stale_pins(tmp_path, ttl_s=10)
    assert removed == [lease.path.name]
    lease.release()  # collected underneath us: a no-op, not ENOENT
    lease.release()  # and idempotent on repeat


def test_expire_stale_pins_sweeps_tmp_orphans_by_the_same_clock(tmp_path):
    """A pin writer killed between mkstemp and the rename leaves a .tmp
    no release() will ever unlink; the TTL sweep collects it while a
    fresh .tmp (a write still in flight) and a live lease survive."""
    import os

    from pyrecover_tpu.checkpoint.zerostall import pins

    mpath = tmp_path / "ckpt_1.zs.json"
    mpath.write_text(json.dumps({"leaves": []}))
    lease = pins.pin_manifest(tmp_path, mpath, owner="t")
    pdir = pins.pins_dir(tmp_path)
    orphan = pdir / "ckpt_0.zs.json.dead.pin.x1.tmp"
    orphan.write_bytes(b"{")
    old = time.time() - 1000
    os.utime(orphan, (old, old))
    fresh = pdir / "ckpt_2.zs.json.live.pin.x2.tmp"
    fresh.write_bytes(b"{")
    removed = pins.expire_stale_pins(tmp_path, ttl_s=10)
    assert removed == [orphan.name]
    assert fresh.exists() and lease.path.exists()
    lease.release()


def test_gc_unlink_drill_interrupts_sweep_keeps_manifests_restorable(
    tmp_ckpt_dir,
):
    """The ckpt_gc_unlink seam's proof load: an EIO injected between
    victim selection and the unlink aborts the sweep mid-pass, every
    live manifest still prechecks, and the next pass (fault drained)
    finishes the collection."""
    state = make_state(seed=31)
    exp = tmp_ckpt_dir / "exp"
    p1 = checkpoint_path(tmp_ckpt_dir, "exp", 1, engine="zerostall")
    save_ckpt_zerostall(p1, state, extra_meta={"step": 1}, background=False)
    store = chunkstore.ChunkStore(exp)
    for fill in (1, 2):
        store.put(bytes([fill]) * 3000)  # orphans from a torn save
    faults.install({"faults": [
        {"type": "transient_io_error", "op": "gc_unlink", "fail_count": 1},
    ]})
    with pytest.raises(OSError):
        chunkstore.collect_garbage(exp)
    ok, why = precheck_ckpt_zerostall(p1, verify=True)
    assert ok, why
    removed, _ = chunkstore.collect_garbage(exp)
    assert removed == 2
    ok, why = precheck_ckpt_zerostall(p1, verify=True)
    assert ok, why


def test_prune_drill_half_finished_prune_stays_restorable(tmp_ckpt_dir):
    """The ckpt_prune seam's proof load: retention interrupted between
    victim selection and the deletion removes NOTHING, and the rerun
    prunes exactly the doomed set while the survivor stays loadable."""
    from pyrecover_tpu.checkpoint.vanilla import precheck_ckpt_vanilla

    state = make_state(seed=32)
    exp = tmp_ckpt_dir / "exp"
    for step in (1, 2, 3):
        p = checkpoint_path(tmp_ckpt_dir, "exp", step)
        save_ckpt_vanilla(p, state, verify=True)
    faults.install({"faults": [
        {"type": "transient_io_error", "op": "prune", "fail_count": 1},
    ]})
    with pytest.raises(OSError):
        prune_checkpoints(exp, max_keep=1, engine="vanilla")
    assert [parse_step(p)
            for p in list_checkpoints(exp, engine="vanilla")] == [1, 2, 3]
    doomed = prune_checkpoints(exp, max_keep=1, engine="vanilla")
    assert [parse_step(p) for p in doomed] == [1, 2]
    (survivor,) = list_checkpoints(exp, engine="vanilla")
    ok, why = precheck_ckpt_vanilla(survivor, verify=True)
    assert ok, why
