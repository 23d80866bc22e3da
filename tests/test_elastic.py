"""Topology-elastic resume tests (pyrecover_tpu/checkpoint/elastic.py).

Reshard-plan grid math from manifests alone, save-on-N/restore-on-M
round-trips across the 1/2/4/8 mesh matrix for BOTH checkpoint engines,
sampler-state merge/split determinism, the ``_resume`` elastic gate
(preflight rejection falls back without quarantine, ``--elastic-resume
off`` raises a typed TopologyMismatchError, telemetry trail), and the
``inspect_checkpoint --reshard-plan`` dry-run CLI.
"""

import io
import json

import jax
import numpy as np
import pytest

from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint import (
    checkpoint_path,
    load_ckpt_sharded,
    load_ckpt_vanilla,
    save_ckpt_sharded,
    save_ckpt_vanilla,
)
from pyrecover_tpu.checkpoint import elastic
from pyrecover_tpu.checkpoint.elastic import TopologyMismatchError
from pyrecover_tpu.config import TrainConfig
from pyrecover_tpu.data.sampler import (
    StatefulSampler,
    merge_sampler_states,
    rescale_sampler_state,
    split_sampler_state,
)
from pyrecover_tpu.metrics import WallTimeTotals
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.optim import build_optimizer
from pyrecover_tpu.parallel.mesh import MeshConfig, create_mesh, state_topology
from pyrecover_tpu.parallel.sharding import spec_for_manifest_path
from pyrecover_tpu.train import _resume, init_sharded_state

CFG = TrainConfig(sequence_length=32)
MODEL_CFG = ModelConfig().tiny(max_seq_len=32)

# the 1/2/4/8 matrix: each count gets a mesh that actually reshards
# parameters where it can (fsdp/tensor), not just the batch axis
MESHES = {
    1: MeshConfig(data=1),
    2: MeshConfig(data=2),
    4: MeshConfig(data=2, fsdp=2),
    8: MeshConfig(data=2, fsdp=2, tensor=2),
}


@pytest.fixture()
def mem_sink():
    sink = telemetry.add_sink(telemetry.MemorySink())
    yield sink
    telemetry.remove_sink(sink)


def events(sink, name):
    return [e for e in sink.events if e["event"] == name]


@pytest.fixture(scope="module")
def grids(devices8):
    """(mesh, saved-values state, different-values target state) per
    device count — built once; jit init per mesh is the slow part."""
    optimizer, _ = build_optimizer(CFG)
    out = {}
    for n, cfg in MESHES.items():
        mesh = create_mesh(cfg, devices=devices8[:n])
        out[n] = (
            mesh,
            init_sharded_state(jax.random.key(1), MODEL_CFG, optimizer, mesh),
            init_sharded_state(jax.random.key(9), MODEL_CFG, optimizer, mesh),
        )
    return out


def assert_tree_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---- plan math (manifest-only, no devices) ----------------------------------


def test_spec_for_manifest_path_matches_rules():
    from jax.sharding import PartitionSpec as P

    assert spec_for_manifest_path(".params['layers']['wq']", 3) == P(
        "pipeline", "fsdp", "tensor"
    )
    assert spec_for_manifest_path(
        ".opt_state[0].mu['layers']['wo']", 3
    ) == P("pipeline", "tensor", "fsdp")
    # rank mismatch with the rule -> replicated, like state_pspecs
    assert spec_for_manifest_path(".params['layers']['wq']", 2) == P(
        None, None
    )
    assert spec_for_manifest_path(".step", 0) == P()
    assert spec_for_manifest_path(".params['unknown_leaf']", 1) == P(None)


def _topo(n, **axes):
    mesh = {"pipeline": 1, "data": n, "fsdp": 1, "tensor": 1,
            "sequence": 1, "expert": 1}
    for k, v in axes.items():
        mesh[k] = v
        mesh["data"] = n // int(np.prod(list(axes.values())))
    return {"devices": n, "processes": 1, "mesh": mesh}


def test_plan_grid_math_split_and_concat():
    manifest = {"leaves": [
        {"path": ".params['layers']['wq']", "shape": [2, 64, 64],
         "dtype": "float32", "spec": ["pipeline", "fsdp", "tensor"]},
        {"path": ".params['final_norm']", "shape": [64],
         "dtype": "float32", "spec": [None]},
    ]}
    plan = elastic.compute_reshard_plan(
        manifest, _topo(8, fsdp=2, tensor=2), _topo(2, fsdp=2)
    )
    wq = plan.leaves[0]
    assert wq.src_grid == (1, 2, 2) and wq.tgt_grid == (1, 2, 1)
    assert wq.ops == ("keep", "keep", "concat 2→1")
    assert wq.reads_per_shard == 2  # two tensor shards concat per target
    norm = plan.leaves[1]
    assert norm.src_grid == (1,) and norm.tgt_grid == (1,)
    assert plan.feasible and plan.resharded_leaves == 1
    assert plan.bytes_moved == plan.total_bytes  # topology changed

    # same topology, same grids: nothing moves
    plan2 = elastic.compute_reshard_plan(
        manifest, _topo(8, fsdp=2, tensor=2), _topo(8, fsdp=2, tensor=2)
    )
    assert plan2.bytes_moved == 0 and plan2.resharded_leaves == 0


def test_plan_infeasible_dim_is_sc11():
    manifest = {"leaves": [
        {"path": ".params['layers']['w1']", "shape": [2, 10, 64],
         "dtype": "float32", "spec": None},
    ]}
    findings, plan = elastic.preflight_elastic(
        manifest, _topo(2), _topo(6, fsdp=3, tensor=2),
    )
    assert not plan.feasible
    assert [f.rule_id for f in findings] == ["SC11"]
    assert "not divisible" in findings[0].message


def test_preflight_sampler_rescale_infeasible():
    manifest = {"leaves": []}
    findings, plan = elastic.preflight_elastic(
        manifest, _topo(4), _topo(3),
        sampler_state={"global_batch_size": 8, "cursor": 0, "replicas": 4},
    )
    assert any(f.rule_id == "SC11" for f in findings)
    assert "not divisible by 3" in plan.sampler["error"]


def test_preflight_hbm_budget_rejects(monkeypatch):
    monkeypatch.setenv(elastic.HBM_BYTES_ENV, "64")
    manifest = {"leaves": [
        {"path": ".params['big']", "shape": [64, 64], "dtype": "float32",
         "spec": None},
    ]}
    findings, _ = elastic.preflight_elastic(manifest, _topo(4), _topo(2))
    assert [f.rule_id for f in findings] == ["SC05"]


def test_topologies_differ_rules():
    assert elastic.topologies_differ(_topo(4), _topo(2))
    assert not elastic.topologies_differ(_topo(4), _topo(4))
    # same device count, different logical shape IS a difference
    assert elastic.topologies_differ(_topo(4), _topo(4, fsdp=2))
    # legacy (unrecorded) saved topology: nothing to diff
    assert not elastic.topologies_differ(None, _topo(4))
    assert not elastic.topologies_differ({}, _topo(4))


# ---- sampler merge/split determinism ----------------------------------------


def _sampler_state(cursor=32, gbs=8):
    return {"epoch": 1, "cursor": cursor, "seed": 5,
            "global_batch_size": gbs, "num_samples": 64, "shuffle": True}


def test_sampler_split_merge_roundtrip_identity():
    state = _sampler_state()
    for n in (1, 2, 4, 8):
        views = split_sampler_state(state, n)
        assert len(views) == n
        rows = [tuple(v["local_rows"]) for v in views]
        # replica row ranges tile the global batch exactly once
        assert rows[0][0] == 0 and rows[-1][1] == state["global_batch_size"]
        for (_, a_end), (b_start, _) in zip(rows, rows[1:]):
            assert a_end == b_start
        merged = merge_sampler_states(views)
        assert merged == state


def test_sampler_merge_rejects_divergence_and_gaps():
    views = split_sampler_state(_sampler_state(), 4)
    views[2]["consumed_batches"] += 1
    with pytest.raises(ValueError, match="diverged on progress"):
        merge_sampler_states(views)
    views = split_sampler_state(_sampler_state(), 4)
    views[1]["seed"] = 99
    with pytest.raises(ValueError, match="diverged on seed"):
        merge_sampler_states(views)
    with pytest.raises(ValueError, match="incomplete"):
        merge_sampler_states(split_sampler_state(_sampler_state(), 4)[:3])


def test_sampler_rescale_preserves_global_cursor():
    state = _sampler_state(cursor=40)
    merged, views = rescale_sampler_state(state, 2)
    assert merged["cursor"] == 40
    assert len(views) == 2
    # the rescaled sampler yields the SAME next global batch
    a = StatefulSampler(64, 8, seed=5)
    a.seek(40 // 8)
    b = StatefulSampler(64, 8, seed=5)
    b.seek(merged["cursor"] // merged["global_batch_size"])
    np.testing.assert_array_equal(a.next_batch(), b.next_batch())


def test_sampler_split_rejects_indivisible():
    with pytest.raises(ValueError, match="not divisible"):
        split_sampler_state(_sampler_state(gbs=6), 4)
    with pytest.raises(ValueError, match="batch boundary"):
        split_sampler_state(_sampler_state(cursor=3), 2)


# ---- save-on-N / restore-on-M round-trips (both engines) --------------------

PAIRS = [(1, 2), (2, 4), (4, 8), (8, 2), (4, 1), (2, 8)]


@pytest.mark.parametrize("src,dst", PAIRS)
def test_vanilla_cross_mesh_roundtrip(tmp_ckpt_dir, grids, src, dst):
    _, state_src, _ = grids[src]
    _, _, target = grids[dst]
    path = checkpoint_path(tmp_ckpt_dir, "exp", 3)
    save_ckpt_vanilla(path, state_src, {"consumed": 3},
                      extra_meta={"step": 3})
    meta = elastic.read_saved_meta(path)
    assert meta["topology"]["devices"] == src
    restored, _, _ = load_ckpt_vanilla(path, target)
    assert_tree_equal(state_src, restored)
    # every leaf landed on ITS target sharding (the reslice+scatter half)
    for t, r in zip(jax.tree_util.tree_leaves(target),
                    jax.tree_util.tree_leaves(restored)):
        assert r.sharding == t.sharding


@pytest.mark.parametrize("src,dst", PAIRS)
def test_sharded_cross_mesh_roundtrip(tmp_ckpt_dir, grids, src, dst):
    _, state_src, _ = grids[src]
    _, _, target = grids[dst]
    path = checkpoint_path(tmp_ckpt_dir, "exp", 5, engine="sharded")
    save_ckpt_sharded(path, state_src, {"consumed": 5},
                      extra_meta={"step": 5})
    assert elastic.read_saved_meta(path)["topology"]["devices"] == src
    restored, _, meta = load_ckpt_sharded(path, target)
    assert meta["step"] == 5
    assert_tree_equal(state_src, restored)
    for t, r in zip(jax.tree_util.tree_leaves(target),
                    jax.tree_util.tree_leaves(restored)):
        assert r.sharding == t.sharding


def test_cross_mesh_equals_same_mesh_restore(tmp_ckpt_dir, grids):
    """Save on 4, restore on 8 vs restore on 4: tree-equal results."""
    _, state_src, target_same = grids[4]
    _, _, target_other = grids[8]
    path = checkpoint_path(tmp_ckpt_dir, "exp", 7)
    save_ckpt_vanilla(path, state_src, {"consumed": 7},
                      extra_meta={"step": 7})
    same, _, _ = load_ckpt_vanilla(path, target_same)
    other, _, _ = load_ckpt_vanilla(path, target_other)
    assert_tree_equal(same, other)


# ---- the _resume elastic gate -----------------------------------------------


def _resume_config(**kw):
    kw.setdefault("resume_from_checkpoint", "latest")
    kw.setdefault("sequence_length", 32)
    kw.setdefault("batch_size", 8)
    return TrainConfig(**kw)


def _save_for_resume(exp_dir, state, step, *, replicas, gbs=8):
    sampler = StatefulSampler(64, gbs, seed=0)
    save_ckpt_vanilla(
        checkpoint_path(exp_dir.parent, exp_dir.name, step), state,
        {"consumed": step, "replicas": replicas, **sampler.state_dict()},
        extra_meta={"step": step, "epoch": 0},
    )


def _rewrite_meta(path, mutate):
    """Rewrite a v2 vanilla checkpoint's meta header in place (leaf
    frames untouched) — how tests forge per-checkpoint preflight facts."""
    from pyrecover_tpu.checkpoint.vanilla import MAGIC

    data = path.read_bytes()
    assert data[: len(MAGIC)] == MAGIC
    off = len(MAGIC)
    mlen = int.from_bytes(data[off:off + 8], "little")
    meta = json.loads(data[off + 8:off + 8 + mlen].decode())
    mutate(meta)
    blob = json.dumps(meta).encode()
    path.write_bytes(
        MAGIC + len(blob).to_bytes(8, "little") + blob
        + data[off + 8 + mlen:]
    )


def test_resume_elastic_shrink_emits_trail(tmp_ckpt_dir, grids, mem_sink):
    _, state4, _ = grids[4]
    _, _, target2 = grids[2]
    exp_dir = tmp_ckpt_dir / "exp"
    _save_for_resume(exp_dir, state4, 3, replicas=4)
    config = _resume_config()
    sampler = StatefulSampler(64, 8, seed=0)
    step, restored = _resume(
        config, exp_dir, target2, sampler, None, WallTimeTotals()
    )
    assert step == 3
    assert_tree_equal(state4, restored)
    (ev,) = events(mem_sink, "elastic_resume")
    assert ev["saved_topology"]["devices"] == 4
    assert ev["target_topology"]["devices"] == 2
    assert ev["plan_bytes_moved"] > 0
    (rs,) = events(mem_sink, "sampler_rescaled")
    assert (rs["saved_replicas"], rs["target_replicas"]) == (4, 2)
    spans = [e for e in events(mem_sink, "span_begin")
             if e.get("name") == "reshard"]
    assert len(spans) == 1


def test_resume_same_topology_stays_plain(tmp_ckpt_dir, grids, mem_sink):
    mesh, state4, target4 = grids[4]
    exp_dir = tmp_ckpt_dir / "exp"
    _save_for_resume(exp_dir, state4, 3, replicas=4)
    step, restored = _resume(
        _resume_config(), exp_dir, target4,
        StatefulSampler(64, 8, seed=0), None, WallTimeTotals(),
    )
    assert step == 3
    assert_tree_equal(state4, restored)
    assert not events(mem_sink, "elastic_resume")
    assert state_topology(target4)["mesh"] == dict(
        (k, int(v)) for k, v in dict(mesh.shape).items()
    )


def test_resume_off_raises_typed_mismatch(tmp_ckpt_dir, grids, mem_sink):
    _, state4, _ = grids[4]
    _, _, target2 = grids[2]
    exp_dir = tmp_ckpt_dir / "exp"
    _save_for_resume(exp_dir, state4, 3, replicas=4)
    with pytest.raises(TopologyMismatchError) as ei:
        _resume(
            _resume_config(elastic_resume="off"), exp_dir, target2,
            StatefulSampler(64, 8, seed=0), None, WallTimeTotals(),
        )
    msg = str(ei.value)
    assert "4 devices" in msg and "2 devices" in msg
    assert events(mem_sink, "topology_mismatch")
    # refused BEFORE any restore I/O
    assert not events(mem_sink, "ckpt_restore_start")


def test_resume_preflight_rejection_falls_back(tmp_ckpt_dir, grids,
                                               mem_sink):
    """The newest checkpoint cannot rescale its data pipeline onto the
    target mesh: the elastic preflight rejects it BEFORE any restore
    I/O, the walk falls back to the older fitting checkpoint, and the
    rejected one is NOT quarantined (it is intact, just misfitting)."""
    _, state2, _ = grids[2]
    _, _, target4 = grids[4]
    exp_dir = tmp_ckpt_dir / "exp"
    _save_for_resume(exp_dir, state2, 3, replicas=2)
    _save_for_resume(exp_dir, state2, 6, replicas=2)
    newest = checkpoint_path(tmp_ckpt_dir, "exp", 6)
    # forge an un-rescalable pipeline record on the newest candidate
    # (gbs 6 cannot split over the 4 batch shards of the target mesh)
    _rewrite_meta(newest, lambda m: m["sampler"].update(
        global_batch_size=6, replicas=3
    ))
    step, restored = _resume(
        _resume_config(), exp_dir, target4,
        StatefulSampler(64, 8, seed=0), None, WallTimeTotals(),
    )
    assert step == 3  # fell back to the older checkpoint
    assert_tree_equal(state2, restored)
    (rej,) = events(mem_sink, "elastic_preflight_failed")
    assert rej["path"].endswith("ckpt_6.ckpt")
    assert "SC11" in rej["reason"]
    assert newest.exists()  # intact, never quarantined
    assert not (exp_dir / ".corrupt").exists()
    # restore I/O happened exactly once, for the accepted candidate
    starts = events(mem_sink, "ckpt_restore_start")
    assert [e["path"].endswith("ckpt_3.ckpt") for e in starts] == [True]


def test_resume_all_rejected_raises_without_io(tmp_ckpt_dir, grids,
                                               mem_sink, monkeypatch):
    _, state2, _ = grids[2]
    _, _, target4 = grids[4]
    exp_dir = tmp_ckpt_dir / "exp"
    _save_for_resume(exp_dir, state2, 3, replicas=2)
    _save_for_resume(exp_dir, state2, 6, replicas=2)
    monkeypatch.setenv(elastic.HBM_BYTES_ENV, "1024")  # nothing fits
    with pytest.raises(RuntimeError, match="rejected by the elastic"):
        _resume(
            _resume_config(), exp_dir, target4,
            StatefulSampler(64, 8, seed=0), None, WallTimeTotals(),
        )
    assert len(events(mem_sink, "elastic_preflight_failed")) == 2
    assert not events(mem_sink, "ckpt_restore_start")  # zero restore I/O
    # both candidates intact: capacity churn must never eat checkpoints
    assert checkpoint_path(tmp_ckpt_dir, "exp", 3).exists()
    assert checkpoint_path(tmp_ckpt_dir, "exp", 6).exists()


def test_resume_explicit_infeasible_raises_typed(tmp_ckpt_dir, grids,
                                                 mem_sink, monkeypatch):
    _, state2, _ = grids[2]
    _, _, target4 = grids[4]
    exp_dir = tmp_ckpt_dir / "exp"
    _save_for_resume(exp_dir, state2, 3, replicas=2)
    monkeypatch.setenv(elastic.HBM_BYTES_ENV, "1024")
    with pytest.raises(TopologyMismatchError, match="SC05"):
        _resume(
            _resume_config(resume_from_checkpoint=str(
                checkpoint_path(tmp_ckpt_dir, "exp", 3)
            )),
            exp_dir, target4, StatefulSampler(64, 8, seed=0), None,
            WallTimeTotals(),
        )


# ---- the dry-run CLI --------------------------------------------------------


def test_inspect_reshard_plan_cli(tmp_ckpt_dir, grids, capsys):
    import inspect_checkpoint

    _, state4, _ = grids[4]
    exp_dir = tmp_ckpt_dir / "exp"
    _save_for_resume(exp_dir, state4, 3, replicas=4)
    ck = str(checkpoint_path(tmp_ckpt_dir, "exp", 3))
    rc = inspect_checkpoint.main([ck, "--reshard-plan", "--devices", "8",
                                  "--mesh", "data=2,fsdp=2,tensor=2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "reshard plan: 4 devices" in out
    assert "8 devices" in out and "feasible" in out
    assert "split" in out  # fsdp/tensor grids grew

    rc = inspect_checkpoint.main([ck, "--reshard-plan", "--devices", "3"])
    out = capsys.readouterr().out
    assert rc == 1  # gbs 8 cannot split over 3 replicas
    assert "SC11" in out

    rc = inspect_checkpoint.main([ck, "--reshard-plan", "--devices", "2",
                                  "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["feasible"] and doc["findings"] == []
    assert doc["saved_topology"]["devices"] == 4
    assert doc["target_topology"]["devices"] == 2


def test_render_plan_marks_infeasible_leaves():
    manifest = {"leaves": [
        {"path": ".params['layers']['w1']", "shape": [2, 10, 64],
         "dtype": "float32", "spec": None},
    ]}
    _, plan = elastic.preflight_elastic(
        manifest, _topo(2), _topo(6, fsdp=3, tensor=2)
    )
    buf = io.StringIO()
    elastic.render_plan(plan, buf)
    assert "INFEASIBLE" in buf.getvalue()
