"""shardcheck: every shipped preset validates clean across the 1/2/4/8-
device virtual mesh matrix; every seeded misconfiguration (non-divisible
axis, unknown mesh axis, oversized replicated leaf, manifest shape/dtype
drift) produces exactly one finding with its own check id; both
checkpoint engines emit the shared manifest schema; the manifest diff
gates resume before any tensor read; the CLI keeps the jaxlint exit-code
and JSON contracts."""

import json
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pyrecover_tpu.analysis.shardcheck import (
    CHECKS,
    ShardcheckConfig,
    diff_manifests,
    read_ckpt_manifest,
    spec_findings,
    state_manifest,
)
from pyrecover_tpu.analysis.shardcheck.checks import memory_budget
from pyrecover_tpu.analysis.shardcheck.runner import (
    abstract_state_leaves,
    check_preset,
    mesh_matrix,
    preflight,
)
from pyrecover_tpu.config import TrainConfig
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.models.presets import PRESETS
from pyrecover_tpu.optim import build_optimizer
from pyrecover_tpu.train_state import create_train_state

MESH8 = {"pipeline": 1, "data": 2, "fsdp": 2, "tensor": 2,
         "sequence": 1, "expert": 1}


def ids(findings):
    return [f.rule_id for f in findings]


# ---------------------------------------------------------------------------
# the shipped presets are the ultimate fixture: clean at 1/2/4/8 devices
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("preset", sorted(PRESETS))
@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_presets_divide_cleanly_on_virtual_meshes(preset, n_devices):
    cfg = PRESETS[preset]()
    leaves, specs = abstract_state_leaves(cfg)
    for mesh_cfg in mesh_matrix(cfg, n_devices):
        findings, mesh_shape = preflight(
            cfg, mesh_cfg, n_devices, locus=preset,
            leaves=leaves, specs=specs,
        )
        assert mesh_shape is not None
        assert findings == [], [f.message for f in findings]


# ---------------------------------------------------------------------------
# seeded misconfigurations: one finding each, distinct check ids
# ---------------------------------------------------------------------------


def test_nondivisible_axis_is_one_sc01():
    leaves = [("params.w", (100, 64), jnp.float32)]
    findings = spec_findings(leaves, [P("fsdp", None)],
                             {"fsdp": 8, "data": 1})
    assert ids(findings) == ["SC01"]
    assert "not divisible" in findings[0].message


def test_unknown_mesh_axis_is_one_sc02():
    leaves = [("params.w", (64, 64), jnp.float32)]
    findings = spec_findings(leaves, [P("tensr", None)],
                             {"tensor": 4, "data": 2})
    assert ids(findings) == ["SC02"]
    assert "'tensr'" in findings[0].message


def test_mesh_axis_double_use_is_one_sc03():
    leaves = [("params.w", (64, 64), jnp.float32)]
    findings = spec_findings(leaves, [P("tensor", "tensor")], {"tensor": 4})
    assert ids(findings) == ["SC03"]


def test_oversized_replicated_leaf_is_one_sc04():
    cfg = ShardcheckConfig(replicated_threshold_bytes=2**20)
    leaves = [("params.table", (1024, 1024), jnp.float32)]  # 4 MiB
    findings = spec_findings(leaves, [P(None, None)], {"fsdp": 2},
                             config=cfg)
    assert ids(findings) == ["SC04"]
    # same leaf on a pure-DP mesh is the DDP design, not a finding
    assert spec_findings(leaves, [P(None, None)], {"data": 8},
                         config=cfg) == []


def test_manifest_shape_drift_is_one_sc08():
    a = {"schema": 1, "num_leaves": 1, "leaves": [
        {"path": ".params['w']", "shape": [64, 64], "dtype": "float32",
         "spec": None}]}
    b = json.loads(json.dumps(a))
    b["leaves"][0]["shape"] = [64, 128]
    assert ids(diff_manifests(a, b)) == ["SC08"]


def test_manifest_dtype_drift_is_one_sc09():
    a = {"schema": 1, "num_leaves": 1, "leaves": [
        {"path": ".params['w']", "shape": [64, 64], "dtype": "float32",
         "spec": None}]}
    b = json.loads(json.dumps(a))
    b["leaves"][0]["dtype"] = "bfloat16"
    assert ids(diff_manifests(a, b)) == ["SC09"]


def test_manifest_leaf_set_drift_is_one_sc07():
    a = {"schema": 1, "num_leaves": 1, "leaves": [
        {"path": ".params['w']", "shape": [4], "dtype": "float32",
         "spec": None}]}
    b = {"schema": 1, "num_leaves": 1, "leaves": [
        {"path": ".params['v']", "shape": [4], "dtype": "float32",
         "spec": None}]}
    assert ids(diff_manifests(a, b)) == ["SC07"]


def test_manifest_pspec_drift_is_one_sc10():
    a = {"schema": 1, "num_leaves": 1, "leaves": [
        {"path": ".params['w']", "shape": [64, 64], "dtype": "float32",
         "spec": [None, "tensor"]}]}
    b = json.loads(json.dumps(a))
    b["leaves"][0]["spec"] = ["fsdp", "tensor"]
    assert ids(diff_manifests(a, b)) == ["SC10"]
    assert diff_manifests(a, b, check_specs=False) == []


def test_ignore_suppresses_a_check():
    cfg = ShardcheckConfig(ignore=frozenset({"SC04"}),
                           replicated_threshold_bytes=2**20)
    leaves = [("params.table", (1024, 1024), jnp.float32)]
    assert spec_findings(leaves, [P(None, None)], {"fsdp": 2},
                         config=cfg) == []


# ---------------------------------------------------------------------------
# memory model + census
# ---------------------------------------------------------------------------


def test_memory_budget_table_and_sc05():
    cfg = PRESETS["llama-1b"]()
    leaves, specs = abstract_state_leaves(cfg)
    rows, findings = memory_budget(
        leaves, specs, MESH8, cfg, batch_size=4, seq_len=cfg.max_seq_len,
    )
    assert findings == []  # no device kind -> report only
    assert rows["hbm_capacity_bytes"] is None
    # params+optimizer are exact metadata math: fp32 state, 3x params
    assert rows["optimizer_bytes"] == pytest.approx(
        2 * rows["params_bytes"], rel=0.01
    )
    assert rows["total_bytes"] > rows["params_bytes"]

    sc = ShardcheckConfig(device_kind="v5e")  # 1B state >> 16G at dp2xfsdp2
    rows, findings = memory_budget(
        leaves, specs, {"data": 1, "fsdp": 1}, cfg,
        batch_size=8, seq_len=cfg.max_seq_len, config=sc,
    )
    assert ids(findings) == ["SC05"]


def test_census_counts_pipeline_collectives(devices8):
    from pyrecover_tpu.analysis.shardcheck.collectives import census
    from pyrecover_tpu.parallel.mesh import MeshConfig, create_mesh

    cfg = ModelConfig().tiny()
    mesh = create_mesh(MeshConfig(data=2, pipeline=2, tensor=2),
                       devices=devices8)
    table, findings = census(cfg, None, 4, cfg.max_seq_len, mesh=mesh)
    assert table["mesh_context"] is True
    assert table["traced"].get("ppermute", 0) > 0  # the pipeline schedule
    assert table["traced"].get("sharding_constraint", 0) > 0
    assert findings == []


def test_census_gather_scan_sees_full_param_shapes(devices8):
    """SC06's core: the jaxpr walk records all_gather output shapes, so a
    gather materializing a full parameter-sized tensor is detectable."""
    from pyrecover_tpu.analysis.shardcheck.collectives import count_prims
    from pyrecover_tpu.parallel.mesh import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(data=1, fsdp=2), devices=devices8[:2])

    def gather_all(x):
        return jax.shard_map(
            lambda s: jax.lax.all_gather(s, "fsdp", tiled=True),
            mesh=mesh, in_specs=P("fsdp", None), out_specs=P(None, None),
            # the gathered value IS replicated, but the replication
            # checker cannot infer that through all_gather — and this
            # fixture is about the jaxpr walk, not the checker
            check_vma=False,
        )(x)

    jaxpr = jax.make_jaxpr(gather_all)(
        jax.ShapeDtypeStruct((512, 512), jnp.float32)
    )
    counts, gathers = {}, []
    count_prims(jaxpr.jaxpr, counts, 1, gathers)
    assert counts.get("all_gather", 0) >= 1
    assert (512, 512) in gathers


def test_census_trace_failure_is_a_finding(devices8):
    """A config the step cannot even trace with (batch not divisible by
    the pipeline microbatches) is a launch failure caught at preflight —
    one SC01 finding, not a crash."""
    from pyrecover_tpu.analysis.shardcheck.collectives import census
    from pyrecover_tpu.parallel.mesh import MeshConfig, create_mesh

    cfg = ModelConfig().tiny()
    mesh = create_mesh(MeshConfig(data=1, pipeline=2), devices=devices8[:2])
    table, findings = census(cfg, None, 3, cfg.max_seq_len, mesh=mesh)
    assert ids(findings) == ["SC01"]
    assert "fails to trace" in findings[0].message
    assert "error" in table


def test_analytic_collectives_model():
    from pyrecover_tpu.analysis.shardcheck.collectives import (
        analytic_collectives,
    )

    leaves = [(".params['w']", (64, 64), jnp.float32),
              (".params['n']", (64,), jnp.float32)]
    specs = [P("fsdp", "tensor"), P(None)]
    out = analytic_collectives(leaves, specs, {"data": 2, "fsdp": 2,
                                               "tensor": 2})
    assert out["dp_grad_allreduce_bytes"] == 64 * 64 * 4 + 64 * 4
    assert out["fsdp_param_allgather_bytes"] == 2 * 64 * 64 * 4
    assert out["sharded_param_bytes_by_axis"]["tensor"] == 64 * 64 * 4


# ---------------------------------------------------------------------------
# manifest: both engines emit it; the diff gates resume
# ---------------------------------------------------------------------------


def tiny_state(vocab=256):
    optimizer, _ = build_optimizer(TrainConfig(sequence_length=16))
    return create_train_state(
        jax.random.key(0),
        ModelConfig().tiny(max_seq_len=16, vocab_size=vocab), optimizer,
    )


def test_vanilla_save_embeds_manifest(tmp_path):
    from pyrecover_tpu.checkpoint.vanilla import (
        read_ckpt_meta,
        save_ckpt_vanilla,
    )

    state = tiny_state()
    path = tmp_path / "ckpt_1.ckpt"
    save_ckpt_vanilla(path, state, {"consumed": 1}, extra_meta={"step": 1})
    meta = read_ckpt_meta(path)
    m = meta["manifest"]
    assert m["schema"] == 1 and m["num_leaves"] == meta["num_leaves"]
    paths = [e["path"] for e in m["leaves"]]
    assert ".params['tok_embed']" in paths
    # read_ckpt_manifest is the one consumer surface for both engines
    assert read_ckpt_manifest(path) == m
    # self-diff is clean
    assert diff_manifests(m, state_manifest(state)) == []


def test_sharded_save_embeds_manifest(tmp_path):
    from pyrecover_tpu.checkpoint import save_ckpt_sharded

    state = tiny_state()
    path = tmp_path / "ckpt_2"
    save_ckpt_sharded(path, state, extra_meta={"step": 2})
    m = read_ckpt_manifest(path)
    assert m["schema"] == 1
    assert diff_manifests(m, state_manifest(state)) == []


def test_vanilla_precheck_rejects_wrong_model_fast(tmp_path):
    from pyrecover_tpu.checkpoint.vanilla import (
        CheckpointStructureError,
        precheck_ckpt_vanilla,
        save_ckpt_vanilla,
    )

    state = tiny_state()
    path = tmp_path / "ckpt_3.ckpt"
    save_ckpt_vanilla(path, state, {"consumed": 3})
    ok, _ = precheck_ckpt_vanilla(path, target_state=state)
    assert ok
    other = tiny_state(vocab=128)  # drifted model config
    with pytest.raises(CheckpointStructureError):
        precheck_ckpt_vanilla(path, target_state=other)


def test_sharded_precheck_uses_manifest(tmp_path):
    from pyrecover_tpu.checkpoint import precheck_ckpt_sharded, save_ckpt_sharded
    from pyrecover_tpu.checkpoint.vanilla import CheckpointStructureError

    state = tiny_state()
    path = tmp_path / "ckpt_4"
    save_ckpt_sharded(path, state)
    ok, _ = precheck_ckpt_sharded(path, state)
    assert ok
    with pytest.raises(CheckpointStructureError):
        precheck_ckpt_sharded(path, tiny_state(vocab=128))


# ---------------------------------------------------------------------------
# reporters + CLI (the format.sh / CI surface)
# ---------------------------------------------------------------------------


def test_check_catalog_complete():
    """SC ids are exactly 1..13, unique, and every one is documented in
    the README (id AND kebab-case name appear) — the PR 7 catalog drift
    (SC11 landing without its README row) can't recur silently."""
    assert set(CHECKS) == {f"SC{i:02d}" for i in range(1, 14)}
    names = [v[0] for v in CHECKS.values()]
    assert len(names) == len(set(names))
    readme = (REPO / "README.md").read_text(encoding="utf-8")
    undocumented = [
        f"{cid} ({name})" for cid, (name, _, _) in CHECKS.items()
        if cid not in readme or name not in readme
    ]
    assert undocumented == [], (
        f"README.md is missing shardcheck catalog entries: {undocumented}"
    )


def test_check_preset_report_shape():
    report = check_preset(
        "llama-150m", PRESETS["llama-150m"](), device_counts=(1, 2),
        run_census=False,
    )
    assert report["findings"] == []
    assert report["memory"]["params_bytes"] > 0
    assert {m["devices"] for m in report["meshes"]} == {1, 2}


def test_cli_strict_gate(tmp_path):
    from pyrecover_tpu.analysis.shardcheck.cli import main

    json_out = tmp_path / "report.json"
    assert main(["--preset", "llama-150m", "--devices", "1,2",
                 "--no-census", "--strict", "--json", str(json_out)]) == 0
    doc = json.loads(json_out.read_text())
    assert doc["tool"] == "shardcheck" and doc["strict"] is True
    assert doc["summary"]["findings"] == 0
    assert doc["reports"][0]["preset"] == "llama-150m"

    assert main(["--preset", "no-such-preset"]) == 2
    assert main([]) == 2
    assert main(["--list-checks"]) == 0


def test_cli_explicit_bad_mesh_fails_strict(capsys):
    from pyrecover_tpu.analysis.shardcheck.cli import main

    # tensor=8 cannot divide the tiny kv width of llama-150m? it can —
    # use pp=7: 12 layers % 7 != 0 -> SC01 findings on the stacked leaves
    rc = main(["--preset", "llama-150m", "--devices", "7", "--pp", "7",
               "--no-census", "--strict"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "SC01" in out


def test_cli_diff_checkpoint(tmp_path, capsys):
    from pyrecover_tpu.analysis.shardcheck.cli import main
    from pyrecover_tpu.checkpoint.vanilla import save_ckpt_vanilla

    state = tiny_state()
    path = tmp_path / "ckpt_9.ckpt"
    save_ckpt_vanilla(path, state, {"consumed": 9})
    # a tiny state against the real preset: leaf shapes drift -> strict 1
    rc = main(["--preset", "llama-150m", "--diff-checkpoint", str(path),
               "--strict"])
    out = capsys.readouterr().out
    assert rc == 1 and "does NOT fit" in out
    assert main(["--preset", "llama-150m",
                 "--diff-checkpoint", str(tmp_path / "missing")]) == 2


def test_inspect_checkpoint_manifest_mode(tmp_path, capsys):
    import sys as _sys
    from pathlib import Path as _Path

    _sys.path.insert(0, str(_Path(__file__).resolve().parent.parent / "tools"))
    from inspect_checkpoint import main as inspect_main

    from pyrecover_tpu.checkpoint.vanilla import save_ckpt_vanilla

    state = tiny_state()
    path = tmp_path / "ckpt_7.ckpt"
    save_ckpt_vanilla(path, state, {"consumed": 7})
    assert inspect_main([str(path), "--manifest"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc == read_ckpt_manifest(path)


def test_spec_axis_drop_emits_telemetry_once(devices8):
    """The _filter_spec_for_mesh satellite: constraining with an axis the
    mesh does not have warns via telemetry exactly once per axis."""
    from pyrecover_tpu import telemetry
    from pyrecover_tpu.parallel import mesh as mesh_mod
    from pyrecover_tpu.parallel.mesh import MeshConfig, constrain, create_mesh

    mesh = create_mesh(MeshConfig(data=2), devices=devices8[:2])
    sink = telemetry.MemorySink()
    handle = telemetry.add_sink(sink)
    mesh_mod._dropped_axes_warned.discard("bogus_axis")
    try:
        with jax.sharding.set_mesh(mesh):
            x = jnp.zeros((4, 4))
            constrain(x, "bogus_axis", None)
            constrain(x, "bogus_axis", None)  # second time: silent
    finally:
        telemetry.remove_sink(handle)
    events = [e for e in sink.events if e["event"] == "spec_axis_dropped"]
    assert len(events) == 1
    assert events[0]["axis"] == "bogus_axis"
    # manual-axis filtering (shard_map) must NOT be reported: the mesh
    # HAS the axis; only truly-absent names warn
    assert all(e["axis"] != "data" for e in events)
