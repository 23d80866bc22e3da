"""Latency-hidden gradients: bucketed comm/compute overlap + remat
autoscaling.

The contract under test (README "Latency-hidden gradients" + "Remat
autoscaling"):

  * bucket layout math: the byte cap is respected (a lone oversized
    leaf gets its own bucket), every leaf lands in exactly one bucket,
    the issue order is reverse-autodiff (loss head first, embedding
    last), offsets are contiguous, and a cap that admits everything
    resolves to the unbucketed path.
  * numerics: bucketed fp32 is BIT-EXACT across any two bucket layouts
    (per-bucket psums are exact elementwise sums) and tracks the
    implicit-GSPMD unbucketed anchor within float-reassociation noise;
    bucketed int8 keeps the per-bucket error-feedback deficit identity
    (the PR 10 single-block pin, re-blocked) with the residual's SHAPE
    unchanged, so bucket flips across resumes are spec-only drift.
  * shardcheck sees it: the census counts one data-axis gradient
    collective per resolved bucket and SC13 `overlap-not-survived`
    fires on the seeded misconfig (configured bucketed, traced fused);
    the traffic model prices per-bucket legs with the exposed-vs-hidden
    split.
  * with `--remat`, `--remat-policy auto` picks a rung of
    utils/remat.py's ladder against the SC05 HBM model (table-pinned on
    the llama presets) and suggests the largest per-chip batch the
    chosen rung still fits (tests/test_remat_ladder.py holds the model
    to the compiler's peaks at the benchmark cells' shapes).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pyrecover_tpu.config import TrainConfig
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.parallel.collectives import (
    compute_bucket_layout,
    grad_leaf_order,
    param_leaf_order,
    quantized_psum_flat,
    resolve_bucket_layout,
)
from pyrecover_tpu.parallel.mesh import AXIS_DATA, MeshConfig, create_mesh

TINY = dict(seq=32, vocab=128, batch=8)


def tiny_model():
    return ModelConfig().tiny(max_seq_len=TINY["seq"], vocab_size=TINY["vocab"])


def run_steps(mesh_cfg, ndev, n_steps=4, accum=1, clip=True, seed=3, lr=1e-3,
              optimizer_sharding="none", grad_allreduce="fp32",
              grad_bucket_mb=0):
    """Seeded mini training run; returns (final_state, losses)."""
    from pyrecover_tpu.data import (
        DataLoader,
        StatefulSampler,
        SyntheticTextDataset,
    )
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.train import init_sharded_state
    from pyrecover_tpu.train_state import make_train_step

    mc = tiny_model()
    tc = TrainConfig(
        sequence_length=TINY["seq"], batch_size=TINY["batch"],
        learning_rate=lr, lr_warmup_steps=2, grad_clipping=clip,
        optimizer_sharding=optimizer_sharding, grad_allreduce=grad_allreduce,
        grad_bucket_mb=grad_bucket_mb,
    )
    optimizer, _ = build_optimizer(tc)
    mesh = create_mesh(mesh_cfg, devices=jax.devices()[:ndev])
    ds = SyntheticTextDataset(
        num_samples=64, seq_len=TINY["seq"], vocab_size=TINY["vocab"],
        seed=seed,
    )
    sampler = StatefulSampler(
        dataset_len=64, global_batch_size=TINY["batch"], seed=seed
    )
    state = init_sharded_state(
        jax.random.key(0), mc, optimizer, mesh,
        optimizer_sharding=optimizer_sharding, grad_allreduce=grad_allreduce,
    )
    loader = DataLoader(ds, sampler, pad_token_id=0, mesh=mesh, prefetch=0)
    step_fn = make_train_step(
        mc, optimizer, donate=False, grad_accumulation_steps=accum,
        optimizer_sharding=optimizer_sharding, grad_allreduce=grad_allreduce,
        grad_bucket_mb=grad_bucket_mb,
    )
    losses = []
    with jax.sharding.set_mesh(mesh):
        for _ in range(n_steps):
            _, batch = next(loader)
            state, m = step_fn(state, batch)
            losses.append(float(m["loss"]))
    return state, losses


def assert_states_bitexact(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    for x, y in zip(la, lb, strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---- bucket layout math ----------------------------------------------------


def test_bucket_layout_cap_coverage_and_padding():
    sizes = [100, 2000, 300, 50, 5000, 10]
    layout = compute_bucket_layout(sizes, 4000, replicas=2, block=8)
    assert len(layout) > 1
    # every leaf in exactly one bucket, in order, offsets contiguous
    covered = []
    offset = 0
    for b in layout:
        covered += list(range(b.leaf_lo, b.leaf_hi))
        assert b.offset == offset
        offset += b.n_elems
        assert b.padded_len % (2 * 8) == 0 and b.padded_len >= b.n_elems
        # cap respected unless the bucket is a single oversized leaf
        assert b.nbytes_f32 <= 4000 or b.leaf_hi - b.leaf_lo == 1
    assert covered == list(range(len(sizes)))
    assert sum(b.n_elems for b in layout) == sum(sizes)


def test_bucket_layout_oversized_leaf_gets_own_bucket():
    # 5000 elems = 20000 bytes f32 >> 4000-byte cap
    layout = compute_bucket_layout([10, 5000, 10], 4000, 1, 8)
    giant = [b for b in layout if b.n_elems == 5000]
    assert len(giant) == 1 and giant[0].leaf_hi - giant[0].leaf_lo == 1


def test_bucket_layout_degenerate_resolves_unbucketed():
    sizes = [100, 200, 300]
    # off
    assert resolve_bucket_layout(sizes, 0) is None
    assert resolve_bucket_layout(sizes, -1) is None
    # cap >= total params: one bucket == the unbucketed path
    assert resolve_bucket_layout(sizes, 1.0) is None
    # a real cap buckets: reversed [300, 200, 100] at a 512-elem cap
    # packs [300, 200] then [100]
    assert len(resolve_bucket_layout(sizes, 2048 / 2**20, 1, 8)) == 2
    with pytest.raises(ValueError, match="bucket_bytes"):
        compute_bucket_layout(sizes, 0)


def test_reverse_autodiff_issue_order():
    """The issue order is reverse-autodiff, not reverse-alphabetical:
    the loss head (output, final_norm — final while most of the
    backward still runs) leads, the scanned layer stack follows, and
    the token embedding (the backward's final product) trails."""
    mc = tiny_model()
    from pyrecover_tpu.models.llama import init_params

    params = jax.eval_shape(lambda k: init_params(k, mc), jax.random.key(0))
    paths = [
        jax.tree_util.keystr(p)
        for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]
    ]
    order = param_leaf_order(params)
    issued = [paths[j] for j in order]
    assert "output" in issued[0]
    assert "final_norm" in issued[1]
    assert "tok_embed" in issued[-1]
    # plain key-level order function agrees
    first_keys = [p.split("'")[1] for p in paths]
    assert grad_leaf_order(first_keys) == order


def test_bucket_layout_follows_issue_order():
    """Bucket 0 holds the loss head; the last bucket holds the
    embedding — so the first-issued collective is the one with the most
    backward compute left to hide behind."""
    mc = tiny_model()
    from pyrecover_tpu.models.llama import init_params

    params = jax.eval_shape(lambda k: init_params(k, mc), jax.random.key(0))
    leaves = jax.tree_util.tree_leaves(params)
    paths = [
        jax.tree_util.keystr(p)
        for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]
    ]
    order = param_leaf_order(params)
    layout = resolve_bucket_layout(
        [x.size for x in leaves], 0.05, 2, 256, order=order
    )
    assert layout is not None and len(layout) >= 3
    first_bucket_paths = [
        paths[order[i]] for i in range(layout[0].leaf_lo, layout[0].leaf_hi)
    ]
    last_bucket_paths = [
        paths[order[i]] for i in range(layout[-1].leaf_lo, layout[-1].leaf_hi)
    ]
    assert any("output" in p for p in first_bucket_paths)
    assert any("tok_embed" in p for p in last_bucket_paths)


# ---- numerics: parity + error feedback -------------------------------------


def test_bucketed_fp32_layouts_bitexact_dp2():
    """Per-bucket fp32 psums are exact elementwise sums: any two bucket
    layouts produce the identical trajectory, bit for bit."""
    sA, lA = run_steps(MeshConfig(data=2), 2, grad_bucket_mb=0.05)
    sB, lB = run_steps(MeshConfig(data=2), 2, grad_bucket_mb=0.2)
    assert lA == lB
    assert_states_bitexact(sA, sB)


# vs the implicit-GSPMD unbucketed anchor the explicit sync is the same
# math in a different program form; XLA's per-op partitioning choices
# (contract-then-reduce vs gather-then-contract) reassociate float sums.
# Measured ~2.5e-5 max relative over 4 tiny-model steps — the same noise
# class as the elastic drill's topology change. The gate leaves headroom
# without ever accepting a real divergence.
ANCHOR_RTOL = 5e-3


@pytest.mark.parametrize("clip", [True, False], ids=["clip", "noclip"])
@pytest.mark.parametrize("ndev", [2, 4])
def test_bucketed_fp32_tracks_gspmd_anchor(ndev, clip):
    _, base = run_steps(MeshConfig(data=ndev), ndev, clip=clip)
    _, bucketed = run_steps(
        MeshConfig(data=ndev), ndev, clip=clip, grad_bucket_mb=0.05
    )
    rel = max(abs(a - b) / max(abs(a), 1e-9) for a, b in zip(base, bucketed))
    assert rel < ANCHOR_RTOL, (
        f"bucketed fp32 drifted {rel} from the GSPMD anchor at dp{ndev}"
    )


def test_bucketed_zero1_bitexact_vs_zero1_buckets():
    """zero1 composes: the decomposed update runs after the sync, so
    bucketed-zero1 layouts are bit-exact with each other too."""
    s1, l1 = run_steps(
        MeshConfig(data=2), 2, optimizer_sharding="zero1", grad_bucket_mb=0.05
    )
    s2, l2 = run_steps(
        MeshConfig(data=2), 2, optimizer_sharding="zero1", grad_bucket_mb=0.2
    )
    assert l1 == l2
    assert_states_bitexact(s1, s2)


def test_bucketed_int8_composes_and_residual_shape_invariant():
    s_i, l_i = run_steps(MeshConfig(data=2), 2, grad_allreduce="int8")
    s_ib, l_ib = run_steps(
        MeshConfig(data=2), 2, grad_allreduce="int8", grad_bucket_mb=0.05
    )
    # re-blocked quantization groups shift low bits, never the curve
    rel = max(abs(a - b) / max(abs(a), 1e-9) for a, b in zip(l_i, l_ib))
    assert rel < 2e-3, f"bucketed int8 drifted {rel} from unbucketed int8"
    # the residual SHAPE is layout-independent: bucket flips across a
    # resume are spec-only drift (the chaos bucket drill's contract)
    assert s_ib.grad_residual.shape == s_i.grad_residual.shape
    assert float(jnp.abs(s_ib.grad_residual).max()) > 0


def test_bucketed_int8_error_feedback_identity_per_bucket():
    """The PR 10 deficit identity, re-blocked per bucket: for every
    bucket, Σ_r deficit_r == true_sum − reduced exactly."""
    n = 4
    mesh = create_mesh(MeshConfig(data=n), devices=jax.devices()[:n])
    sizes = [700, 1800, 900]
    layout = compute_bucket_layout(sizes, 4 * 1024, replicas=n, block=64)
    assert len(layout) >= 2
    rng = np.random.RandomState(7)
    xs = {
        b.index: rng.randn(n, b.padded_len).astype(np.float32)
        for b in layout
    }
    # zero the per-bucket padding (grads pad with zeros there)
    for b in layout:
        xs[b.index][:, b.n_elems:] = 0.0

    for b in layout:
        def region(xloc):
            red, dfc = quantized_psum_flat(
                xloc[0], mode="int8", block=64, axis_name=AXIS_DATA
            )
            return red, dfc[None]

        with jax.sharding.set_mesh(mesh):
            red, dfc = jax.jit(jax.shard_map(
                region, mesh=mesh, in_specs=(P(AXIS_DATA),),
                out_specs=(P(), P(AXIS_DATA)), axis_names={AXIS_DATA},
                check_vma=False,
            ))(jnp.asarray(xs[b.index]))
        true = xs[b.index].sum(0)
        np.testing.assert_allclose(
            np.asarray(dfc).sum(0), true - np.asarray(red),
            rtol=0, atol=2e-5 * max(np.abs(true).max(), 1.0),
            err_msg=f"deficit identity broken in bucket {b.index}",
        )
        # padding coords owe nothing: their deficit is exactly zero
        assert (np.asarray(dfc)[:, b.n_elems:] == 0).all()


def test_grad_accum_composes_with_buckets():
    _, plain = run_steps(MeshConfig(data=2), 2, grad_bucket_mb=0.05)
    _, accum = run_steps(MeshConfig(data=2), 2, accum=2, grad_bucket_mb=0.05)
    rel = max(abs(a - b) / max(abs(a), 1e-9) for a, b in zip(plain, accum))
    assert rel < 5e-3


def test_bf16_buckets_run():
    _, losses = run_steps(
        MeshConfig(data=2), 2, grad_allreduce="bf16", grad_bucket_mb=0.05
    )
    assert all(np.isfinite(losses))


# ---- config + wiring guards ------------------------------------------------


def test_config_rejects_bucket_compositions():
    with pytest.raises(ValueError, match="bucket-mb"):
        TrainConfig(grad_bucket_mb=-1)
    with pytest.raises(ValueError, match="pipeline"):
        TrainConfig(grad_bucket_mb=4, mesh=MeshConfig(pipeline=2))
    with pytest.raises(ValueError, match="sequence"):
        TrainConfig(grad_bucket_mb=4, mesh=MeshConfig(sequence=2))
    with pytest.raises(ValueError, match="data-parallel"):
        TrainConfig(grad_bucket_mb=4, mesh=MeshConfig(data=2, fsdp=2))
    # buckets compose with pure DP + zero1 + quantized wire
    TrainConfig(grad_bucket_mb=4, optimizer_sharding="zero1",
                grad_allreduce="int8", mesh=MeshConfig(data=2))


def test_make_train_step_rejects_bad_buckets():
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.train_state import make_train_step

    optimizer, _ = build_optimizer(TrainConfig())
    with pytest.raises(ValueError, match="grad_bucket_mb"):
        make_train_step(tiny_model(), optimizer, grad_bucket_mb=-2)
    mc_1f1b = dataclasses.replace(tiny_model(), pp_schedule="1f1b")
    with pytest.raises(ValueError, match="manual region"):
        make_train_step(mc_1f1b, optimizer, grad_bucket_mb=4)


def test_cli_flags_reach_config():
    from pyrecover_tpu.config import get_args

    cfg = get_args(["--grad-bucket-mb", "0.5", "--remat-policy", "auto"])
    assert cfg.grad_bucket_mb == 0.5
    assert cfg.model.remat_policy == "auto"
    # ModelConfig accepts "auto" only as a pre-resolution placeholder
    with pytest.raises(ValueError, match="remat_policy"):
        ModelConfig(remat_policy="sometimes")


# ---- shardcheck: SC13, census, traffic -------------------------------------


def test_overlap_missing_detector():
    from pyrecover_tpu.analysis.shardcheck.collectives import overlap_missing

    # quantized wire: one all_to_all per bucket expected
    assert overlap_missing({"all_to_all": 1}, [], "int8", 4, 2)
    assert not overlap_missing({"all_to_all": 8}, [], "int8", 4, 2)
    # fp32 wire: one non-scalar psum per bucket expected
    assert overlap_missing({}, [1000], "fp32", 3, 2)
    assert not overlap_missing({}, [1000, 1000, 1000], "fp32", 3, 2)
    # no buckets resolved / no data axis: nothing to judge
    assert not overlap_missing({}, [], "fp32", 0, 8)
    assert not overlap_missing({}, [], "int8", 5, 1)


@pytest.mark.parametrize("mode", ["int8", "fp32"])
def test_census_counts_per_bucket_collectives(mode):
    from pyrecover_tpu.analysis.shardcheck.collectives import census

    mesh = create_mesh(MeshConfig(data=2), devices=jax.devices()[:2])
    table, findings = census(
        tiny_model(), None, TINY["batch"], TINY["seq"], mesh=mesh,
        grad_allreduce=mode, grad_bucket_mb=0.05,
    )
    assert table["grad_buckets"] >= 2
    if mode == "int8":
        assert table["traced"].get("all_to_all", 0) >= table["grad_buckets"]
    else:
        assert len(table["psum_vector_payloads"]) >= table["grad_buckets"]
    assert findings == []


@pytest.mark.parametrize("mode", ["int8", "fp32"])
def test_sc13_fires_on_seeded_misconfig(mode):
    """The seeded misconfig: bucketing CONFIGURED but the traced step
    built unbucketed — a single fused tail collective in the jaxpr."""
    from pyrecover_tpu.analysis.shardcheck.collectives import census

    mesh = create_mesh(MeshConfig(data=2), devices=jax.devices()[:2])
    _, findings = census(
        tiny_model(), None, TINY["batch"], TINY["seq"], mesh=mesh,
        grad_allreduce=mode, grad_bucket_mb=0.05, traced_bucket_mb=0,
    )
    assert [f.rule_id for f in findings] == ["SC13"]


def test_check_preset_bucketed_lean_report():
    """check_preset in the full bucketed bandwidth-lean configuration —
    the format.sh gate's exact shape: pure-DP matrix, per-bucket
    traffic with the exposed-vs-hidden split, zero findings."""
    from pyrecover_tpu.analysis.shardcheck.runner import check_preset

    report = check_preset(
        "tiny", tiny_model(), device_counts=(1, 2),
        optimizer_sharding="zero1", grad_allreduce="int8",
        grad_bucket_mb=0.05,
    )
    assert report["findings"] == []
    assert all("fsdp" not in m["mesh"] for m in report["meshes"])
    ov = report["traffic"]["overlap"]
    assert ov["buckets"] >= 2
    assert sum(ov["per_bucket_wire_bytes"]) == ov["total_wire_bytes"]
    assert ov["exposed_wire_bytes"] == ov["per_bucket_wire_bytes"][-1]
    assert ov["hidden_wire_bytes"] == (
        ov["total_wire_bytes"] - ov["exposed_wire_bytes"]
    )


def test_overlap_model_numbers():
    from pyrecover_tpu.analysis.shardcheck.collectives import overlap_model

    leaves = [
        (".params['output']", (64, 128), np.dtype("float32")),
        (".params['tok_embed']", (128, 64), np.dtype("float32")),
    ]
    # unbucketed: the whole sync is the exposed tail
    flat = overlap_model(leaves, {"data": 4}, grad_bucket_mb=0)
    assert flat["buckets"] == 0
    assert flat["exposed_wire_bytes"] == flat["total_wire_bytes"] > 0
    assert flat["hidden_wire_bytes"] == 0
    # bucketed: totals conserved, only the last bucket exposed
    ov = overlap_model(
        leaves, {"data": 4}, grad_bucket_mb=16 * 1024 / 2**20
    )
    assert ov["buckets"] == 2
    assert sum(ov["per_bucket_wire_bytes"]) == ov["total_wire_bytes"]
    assert ov["total_wire_bytes"] == flat["total_wire_bytes"]
    assert ov["exposed_wire_bytes"] == ov["per_bucket_wire_bytes"][-1]
    assert 0 < ov["hidden_pct"] < 100
    # the exposed tail is the EMBEDDING bucket (issued last), not the head
    assert ov["per_bucket_wire_bytes"][-1] == ov["per_bucket_wire_bytes"][0]
    # no data axis: no wire at all
    assert overlap_model(leaves, {"data": 1}, grad_bucket_mb=1)[
        "total_wire_bytes"] == 0


# ---- remat autoscaling -----------------------------------------------------


def test_remat_auto_table_pinned():
    """The README worked example, pinned: rungs chosen on the llama
    presets under `--remat` against the v5e compiler's limit (zero1)."""
    from pyrecover_tpu.models.presets import PRESETS
    from pyrecover_tpu.utils.remat import resolve_remat_policy

    def decide(preset, batch, kind, mesh, remat=True):
        mc = dataclasses.replace(PRESETS[preset](), remat=remat)
        return resolve_remat_policy(
            mc, mesh, batch_size=batch, seq_len=mc.max_seq_len,
            device_kind=kind, optimizer_sharding="zero1",
        )

    room = lambda d: d.limit_bytes - d.margin_bytes  # noqa: E731
    d = decide("llama-150m", 8, "v5e", {"data": 8})
    assert d.rung == "none" and d.fits and not d.remat
    assert d.suggested_batch_per_chip == 16
    assert d.suggested_total_bytes <= room(d)

    d = decide("llama-1b", 8, "v5e", {"data": 8})
    assert d.rung == "none" and d.fits
    assert d.suggested_batch_per_chip == 1

    d = decide("llama-1b", 32, "v5e", {"data": 8})
    assert d.rung == "flash+qkv" and d.fits and d.remat
    assert d.apply(PRESETS["llama-1b"]()).remat_save == d.saved_names
    assert d.suggested_batch_per_chip == 4
    assert d.suggested_total_bytes <= room(d)

    # nothing fits: leanest rung chosen, loudly not-fitting — SC05 and
    # the compiler keep the last word at launch
    d = decide("llama-8b", 8, "v5e", {"data": 8})
    assert d.rung == "full" and d.fits is False and d.remat

    # a device kind nobody asked the compiler about: nothing to size
    # against — what `--remat` always was, no batch advice
    for kind in ("v5p", ""):
        d = decide("llama-1b", 8, kind, {"data": 8})
        assert d.rung == "full" and d.fits is None
        assert d.limit_bytes is None
        assert d.suggested_batch_size == 8

    # without --remat nothing is rematerialized, whatever would fit
    d = decide("llama-8b", 8, "v5e", {"data": 8}, remat=False)
    assert d.rung == "none" and d.fits is None and not d.remat


def test_remat_auto_policy_ordering_and_env_override(monkeypatch):
    from pyrecover_tpu.utils.remat import (
        RUNGS,
        modelled_total_bytes,
        resolve_remat_policy,
    )

    mc = dataclasses.replace(tiny_model(), remat=True)
    # the walk is richest-first and monotone in modelled HBM, from no
    # remat down to nothing kept, the explicit save-attn among the rungs
    assert RUNGS[0] == "none" and RUNGS[-2:] == ("save-attn", "full")
    totals = [
        modelled_total_bytes(
            mc, {"data": 2}, batch_size=8, seq_len=32, rung=rung
        )
        for rung in RUNGS
    ]
    assert totals == sorted(totals, reverse=True)
    # $PYRECOVER_DEVICE_KIND beats the live/passed device kind (the
    # elastic-preflight convention): a CPU host sizes against v5e
    monkeypatch.setenv("PYRECOVER_DEVICE_KIND", "v5e")
    d = resolve_remat_policy(
        mc, {"data": 2}, batch_size=8, seq_len=32, device_kind="cpu"
    )
    assert d.device_kind == "v5e" and d.limit_bytes is not None
    assert d.as_event()["rung"] == d.rung


# ---- driver-level: events + flag flips -------------------------------------


def driver_config(tmp_path, **overrides):
    base = dict(
        sequence_length=TINY["seq"], batch_size=TINY["batch"],
        training_samples=64, training_steps=8, learning_rate=1e-3,
        lr_warmup_steps=2, seed=13, checkpoint_dir=str(tmp_path),
        checkpoint_frequency=4, experiment_name="ov",
        logging_frequency=100, verify_checkpoints=True,
        async_checkpoint=False,
    )
    base.update(overrides)
    cfg = TrainConfig(**base)
    cfg.model = tiny_model()
    cfg.__post_init__()
    return cfg


@pytest.mark.slow
def test_driver_bucket_layout_flip_resume_bitexact(tmp_path):
    """A checkpoint saved under one bucket layout restores onto a run
    with a different cap and the stitched trajectory is bit-exact vs an
    uninterrupted bucketed baseline — the chaos bkf drill's contract at
    unit scale."""
    from pyrecover_tpu.train import train

    straight, _, _ = train(driver_config(
        tmp_path / "straight", grad_bucket_mb=0.05
    ))
    train(driver_config(
        tmp_path / "flip", training_steps=4, grad_bucket_mb=0.05
    ))
    flipped, end, stopped = train(driver_config(
        tmp_path / "flip", resume_from_checkpoint="latest",
        grad_bucket_mb=0.2,
    ))
    assert end == 8 and not stopped
    assert_states_bitexact(straight, flipped)


@pytest.mark.slow
def test_bucketed_int8_tracks_fp32_within_policy_tolerance():
    """The PR 10 convergence-parity policy, bucketed: int8 with
    per-bucket error feedback stays within 2% relative of the fp32 loss
    curve on a seeded 50-step run."""
    steps = 50
    _, base = run_steps(MeshConfig(data=2), 2, n_steps=steps, lr=3e-3)
    i8_state, i8 = run_steps(
        MeshConfig(data=2), 2, n_steps=steps, lr=3e-3,
        grad_allreduce="int8", grad_bucket_mb=0.05,
    )
    rel = max(abs(a - b) / max(abs(a), 1e-9) for a, b in zip(base, i8))
    assert rel < 0.02, (
        f"bucketed int8+feedback drifted {rel:.4f} (policy: <2%)"
    )
    assert float(jnp.abs(i8_state.grad_residual).max()) > 0


@pytest.mark.slow
def test_driver_int8_bucket_flip_on_resume(tmp_path):
    """The vice-versa restore direction: an UNbucketed int8 checkpoint
    resumes onto a bucketed-int8 run — the residual schema is
    layout-independent, so the restore is clean and training finishes
    (the re-blocked feedback reinterprets the carried deficit once,
    within the quantization-noise class the chaos bk drill gates)."""
    from pyrecover_tpu.train import train

    train(driver_config(
        tmp_path, training_steps=4, grad_allreduce="int8",
    ))
    resumed, end, stopped = train(driver_config(
        tmp_path, resume_from_checkpoint="latest",
        grad_allreduce="int8", grad_bucket_mb=0.05,
    ))
    assert end == 8 and not stopped
    assert float(jnp.abs(resumed.grad_residual).max()) > 0


@pytest.mark.slow
def test_grad_bucket_and_remat_autosize_events(tmp_path, monkeypatch):
    from pyrecover_tpu import telemetry
    from pyrecover_tpu.train import train

    monkeypatch.setenv("PYRECOVER_DEVICE_KIND", "v5e")
    cfg = driver_config(
        tmp_path, training_steps=2, checkpoint_frequency=-1,
        grad_allreduce="int8", grad_bucket_mb=0.05,
    )
    cfg.model = dataclasses.replace(cfg.model, remat=True)  # auto by default
    sink = telemetry.add_sink(telemetry.MemorySink())
    try:
        train(cfg)
    finally:
        telemetry.remove_sink(sink)
    buckets = [e for e in sink.events if e["event"] == "grad_bucket"]
    assert len(buckets) == 1
    e = buckets[0]
    assert e["mode"] == "int8" and e["buckets"] >= 2
    assert not e["degenerate"]
    assert sum(e["bucket_bytes_f32"]) > 0
    assert e["max_bucket_bytes"] == max(e["bucket_bytes_f32"])
    remats = [e for e in sink.events if e["event"] == "remat_autosize"]
    assert len(remats) == 1
    from pyrecover_tpu.utils.remat import RUNGS

    assert remats[0]["device_kind"] == "v5e"
    assert remats[0]["rung"] in RUNGS and remats[0]["fell_back"] == 0
    assert set(remats[0]["modelled_bytes"]) == set(RUNGS)
    assert remats[0]["compiled_peak_bytes"] > 0  # the step compiled once


def test_summarizer_renders_wire_section():
    """tools/summarize_telemetry.py surfaces the grad_bucket /
    remat_autosize / grad_quantize trail in text and JSON."""
    import io

    import summarize_telemetry as st

    events = [
        {"ts": 1.0, "event": "run_start", "host": 0},
        {"ts": 2.0, "event": "grad_quantize", "host": 0, "mode": "int8",
         "optimizer_sharding": "zero1", "data_replicas": 2,
         "wire_bytes_per_leg": 1 << 20, "grad_bytes_fp32": 4 << 20},
        {"ts": 2.1, "event": "grad_bucket", "host": 0, "bucket_mb": 0.05,
         "mode": "int8", "buckets": 7, "degenerate": False,
         "bucket_bytes_f32": [100, 200], "min_bucket_bytes": 100,
         "max_bucket_bytes": 200},
        {"ts": 2.2, "event": "remat_autosize", "host": 0,
         "rung": "flash+qkv", "fits": True, "device_kind": "v5e",
         "saved_names": ["flash_out", "flash_lse", "attn_q"],
         "limit_bytes": 15 << 30, "compiled_peak_bytes": 13 << 30,
         "fell_back": 0, "suggested_batch_per_chip": 16},
    ]
    agg = st.aggregate(events)
    assert agg["wire"]["grad_bucket"]["buckets"] == 7
    assert agg["wire"]["remat_autosize"]["rung"] == "flash+qkv"
    assert agg["wire"]["grad_quantize"]["mode"] == "int8"
    out = io.StringIO()
    st.render(agg, out=out)
    text = out.getvalue()
    assert "grad buckets" in text and "7 @ cap 0.05" in text
    assert "rung flash+qkv on v5e" in text and "flash_lse" in text
    assert "13.00 GiB of 15.00 GiB" in text
