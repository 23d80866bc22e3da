"""Test environment bootstrap.

The test suite runs on CPU with 8 virtual XLA devices — the fake-cluster
mechanism (SURVEY §4: `--xla_force_host_platform_device_count`) that lets
multi-device sharding, collectives, and distributed-checkpoint tests run on
any host, deterministically, with no TPU attached.

XLA flags are latched when the first backend client is created, and jax
reads ``JAX_PLATFORMS`` when it is imported — so the environment is set
here, before anything imports jax. Subprocesses the tests spawn (launcher,
multiprocess rendezvous, tools) inherit it.
"""

import os
import sys
from pathlib import Path as _Path

# tools/ scripts are imported by tests (test_tools.py, test_pipeline.py);
# anchor the path at the repo root so pytest works from any cwd
sys.path.insert(0, str(_Path(__file__).resolve().parent.parent / "tools"))

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture()
def tmp_ckpt_dir(tmp_path):
    d = tmp_path / "checkpoints"
    d.mkdir()
    return d


def run_train_steps(mesh_cfg, model_cfg, train_cfg, n_steps=3, data_seed=3):
    """Shared parallelism-test harness: run ``n_steps`` of training —
    single-device when ``mesh_cfg`` is None, else on the given mesh — and
    return ``(final_state, losses)``. Used by test_parallel / test_pipeline
    to compare sharded runs against the single-device reference."""
    import contextlib

    from pyrecover_tpu.data import (
        DataLoader,
        StatefulSampler,
        SyntheticTextDataset,
    )
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.parallel.mesh import create_mesh
    from pyrecover_tpu.train import init_sharded_state
    from pyrecover_tpu.train_state import create_train_state, make_train_step

    optimizer, _ = build_optimizer(train_cfg)
    ds = SyntheticTextDataset(
        num_samples=64, seq_len=train_cfg.sequence_length,
        vocab_size=model_cfg.vocab_size, seed=data_seed,
    )
    sampler = StatefulSampler(
        dataset_len=64, global_batch_size=train_cfg.batch_size, seed=data_seed
    )

    if mesh_cfg is None:
        state = create_train_state(jax.random.key(0), model_cfg, optimizer)
        loader = DataLoader(ds, sampler, pad_token_id=0, prefetch=0)
        ctx = contextlib.nullcontext()
    else:
        mesh = create_mesh(mesh_cfg)
        state = init_sharded_state(jax.random.key(0), model_cfg, optimizer, mesh)
        loader = DataLoader(ds, sampler, pad_token_id=0, mesh=mesh, prefetch=0)
        ctx = jax.sharding.set_mesh(mesh)

    step_fn = make_train_step(model_cfg, optimizer, donate=False)
    losses = []
    with ctx:
        for _ in range(n_steps):
            _, batch = next(loader)
            state, m = step_fn(state, batch)
            losses.append(float(m["loss"]))
    return state, losses


_OBS_MODEL = None


def obs_model():
    """The repo's extracted observability model (obscheck over
    ``pyrecover_tpu/``), built once per test session. The per-feature
    catalog-pin tests consult this instead of each re-implementing its
    own grep-the-docstring check."""
    global _OBS_MODEL
    if _OBS_MODEL is None:
        from pyrecover_tpu.analysis.obscheck import build_model

        _OBS_MODEL = build_model(
            [_Path(__file__).resolve().parent.parent / "pyrecover_tpu"]
        )
    return _OBS_MODEL


def assert_observed(events=(), metrics=(), spans=()):
    """Shared catalog pin: every ``events`` name must have >=1 literal
    emit site AND an entry in BOTH catalogs (the telemetry docstring and
    the README event table — parsed entries, not substring hits); every
    ``metrics`` name a registration site (wildcards honored); every
    ``spans`` name a span site."""
    import re

    m = obs_model()
    assert m.cross_surface_armed, "telemetry docstring catalog not found"
    assert m.readme_catalog is not None, "README event table not found"
    for name in events:
        assert name in m.sites_by_event, f"{name}: no emit site in the tree"
        assert name in m.doc_catalog, (
            f"{name} missing from the telemetry docstring catalog"
        )
        assert name in m.readme_catalog, (
            f"{name} missing from the README event table"
        )
    if metrics:
        literal = {r.name for r in m.metric_regs if not r.wildcard}
        wild = [r.name for r in m.metric_regs if r.wildcard]
        for name in metrics:
            assert name in literal or any(
                re.fullmatch(p, name) for p in wild
            ), f"{name}: no metric registration site"
    for name in spans:
        assert name in m.span_names, f"{name}: no span site in the tree"


def assert_params_match(ref_state, state, rtol=2e-3, atol=2e-3):
    """Per-leaf closeness of two TrainState param trees (the standard
    sharded-vs-single-device equality check; strict zip catches a
    leaf-count drift between the trees)."""
    import numpy as np

    ref_leaves = jax.tree_util.tree_leaves(ref_state.params)
    leaves = jax.tree_util.tree_leaves(state.params)
    for a, b in zip(ref_leaves, leaves, strict=True):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b, dtype=np.float32),
            rtol=rtol, atol=atol,
        )
