"""Tests for the weight-equality CLI (reference tests/check_weights_equality.py
semantics: exit 0 equal / 1 different / 2 error; cross-format comparison)."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, "tools")
from check_equality import compare, load_checkpoint, main  # noqa: E402

from pyrecover_tpu.checkpoint import (
    checkpoint_path,
    save_ckpt_sharded,
    save_ckpt_vanilla,
)
from pyrecover_tpu.config import TrainConfig
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.optim import build_optimizer
from pyrecover_tpu.train_state import create_train_state

MODEL_CFG = ModelConfig().tiny(max_seq_len=32)


def make_state(seed=0):
    optimizer, _ = build_optimizer(TrainConfig(sequence_length=32))
    return create_train_state(jax.random.key(seed), MODEL_CFG, optimizer)


def test_equal_and_different(tmp_ckpt_dir):
    s1, s2 = make_state(1), make_state(2)
    a = checkpoint_path(tmp_ckpt_dir, "x", 1)
    b = checkpoint_path(tmp_ckpt_dir, "x", 2)
    c = checkpoint_path(tmp_ckpt_dir, "x", 3)
    save_ckpt_vanilla(a, s1)
    save_ckpt_vanilla(b, s1)
    save_ckpt_vanilla(c, s2)
    assert main([str(a), str(b)]) == 0
    assert main([str(a), str(c)]) == 1
    assert main([str(a), str(tmp_ckpt_dir / "missing.ckpt")]) == 2


def test_cross_format_equality(tmp_ckpt_dir):
    """A vanilla file and a sharded dir holding the same state compare equal."""
    s = make_state(3)
    v = checkpoint_path(tmp_ckpt_dir, "x", 1)
    d = checkpoint_path(tmp_ckpt_dir, "x", 1, engine="sharded")
    save_ckpt_vanilla(v, s)
    save_ckpt_sharded(d, s)
    assert main([str(v), str(d)]) == 0


def test_tolerance(tmp_ckpt_dir):
    s = make_state(4)
    a = checkpoint_path(tmp_ckpt_dir, "x", 1)
    save_ckpt_vanilla(a, s)
    bumped = jax.tree_util.tree_map(
        lambda x: x + jnp.asarray(5e-7, dtype=x.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        s,
    )
    b = checkpoint_path(tmp_ckpt_dir, "x", 2)
    save_ckpt_vanilla(b, bumped)
    assert main([str(a), str(b), "--tolerance", "1e-7"]) == 1
    assert main([str(a), str(b), "--tolerance", "1e-5"]) == 0


def test_all_state_flag(tmp_ckpt_dir):
    """Same params, different step counter: equal by default, different
    with --all-state."""
    s = make_state(5)
    s_stepped = jax.tree_util.tree_map(lambda x: x, s)
    s_stepped.step = s.step + 7
    a = checkpoint_path(tmp_ckpt_dir, "x", 1)
    b = checkpoint_path(tmp_ckpt_dir, "x", 2)
    save_ckpt_vanilla(a, s)
    save_ckpt_vanilla(b, s_stepped)
    assert main([str(a), str(b)]) == 0
    assert main([str(a), str(b), "--all-state"]) == 1
