"""The float32 references against the program at a toy width on the CPU:
the seed's weights, the dense block, Mixtral's route and experts, the loss, the
gradients and the AdamW update. Program and reference both compute in float32
here, so they agree to rounding; the cells' own limits are read on the chip."""

import numpy as np
import pytest

from benchmark.lib.manifest import Manifest
from benchmark.run import REHEARSAL
from benchmark.runners import train_window as tw

TOY = REHEARSAL["cfg"]  # the toy width the harness's CPU rehearsal runs


def toy(name):
    man = Manifest()
    cfg = man.config(name)
    tm = {**cfg.get("trainer_model", {}), **TOY["trainer_model"]}
    return man, {**cfg, **TOY, "trainer_model": tm}


def program_steps(cfg, seed, rows, dtype="fp32"):
    """The trainer's own jitted step, driven by hand at the toy width."""
    import jax

    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.train_state import create_train_state, make_train_step

    config = TrainConfig(
        model=tw.model_config(cfg), sequence_length=rows[0]["inputs"].shape[1],
        batch_size=rows[0]["inputs"].shape[0], seed=seed, model_dtype=dtype,
        param_dtype=dtype, learning_rate=3e-4, lr_warmup_steps=1,
        loss_chunk_size=16, remat=True)
    opt, _ = build_optimizer(config)
    state = create_train_state(jax.random.key(seed), config.model, opt)
    probe = tw.Probe(
        make_train_step(config.model, opt, loss_chunk_size=16, donate=False),
        len(rows), config)
    for r in rows:
        state, _ = probe(state, {k: jax.numpy.asarray(v) for k, v in r.items()})
    return probe.readings(), config


def rows_for(seed, n, batch=4, seq=64, vocab=256):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        toks = rng.integers(1, vocab, size=(batch, seq + 1)).astype(np.int32)
        labels = toks[:, 1:].copy()
        labels[:, -5:] = -100  # a masked tail, as padding gives
        out.append({"inputs": toks[:, :-1], "labels": labels})
    return out


@pytest.mark.parametrize("name", ["mistral-7b", "mixtral-8x7b"])
def test_reference_follows_the_program(name):
    import jax

    man, cfg = toy(name)
    rows = rows_for(3, 3)
    prog, config = program_steps(cfg, 11, rows)
    ref = man.reference(cfg["reference"]).Reference(
        cfg, tw.optimizer_facts(config), jax.devices()[:1])
    out = tw.follow(ref, 11, rows)
    got = tw.compare(prog, out)
    assert got["weights_gap"] < 1e-6          # the seed's weights (norms sum in another order)
    for k in ("loss1_gap", "loss2_gap", "loss3_gap"):
        assert got[k] < 2e-6, (k, got)
    assert got["gnorm1_gap"] < 1e-4, got
    assert got["grad_leaf_gap"] < 1e-4, got
    assert got["change_leaf_gap"] < 1e-3, got


def test_moe_reference_is_the_same_on_four_devices():
    import jax

    man, cfg = toy("mixtral-8x7b")
    rows = rows_for(5, 2)
    _, config = program_steps(cfg, 13, rows[:1])
    Ref = man.reference(cfg["reference"]).Reference
    one = tw.follow(Ref(cfg, tw.optimizer_facts(config), jax.devices()[:1]), 13, rows)
    four = tw.follow(Ref(cfg, tw.optimizer_facts(config), jax.devices()[:4]), 13, rows)
    got = tw.compare(four, one)
    assert max(got.values()) < 1e-5, got


def test_lower_precision_reads_higher():
    """The control (the reference with every product's operands in fp8) reads
    a wider gap than bfloat16 does, which reads wider than float32."""
    import jax

    man, cfg = toy("mistral-7b")
    rows = rows_for(7, 2)
    _, config = program_steps(cfg, 17, rows[:1])
    Ref = man.reference(cfg["reference"]).Reference
    facts = tw.optimizer_facts(config)
    out = {k: tw.follow(Ref(cfg, facts, jax.devices()[:1], precision=k), 17, rows)
           for k in ("f32", "bf16", "fp8")}
    bf16 = tw.compare(out["bf16"], out["f32"])
    fp8 = tw.compare(out["fp8"], out["f32"])
    assert fp8["loss1_gap"] > 3 * bf16["loss1_gap"] > 0
    assert fp8["grad_leaf_gap"] > 3 * bf16["grad_leaf_gap"] > 0
