"""Config/CLI surface tests: a reference-style command line (the flag
vocabulary of utils.py:105-261 / submit-training-simple.sh) must parse into
the right TrainConfig."""

import pytest

from pyrecover_tpu.config import TrainConfig, get_args


def test_reference_style_command_line():
    cfg = get_args([
        "--dataset", "/data/train.parquet",
        "--tokenizer-name-or-path", "unsloth/Mistral-Nemo-Base-2407-bnb-4bit",
        "--sequence-length", "2048",
        "--batch-size", "32",
        "--learning-rate", "1e-5",
        "--lr-warmup-steps", "10",
        "--training-steps", "3000",
        "--logging-frequency", "10",
        "--checkpoint-dir", "checkpoints/",
        "--checkpoint-frequency", "1000",
        "--experiment_name", "my-exp",
        "--verify-checkpoints",
        "--max-kept-checkpoints", "3",
        "--checkpoint-engine", "sharded",
        "--timeaware-checkpointing",
        "--default-iter-time", "1.0",
        "--default-ckpt-time", "10.0",
        "--use_flash_attention",
        "--log-loss-to-csv",
        "--fused-optimizer",
        "--compile",
        "--distributed",
        "--model-dtype", "bf16",
        "--grad-max-norm", "1",
        "--profile", "--profile-step-start", "10", "--profile-step-end", "12",
        "--resume-from-checkpoint", "latest",
    ])
    assert cfg.dataset == "/data/train.parquet"
    assert cfg.sequence_length == 2048
    assert cfg.model.max_seq_len == 2048
    assert cfg.batch_size == 32
    assert cfg.training_steps == 3000
    assert cfg.experiment_name == "my-exp"
    assert cfg.verify_checkpoints
    assert cfg.checkpoint_engine == "sharded"
    assert cfg.timeaware_checkpointing
    assert cfg.model.attention_impl == "flash"  # --use_flash_attention
    assert cfg.log_loss_to_csv
    assert cfg.resume_from_checkpoint == "latest"
    assert cfg.model.compute_dtype == "bfloat16"
    assert cfg.grad_max_norm == 1.0
    assert cfg.profile and cfg.profile_step_start == 10


@pytest.mark.parametrize(
    "flag", ["--use-torch-distributed-ckpt", "--sharded-checkpoint"])
def test_legacy_engine_flags_are_refused(flag):
    """One spelling of the engine choice: ``--checkpoint-engine``."""
    with pytest.raises(SystemExit):
        get_args([flag])
    assert not hasattr(TrainConfig(), "sharded_checkpoint")


def test_mesh_flags():
    cfg = get_args(["--dp", "2", "--fsdp", "2", "--tp", "2", "--sp", "1"])
    assert (cfg.mesh.data, cfg.mesh.fsdp, cfg.mesh.tensor, cfg.mesh.sequence) == (
        2, 2, 2, 1
    )


def test_defaults_mirror_reference():
    cfg = get_args([])
    # reference defaults: seq 2048, batch 1 (global), lr 1e-5, warmup 10,
    # ckpt freq 10, max kept 3, experiment 'default-exp' (utils.py:105-261)
    assert cfg.sequence_length == 2048
    assert cfg.batch_size == 1
    assert cfg.learning_rate == 1e-5
    assert cfg.lr_warmup_steps == 10
    assert cfg.checkpoint_frequency == 10
    assert cfg.max_kept_checkpoints == 3
    assert cfg.experiment_name == "default-exp"
    # 8B reference model shape (train.py:88-99)
    assert cfg.model.dim == 4096 and cfg.model.n_layers == 32
    assert cfg.model.n_heads == 32 and cfg.model.n_kv_heads == 8
    # grad clipping ON here (the reference comments out its call site)
    assert cfg.grad_clipping


def test_checkpoint_frequency_disable():
    cfg = get_args(["--checkpoint-frequency", "-1"])
    assert cfg.checkpoint_frequency == -1


def test_checkpoint_frequency_normalizes_any_disable_value():
    """ISSUE 14 satellite: the docs promise "-1 disables" while the train
    gate was `> 0`, so 0 and other negatives silently disabled too. Every
    value < 1 now canonicalizes to -1, loudly."""
    import logging

    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    # the project logger sets propagate=False, so capture directly on it
    logger = logging.getLogger("pyrecover_tpu")
    handler = _Capture(level=logging.WARNING)
    logger.addHandler(handler)
    prior_level = logger.level
    logger.setLevel(logging.WARNING)
    try:
        assert get_args(["--checkpoint-frequency", "0"]
                        ).checkpoint_frequency == -1
        assert get_args(["--checkpoint-frequency", "-7"]
                        ).checkpoint_frequency == -1
        hits = [m for m in records if "disables periodic checkpoints" in m]
        assert len(hits) == 2
        # the canonical -1 is already the documented spelling: no noise
        records.clear()
        assert get_args(["--checkpoint-frequency", "-1"]
                        ).checkpoint_frequency == -1
        assert not [m for m in records if "disables periodic" in m]
    finally:
        logger.removeHandler(handler)
        logger.setLevel(prior_level)


def test_checkpoint_frequency_auto_and_knobs():
    cfg = get_args(["--checkpoint-frequency", "auto"])
    assert cfg.checkpoint_auto
    # the numeric default survives as the static-counterfactual baseline
    assert cfg.checkpoint_frequency == 10
    assert not get_args([]).checkpoint_auto
    cfg2 = get_args(["--checkpoint-frequency", "auto",
                     "--ckpt-auto-floor", "2", "--ckpt-auto-ceiling", "64",
                     "--ckpt-auto-mtti-prior", "120",
                     "--ckpt-auto-window", "6"])
    assert (cfg2.ckpt_auto_floor, cfg2.ckpt_auto_ceiling) == (2, 64)
    assert cfg2.ckpt_auto_mtti_prior_s == 120.0
    assert cfg2.ckpt_auto_window == 6
    import pytest

    with pytest.raises(SystemExit):  # argparse rejects non-int non-auto
        get_args(["--checkpoint-frequency", "sometimes"])
    from pyrecover_tpu.config import TrainConfig

    with pytest.raises(ValueError):
        TrainConfig(ckpt_auto_floor=0)
    with pytest.raises(ValueError):
        TrainConfig(ckpt_auto_floor=8, ckpt_auto_ceiling=4)
    with pytest.raises(ValueError):
        TrainConfig(ckpt_auto_mtti_prior_s=0.0)
    with pytest.raises(ValueError):
        TrainConfig(ckpt_auto_window=0)


def test_attention_impl_auto_selection():
    """auto → ring under --sp > 1, flash under --use_flash_attention,
    sdpa otherwise; explicit choice always wins."""
    from pyrecover_tpu.config import get_args

    assert get_args([]).model.attention_impl == "sdpa"
    assert get_args(["--use_flash_attention"]).model.attention_impl == "flash"
    assert get_args(["--sp", "2"]).model.attention_impl == "ring"
    assert get_args(
        ["--sp", "2", "--attention-impl", "flash"]
    ).model.attention_impl == "flash"
    assert get_args(["--attention-impl", "ring"]).model.attention_impl == "ring"
