"""Tools tests: checkpoint inspector and loss-convergence comparator —
including the reference's signature workflow: interrupted+resumed run's loss
CSV must match the straight run's exactly on the post-resume range."""

import sys
from pathlib import Path

import jax

# tools/ is on sys.path via conftest (anchored at the repo root)
from compare_loss_csv import main as compare_main  # noqa: E402
from inspect_checkpoint import main as inspect_main  # noqa: E402

from pyrecover_tpu.checkpoint import checkpoint_path, save_ckpt_sharded, save_ckpt_vanilla
from pyrecover_tpu.config import TrainConfig
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.optim import build_optimizer
from pyrecover_tpu.train import train
from pyrecover_tpu.train_state import create_train_state
import pytest


def make_state():
    optimizer, _ = build_optimizer(TrainConfig(sequence_length=16))
    return create_train_state(
        jax.random.key(0), ModelConfig().tiny(max_seq_len=16), optimizer
    )


def test_inspect_both_formats(tmp_path, capsys):
    state = make_state()
    v = checkpoint_path(tmp_path, "x", 1)
    save_ckpt_vanilla(v, state, {"consumed": 1}, extra_meta={"step": 1})
    assert inspect_main([str(v), "--leaves"]) == 0
    out = capsys.readouterr().out
    assert "vanilla" in out and "step: 1" in out and "tok_embed" in out

    d = checkpoint_path(tmp_path, "x", 2, engine="sharded")
    save_ckpt_sharded(d, state, extra_meta={"step": 2})
    assert inspect_main([str(d)]) == 0
    out = capsys.readouterr().out
    assert "sharded" in out and "step: 2" in out

    assert inspect_main([str(tmp_path / "nope")]) == 2


def write_csv(path, rows):
    path.write_text("step,loss\n" + "\n".join(f"{s},{l}" for s, l in rows) + "\n")


def test_compare_loss_csv(tmp_path, capsys):
    a = tmp_path / "a.csv"
    b = tmp_path / "b.csv"
    write_csv(a, [(1, 4.0), (2, 3.5), (3, 3.2)])
    write_csv(b, [(2, 3.5), (3, 3.2), (4, 3.0)])
    assert compare_main([str(a), str(b)]) == 0
    write_csv(b, [(2, 3.5), (3, 3.9)])
    assert compare_main([str(a), str(b)]) == 1
    assert compare_main([str(a), str(b), "--tolerance", "1.0"]) == 0
    assert compare_main([str(a), str(tmp_path / "missing.csv")]) == 2


@pytest.mark.slow
def test_resume_loss_curve_matches_straight(tmp_path):
    """The reference's loss-convergence benchmark, end to end: per-step loss
    of interrupted+resumed == straight run, bit-exact, on the resumed range."""

    def cfg(d, steps, resume=None):
        c = TrainConfig(
            sequence_length=32, batch_size=8, training_samples=64,
            training_steps=steps, learning_rate=1e-3, seed=3,
            checkpoint_dir=str(d), checkpoint_frequency=3,
            experiment_name="exp", logging_frequency=100,
            log_loss_to_csv=True, resume_from_checkpoint=resume,
            async_checkpoint=False,
        )
        c.model = ModelConfig().tiny(max_seq_len=32, vocab_size=128)
        c.__post_init__()
        return c

    d1, d2 = tmp_path / "straight", tmp_path / "resumed"
    train(cfg(d1, 6))
    train(cfg(d2, 3))
    csv_first = (d2 / "exp" / "exp_loss_log.csv").read_text()
    train(cfg(d2, 6, resume="latest"))

    a = d1 / "exp" / "exp_loss_log.csv"
    b = d2 / "exp" / "exp_loss_log.csv"
    # the resumed run overwrote the CSV with steps 4-6; compare that range
    assert compare_main([str(a), str(b), "--tolerance", "0", "--from-step", "4"]) == 0
    # sanity: the pre-resume run actually logged steps 1-3
    first_steps = [
        int(line.split(",")[0])
        for line in csv_first.strip().splitlines()[1:]
    ]
    assert first_steps == [1, 2, 3], first_steps


@pytest.mark.slow
def test_generate_from_checkpoint(tmp_path):
    """tools/generate.py decodes from a trained checkpoint in both sampling
    modes; greedy output is deterministic."""
    import subprocess
    import sys
    from pathlib import Path

    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.models import ModelConfig
    from pyrecover_tpu.train import train

    cfg = TrainConfig(
        sequence_length=32, batch_size=8, training_samples=16,
        training_steps=2, checkpoint_dir=str(tmp_path),
        checkpoint_frequency=2, experiment_name="gen",
    )
    cfg.model = ModelConfig().tiny(max_seq_len=32, vocab_size=128)
    cfg.__post_init__()
    train(cfg)
    ckpt = next((tmp_path / "gen").glob("ckpt_*.ckpt"))

    repo = Path(__file__).resolve().parent.parent
    args = [
        sys.executable, str(repo / "tools" / "generate.py"), str(ckpt),
        "--model-dim", "64", "--model-layers", "2", "--model-heads", "4",
        "--model-kv-heads", "2", "--vocab-size", "128", "--max-seq-len", "32",
        "--multiple-of", "32", "--prompt-ids", "1,2,3",
        "--max-new-tokens", "5",
    ]
    import os

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}  # no accelerator in tests
    out1 = subprocess.run(args, capture_output=True, text=True, timeout=300,
                          env=env)
    assert out1.returncode == 0, out1.stderr[-2000:]
    ids = [int(x) for x in out1.stdout.strip().split(",")]
    assert len(ids) == 8 and ids[:3] == [1, 2, 3]
    assert all(0 <= i < 128 for i in ids)
    # greedy is deterministic
    out2 = subprocess.run(args, capture_output=True, text=True, timeout=300,
                          env=env)
    assert out2.stdout == out1.stdout
    # temperature sampling runs
    out3 = subprocess.run(args + ["--temperature", "1.0"], capture_output=True,
                          text=True, timeout=300, env=env)
    assert out3.returncode == 0, out3.stderr[-2000:]
    # batched prompts (';'-separated): one line per prompt, row 0 equals
    # the single-prompt greedy output (lockstep decode through one cache)
    batched = [
        a if a != "1,2,3" else "1,2,3;7,5,9" for a in args
    ]
    out4 = subprocess.run(batched, capture_output=True, text=True,
                          timeout=300, env=env)
    assert out4.returncode == 0, out4.stderr[-2000:]
    lines = out4.stdout.strip().splitlines()
    assert len(lines) == 2
    assert lines[0] == out1.stdout.strip()
    assert lines[1].startswith("7,5,9,") and len(lines[1].split(",")) == 8


def test_inspect_diagnoses_corrupt_checkpoint(tmp_path, capsys):
    """tools/inspect_checkpoint.py is where the trainer's corrupt-
    checkpoint errors send people: on a truncated file it must print
    forensics (checksum verdict, intact frame count) and exit 1, not
    crash with a decode traceback."""
    state = make_state()
    path = tmp_path / "ckpt_5.ckpt"
    save_ckpt_vanilla(path, state, {"consumed": 5}, verify=True,
                      extra_meta={"step": 5})
    data = path.read_bytes()
    path.write_bytes(data[: len(data) // 2])

    rc = inspect_main([str(path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "CORRUPT" in out
    assert "MISMATCH" in out
    assert "intact leaf frames" in out
