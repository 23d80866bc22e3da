"""Metrics/observability tests: FLOPs model, MFU denominators, CSV logger
(reference train.py:277-296, utils.py:30-56)."""

import csv

import jax

from pyrecover_tpu.metrics import LossCSVLogger, ThroughputMeter, WallTimeTotals
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.utils.perf import (
    get_num_flop_per_token,
    get_num_params,
    tpu_peak_flops,
)


def test_flops_model():
    # 6N + 12·l·h·q·t (reference utils.py:41-56)
    assert get_num_flop_per_token(100, 2, 4, 16, 128) == 600 + 12 * 2 * 4 * 16 * 128


def test_num_params_excl_embedding():
    from pyrecover_tpu.models import init_params

    cfg = ModelConfig().tiny()
    params = init_params(jax.random.key(0), cfg)
    total = get_num_params(params)
    no_embed = get_num_params(params, exclude_embedding=True)
    assert total - no_embed == cfg.vocab_size * cfg.dim


def test_tpu_peak_flops_table():
    class FakeDev:
        device_kind = "TPU v5 lite"

    assert tpu_peak_flops(FakeDev()) == 197e12

    class Unknown:
        device_kind = "cpu"

    assert tpu_peak_flops(Unknown()) is None  # no stand-in peak


def test_throughput_meter_counts():
    cfg = ModelConfig().tiny()
    meter = ThroughputMeter(cfg, num_params=1000, seq_len=32, n_devices=2)
    meter.update(n_tokens=48, batch_size=2)  # 64 positions, 48 non-pad
    snap = meter.snapshot()
    assert snap["training_tokens_pct"] == 75.0
    assert snap["steps"] == 1
    assert snap["tokens_per_sec"] > 0
    assert snap["tokens_per_sec_per_chip"] * 2 == snap["tokens_per_sec"]
    # the CPU this test runs on is not in the peak table: no MFU, and the
    # log line says so instead of printing a number
    assert snap["mfu_pct"] is None
    meter.log(1, 0, 2.5)
    meter.peak_flops = 197e12
    meter.update(n_tokens=48, batch_size=2)
    assert meter.snapshot()["mfu_pct"] > 0


def test_loss_csv_logger(tmp_path):
    logger = LossCSVLogger(tmp_path, "exp", enabled=True)
    logger.log(1, 2.5)
    logger.log(2, 2.25)
    logger.close()
    rows = list(csv.reader(open(tmp_path / "exp_loss_log.csv")))
    assert rows[0] == ["step", "loss"]
    assert rows[1] == ["1", "2.5"]
    assert len(rows) == 3


def test_loss_csv_resume_drops_torn_rows(tmp_path):
    """A kill mid-write can tear the CSV's final row; resume must drop the
    unparseable row(s) and keep going, not abort training startup."""
    path = tmp_path / "exp_loss_log.csv"
    path.write_text("step,loss\n1,2.5\n2,2.25\n3,2.1\nbad-row\n4")
    logger = LossCSVLogger(tmp_path, "exp", enabled=True, resume_step=2)
    logger.log(3, 2.0)
    logger.close()
    rows = list(csv.reader(open(path)))
    assert rows == [["step", "loss"], ["1", "2.5"], ["2", "2.25"], ["3", "2.0"]]


def test_walltime_totals_summary():
    t = WallTimeTotals()
    t.train_s, t.ckpt_save_s, t.ckpt_load_s = 10.0, 1.5, 0.5
    t.eval_s = 2.5
    s = t.summary()
    # all four buckets appear: train, ckpt save, ckpt load, eval
    assert "10.0" in s and "1.5" in s and "0.5" in s and "eval 2.5s" in s
    # the same four land in the run-summary telemetry payload
    d = t.as_dict()
    assert (d["train_s"], d["ckpt_save_s"], d["ckpt_load_s"], d["eval_s"]) == (
        10.0, 1.5, 0.5, 2.5
    )


def test_loss_csv_flush_makes_rows_durable(tmp_path):
    """flush() must push buffered rows to the OS without closing — the rows
    a SIGTERM kill would otherwise lose."""
    logger = LossCSVLogger(tmp_path, "exp", enabled=True)
    logger.log(1, 2.5)
    logger.flush()
    rows = list(csv.reader(open(tmp_path / "exp_loss_log.csv")))
    assert rows == [["step", "loss"], ["1", "2.5"]]  # visible pre-close
    logger.close()


def test_analytic_param_count_matches_init():
    from pyrecover_tpu.models import init_params
    from pyrecover_tpu.models.presets import analytic_param_count

    cfg = ModelConfig().tiny()
    params = init_params(jax.random.key(0), cfg)
    assert analytic_param_count(cfg) == get_num_params(params)


def test_analytic_count_exclude_embedding():
    """The MFU 6N convention drops tok_embed but keeps the untied output
    projection (reference train.py:126-127)."""
    from pyrecover_tpu.models.presets import (
        analytic_active_param_count,
        analytic_param_count,
    )

    cfg = ModelConfig().tiny()
    total = analytic_param_count(cfg)
    no_embed = analytic_param_count(cfg, exclude_embedding=True)
    assert total - no_embed == cfg.vocab_size * cfg.dim
    assert (
        analytic_active_param_count(cfg, exclude_embedding=True) == no_embed
    )


def test_preset_8b_matches_reference_size():
    """The llama-8b preset must land at the reference's ≈8.05B params
    (SURVEY §2: dim 4096 × 32L, GQA 32/8, FFN 14336, vocab 131072)."""
    from pyrecover_tpu.models.presets import analytic_param_count, llama_8b

    n = analytic_param_count(llama_8b())
    assert 7.9e9 < n < 8.2e9, n
