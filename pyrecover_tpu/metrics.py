"""Observability: throughput, MFU, TFLOPs, per-step loss CSV.

Parity with the reference's metrics block (train.py:277-296): every
``logging_frequency`` steps emit loss, tokens/sec, the fraction of non-pad
training tokens, MFU, and TFLOP/s — but the MFU denominator is the actual
per-chip TPU peak (utils/perf.py) instead of the hard-coded H100 989e12
(reference defect #7, train.py:287). The per-step loss CSV
(`<exp_dir>/<exp>_loss_log.csv`, train.py:143-151) is host-0-only.
"""

import csv
import time
from pathlib import Path

import jax

from pyrecover_tpu.utils.logging import log_host0
from pyrecover_tpu.utils.perf import model_flop_per_token, tpu_peak_flops


class LossCSVLogger:
    """Rank-0 per-step (step, loss) CSV (reference train.py:143-151, 277-280).

    ``resume_step`` (the checkpoint step resumed from, > 0) appends to an
    existing CSV instead of truncating it, so an interrupt/resume cycle
    yields ONE continuous loss curve — the very artifact
    ``tools/compare_loss_csv.py`` exists to compare. (The reference
    truncates on every start, train.py:143-151 — destroying the pre-resume
    segment.) Rows PAST the resume point are dropped first: a kill between
    the last checkpoint and the last logged step would otherwise leave
    steps duplicated with diverging losses when the resumed run replays
    them.
    """

    def __init__(self, exp_dir, experiment_name, enabled=True, resume_step=0):
        self.enabled = enabled and jax.process_index() == 0
        self._file = None
        self._writer = None
        if self.enabled:
            exp_dir = Path(exp_dir)
            exp_dir.mkdir(parents=True, exist_ok=True)
            path = exp_dir / f"{experiment_name}_loss_log.csv"
            append = resume_step > 0 and path.exists() and path.stat().st_size > 0
            if append:
                with open(path, newline="") as f:
                    rows = list(csv.reader(f))
                # a kill mid-write can leave a torn final row (or torn
                # file): drop rows that don't parse instead of refusing to
                # resume — the CSV is observability, not state
                kept = [rows[0] if rows else ["step", "loss"]]
                for r in rows[1:]:
                    try:
                        # both fields must parse — a torn row can lose the
                        # loss column while keeping a valid step
                        if len(r) >= 2 and int(r[0]) <= resume_step:
                            float(r[1])
                            kept.append(r)
                    except ValueError:
                        continue
                # jaxlint: disable-next=torn-write -- resume-time rewrite
                # keeps only rows <= resume_step; a tear costs log rows,
                # never training state, and the next resume re-truncates
                with open(path, "w", newline="") as f:
                    csv.writer(f).writerows(kept)
            self._file = open(path, "a" if append else "w", newline="")
            self._writer = csv.writer(self._file)
            if not append:
                self._writer.writerow(["step", "loss"])

    def log(self, step, loss):
        if self._writer is not None:
            self._writer.writerow([int(step), float(loss)])

    def flush(self):
        """Push buffered rows to the OS now. The logger's rows otherwise sit
        in the file object's userspace buffer until ``close()`` — a SIGTERM
        kill would lose every row since the last sync point, exactly the
        rows the post-mortem needs."""
        if self._file is not None:
            self._file.flush()

    def close(self):
        if self._file is not None:
            self._file.flush()
            self._file.close()
            self._file = None


class ThroughputMeter:
    """Windowed tokens/sec + MFU accounting between logging points."""

    def __init__(self, model_config, num_params, seq_len, n_devices=None):
        from pyrecover_tpu.models.presets import inactive_expert_param_count

        # MoE: only the top-k active experts' FLOPs count toward MFU
        num_params -= inactive_expert_param_count(model_config)
        # layers of each kind, passes, a tied head, the recurrence: one
        # place knows the model's shape (utils/perf.py)
        self.flop_per_token = model_flop_per_token(
            model_config, num_params, seq_len
        )
        self.peak_flops = tpu_peak_flops()
        self.n_devices = n_devices or jax.device_count()
        self.seq_len = seq_len
        self.reset()

    def reset(self):
        self._t0 = time.monotonic()
        self._tokens = 0  # non-pad tokens actually trained on
        self._positions = 0  # total token positions processed (incl. pad)
        self._steps = 0

    def update(self, n_tokens, batch_size):
        self._tokens += int(n_tokens)
        self._positions += int(batch_size) * self.seq_len
        self._steps += 1

    def snapshot(self):
        dt = max(time.monotonic() - self._t0, 1e-9)
        tokens_per_sec = self._positions / dt
        flops = self.flop_per_token * self._positions
        tflops = flops / dt / 1e12
        # unknown device kind -> no peak -> no MFU (None), never a number
        mfu = (
            flops / dt / (self.peak_flops * self.n_devices) * 100.0
            if self.peak_flops else None
        )
        training_pct = 100.0 * self._tokens / max(self._positions, 1)
        return {
            "tokens_per_sec": tokens_per_sec,
            "tokens_per_sec_per_chip": tokens_per_sec / self.n_devices,
            "tflops": tflops,
            "mfu_pct": mfu,
            "training_tokens_pct": training_pct,
            "seconds": dt,
            "steps": self._steps,
        }

    def log(self, step, epoch, loss):
        snap = self.snapshot()
        log_host0(
            "step %d | epoch %d | loss %.4f | %.0f tok/s (%.0f/chip) | "
            "%.1f%% training tokens | %.2f TFLOP/s | MFU %s",
            step, epoch, loss,
            snap["tokens_per_sec"], snap["tokens_per_sec_per_chip"],
            snap["training_tokens_pct"], snap["tflops"],
            "n/a" if snap["mfu_pct"] is None else f"{snap['mfu_pct']:.2f}%",
        )
        self.reset()
        return snap


class WallTimeTotals:
    """Cumulative wall-time + goodput accounting, logged at exit and emitted
    as the ``run_summary`` telemetry event (reference train.py:381-398,
    extended).

    Buckets:
      * ``train_s`` — hot-loop wall time (includes in-loop ckpt/eval).
      * ``step_s`` — time actually spent stepping (interval sums between
        sync points, checkpoint and eval excluded).
      * ``ckpt_save_s`` / ``ckpt_load_s`` — blocking checkpoint seconds.
        ``ckpt_blocking_s`` is the same train-loop-stall charge under its
        honest name; ``ckpt_shadow_s`` counts the OVERLAPPED background
        save work (async vanilla writes, the zerostall pipeline) —
        recovered goodput, visible but never charged to ``lost_s``.
      * ``eval_s`` — held-out evaluation wall time.
      * ``setup_s`` — pre-loop warmup (mesh/model init, compile staging);
        on a restarted run this is part of the restart tax.
      * ``replayed_steps`` / ``replayed_s`` — post-resume steps at or below
        the previous attempt's high-water mark: work done twice.
      * ``wall_s`` — whole ``train()`` call, entry to exit.

    Goodput = productive stepping (step_s − replayed_s) over total wall —
    the fraction of the run that moved training forward exactly once.
    """

    def __init__(self):
        self.train_s = 0.0
        self.step_s = 0.0
        self.ckpt_save_s = 0.0
        self.ckpt_blocking_s = 0.0
        self.ckpt_shadow_s = 0.0
        self.ckpt_load_s = 0.0
        self.eval_s = 0.0
        self.setup_s = 0.0
        self.wall_s = 0.0
        self.replayed_steps = 0
        self.replayed_s = 0.0

    def productive_s(self):
        return max(self.step_s - self.replayed_s, 0.0)

    def lost_s(self):
        """Resilience overhead: time that bought durability, not progress.
        Only the BLOCKING checkpoint seconds count — shadow (overlapped)
        save work ran while training stepped, so charging it would hide
        exactly the goodput an async engine recovers."""
        return (
            self.ckpt_save_s + self.ckpt_load_s + self.replayed_s + self.setup_s
        )

    def goodput_pct(self):
        total = self.wall_s or (self.train_s + self.ckpt_load_s + self.setup_s)
        if total <= 0:
            return 0.0
        return 100.0 * self.productive_s() / total

    def as_dict(self):
        return {
            "train_s": round(self.train_s, 3),
            "step_s": round(self.step_s, 3),
            "ckpt_save_s": round(self.ckpt_save_s, 3),
            "ckpt_blocking_s": round(self.ckpt_blocking_s, 3),
            "ckpt_shadow_s": round(self.ckpt_shadow_s, 3),
            "ckpt_load_s": round(self.ckpt_load_s, 3),
            "eval_s": round(self.eval_s, 3),
            "setup_s": round(self.setup_s, 3),
            "wall_s": round(self.wall_s, 3),
            "replayed_steps": int(self.replayed_steps),
            "replayed_s": round(self.replayed_s, 3),
            "productive_s": round(self.productive_s(), 3),
            "lost_s": round(self.lost_s(), 3),
            "goodput_pct": round(self.goodput_pct(), 2),
        }

    def summary(self):
        s = (
            f"total train {self.train_s:.1f}s | "
            f"ckpt save {self.ckpt_save_s:.1f}s | ckpt load {self.ckpt_load_s:.1f}s | "
            f"eval {self.eval_s:.1f}s"
        )
        if self.ckpt_shadow_s:
            s += f" | ckpt shadow {self.ckpt_shadow_s:.1f}s (overlapped)"
        if self.replayed_steps:
            s += (
                f" | replayed {self.replayed_steps} steps"
                f" ({self.replayed_s:.1f}s)"
            )
        if self.wall_s:
            s += f" | goodput {self.goodput_pct():.1f}%"
        return s
