"""The whole step's share of the chip's bf16 peak for a looped model: the
operations forward and backward require per token with the layers counted
``total_ut_steps`` times and the head as often (benchmark/lib/counts_looped.py:
no recomputation, causal attention as half the square) x tokens/s/chip of the
whole window / peak. ``step_mfu_pct`` counts the stack once and would read a
quarter of this."""

from benchmark.lib import counts_looped


def read(run):
    if "total_ut_steps" not in run.cfg or not run.peaks:
        return None
    per_token = counts_looped.train_flops_per_token(
        run.cfg, run.cell["sequence_length"])
    return 100.0 * per_token * run.rate / run.peaks["bf16_flops_per_s"]
