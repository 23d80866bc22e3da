"""The whole step's share of the chip's bf16 peak: operations forward and
backward require per token (active experts only, no recomputation, causal
attention as half the square; benchmark/lib/counts.py) x tokens/s/chip of the
whole window / peak."""


def read(run):
    return run.mfu_pct(run.rate)
