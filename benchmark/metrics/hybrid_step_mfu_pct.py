"""The whole step's share of the chip's bf16 peak for a hybrid stack of Mamba
and attention layers: the operations forward and backward require per token
(benchmark/lib/counts_hybrid.py: the mixers' products, the recurrence at 6
operations a (channel, state) pair, causal attention as half the square on
the attention layers only, the tied head once; no recomputation) x
tokens/s/chip of the whole window / peak. ``step_mfu_pct`` would count an
attention layer in every place and no mixer."""

from benchmark.lib import counts_hybrid


def read(run):
    if "attn_layer_period" not in run.cfg or not run.peaks:
        return None
    per_token = counts_hybrid.train_flops_per_token(
        run.cfg, run.cell["sequence_length"])
    return 100.0 * per_token * run.rate / run.peaks["bf16_flops_per_s"]
