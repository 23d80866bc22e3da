"""Named model configurations.

``llama_8b`` is the reference's hard-coded default run shape
(train.py:88-99: dim 4096, 32 layers, GQA 32/8, ffn_mult 1.3 → hidden 14336,
vocab 131072 from the Mistral-Nemo tokenizer — ≈8.05B params).
``llama_1b`` is the BASELINE.md benchmark point (~1B params);
the smaller presets are for tests and CI.
"""

from pyrecover_tpu.models.llama import ModelConfig


def llama_8b(max_seq_len=2048, vocab_size=131072):
    return ModelConfig(
        dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        ffn_dim_multiplier=1.3, multiple_of=1024, rope_theta=500000.0,
        vocab_size=vocab_size, max_seq_len=max_seq_len,
    )


def llama_1b(max_seq_len=2048, vocab_size=32768):
    """≈1.2B params: dim 2048, 20 layers, GQA 16/8, ffn hidden 7168."""
    return ModelConfig(
        dim=2048, n_layers=20, n_heads=16, n_kv_heads=8,
        ffn_dim_multiplier=1.3, multiple_of=1024, rope_theta=500000.0,
        vocab_size=vocab_size, max_seq_len=max_seq_len,
    )


def llama_150m(max_seq_len=1024, vocab_size=32768):
    """≈150M params: dim 768, 12 layers, GQA 12/4."""
    return ModelConfig(
        dim=768, n_layers=12, n_heads=12, n_kv_heads=4,
        ffn_dim_multiplier=1.0, multiple_of=256, rope_theta=500000.0,
        vocab_size=vocab_size, max_seq_len=max_seq_len,
    )


def moe_8x1b(max_seq_len=2048, vocab_size=32768):
    """Mixtral-style sparse model: the llama-1b backbone with 8 top-2
    experts per FFN (≈6.9B params, ~2.3B active per token). The reference
    has no MoE (SURVEY §2.2) — this preset exists to exercise expert
    parallelism at a benchmarkable scale."""
    return ModelConfig(
        dim=2048, n_layers=20, n_heads=16, n_kv_heads=8,
        ffn_dim_multiplier=1.3, multiple_of=1024, rope_theta=500000.0,
        vocab_size=vocab_size, max_seq_len=max_seq_len,
        n_experts=8, moe_top_k=2,
    )


def moe_8x150m(max_seq_len=1024, vocab_size=32768):
    """Single-chip-sized MoE (0.52B params, 0.18B active): the llama-150m
    backbone with 8 top-2 experts — fits one 16G chip for MoE benchmarking."""
    return ModelConfig(
        dim=768, n_layers=12, n_heads=12, n_kv_heads=4,
        ffn_dim_multiplier=1.0, multiple_of=256, rope_theta=500000.0,
        vocab_size=vocab_size, max_seq_len=max_seq_len,
        n_experts=8, moe_top_k=2,
    )


def moe_4x1b(max_seq_len=1024, vocab_size=32768):
    """Chip-sized MoE at MXU-viable width (≈1.8B params, ≈1.0B active):
    the llama-1b backbone's dim 2048 / ffn 7168 with 8 layers of 4 top-2
    experts. The 768-wide moe-8x150m is VPU/HBM-limited (a D=768 matmul
    tops out near 45% of v5e peak — measured, see PARITY.md), so this
    preset is where active-param MFU meaningfully measures the MoE path."""
    return ModelConfig(
        dim=2048, n_layers=8, n_heads=16, n_kv_heads=8,
        ffn_dim_multiplier=1.3, multiple_of=1024, rope_theta=500000.0,
        vocab_size=vocab_size, max_seq_len=max_seq_len,
        n_experts=4, moe_top_k=2,
    )


PRESETS = {
    "llama-8b": llama_8b,
    "llama-1b": llama_1b,
    "llama-150m": llama_150m,
    "moe-8x1b": moe_8x1b,
    "moe-8x150m": moe_8x150m,
    "moe-4x1b": moe_4x1b,
}


def analytic_param_count(cfg, exclude_embedding=False):
    """Closed-form parameter count (no initialization needed) — the
    capability of the reference's model smoke test (test_model.py:6-25),
    which instantiates the full 8B model just to count.

    ``exclude_embedding`` drops the token-embedding table (the reference's
    FLOPs-accounting convention, train.py:126-127); the untied output
    projection stays, as it does in the reference. A tied head
    (``tie_embeddings``) is the table itself and counts once; a hybrid
    stack counts its attention layers and its Mamba layers each at their
    own size.
    """
    hd = cfg.head_dim
    per_layer = (
        2 * cfg.dim
        + cfg.dim * cfg.n_heads * hd
        + 2 * cfg.dim * cfg.n_kv_heads * hd
        + cfg.n_heads * hd * cfg.dim
    )
    if cfg.n_experts > 0:
        per_layer += cfg.dim * cfg.n_experts  # router
        per_layer += cfg.n_experts * 3 * cfg.dim * cfg.expert_hidden_dim
    else:
        per_layer += 3 * cfg.dim * cfg.ffn_hidden_dim
    if cfg.post_norms:
        per_layer += 2 * cfg.dim
    layers = cfg.n_attn_layers * per_layer
    if cfg.hybrid:
        # a Mamba layer (models/mamba.py): its mixer's leaves, the SwiGLU
        # and two norms
        import math

        from pyrecover_tpu.models.mamba import mamba_leaf_shapes

        layers += cfg.n_mamba_layers * sum(
            math.prod(shape) for shape in mamba_leaf_shapes(cfg).values())
    embed = 0 if exclude_embedding else cfg.vocab_size * cfg.dim
    head = 0 if cfg.tie_embeddings else cfg.dim * cfg.vocab_size
    return (
        embed
        + layers
        + cfg.dim
        + head
        + (cfg.dim + 1 if cfg.exit_gate else 0)
    )


def inactive_expert_param_count(cfg):
    """Parameters NOT touched per token: the (E - top_k) unused experts'
    FFN weights per layer. 0 for dense models. Subtract from any param
    count (analytic or measured) before feeding the 6N FLOPs/token model
    (reference utils.py:41-56) — otherwise MoE MFU is overstated by ~E/k."""
    if cfg.n_experts <= 0:
        return 0
    unused = cfg.n_experts - cfg.moe_top_k
    return cfg.n_layers * unused * 3 * cfg.dim * cfg.expert_hidden_dim


def analytic_active_param_count(cfg, exclude_embedding=False):
    """Parameters touched per token (see inactive_expert_param_count)."""
    return (
        analytic_param_count(cfg, exclude_embedding=exclude_embedding)
        - inactive_expert_param_count(cfg)
    )


if __name__ == "__main__":
    for name, fn in PRESETS.items():
        cfg = fn()
        n = analytic_param_count(cfg)
        print(
            f"{name}: {n:,} params ({n / 1e9:.2f}B) | dim {cfg.dim} x "
            f"{cfg.n_layers}L | GQA {cfg.n_heads}/{cfg.n_kv_heads} | "
            f"ffn {cfg.ffn_hidden_dim} | vocab {cfg.vocab_size}"
        )
