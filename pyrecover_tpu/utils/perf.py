"""Performance accounting: parameter counts, analytic FLOPs, TPU peak FLOPs.

Parity: reference `utils.py:30-56` (``get_num_params``,
``get_num_flop_per_token`` = 6N + 12·layers·heads·head_dim·seq_len) and the
hard-coded H100 peak of 989e12 FLOP/s at `train.py:287`, replaced here by a
per-generation TPU peak table so MFU is meaningful on the hardware actually
in use.
"""

import jax
import jax.numpy as jnp

# Dense bf16 peak FLOP/s per chip, per TPU generation. Sources: public Cloud
# TPU system architecture docs (v4: 275 TFLOP/s bf16; v5e: 197; v5p: 459;
# v6e/Trillium: 918).
TPU_PEAK_FLOPS_BF16 = {
    "v3": 123e12,
    "v4": 275e12,
    "v5e": 197e12,
    "v5litepod": 197e12,
    "v5 lite": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}

# HBM per JAX device, bytes, per TPU generation (same public docs; v3
# counts per core — a JAX device is one core there). Consumed by the
# shardcheck memory budget (analysis/shardcheck).
TPU_HBM_BYTES = {
    "v3": 16 * 2**30,
    "v4": 32 * 2**30,
    "v5e": 16 * 2**30,
    "v5litepod": 16 * 2**30,
    "v5 lite": 16 * 2**30,
    "v5p": 95 * 2**30,
    "v6e": 32 * 2**30,
}

_warned_unknown_kinds = set()


def tpu_peak_flops(device=None):
    """Peak bf16 FLOP/s for the local accelerator, or ``None`` when its
    device kind is not in the table.

    An unknown device (CPU included) yields NO peak — so no MFU — rather
    than a stand-in: a utilization computed against an invented
    denominator reads like a device number and is not one. Announced once
    per kind per process (warning + ``mfu_peak_unknown`` event) so a new
    TPU generation missing from the table is noticed, not silently
    MFU-less."""
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in TPU_PEAK_FLOPS_BF16.items():
        if key in kind:
            return peak
    if kind not in _warned_unknown_kinds:
        _warned_unknown_kinds.add(kind)
        from pyrecover_tpu import telemetry
        from pyrecover_tpu.utils.logging import log_host0

        log_host0(
            "device kind %r is not in the TPU peak-FLOPs table; MFU is "
            "not reported for this run (n/a)", kind,
            level=30,  # WARNING
        )
        telemetry.emit("mfu_peak_unknown", device_kind=kind)
    return None


def tpu_hbm_bytes(device_kind=None, device=None):
    """HBM bytes for a device kind (or the local accelerator), or None
    when unknown: callers (the shardcheck budget) treat None as
    "capacity unknown, report without judging"."""
    if device_kind is None:
        if device is None:
            device = jax.devices()[0]
        device_kind = getattr(device, "device_kind", "")
    kind = device_kind.lower()
    for key, cap in TPU_HBM_BYTES.items():
        if key in kind:
            return cap
    return None


def get_num_params(params, exclude_embedding=False):
    """Total parameter count of a pytree (reference `utils.py:30-38`).

    ``exclude_embedding`` drops leaves whose path contains ``embed`` —
    matching the reference's exclusion of the token embedding for FLOPs
    accounting.
    """
    leaves = jax.tree_util.tree_leaves_with_path(params)
    total = 0
    for path, leaf in leaves:
        if exclude_embedding and any(
            "embed" in str(getattr(p, "key", getattr(p, "name", ""))).lower()
            for p in path
        ):
            continue
        total += int(jnp.size(leaf))
    return total


def get_num_flop_per_token(num_params, n_layers, n_heads, head_dim, seq_len):
    """Analytic FLOPs/token: 6N + 12·l·h·q·t (reference `utils.py:41-56`).

    6N covers fwd+bwd matmul FLOPs on non-embedding params; the second term
    is the attention score/value FLOPs which scale with sequence length.
    ``model_flop_per_token`` feeds it a looped, hybrid or tied model's
    counts (metrics.ThroughputMeter).
    """
    return 6 * num_params + 12 * n_layers * n_heads * head_dim * seq_len


def model_flop_per_token(model_config, num_params, seq_len):
    """``get_num_flop_per_token`` for a ``ModelConfig``: ``num_params`` the
    non-embedding parameters a token is multiplied with in one pass
    (inactive experts already taken out). A looped model multiplies every
    weight ``loop_steps`` times; the attention term counts the ATTENTION
    layers alone (a hybrid stack has ``n_attn_layers`` of them, not
    ``n_layers``); a tied head multiplies the embedding table once, which
    the non-embedding count left out; a Mamba layer adds its recurrence (6
    operations a (channel, state) pair forward, 3 x that trained) and its
    depthwise convolution, which are no parameters' products."""
    cfg = model_config
    if cfg.tie_embeddings:
        num_params += cfg.vocab_size * cfg.dim
    flops = get_num_flop_per_token(
        num_params * cfg.loop_steps,
        cfg.n_attn_layers * cfg.loop_steps,
        cfg.n_heads, cfg.head_dim, seq_len,
    )
    if cfg.hybrid:
        flops += 3 * cfg.n_mamba_layers * cfg.d_inner * (
            6 * cfg.mamba_d_state + 2 * cfg.mamba_d_conv)
    return flops
