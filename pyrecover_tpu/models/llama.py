"""Llama-3-style decoder-only Transformer as pure functions over a pytree.

Capability parity with the reference `model.py` (Transformer :330-395,
TransformerBlock :272-327, Attention :142-230, FeedForward :233-269,
RMSNorm :25-49), re-designed TPU-first:

  * Pure ``init_params`` / ``forward`` functions — no module objects, no
    mutable state. The parameter pytree IS the checkpointable object, which
    makes bit-exact resume structural instead of effortful.
  * Layers are *stacked* along a leading axis and iterated with
    ``jax.lax.scan`` — one compiled layer body regardless of depth (fast
    compiles, friendly to pipeline-style sharding later).
  * Optional rematerialization (``jax.checkpoint``) of each block — the HBM
    bandwidth lever the reference has no equivalent of.
  * Activation sharding constraints via ``parallel.mesh.constrain`` — under
    a mesh, activations carry (data, sequence, tensor) shardings; on one
    device the constraints vanish.
  * Params stored in ``param_dtype`` (fp32 master by default), compute in
    ``compute_dtype`` (bf16 default — the MXU's native format). The
    reference instead builds the whole model in bf16 (`train.py:100-101`).
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from pyrecover_tpu.ops.attention import sdpa_attention
from pyrecover_tpu.ops.rope import apply_rope, precompute_rope
from pyrecover_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP, AXIS_SEQ, AXIS_TENSOR, constrain
from pyrecover_tpu.telemetry.stepscopes import (
    ATTN,
    EMBED,
    FFN,
    FLASH_ATTENTION,
    LAYERS,
    LOOP_PASS,
    LOSS_HEAD,
)
from pyrecover_tpu.utils.dtypes import resolve_dtype
from pyrecover_tpu.utils.remat import FLASH_LSE, checkpoint_policy, saved_names


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Shape parity with reference ``TransformerModelArgs`` (model.py:9-22).

    Defaults mirror the reference's 8B default config (train.py:88-99):
    dim 4096, 32 layers, GQA 32q/8kv, ffn multiplier 1.3, multiple_of 1024,
    rope theta 5e5 — vocab/seq come from tokenizer/flags at call sites.
    """

    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    vocab_size: int = 131072
    ffn_dim_multiplier: float = 1.3
    multiple_of: int = 1024
    norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    max_seq_len: int = 2048
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    attention_impl: str = "sdpa"  # "sdpa" | "flash" | "ring"
    pp_microbatches: int = 0  # pipeline microbatch count; 0 → stage count
    # pipeline training schedule: "gpipe" (AD-derived backward wave) or
    # "1f1b" (explicit interleaved backward — in-flight microbatches per
    # stage bounded to the stage count; parallel/pipeline.py)
    pp_schedule: str = "gpipe"
    # virtual (interleaved) stages per physical pipeline stage, 1f1b only:
    # V > 1 assigns each stage V non-contiguous layer chunks, dropping the
    # bubble from (S-1)/(M+S-1) to (S-1)/(V·M+S-1) (Megatron-style
    # interleaving; parallel/pipeline.py::build_interleaved_tables)
    pp_virtual_stages: int = 1
    remat: bool = False
    # what the layer scan keeps for the backward sweep when remat=True:
    # "full" nothing (every block recomputed from its carry), "save-attn"
    # the flash call's two residuals (the forward kernel then runs once,
    # not twice), "auto" the richest save-set of utils/remat.py's ladder
    # that fits the device, resolved by the trainer BEFORE the model is
    # built and handed over in ``remat_save``. An "auto" nobody resolved
    # (a library caller, a device kind with no limit) keeps nothing: full.
    remat_policy: str = "auto"
    # the checkpoint names "auto" resolved to (utils/remat.py
    # resolve_remat_policy); None = not resolved, ``remat_policy`` decides
    remat_save: tuple = None
    # flash-attention (block_q, block_kv) tiling; 0 = auto-resolve from
    # the per-device-kind defaults table (ops/flash_attention.py
    # DEFAULT_BLOCKS, measured with tools/bench_flash_blocks.py — on v5e
    # that resolves to the 1024x1024 the r03 sweep picked, ~6% MFU over
    # 512x512 at 1B/seq-2048). Explicit values always win.
    flash_block_q: int = 0
    flash_block_kv: int = 0
    # -- mixture of experts (0 experts = dense; reference is dense-only) --
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01  # load-balance loss scale
    moe_ffn_hidden: int = 0  # per-expert hidden size; 0 → ffn_hidden_dim
    moe_dispatch: str = "auto"  # "auto" | "grouped" | "einsum" | "scatter" (moe.py)
    # -- looped stack (1 pass, no post-norms, no gate = the plain decoder) --
    # the SAME n_layers run loop_steps times a forward, the final norm
    # closing every pass (its output feeds the head AND the next pass)
    loop_steps: int = 1
    # "sandwich" norms: a second RMSNorm after each sublayer, inside the
    # residual branch (x + norm(sublayer(norm(x))))
    post_norms: bool = False
    # exit gate Linear(dim -> 1) read from every pass's normed state; the
    # training loss becomes the expectation of the per-pass cross-entropy
    # under the exit distribution, less exit_beta * its entropy
    # (train_state.chunked_exit_loss)
    exit_gate: bool = False
    exit_beta: float = 0.1
    # -- hybrid stack: a period of layer kinds (period 1 = every layer an
    # attention layer, the plain decoder). Layer i is an attention layer
    # where i % attn_layer_period == attn_layer_offset and a Mamba-1 layer
    # otherwise (the names of AI21's Jamba configs); every layer keeps the
    # SwiGLU feed-forward. The stack is scanned by period: the Mamba layers
    # before the attention layer as one scan, the attention layer, the
    # Mamba layers after it as another.
    attn_layer_period: int = 1
    attn_layer_offset: int = 0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0  # 0 -> ceil(dim / 16)
    # rotary positions on q and k (a hybrid stack takes its positions from
    # the recurrence and publishes no rope)
    rope: bool = True
    # the head reads the embedding table: logits = h . tok_embed^T, one
    # leaf fed gradients from both ends, no "output" leaf
    tie_embeddings: bool = False

    def __post_init__(self):
        if self.attn_layer_period < 1 or not (
            0 <= self.attn_layer_offset < self.attn_layer_period
        ):
            raise ValueError(
                f"attn_layer_period={self.attn_layer_period} / "
                f"attn_layer_offset={self.attn_layer_offset}: the period "
                "must be >= 1 and the offset inside it"
            )
        if self.hybrid and self.n_layers % self.attn_layer_period:
            raise ValueError(
                f"a hybrid stack is scanned by period: n_layers="
                f"{self.n_layers} is not a multiple of attn_layer_period="
                f"{self.attn_layer_period}"
            )
        if self.hybrid and self.n_experts > 0:
            raise ValueError(
                "a mixture of experts with Mamba layers is not supported "
                "(--moe-experts with --model-attn-period > 1): the expert "
                "layers of a hybrid stack have a period of their own, "
                "which the stack does not describe (ROADMAP.md M3)"
            )
        if self.hybrid and (
            self.loop_steps > 1 or self.post_norms or self.exit_gate
        ):
            raise ValueError(
                "a looped, sandwich-normed or gated hybrid stack is not "
                "supported (untested); drop --model-attn-period or the "
                "--model-loop-* / --model-post-norms flags"
            )
        if self.loop_steps < 1:
            raise ValueError(
                f"loop_steps (--model-loop-steps) must be >= 1, got "
                f"{self.loop_steps}"
            )
        if self.exit_gate and self.loop_steps < 2:
            raise ValueError(
                "exit_gate (--model-exit-gate) needs loop_steps >= 2: one "
                "pass has no exit to choose between"
            )
        if self.n_experts > 0 and (
            self.loop_steps > 1 or self.post_norms or self.exit_gate
        ):
            raise ValueError(
                "a looped, sandwich-normed or gated mixture of experts is "
                "not supported (untested: the load-balance loss has no "
                "per-pass form); drop --moe-experts or the --model-loop-* "
                "flags"
            )
        if self.n_experts > 0 and self.moe_top_k > self.n_experts:
            raise ValueError(
                f"moe_top_k={self.moe_top_k} must be <= "
                f"n_experts (--moe-experts) = {self.n_experts}"
            )
        if self.remat_policy not in ("full", "save-attn", "auto"):
            raise ValueError(
                f"remat_policy={self.remat_policy!r}: expected 'auto', "
                "'save-attn' or 'full'"
            )
        if self.pp_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"pp_schedule={self.pp_schedule!r}: expected 'gpipe' or '1f1b'"
            )
        if self.pp_virtual_stages < 1:
            raise ValueError(
                f"--pp-virtual-stages must be >= 1, got "
                f"{self.pp_virtual_stages}"
            )
        if self.pp_virtual_stages > 1 and self.pp_schedule != "1f1b":
            raise ValueError(
                "--pp-virtual-stages > 1 requires --pp-schedule 1f1b (the "
                "interleaved schedule is a 1F1B variant)"
            )

    @property
    def head_dim(self):
        return self.dim // self.n_heads

    @property
    def hybrid(self):
        """Whether the stack holds Mamba layers beside attention layers."""
        return self.attn_layer_period > 1

    @property
    def n_attn_layers(self):
        return self.n_layers // self.attn_layer_period

    @property
    def n_mamba_layers(self):
        return self.n_layers - self.n_attn_layers

    @property
    def d_inner(self):
        return self.mamba_expand * self.dim

    @property
    def dt_rank(self):
        return self.mamba_dt_rank or -(-self.dim // 16)

    @property
    def ssm_state_elems(self):
        """Floats of recurrent state one token of one Mamba layer has."""
        return self.d_inner * self.mamba_d_state

    def layer_groups(self):
        """The groups of stacked layer leaves a stack holds, in the order a
        period runs them: ``[(name, kind, layers a period)]``; a stack of
        one kind has the one group ``attn``. The Mamba layers of a period
        lie in two groups, before and after its attention layer, so that
        each is scanned without a slice."""
        pre = self.attn_layer_offset
        post = self.attn_layer_period - 1 - pre
        groups = [("mamba_pre", "mamba", pre), ("attn", "attn", 1),
                  ("mamba_post", "mamba", post)]
        return [g for g in groups if g[2] > 0]

    @property
    def layer_passes(self):
        """Layer evaluations a forward makes: the work and the saved
        activations scale with this, the parameters with ``n_layers``."""
        return self.n_layers * self.loop_steps

    @property
    def expert_hidden_dim(self):
        return self.moe_ffn_hidden or self.ffn_hidden_dim

    @property
    def ffn_hidden_dim(self):
        """SwiGLU hidden size: round-up-to-multiple_of of
        ffn_dim_multiplier * (2/3 * 4 * dim) (reference model.py:258-262)."""
        hidden = int(2 * (4 * self.dim) / 3)
        hidden = int(self.ffn_dim_multiplier * hidden)
        return self.multiple_of * (
            (hidden + self.multiple_of - 1) // self.multiple_of
        )

    def tiny(self, **overrides):
        """A small test-sized variant of this config."""
        base = dict(
            dim=64, n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=256,
            multiple_of=32, max_seq_len=64,
        )
        base.update(overrides)
        return dataclasses.replace(self, **base)


def refuse_looped(config, path):
    """One sentence for the paths that run the stack once and cannot run
    it ``loop_steps`` times; called where the model or the engine is
    built, never inside a trace."""
    if config.loop_steps > 1:
        raise ValueError(
            f"{path} cannot run a looped model (loop_steps="
            f"{config.loop_steps}): it sweeps the layers once a forward "
            "(ROADMAP.md, Reach: looped stack)"
        )


def refuse_hybrid(config, path):
    """One sentence for the paths that hold a key/value cache per layer or
    split a stack of one layer kind, and cannot hold a recurrent state or
    split a period; called where the model or the engine is built."""
    if config.hybrid:
        raise ValueError(
            f"{path} cannot run a hybrid stack (attn_layer_period="
            f"{config.attn_layer_period}: {config.n_mamba_layers} Mamba "
            "layers): it has no place for a recurrent state and splits "
            "layers of one kind (ROADMAP.md M3, M7)"
        )


def _normal_init(key, shape, std, dtype):
    return (jax.random.normal(key, shape, dtype=jnp.float32) * std).astype(dtype)


def init_params(rng, config):
    """Initialize the parameter pytree.

    GPT-2-style scaled init: std 0.02 everywhere, with the residual-output
    projections (wo, w2) scaled by 1/sqrt(2*n_layers). (The reference leans
    on torch's nn.Linear defaults — init parity is not a capability, training
    stability is.)

    ``layers`` holds one group of stacked leaves per entry of
    ``cfg.layer_groups()``, a group's leading axis running over its layers
    in stack order (period after period). A stack of one kind is the period
    of one: its one group IS ``layers`` (the tree every checkpoint holds)
    and draws from the ten keys it always drew from; a hybrid stack's
    ``layers/mamba_pre``, ``layers/attn``, ``layers/mamba_post`` draw from
    ``fold_in(rng, 100 + g)`` each, so that no group's draws move when
    another's leaves change.
    """
    from pyrecover_tpu.models.mamba import init_mamba_layers

    cfg = config
    pdt = resolve_dtype(cfg.param_dtype)
    std = 0.02
    resid_std = std / (2 * cfg.n_layers) ** 0.5

    keys = jax.random.split(rng, 10)
    groups = {}
    for g, (name, kind, per_period) in enumerate(cfg.layer_groups()):
        count = per_period * cfg.n_attn_layers
        # jaxlint: disable-next=prng-key-reuse -- deliberate: fold_in
        # derives one stream a group beside the ten split above
        key = jax.random.fold_in(rng, 100 + g) if cfg.hybrid else None
        if kind == "mamba":
            groups[name] = init_mamba_layers(
                key, cfg, count, pdt, std, resid_std)
        else:
            drawn = (jax.random.split(key, 7) if cfg.hybrid
                     else (*keys[1:8], keys[9]))
            groups[name] = _init_attn_layers(
                drawn, cfg, count, pdt, std, resid_std)
    params = {
        "tok_embed": _normal_init(keys[0], (cfg.vocab_size, cfg.dim), std, pdt),
        "layers": groups if cfg.hybrid else groups["attn"],
        "final_norm": jnp.ones((cfg.dim,), dtype=pdt),
    }
    if not cfg.tie_embeddings:
        params["output"] = _normal_init(
            keys[8], (cfg.dim, cfg.vocab_size), std, pdt)
    if cfg.exit_gate:
        # the gate's key is folded in BESIDE the ten: split(rng, 11)[:10]
        # is not split(rng, 10), and every other leaf must draw as before
        # jaxlint: disable-next=prng-key-reuse -- deliberate: fold_in
        # derives an eleventh stream without moving the ten split above
        gate_key = jax.random.fold_in(rng, 10)
        params["exit_gate_w"] = _normal_init(gate_key, (cfg.dim, 1), std, pdt)
        params["exit_gate_b"] = jnp.zeros((1,), dtype=pdt)
    return params


def _init_attn_layers(keys, cfg, count, pdt, std, resid_std):
    """``count`` attention layers stacked on axis 0, one independent draw a
    layer and leaf. ``keys``: wq, wk, wv, wo, then the feed-forward's (w1,
    w3, w2; with experts router, moe_w1, moe_w3 and, eighth, moe_w2)."""
    hd, ffn = cfg.head_dim, cfg.ffn_hidden_dim

    def stacked(key, shape, s):
        ks = jax.random.split(key, count)
        return jnp.stack([_normal_init(k, shape, s, pdt) for k in ks])

    layers = {
        "attn_norm": jnp.ones((count, cfg.dim), dtype=pdt),
        "wq": stacked(keys[0], (cfg.dim, cfg.n_heads * hd), std),
        "wk": stacked(keys[1], (cfg.dim, cfg.n_kv_heads * hd), std),
        "wv": stacked(keys[2], (cfg.dim, cfg.n_kv_heads * hd), std),
        "wo": stacked(keys[3], (cfg.n_heads * hd, cfg.dim), resid_std),
        "ffn_norm": jnp.ones((count, cfg.dim), dtype=pdt),
    }
    if cfg.post_norms:
        layers["attn_post_norm"] = jnp.ones((count, cfg.dim), dtype=pdt)
        layers["ffn_post_norm"] = jnp.ones((count, cfg.dim), dtype=pdt)
    if cfg.n_experts > 0:
        E, F = cfg.n_experts, cfg.expert_hidden_dim
        layers.update({
            # router in f32 regardless of param dtype: routing decisions are
            # discrete (top-k), so router precision moves token assignment
            "router": stacked(keys[4], (cfg.dim, E), std).astype(jnp.float32),
            "moe_w1": stacked(keys[5], (E, cfg.dim, F), std),
            "moe_w3": stacked(keys[6], (E, cfg.dim, F), std),
            "moe_w2": stacked(keys[7], (E, F, cfg.dim), resid_std),
        })
    else:
        layers.update({
            "w1": stacked(keys[4], (cfg.dim, ffn), std),
            "w3": stacked(keys[5], (cfg.dim, ffn), std),
            "w2": stacked(keys[6], (ffn, cfg.dim), resid_std),
        })
    return layers


def rms_norm(x, scale, eps):
    """RMSNorm, fp32 internally then cast back (reference model.py:25-49)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * scale.astype(jnp.float32)).astype(x.dtype)


def _flash_scoped(q, k, v, **kw):
    """The flash kernels' call under its scope (the backward's two kernels
    inherit it from the forward's equation)."""
    from pyrecover_tpu.ops.flash_attention import flash_attention

    with jax.named_scope(FLASH_ATTENTION):
        return flash_attention(q, k, v, **kw)


def _attention_fn(config):
    if config.attention_impl == "flash":
        from pyrecover_tpu.ops.flash_attention import default_blocks

        bq, bk = config.flash_block_q, config.flash_block_kv
        if bq <= 0 or bk <= 0:
            # auto: the per-device-kind defaults table (measured by
            # tools/bench_flash_blocks.py); an explicit axis keeps its
            # value while the other resolves
            dq, dk = default_blocks()
            bq, bk = (bq if bq > 0 else dq), (bk if bk > 0 else dk)
        # the kernel's row statistics are kept slim (lane 0) where a remat
        # policy saves them; every other program is the one it was
        slim = config.remat and FLASH_LSE in saved_names(config)
        return partial(_flash_scoped, block_q=bq, block_kv=bk, slim_lse=slim)
    if config.attention_impl == "ring":
        from pyrecover_tpu.ops.ring_attention import ring_attention

        return ring_attention
    return sdpa_attention


def qkv_proj(h, layer, config, cos, sin):
    """Project + reshape + RoPE the q/k/v heads for one block — shared by
    the training forward and the KV-cached decoder (models/decode.py), so
    the two paths cannot drift."""
    cfg = config
    cdt = resolve_dtype(cfg.compute_dtype)
    b, s, _ = h.shape
    hd = cfg.head_dim
    q = (h @ layer["wq"].astype(cdt)).reshape(b, s, cfg.n_heads, hd)
    k = (h @ layer["wk"].astype(cdt)).reshape(b, s, cfg.n_kv_heads, hd)
    v = (h @ layer["wv"].astype(cdt)).reshape(b, s, cfg.n_kv_heads, hd)
    if not cfg.rope:
        return q, k, v
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def attn_residual(x, attn, layer, config):
    """Output projection of the attention heads (B, S, heads*hd) added to
    the residual, through the post-sublayer norm where the model has one —
    shared by the training forward and the KV-cached decoder."""
    y = attn @ layer["wo"].astype(resolve_dtype(config.compute_dtype))
    if config.post_norms:
        y = rms_norm(y, layer["attn_post_norm"], config.norm_eps)
    return x + y


def ffn_sublayer(x, layer, config):
    """Post-attention FFN sublayer (pre-norm residual): dense SwiGLU
    (reference model.py:268-269) or MoE. Returns ``(x, aux)`` — shared by
    the training forward and the KV-cached decoder."""
    cfg = config
    cdt = resolve_dtype(cfg.compute_dtype)
    with jax.named_scope(FFN):
        h = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
        if cfg.n_experts > 0:
            from pyrecover_tpu.models.moe import moe_ffn

            y, aux = moe_ffn(
                h, layer["router"], layer["moe_w1"], layer["moe_w3"],
                layer["moe_w2"], cfg,
            )
            return x + y, aux
        # the two products before the activation carry names a remat policy
        # can keep (utils/remat.py); the MoE path has none
        gate = jax.nn.silu(
            checkpoint_name(h @ layer["w1"].astype(cdt), "ffn_w1"))
        up = checkpoint_name(h @ layer["w3"].astype(cdt), "ffn_w3")
        y = (gate * up) @ layer["w2"].astype(cdt)
        if cfg.post_norms:
            y = rms_norm(y, layer["ffn_post_norm"], cfg.norm_eps)
        return x + y, jnp.zeros((x.shape[0],), dtype=jnp.float32)


def _block(x, layer, cos, sin, config, attn_fn, segment_ids=None):
    """One pre-norm transformer block (reference model.py:272-327).

    Returns ``(x, aux)`` where aux is the per-row MoE load-balance loss
    ((B,) f32; zeros for dense FFN layers). ``segment_ids`` (B, S) carries
    packed-sequence boundaries into the attention mask.
    """
    cfg = config
    b, s, d = x.shape
    hd = cfg.head_dim

    # --- attention sublayer ---
    with jax.named_scope(ATTN):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = qkv_proj(h, layer, cfg, cos, sin)
        # named AFTER rope and the constraint: the very arrays that enter
        # the attention call, so the kernel's residual tuple holds the
        # named values
        q = checkpoint_name(
            constrain(q, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ, AXIS_TENSOR, None),
            "attn_q")
        k = checkpoint_name(
            constrain(k, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ, AXIS_TENSOR, None),
            "attn_k")
        v = checkpoint_name(
            constrain(v, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ, AXIS_TENSOR, None),
            "attn_v")
        if segment_ids is None:
            attn = attn_fn(q, k, v, causal=True)
        else:
            attn = attn_fn(q, k, v, causal=True, segment_ids=segment_ids)
        attn = attn.reshape(b, s, cfg.n_heads * hd)
        x = attn_residual(x, attn, layer, cfg)
        x = checkpoint_name(
            constrain(x, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ, None),
            "attn_resid")

    # --- FFN sublayer ---
    x, aux = ffn_sublayer(x, layer, cfg)
    x = constrain(x, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ, None)
    return x, aux


def _stack(params, tokens, config, segment_ids):
    """The embedded carry and the function that runs it through the
    ``n_layers`` blocks once: ``(carry, run_stack)``."""
    cfg = config
    cdt = resolve_dtype(cfg.compute_dtype)
    seq_len = tokens.shape[1]

    cos = sin = None
    if cfg.rope:
        with jax.named_scope(ATTN):
            cos, sin = precompute_rope(cfg.head_dim, seq_len, cfg.rope_theta)
    attn_fn = _attention_fn(cfg)

    # Stage the post-gather reshard: the gather's natural output is
    # model-dim-sharded (the table is (None, tensor×fsdp)); jumping straight
    # to the batch/seq-sharded activation layout makes GSPMD emit its
    # "Involuntary full rematerialization" fallback (the tile assignments
    # are permuted incompatibly). An explicit replicated waypoint turns the
    # transition into all-gather (dim) + local slice (batch/seq) — the same
    # bytes, proper collectives, no fallback. Cost: one B·S·D all-gather at
    # the model entry only.
    with jax.named_scope(EMBED):
        x = params["tok_embed"].astype(cdt)[tokens]
        x = constrain(x, None, None, None)
        x = constrain(x, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ, None)

    block = partial(_block, cos=cos, sin=sin, config=cfg, attn_fn=attn_fn)

    # Carry = {"x": activations, "aux": per-row aux accumulator, and — when
    # packing — "seg": the per-row segment ids}. Everything per-row so
    # pipeline microbatching splits the carry along the batch like
    # everything else and the result is identical with and without PP
    # (segment ids ride the carry rather than a closure for exactly that
    # reason: a closed-over full-batch array would not be microbatched).
    def block_carry(carry, layer):
        new_x, aux = block(carry["x"], layer, segment_ids=carry.get("seg"))
        out = dict(carry, x=new_x, aux=carry["aux"] + aux)
        return out

    # one carry function a layer kind, each rematerialised alike
    kinds = {"attn": block_carry}
    if cfg.hybrid:
        from pyrecover_tpu.models.mamba import mamba_block

        if segment_ids is not None:
            raise ValueError(
                "packed sequences (--pack-sequences, segment_ids) cannot run "
                "through a hybrid stack: the selective scan carries its "
                "state across a document boundary (ROADMAP.md M3)"
            )

        def mamba_carry(carry, layer):
            new_x, aux = mamba_block(carry["x"], layer, cfg)
            return dict(carry, x=new_x, aux=carry["aux"] + aux)

        kinds["mamba"] = mamba_carry
    if cfg.remat:
        policy = checkpoint_policy(cfg)
        kinds = {
            kind: jax.checkpoint(fn, policy=policy)
            for kind, fn in kinds.items()
        }

    carry = {
        "x": x,
        "aux": jnp.zeros((x.shape[0],), dtype=jnp.float32),
    }
    if segment_ids is not None:
        carry["seg"] = segment_ids.astype(jnp.int32)

    def run_stack(carry):
        with jax.named_scope(LAYERS):
            return _run_periods(params["layers"], cfg, kinds, carry)

    return carry, run_stack


def _run_periods(layers, config, kinds, carry):
    """The stack, period by period. Inside a period each group of
    ``config.layer_groups()`` in turn: a group of several layers as one scan
    (the Mamba layers before the attention layer, those after it), a group
    of one layer applied as it is. The periods as a scan round that, none
    for one period. A stack of one kind is the period of one attention
    layer, ``layers`` its one group: the plain scan over the stacked layers
    it always was. ``kinds`` maps a layer kind to its carry function."""
    from pyrecover_tpu.parallel.pipeline import (
        pipeline_axis_size,
        pipeline_blocks,
    )

    cfg = config
    if cfg.hybrid and pipeline_axis_size() > 1:
        refuse_hybrid(cfg, "the pipeline schedule (--pp > 1)")
    tmap = jax.tree_util.tree_map
    periods = cfg.n_attn_layers
    order = cfg.layer_groups()
    groups = layers if cfg.hybrid else {"attn": layers}

    def one_period(carry, leaves):
        for name, kind, per_period in order:
            if per_period == 1:
                carry = kinds[kind](carry, leaves[name])
            else:
                carry, _ = jax.lax.scan(
                    lambda c, layer, fn=kinds[kind]: (fn(c, layer), None),
                    carry, leaves[name])
        return carry

    # (periods * n, ...) -> (periods, n, ...): a reshape, never a slice,
    # which would copy a group's weights
    leaves = {
        name: groups[name] if n == 1 else tmap(
            lambda a, n=n: a.reshape(periods, n, *a.shape[1:]), groups[name])
        for name, _, n in order
    }
    if periods == 1:
        return one_period(
            carry, tmap(lambda a: a.reshape(a.shape[1:]), leaves))
    # Under a mesh with a pipeline axis >1 this runs the microbatched
    # ppermute schedule (stages hold layer slices); otherwise it reduces to
    # a plain lax.scan over the periods.
    return pipeline_blocks(
        leaves, carry, one_period, n_microbatches=cfg.pp_microbatches)


def exit_gate_logits(params, hidden, config):
    """The exit gate's logit of every token, f32 (batch, seq): one
    ``Linear(dim -> 1)`` with a bias, read from a pass's normed state."""
    w = params["exit_gate_w"][:, 0].astype(hidden.dtype)
    logit = jnp.einsum(
        "bsd,d->bs", hidden, w, preferred_element_type=jnp.float32
    )
    return logit + params["exit_gate_b"].astype(jnp.float32)


def forward_passes_with_aux(params, tokens, config, segment_ids=None):
    """The stack run ``loop_steps`` times over the same layers, the final
    norm closing every pass: returns ``(hiddens, gate_logits, aux)`` with
    the normed state of every pass stacked (T, batch, seq, dim), the exit
    gate's logits (T, batch, seq) f32 (``None`` without a gate) and the
    aux loss of :func:`forward_hidden_with_aux`. One compiled layer body
    whatever T and L are: a scan over passes round the scan over layers,
    each layer pass rematerialised as the config says (T·L saved carries).
    """
    cfg = config
    from pyrecover_tpu.parallel.pipeline import pipeline_axis_size

    if cfg.loop_steps > 1 and pipeline_axis_size() > 1:
        refuse_looped(cfg, "the pipeline schedule (--pp > 1)")
    carry, run_stack = _stack(params, tokens, cfg, segment_ids)

    def one_pass(carry, _):
        with jax.named_scope(LOOP_PASS):
            carry = run_stack(carry)
            h = rms_norm(carry["x"], params["final_norm"], cfg.norm_eps)
            gate = exit_gate_logits(params, h, cfg) if cfg.exit_gate else None
        return dict(carry, x=h), (h, gate)

    # (the scan over passes is the stack's outer loop: what it stacks and
    # carries is the layer scan's plumbing as much as the inner scan's)
    with jax.named_scope(LAYERS):
        carry, (hiddens, gates) = jax.lax.scan(
            one_pass, carry, None, length=cfg.loop_steps
        )
    return hiddens, gates, jnp.mean(carry["aux"])


def forward_hidden_with_aux(params, tokens, config, segment_ids=None):
    """Embed → n_layers pre-norm blocks → final RMSNorm; returns
    ``(hidden, aux)``: the hidden states (batch, seq, dim) BEFORE the vocab
    projection (split out so the loss can fuse projection + cross-entropy
    per sequence chunk without ever materializing (batch, seq, vocab)
    logits — an HBM optimization the reference, which always materializes
    full logits at train.py:262-266, has no analogue of), and the scalar
    MoE load-balance aux loss summed over layers, averaged over rows
    (0 for dense models). ``segment_ids`` (batch, seq) enables packed-
    sequence attention masking (``--pack-sequences``). A looped model
    returns its LAST pass's state (:func:`forward_passes_with_aux`)."""
    cfg = config
    if cfg.loop_steps > 1:
        hiddens, _, aux = forward_passes_with_aux(
            params, tokens, cfg, segment_ids
        )
        return hiddens[-1], aux
    carry, run_stack = _stack(params, tokens, cfg, segment_ids)
    carry = run_stack(carry)
    with jax.named_scope(LOSS_HEAD):
        hidden = rms_norm(carry["x"], params["final_norm"], cfg.norm_eps)
    return hidden, jnp.mean(carry["aux"])


def forward_hidden(params, tokens, config, segment_ids=None):
    """`forward_hidden_with_aux` without the aux loss (dense callers)."""
    return forward_hidden_with_aux(params, tokens, config, segment_ids)[0]


def head_logits(params, hidden, config):
    """fp32 logits of (batch, tokens, dim) states: through the ``output``
    leaf, or with ``tie_embeddings`` through the embedding table read the
    other way. Shared by the training head and the cached decoder."""
    cdt = resolve_dtype(config.compute_dtype)
    leaf, product = (("tok_embed", "bsd,vd->bsv") if config.tie_embeddings
                     else ("output", "bsd,dv->bsv"))
    return jnp.einsum(product, hidden, params[leaf].astype(cdt),
                      preferred_element_type=jnp.float32)


def project_vocab(params, hidden, config):
    """Vocab projection, fp32 logits: untied (reference model.py:367,394)
    or, with ``tie_embeddings``, through the embedding table."""
    return constrain(
        head_logits(params, hidden, config),
        (AXIS_DATA, AXIS_FSDP), AXIS_SEQ, AXIS_TENSOR)


def forward(params, tokens, config, segment_ids=None):
    """Forward pass: tokens (batch, seq) int32 → logits (batch, seq, vocab) fp32.

    Mirrors reference `Transformer.forward` (model.py:376-395): embed →
    n_layers pre-norm blocks → final RMSNorm → untied vocab projection.
    Logits are returned in fp32 (the reference casts in its loss,
    train.py:263-266).
    """
    return project_vocab(
        params, forward_hidden(params, tokens, config, segment_ids), config
    )
