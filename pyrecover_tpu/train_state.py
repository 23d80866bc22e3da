"""The single checkpointable training-state pytree and the jitted train step.

Design stance (SURVEY §7): everything the reference scatters across mutable
objects — model weights, optimizer state, LR-schedule position, RNG, loop
counters (`train.py` + `checkpoint.py:58-73`) — lives in ONE functional
pytree. A checkpoint is exactly this pytree (plus the host-side data-order
state); bit-exact resume is therefore structural, not effortful.

The loss matches the reference's normalization exactly: sum-reduced
cross-entropy on fp32 logits divided by the number of non-masked tokens
(`train.py:263-266`) — the normalization the reference calls out as critical
for resume parity.
"""

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import optax

from pyrecover_tpu.models.llama import forward
from pyrecover_tpu.telemetry.stepscopes import (
    EMBED,
    EXIT_HEAD_LOSS,
    LOSS_HEAD,
    OPTIMIZER,
)

IGNORE_INDEX = -100  # label mask value (reference dataset.py:50-55)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array  # int32 scalar
    epoch: jax.Array  # int32 scalar (reference tracks epoch alongside step)
    rng: jax.Array  # raw uint32 key data (jax.random.key_data form)
    # per-replica error-feedback residual for the quantized gradient
    # collectives (parallel/collectives.py): f32 of shape (data_replicas,
    # padded_flat_param_count), data-sharded on dim 0. None (an EMPTY
    # pytree node — zero leaves, so checkpoints without it keep their
    # schema) whenever --grad-allreduce is not int8.
    grad_residual: Any = None

    def next_key(self):
        return jax.random.wrap_key_data(self.rng)


def create_train_state(rng, model_config, optimizer, params=None,
                       grad_residual_replicas=0,
                       grad_quant_block=None):
    from pyrecover_tpu.models.llama import init_params

    if params is None:
        params = init_params(rng, model_config)
    opt_state = optimizer.init(params)
    grad_residual = None
    if grad_residual_replicas > 0:
        from pyrecover_tpu.parallel.collectives import (
            DEFAULT_QUANT_BLOCK,
            padded_flat_len,
        )

        n_elems = sum(x.size for x in jax.tree_util.tree_leaves(params))
        grad_residual = jnp.zeros(
            (int(grad_residual_replicas),
             padded_flat_len(n_elems, grad_residual_replicas,
                             grad_quant_block or DEFAULT_QUANT_BLOCK)),
            jnp.float32,
        )
    return TrainState(
        params=params,
        opt_state=opt_state,
        step=jnp.zeros((), dtype=jnp.int32),
        epoch=jnp.zeros((), dtype=jnp.int32),
        rng=jax.random.key_data(rng),
        grad_residual=grad_residual,
    )


def _token_logprob(logprobs, safe_labels):
    """Per-token label log-probs. Inside a manual region (the 1F1B head
    runs under the pipeline shard_map) the vocab-dim gather on batch-
    sharded indices CHECK-fails XLA's partial-manual partitioner — same
    weakness models/moe.py documents — so a one-hot einsum (the form
    every partitioner handles) replaces take_along_axis there."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is not None and not mesh.empty:
        from pyrecover_tpu.parallel.mesh import nonmanual_axes

        if len(nonmanual_axes(mesh)) != len(mesh.axis_names):
            onehot = jax.nn.one_hot(
                safe_labels, logprobs.shape[-1], dtype=logprobs.dtype
            )
            return jnp.einsum("...v,...v->...", logprobs, onehot)
    return jnp.take_along_axis(logprobs, safe_labels[..., None], axis=-1)[..., 0]


def masked_ce_sum(logits, labels):
    """UN-normalized sum-reduced CE over non-masked tokens.

    Returns (loss_sum, n_valid_tokens). The per-replica explicit-sync
    objective needs the raw sum — reconstructing it from the mean
    (``ce * n``) is a lossy float roundtrip that costs the bucketed-fp32
    path its bit-exactness vs the implicit GSPMD allreduce.
    """
    valid = labels != IGNORE_INDEX
    safe_labels = jnp.where(valid, labels, 0)
    logprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    token_ll = _token_logprob(logprobs, safe_labels)
    loss_sum = -jnp.sum(jnp.where(valid, token_ll, 0.0))
    return loss_sum, jnp.sum(valid)


def masked_cross_entropy(logits, labels):
    """Sum-reduced CE over non-masked tokens / count (reference train.py:263-266).

    Returns (loss, n_valid_tokens).
    """
    loss_sum, n_valid = masked_ce_sum(logits, labels)
    return loss_sum / jnp.maximum(n_valid, 1).astype(jnp.float32), n_valid


def chunked_ce_sum(params, hidden, labels, model_config, chunk_size):
    """UN-normalized twin of :func:`chunked_ce`: ``(loss_sum, n_valid)``
    with no mean division — the exact per-replica partial the explicit
    gradient sync's objective (``Σ CE / N_total``) is built from."""
    from pyrecover_tpu.models.llama import project_vocab

    b, s, d = hidden.shape
    with jax.named_scope(LOSS_HEAD):
        if chunk_size <= 0 or s % chunk_size or s == chunk_size:
            logits = project_vocab(params, hidden, model_config)
            return masked_ce_sum(logits, labels)

        n = s // chunk_size
        h_chunks = jnp.moveaxis(hidden.reshape(b, n, chunk_size, d), 1, 0)
        l_chunks = jnp.moveaxis(labels.reshape(b, n, chunk_size), 1, 0)

        # remat per chunk: without it the scanned backward SAVES each
        # chunk's f32 logits/logprobs — i.e. the full (b, s, vocab) cost
        # the chunking exists to avoid (observed: +8G HBM at the 1B bench
        # point). Recompute is one extra (chunk, d)x(d, vocab) matmul per
        # chunk.
        @jax.checkpoint
        def per_chunk(args):
            h, lab = args
            logits = project_vocab(params, h, model_config)
            valid = lab != IGNORE_INDEX
            safe = jnp.where(valid, lab, 0)
            logprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            ll = _token_logprob(logprobs, safe)
            return -jnp.sum(jnp.where(valid, ll, 0.0)), jnp.sum(valid)

        sums, counts = jax.lax.map(per_chunk, (h_chunks, l_chunks))
        return jnp.sum(sums), jnp.sum(counts)


def chunked_ce(params, hidden, labels, model_config, chunk_size):
    """Fused projection + CE over sequence chunks: never materializes the
    full (batch, seq, vocab) logits — the dominant HBM cost of the naive
    loss at LLM vocab sizes. ``lax.map`` over chunks keeps one chunk of
    logits live at a time (in fwd AND in the scanned backward)."""
    loss_sum, n_valid = chunked_ce_sum(
        params, hidden, labels, model_config, chunk_size
    )
    return loss_sum / jnp.maximum(n_valid, 1).astype(jnp.float32), n_valid


def chunked_loss(params, tokens, labels, model_config, chunk_size):
    """Forward + `chunked_ce` (kept as the standalone fused-loss entry)."""
    from pyrecover_tpu.models.llama import forward_hidden

    hidden = forward_hidden(params, tokens, model_config)
    return chunked_ce(params, hidden, labels, model_config, chunk_size)


def exit_distribution(gate_logits):
    """Exit probabilities of a looped model from its gate's logits
    (T, ...): ``p_1 = l_1``, ``p_t = l_t * prod_{j<t}(1 - l_j)``, the last
    pass taking the remainder ``prod_{j<T}(1 - l_j)`` so that the T sum to
    one (``l = sigmoid(logit)``). Returns ``(p, log p)``, both f32, taken
    through log-sigmoids so no product underflows."""
    g = gate_logits.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)  # log prod_{j<=t}(1-l_j)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], axis=0)
    log_p = jnp.concatenate(
        [before[:-1] + jax.nn.log_sigmoid(g[:-1]), before[-1:]], axis=0
    )
    return jnp.exp(log_p), log_p


def chunked_exit_loss(params, hiddens, gate_logits, labels, model_config,
                      chunk_size):
    """Expected loss over the exits of a looped model, per valid token
    ``sum_t p_t CE(logits_t, y) - beta H(p)``, the head's product and its
    cross-entropy taken T times a sequence chunk with the per-token
    weights ``p_t`` — no (batch, seq, vocab) logits ever exist, and the
    gate gets its gradient through ``p_t`` and ``H``.

    ``hiddens`` (T, B, S, D), ``gate_logits`` (T, B, S). Returns
    ``(objective, n_valid, stats)``, means over the valid tokens; ``stats``
    is ONE f32 vector ``[expected_ce, loop_ce (T), exit_mass (T),
    exit_entropy]`` (:func:`exit_stats_fields` names its parts): the expected
    cross-entropy, the plain cross-entropy of every pass, the mean of
    ``p_t`` and the mean entropy. One vector because the trainer fetches it
    in the loss's place, in one transfer.
    """
    from pyrecover_tpu.models.llama import project_vocab

    T, b, s, d = hiddens.shape
    with jax.named_scope(EXIT_HEAD_LOSS):
        valid = labels != IGNORE_INDEX
        n_valid = jnp.sum(valid)
        p, log_p = exit_distribution(gate_logits)
        p = jnp.where(valid[None], p, 0.0)
        entropy = -jnp.sum(p * log_p)

        chunk = chunk_size if 0 < chunk_size < s and s % chunk_size == 0 else s
        n = s // chunk
        # (T, B, S, ...) -> (T * n, B, chunk, ...): one compiled head body
        # mapped over every (pass, chunk) pair
        split = lambda a: jnp.moveaxis(
            a.reshape(T, b, n, chunk, *a.shape[3:]), 2, 1
        ).reshape(T * n, b, chunk, *a.shape[3:])
        l_chunks = jnp.tile(
            jnp.moveaxis(labels.reshape(b, n, chunk), 1, 0), (T, 1, 1)
        )

        # remat per chunk, as chunked_ce_sum: the backward recomputes one
        # chunk's logits instead of saving T x (b, s, vocab) of them
        @jax.checkpoint
        def per_chunk(args):
            h, lab, w = args
            logits = project_vocab(params, h, model_config)
            ok = lab != IGNORE_INDEX
            logprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            ce = jnp.where(
                ok, -_token_logprob(logprobs, jnp.where(ok, lab, 0)), 0.0
            )
            return jnp.sum(w * ce), jnp.sum(ce)

        weighted, plain = jax.lax.map(
            per_chunk, (split(hiddens), l_chunks, split(p))
        )
        stats = jnp.concatenate([
            jnp.sum(weighted)[None],
            jnp.sum(plain.reshape(T, n), axis=1),
            jnp.sum(p, axis=(1, 2)),
            entropy[None],
        ]) / jnp.maximum(n_valid, 1).astype(jnp.float32)
        objective = stats[0] - model_config.exit_beta * stats[-1]
    return objective, n_valid, stats


def exit_stats_fields(stats):
    """The parts of :func:`chunked_exit_loss`'s vector by name, as the
    ``train_sync`` event carries them (the expected cross-entropy, its first
    entry, is the event's ``loss``). ``stats``: a host list, or None for a
    model without an exit gate, which gives no fields."""
    if stats is None:
        return {}
    T = (len(stats) - 2) // 2
    return {
        "loop_ce": [round(x, 6) for x in stats[1:1 + T]],
        "exit_mass": [round(x, 6) for x in stats[1 + T:1 + 2 * T]],
        "exit_entropy": round(stats[-1], 6),
    }


def model_loss(params, inputs, labels, segments, model_config, chunk_size):
    """Forward + the model's own token loss: ``(objective, ce, n_valid,
    moe_aux, stats)``, ``objective`` and ``ce`` means over the valid
    tokens. A plain model's objective IS its cross-entropy and ``stats``
    is None; an exit-gated looped model's is :func:`chunked_exit_loss`,
    ``ce`` the expected cross-entropy (what the step reports as ``loss``,
    and ``stats[0]``). The MoE aux term is the caller's to add."""
    from pyrecover_tpu.models.llama import (
        forward_hidden_with_aux,
        forward_passes_with_aux,
    )

    if not model_config.exit_gate:
        hidden, moe_aux = forward_hidden_with_aux(
            params, inputs, model_config, segment_ids=segments
        )
        ce, n = chunked_ce(params, hidden, labels, model_config, chunk_size)
        return ce, ce, n, moe_aux, None
    hiddens, gates, moe_aux = forward_passes_with_aux(
        params, inputs, model_config, segment_ids=segments
    )
    objective, n, stats = chunked_exit_loss(
        params, hiddens, gates, labels, model_config, chunk_size
    )
    return objective, stats[0], n, moe_aux, stats


def _pipelined_1f1b_value_and_grad(params, batch, model_config,
                                   loss_chunk_size):
    """Manual value-and-grad through the explicit 1F1B pipeline schedule
    (parallel/pipeline.py::pipeline_1f1b_grads): the embed/block/head
    pieces of the model are handed to the schedule, which interleaves each
    microbatch's backward as soon as its forward drains — in-flight
    activations per stage bounded to the stage count instead of the
    microbatch count. Numerically equivalent to differentiating the GPipe
    schedule (equality-tested); returns ``(ce_loss, n_valid, moe_aux,
    grads)`` with the same semantics as the AD path."""
    from pyrecover_tpu.models.llama import (
        _attention_fn,
        _block,
        rms_norm,
    )
    from pyrecover_tpu.ops.rope import precompute_rope
    from pyrecover_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP, AXIS_SEQ, constrain
    from pyrecover_tpu.parallel.pipeline import (
        pipeline_1f1b_grads,
        pipeline_axis_size,
    )
    from pyrecover_tpu.utils.dtypes import resolve_dtype
    from pyrecover_tpu.utils.remat import checkpoint_policy

    cfg = model_config
    cdt = resolve_dtype(cfg.compute_dtype)
    B, seq_len = batch["inputs"].shape
    S = pipeline_axis_size()
    M = cfg.pp_microbatches or S
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    n_total = jnp.maximum(
        jnp.sum(batch["labels"] != IGNORE_INDEX), 1
    ).astype(jnp.float32)

    cos, sin = precompute_rope(cfg.head_dim, seq_len, cfg.rope_theta)
    attn_fn = _attention_fn(cfg)

    data_mbs = {
        "labels": batch["labels"].reshape(M, B // M, seq_len),
        # scalar companions ride the (replicated, non-diff) data pytree so
        # the head never closes over values from outside the shard_map
        "n_total": jnp.broadcast_to(n_total, (M,)),
    }
    if batch.get("segments") is not None:
        data_mbs["segments"] = batch["segments"].reshape(M, B // M, seq_len)

    # Embedding runs OUTSIDE the pipeline's manual region (the gather on
    # batch-sharded token indices CHECK-fails XLA's partial-manual
    # partitioner); the schedule hands the input-carry cotangents back and
    # the embedding vjp closes the chain here, under full-auto GSPMD.
    def embed_all(ep):
        with jax.named_scope(EMBED):
            x = ep["tok_embed"].astype(cdt)[batch["inputs"]]
            # same staged reshard waypoints as forward_hidden_with_aux
            x = constrain(x, None, None, None)
            x = constrain(x, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ, None)
        return {
            "x": x.reshape(M, B // M, seq_len, -1),
            "aux": jnp.zeros((M, B // M), jnp.float32),
        }

    def block_fn(carry, layer, d):
        new_x, aux = _block(
            carry["x"], layer, cos=cos, sin=sin, config=cfg, attn_fn=attn_fn,
            segment_ids=d.get("segments"),
        )
        return {"x": new_x, "aux": carry["aux"] + aux}

    if cfg.remat:
        block_fn = jax.checkpoint(block_fn, policy=checkpoint_policy(cfg))

    def head_fn(hp, carry, d):
        with jax.named_scope(LOSS_HEAD):
            hidden = rms_norm(carry["x"], hp["final_norm"], cfg.norm_eps)
        ce, n = chunked_ce(
            {"output": hp["output"]}, hidden, d["labels"], cfg,
            loss_chunk_size,
        )
        ce_sum = ce * jnp.maximum(n, 1).astype(jnp.float32)
        aux_sum = jnp.sum(carry["aux"])
        total = ce_sum / d["n_total"]
        if cfg.n_experts > 0:
            total = total + cfg.moe_aux_weight * aux_sum / B
        # extras carry metric values out (no gradient flows through them)
        return total, (jax.lax.stop_gradient(ce_sum),
                       jax.lax.stop_gradient(aux_sum))

    head_params = {
        "final_norm": params["final_norm"],
        "output": params["output"],
    }
    x0_mbs, embed_vjp = jax.vjp(embed_all, {"tok_embed": params["tok_embed"]})
    _, (ce_total, aux_total), dx0_mbs, dlayers, dhead = pipeline_1f1b_grads(
        params["layers"], x0_mbs, data_mbs, head_params,
        block_fn, head_fn, n_microbatches=M,
        n_virtual=cfg.pp_virtual_stages,
    )
    (dembed,) = embed_vjp(
        jax.tree_util.tree_map(
            lambda d, x: d.astype(x.dtype), dx0_mbs, x0_mbs
        )
    )
    grads = {
        "tok_embed": dembed["tok_embed"],
        "layers": dlayers,
        "final_norm": dhead["final_norm"],
        "output": dhead["output"],
    }
    grads = jax.tree_util.tree_map(
        lambda g, p: g.astype(p.dtype), grads, params
    )
    return ce_total / n_total, n_total.astype(jnp.int32), aux_total / B, grads


def make_train_step(model_config, optimizer, donate=True, loss_chunk_size=0,
                    grad_accumulation_steps=1, optimizer_sharding="none",
                    grad_allreduce="fp32", grad_quant_block=None,
                    grad_error_feedback=True, grad_bucket_mb=0):
    """Build the jitted functional train step.

    state, batch → new_state, metrics. Under a mesh, batch/params shardings
    propagate through (GSPMD); the DP gradient AllReduce the reference gets
    from DDP (`train.py:268-269`) is inserted by XLA automatically.
    ``loss_chunk_size`` > 0 enables the chunked fused loss (see
    ``chunked_loss``). ``grad_accumulation_steps`` > 1 splits the global
    batch into that many micro-batches scanned inside the SAME jitted step
    — one live micro-batch of activations at a time, one optimizer update —
    with EXACT full-batch normalization: the valid-token total is counted
    from the labels up front (data-only, no model), so each micro-step's
    objective is ``Σ_chunk CE / N_total`` and the accumulated f32 gradient
    equals the unaccumulated one.

    Bandwidth-lean update path (both opt-in, composable, still ONE jitted
    program):

    * ``optimizer_sharding="zero1"`` — the decomposed cross-replica
      weight update (arxiv 2004.13336): gradients are constrained to the
      zero1 specs before the optax update (XLA lowers the DP allreduce
      to a reduce-scatter), the AdamW update runs shard-local against
      data-sharded moments, and the updates are constrained back to the
      param rules (the allgather). Same semantics as the replicated
      update — the zero1-fp32 parity gate is bit-exact — with optimizer
      HBM divided by the data-axis size.
    * ``grad_allreduce="int8"|"bf16"`` — the gradient sync over the data
      axis runs as an EXPLICIT block-scaled quantized allreduce
      (parallel/collectives.py) inside a ``shard_map`` manual over
      ``data``: per-replica partial gradients are computed on the local
      batch shard (every other mesh axis stays under GSPMD), compensated
      with the error-feedback residual carried in
      ``state.grad_residual`` (int8 only), and reduced with quantized
      bytes on both wire legs. Composes with pure DP, fsdp and tensor;
      the 1f1b pipeline schedule and sequence parallelism are rejected
      at config time (their own manual regions would nest).
    * ``grad_bucket_mb > 0`` — latency-hidden gradients: the flattened
      gradient pytree is partitioned into fixed-byte buckets in
      reverse-autodiff order (parallel/collectives.py:
      ``compute_bucket_layout``) and each bucket's data-axis reduction
      is issued as its OWN collective, depending only on that bucket's
      leaves — XLA's latency-hiding scheduler can start each reduction
      as soon as its gradients are final and overlap the wire time with
      the remaining backward compute. Composes with every wire mode
      (fp32 buckets are explicit per-bucket ``psum``s; int8 re-blocks
      the error-feedback residual per bucket with the residual SHAPE
      unchanged, so flipping the flag across a resume is spec-only
      drift), with zero1 (the update decomposition runs after the
      sync), and with grad accumulation (buckets sync the accumulated
      gradient once). A cap that admits everything into one bucket
      resolves to the unbucketed path unchanged.

      Numerics contract (test- and chaos-gated): a per-bucket fp32
      ``psum`` is an exact elementwise sum, so bucketed fp32 is
      BIT-EXACT across every bucket layout — resuming with a different
      ``--grad-bucket-mb`` continues the identical trajectory. Against
      the implicit-GSPMD fp32/no-bucket path (the untouched default)
      the explicit sync is the same math but a different program form,
      and XLA's per-op partitioning choices (contract-then-reduce vs
      gather-then-contract) reassociate float sums — measured ~5e-4
      relative loss drift over 20 tiny-model steps, the same noise
      class as the elastic drill's topology change, tolerance-gated.
    """
    A = int(grad_accumulation_steps)
    if A < 1:
        raise ValueError(
            f"grad_accumulation_steps must be >= 1, got {grad_accumulation_steps}"
        )
    if optimizer_sharding not in ("none", "zero1"):
        raise ValueError(
            f"optimizer_sharding must be 'none' or 'zero1', "
            f"got {optimizer_sharding!r}"
        )
    if optimizer_sharding == "zero1" and not getattr(
        optimizer.update, "_pyrecover_zero1", False
    ):
        raise ValueError(
            "optimizer_sharding='zero1' requires the optimizer built by "
            "build_optimizer with config.optimizer_sharding='zero1' (the "
            "zero1_wrap carries the sharded update; a plain optimizer "
            "would silently train unsharded)"
        )
    from pyrecover_tpu.parallel.collectives import (
        DEFAULT_QUANT_BLOCK,
        GRAD_ALLREDUCE_MODES,
    )

    if grad_allreduce not in GRAD_ALLREDUCE_MODES:
        raise ValueError(
            f"grad_allreduce must be one of {GRAD_ALLREDUCE_MODES}, "
            f"got {grad_allreduce!r}"
        )
    use_quant = grad_allreduce != "fp32"
    quant_block = int(grad_quant_block or DEFAULT_QUANT_BLOCK)
    bucket_mb = float(grad_bucket_mb or 0)
    if bucket_mb < 0:
        raise ValueError(
            f"grad_bucket_mb must be >= 0, got {grad_bucket_mb}"
        )
    if (use_quant or bucket_mb > 0) and model_config.exit_gate:
        raise ValueError(
            "--grad-allreduce bf16/int8 and --grad-bucket-mb cannot train "
            "an exit-gated model: the explicit gradient sync sums one "
            "cross-entropy per replica, not the expected loss over the exits"
        )
    if (use_quant or bucket_mb > 0) and model_config.pp_schedule == "1f1b":
        raise ValueError(
            "--grad-allreduce bf16/int8 and --grad-bucket-mb compose with "
            "the gpipe schedule only; the 1f1b pipeline runs its own "
            "manual region"
        )
    if model_config.pp_schedule == "1f1b" and (
        model_config.hybrid or model_config.tie_embeddings
        or not model_config.rope
    ):
        raise ValueError(
            "--pp-schedule 1f1b hands the schedule an embedding, blocks of "
            "one kind with rotary positions and an untied head as separate "
            "pieces: a hybrid stack (--model-attn-period > 1), "
            "--model-tie-embeddings and --model-no-rope train under the "
            "gpipe schedule or without --pp"
        )
    if model_config.pp_schedule == "1f1b" and A > 1:
        raise ValueError(
            "--grad-accumulation-steps composes with the gpipe pipeline "
            "schedule only; under --pp-schedule 1f1b raise "
            "--pp-microbatches instead — 1F1B's microbatches ARE the "
            "accumulation, with bounded in-flight activations. Measured "
            "(tools/pp_memory_sweep.py, table in PARITY.md): at fixed "
            "global batch, raising M costs NO memory (boundary bytes are "
            "M-independent) and compiles ~5x smaller than GPipe+accum; "
            "only batch-scaling far past M ~ 64*S approaches the "
            "GPipe+accum crossover."
        )

    def micro_loss(params, inputs, labels, segments, n_total, rows_total):
        """Micro-batch objective: ``Σ_chunk CE / N_total`` (+ row-weighted
        aux). Its grads SUM over micro-steps to the full-batch grads, as
        do the exit statistics it returns beside the aux loss."""
        obj, ce, n, moe_aux, stats = model_loss(
            params, inputs, labels, segments, model_config, loss_chunk_size
        )
        n_here = jnp.maximum(n, 1).astype(jnp.float32)
        total = obj * n_here / n_total
        if model_config.n_experts > 0:
            # moe_aux is this micro-batch's per-row mean; reweight so the
            # sum over micro-steps is the full-batch row mean
            total = total + model_config.moe_aux_weight * moe_aux * (
                inputs.shape[0] / rows_total
            )
        if stats is not None:
            stats = stats * n_here / n_total
        return total, (moe_aux, stats)

    def _local_value_and_grad(params, inputs, labels, segs, n_total, B):
        """Per-replica value-and-grad of the LOCAL batch shard, objective
        ``Σ_chunk CE / N_total`` so partial grads SUM over replicas to the
        full-batch grads (micro_loss's invariant, reused shard-side).
        Handles grad accumulation by scanning local micro-batches.
        Returns ``(grads, ce_sum, n_valid, aux_rowsum)`` — all LOCAL."""
        from pyrecover_tpu.models.llama import forward_hidden_with_aux

        def loss_local(p, inp, lab, sg):
            hidden, moe_aux = forward_hidden_with_aux(
                p, inp, model_config, segment_ids=sg
            )
            # the RAW local CE sum (chunked_ce_sum): dividing by the local
            # count and multiplying it back would be a lossy roundtrip —
            # the objective Σ CE / N_total must see the exact partial for
            # the explicit sync to match the GSPMD allreduce bit-for-bit
            ce_sum, n = chunked_ce_sum(
                p, hidden, lab, model_config, loss_chunk_size
            )
            obj = ce_sum / n_total
            aux_rows = moe_aux * (inp.shape[0] / B)
            if model_config.n_experts > 0:
                obj = obj + model_config.moe_aux_weight * aux_rows
            return obj, (ce_sum, n, aux_rows)

        rows = inputs.shape[0]
        if A == 1:
            (_, (ce_sum, n_valid, aux)), g = jax.value_and_grad(
                loss_local, has_aux=True
            )(params, inputs, labels, segs)
            return g, ce_sum, n_valid, aux
        if rows % A:
            raise ValueError(
                f"local batch {rows} not divisible by "
                f"grad_accumulation_steps {A}"
            )
        inp = inputs.reshape(A, rows // A, -1)
        lab = labels.reshape(A, rows // A, -1)
        sgs = None if segs is None else segs.reshape(A, rows // A, -1)

        def micro(acc, xs):
            i_, l_, s_ = xs if sgs is not None else (*xs, None)
            (_, (cs, nv, aw)), g_ = jax.value_and_grad(
                loss_local, has_aux=True
            )(params, i_, l_, s_)
            acc_g, acs, anv, aaw = acc
            acc_g = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), acc_g, g_
            )
            return (acc_g, acs + cs, anv + nv, aaw + aw), None

        zero_g = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        xs = (inp, lab) if sgs is None else (inp, lab, sgs)
        (g, ce_sum, n_valid, aux), _ = jax.lax.scan(
            micro, (zero_g, jnp.float32(0), jnp.int32(0), jnp.float32(0)), xs
        )
        g = jax.tree_util.tree_map(
            lambda x, p: x.astype(p.dtype), g, params
        )
        return g, ce_sum, n_valid, aux

    def _quantized_grads(state, batch, segments, layout=None, order=None):
        """Gradients with the explicit cross-replica sync: per-replica
        partials inside a data-manual shard_map, error-feedback
        compensation (int8), quantized reduce-scatter + allgather legs
        (or a plain per-bucket ``psum`` in fp32 mode). ``layout`` (a
        ``compute_bucket_layout`` result) splits the sync into one
        collective per bucket in reverse-autodiff order — the overlap
        path; None keeps the single-collective PR 10 form bit-for-bit.
        Returns ``(grads, loss, n_valid, moe_aux, new_residual)``."""
        from pyrecover_tpu.parallel.collectives import (
            flatten_grads,
            padded_flat_len,
            quantized_psum_flat,
            quantized_roundtrip_local,
        )
        from pyrecover_tpu.parallel.mesh import AXIS_DATA

        mesh = jax.sharding.get_abstract_mesh()
        data_n = (
            int(dict(mesh.shape).get(AXIS_DATA, 1))
            if mesh is not None and not mesh.empty else 1
        )
        B = batch["inputs"].shape[0]
        n_elems = sum(
            x.size for x in jax.tree_util.tree_leaves(state.params)
        )
        pad_len = padded_flat_len(n_elems, data_n, quant_block)
        residual = state.grad_residual

        def reduce_one(flat, manual):
            if manual:
                return quantized_psum_flat(
                    flat, mode=grad_allreduce, block=quant_block,
                    axis_name=AXIS_DATA,
                )
            return quantized_roundtrip_local(
                flat, mode=grad_allreduce, block=quant_block
            )

        def sync_whole(g, res, manual, use_feedback):
            """The PR 10 single-collective sync (layout is None)."""
            flat, unflatten = flatten_grads(g, pad_len)
            if use_feedback:
                flat = flat + res[0]
            reduced, deficit = reduce_one(flat, manual)
            return unflatten(reduced), deficit

        def sync_bucketed(g, res, manual, use_feedback):
            """One collective per bucket, issued in reverse-autodiff
            order (``order``, a grad_leaf_order permutation): bucket 0
            — the loss head, final while most of the backward still
            runs — goes out first, depending only on its own leaves;
            the remaining backward compute is what hides its wire time.
            Deficits are re-blocked per bucket but stored at each
            bucket's element offset in one flat residual row, and the
            issue order depends only on the parameter structure, so the
            residual SHAPE and index space are layout-independent
            (bucket flips across resumes are spec-only drift)."""
            leaves, treedef = jax.tree_util.tree_flatten(g)
            ordered = [leaves[j] for j in order]
            out = [None] * len(leaves)
            deficit_parts = []
            for b in layout:
                flat, unflatten = flatten_grads(
                    ordered[b.leaf_lo:b.leaf_hi], b.padded_len
                )
                if use_feedback:
                    part = res[0, b.offset:b.offset + b.n_elems]
                    flat = flat.at[:b.n_elems].add(part)
                reduced, deficit = reduce_one(flat, manual)
                for j, leaf in enumerate(unflatten(reduced)):
                    out[order[b.leaf_lo + j]] = leaf
                if deficit is not None:
                    # per-bucket padding coords quantize exactly (zero
                    # blocks), so dropping their always-zero deficit
                    # loses nothing
                    deficit_parts.append(deficit[:b.n_elems])
            g_red = jax.tree_util.tree_unflatten(treedef, out)
            if not deficit_parts:
                return g_red, None
            row = jnp.concatenate(deficit_parts)
            if row.shape[0] < pad_len:
                row = jnp.concatenate(
                    [row, jnp.zeros((pad_len - row.shape[0],), jnp.float32)]
                )
            return g_red, row

        def sync_region(params, inputs, labels, segs, res):
            from pyrecover_tpu.parallel.mesh import constraints_disabled

            manual = data_n > 1
            n_local = jnp.sum(labels != IGNORE_INDEX)
            n_total = (
                jax.lax.psum(n_local, AXIS_DATA) if manual else n_local
            )
            n_total = jnp.maximum(n_total, 1).astype(jnp.float32)
            # constraints off inside the manual region (the 1f1b
            # precedent): the model's reshard waypoints name the data
            # axis, which is manually bound here; propagation from the
            # already-sharded inputs carries the fsdp/tensor layouts
            with constraints_disabled():
                g, ce_sum, n_valid, aux = _local_value_and_grad(
                    params, inputs, labels, segs, n_total, B
                )
            # error feedback: re-inject last step's deficit before
            # quantizing (grad_error_feedback=False is the test-only
            # ablation knob proving the mechanism matters)
            use_feedback = res is not None and grad_error_feedback
            with jax.named_scope(OPTIMIZER):
                if layout is None:
                    g_red, deficit = sync_whole(g, res, manual, use_feedback)
                else:
                    g_red, deficit = sync_bucketed(
                        g, res, manual, use_feedback)
            if manual:
                ce_sum = jax.lax.psum(ce_sum, AXIS_DATA)
                n_valid = jax.lax.psum(n_valid, AXIS_DATA)
                aux = jax.lax.psum(aux, AXIS_DATA)
            if deficit is None or res is None:
                new_res = res  # fp32/bf16 / no residual: nothing carried
            elif grad_error_feedback:
                new_res = deficit[None, :]
            else:
                new_res = res  # ablation: deficit computed, never fed back
            return g_red, ce_sum / n_total, n_valid, aux, new_res

        if data_n > 1:
            from jax.sharding import PartitionSpec as P

            shard = P(AXIS_DATA)
            outs = jax.shard_map(
                sync_region,
                mesh=mesh,
                in_specs=(P(), shard, shard, shard, shard),
                out_specs=(P(), P(), P(), P(), shard),
                axis_names={AXIS_DATA},
                check_vma=False,
            )(state.params, batch["inputs"], batch["labels"], segments,
              residual)
        else:
            outs = sync_region(
                state.params, batch["inputs"], batch["labels"], segments,
                residual,
            )
        return outs

    def train_step(state, batch):
        from pyrecover_tpu.parallel.collectives import (
            param_leaf_order,
            resolve_bucket_layout,
        )
        from pyrecover_tpu.parallel.mesh import AXIS_DATA
        from pyrecover_tpu.parallel.pipeline import pipeline_axis_size

        segments = batch.get("segments")  # packed-sequence ids or None
        use_1f1b = (
            model_config.pp_schedule == "1f1b" and pipeline_axis_size() > 1
        )
        mesh = jax.sharding.get_abstract_mesh()
        data_n = (
            int(dict(mesh.shape).get(AXIS_DATA, 1))
            if mesh is not None and not mesh.empty else 1
        )
        layout = order = None
        if bucket_mb > 0:
            order = param_leaf_order(state.params)
            layout = resolve_bucket_layout(
                [x.size for x in jax.tree_util.tree_leaves(state.params)],
                bucket_mb, data_n, quant_block, order=order,
            )
        # fp32 without a real data axis has no wire to bucket — the
        # implicit-GSPMD path stays the parity anchor there; quantized
        # modes always take the explicit sync (their numerics ARE the
        # explicit collective, mesh or not)
        use_explicit = use_quant or (layout is not None and data_n > 1)
        new_residual = state.grad_residual
        exit_stats = None  # the explicit-sync and 1f1b paths refuse a gate
        if use_explicit:
            grads, loss, n_valid, moe_aux, new_residual = _quantized_grads(
                state, batch, segments, layout, order
            )
        elif use_1f1b:
            loss, n_valid, moe_aux, grads = _pipelined_1f1b_value_and_grad(
                state.params, batch, model_config, loss_chunk_size
            )
        elif A == 1:
            def loss_fn(params):
                obj, ce, n_valid, moe_aux, stats = model_loss(
                    params, batch["inputs"], batch["labels"], segments,
                    model_config, loss_chunk_size,
                )
                total = obj
                if model_config.n_experts > 0:
                    total = obj + model_config.moe_aux_weight * moe_aux
                return total, (ce, n_valid, moe_aux, stats)

            (_, (loss, n_valid, moe_aux, exit_stats)), grads = (
                jax.value_and_grad(loss_fn, has_aux=True)(state.params)
            )
        else:
            B = batch["inputs"].shape[0]
            if B % A:
                raise ValueError(
                    f"batch {B} not divisible by grad_accumulation_steps {A}"
                )
            inputs = batch["inputs"].reshape(A, B // A, -1)
            labels = batch["labels"].reshape(A, B // A, -1)
            segs = (
                None if segments is None
                else segments.reshape(A, B // A, -1)
            )
            n_total = jnp.maximum(
                jnp.sum(labels != IGNORE_INDEX), 1
            ).astype(jnp.float32)

            def micro(acc, xs):
                inp, lab, sg = xs if segs is not None else (*xs, None)
                (obj, (moe_aux, stats)), g = jax.value_and_grad(
                    micro_loss, has_aux=True
                )(state.params, inp, lab, sg, n_total, float(B))
                acc_g, acc_obj, acc_aux, acc_stats = acc
                acc_g = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), acc_g, g
                )
                if stats is not None:
                    acc_stats = acc_stats + stats
                return (acc_g, acc_obj + obj,
                        acc_aux + moe_aux * (inp.shape[0] / B),
                        acc_stats), None

            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            xs = (
                (inputs, labels) if segs is None else (inputs, labels, segs)
            )
            zero_stats = None if not model_config.exit_gate else jnp.zeros(
                (2 * model_config.loop_steps + 2,), jnp.float32
            )
            (grads, obj, moe_aux, exit_stats), _ = jax.lax.scan(
                micro,
                (zero_g, jnp.float32(0), jnp.float32(0), zero_stats), xs,
            )
            grads = jax.tree_util.tree_map(
                lambda g, p: g.astype(p.dtype), grads, state.params
            )
            n_valid = n_total.astype(jnp.int32)
            loss = obj
            if model_config.n_experts > 0:
                loss = obj - model_config.moe_aux_weight * moe_aux
            if model_config.exit_gate:
                # the objective holds the entropy bonus; `loss` stays a
                # cross-entropy (the expected one), as without accumulation
                loss = exit_stats[0]

        # zero1's decomposed update lives INSIDE the optimizer chain
        # (optim.zero1_wrap, placed after global-norm clipping so the norm
        # reduction keeps the unsharded shape — the bit-exactness anchor);
        # nothing to do here beyond the wiring check in make_train_step
        # (the scope holds the whole of the update: the gradient's norm
        # and clip, the moments, the new weights, the rng's fold)
        with jax.named_scope(OPTIMIZER):
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
            grad_norm = optax.global_norm(grads)
            new_rng = jax.random.key_data(
                jax.random.fold_in(jax.random.wrap_key_data(state.rng), 1)
            )
        new_state = TrainState(
            params=new_params,
            opt_state=new_opt_state,
            step=state.step + 1,
            epoch=state.epoch,
            rng=new_rng,
            grad_residual=new_residual,
        )
        metrics = {
            "loss": loss,  # CE only — comparable to the reference's loss CSV
            "n_tokens": n_valid,
            "grad_norm": grad_norm,
            "moe_aux": moe_aux,
        }
        if model_config.exit_gate:
            # [loss, loop_ce (T), exit_mass (T), entropy]: the trainer's
            # loss sync fetches THIS in the loss's place, so the exit
            # statistics cost no transfer of their own
            metrics["exit_stats"] = exit_stats
        return new_state, metrics

    donate_argnums = (0,) if donate else ()
    return jax.jit(train_step, donate_argnums=donate_argnums)


def eval_loss_fn(model_config):
    """Jitted forward+loss only (no update) — used by tests and verification."""

    @partial(jax.jit)
    def fn(params, batch):
        logits = forward(params, batch["inputs"], model_config)
        return masked_cross_entropy(logits, batch["labels"])[0]

    return fn


def make_eval_step(model_config, loss_chunk_size=0):
    """Jitted evaluation step: (params, batch) → (ce_sum, n_valid).

    Returns the UN-normalized CE sum plus the valid-token count so the
    caller can average exactly over many eval batches. Uses the chunked
    fused loss (never materializes full logits) like the train step.
    """
    from pyrecover_tpu.models.llama import forward_hidden

    @partial(jax.jit)
    def fn(params, batch):
        hidden = forward_hidden(
            params, batch["inputs"], model_config,
            segment_ids=batch.get("segments"),
        )
        ce, n_valid = chunked_ce(
            params, hidden, batch["labels"], model_config, loss_chunk_size
        )
        return ce * jnp.maximum(n_valid, 1).astype(jnp.float32), n_valid

    return fn
