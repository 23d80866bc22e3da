"""Blockwise cached attention through a block table (the paged forward).

Same math as ``models/decode.py`` — shared ``qkv_proj`` / ``rms_norm`` /
``ffn_sublayer`` building blocks, fp32 online softmax over KV blocks,
RoPE at absolute positions — with two serving-specific generalizations:

  * **Ragged positions.** Every sequence in the batch sits at its own
    absolute position (``pos`` is a vector, not a scalar): the RoPE
    tables are gathered per ``(sequence, chunk)`` cell and the causal
    mask compares per-sequence position columns, so a freshly admitted
    request decodes in the same jitted call as one that is 900 tokens
    deep. Chunk width ``C`` is static (two compiles serve everything:
    the prefill chunk and the ``C=1`` decode step); batch width is the
    engine's fixed slot count, so admissions never retrace.
  * **Block-table indirection.** KV blocks are gathered from the shared
    pool by physical id (``pool[table[seq, i]]``) inside the same
    fill-bounded ``fori_loop`` the lockstep decoder uses — per-step cost
    scales with the deepest LIVE sequence, not the pool size. Writes
    scatter each new position into ``(table[p // bs], p % bs)``; writes
    that fall outside a sequence's table (prefill padding, inactive
    slots) clamp to the trash block, whose contents no query ever
    attends (see ``kvpool``).

int8 KV blocks dequantize inside the gather loop with the collectives
quantizer (``block_dequantize_int8`` at ``block=head_dim``); appends
quantize once. fp32-vs-int8 is therefore a pure storage-format choice —
the surrounding program is identical.
"""

import dataclasses

import jax
import jax.numpy as jnp

from pyrecover_tpu.models.decode import NEG_INF
from pyrecover_tpu.models.llama import (
    attn_residual,
    ffn_sublayer,
    qkv_proj,
    rms_norm,
)
from pyrecover_tpu.ops.rope import precompute_rope
from pyrecover_tpu.parallel.collectives import (
    block_dequantize_int8,
    block_quantize_int8,
)
from pyrecover_tpu.serving.kvpool import TRASH_BLOCK
from pyrecover_tpu.utils.dtypes import resolve_dtype


def _scatter_positions(tables, qpos, block_size):
    """(physical block, offset) for every ``(seq, chunk)`` position; out
    of-table positions clamp to the trash block."""
    width = tables.shape[1]
    blk_idx = qpos // block_size
    off = qpos % block_size
    safe = blk_idx < width
    phys = jnp.take_along_axis(
        tables, jnp.minimum(blk_idx, width - 1), axis=1
    )
    return jnp.where(safe, phys, TRASH_BLOCK), off


def _append_block_kv(layer_pool, k, v, phys, off, kv_mode):
    """Scatter this chunk's k/v (B, C, Hkv, hd) into one layer's pool
    slices at ``(phys, off)``; int8 pools quantize on append (one f32
    scale per head per token — ``block=head_dim``)."""
    b, c = phys.shape
    flat = lambda x: x.reshape(b * c, *x.shape[2:])  # noqa: E731
    pb, po = phys.reshape(-1), off.reshape(-1)
    out = dict(layer_pool)
    if kv_mode == "int8":
        hd = k.shape[-1]
        qk, sk = block_quantize_int8(k.astype(jnp.float32), block=hd)
        qv, sv = block_quantize_int8(v.astype(jnp.float32), block=hd)
        out["k"] = out["k"].at[pb, po].set(flat(qk))
        out["v"] = out["v"].at[pb, po].set(flat(qv))
        out["k_scale"] = out["k_scale"].at[pb, po].set(flat(sk[..., 0]))
        out["v_scale"] = out["v_scale"].at[pb, po].set(flat(sv[..., 0]))
        return out
    out["k"] = out["k"].at[pb, po].set(flat(k.astype(out["k"].dtype)))
    out["v"] = out["v"].at[pb, po].set(flat(v.astype(out["v"].dtype)))
    return out


def paged_attention(q, layer_pool, tables, qpos, scale, block_size,
                    kv_mode):
    """q (B, C, Hq, hd) at absolute positions ``qpos`` (B, C) against the
    paged pool slices for one layer; returns (B, C, Hq*hd).

    Blockwise online softmax over physical KV blocks gathered through the
    block table — the ``models/decode.py:_cached_attention`` loop with the
    ``dynamic_slice`` swapped for a table gather and the scalar position
    replaced by a per-sequence column. Trip count is the deepest live
    fill in the batch (traced), so cost follows fill, not pool capacity.
    """
    b, c, hq, d = q.shape
    hkv = layer_pool["k"].shape[2]
    group = hq // hkv
    f32 = jnp.float32
    qg = q.reshape(b, c, hkv, group, d)
    n_blocks = jnp.minimum(
        (jnp.max(qpos) + block_size) // block_size, tables.shape[1]
    )

    def gather(name, blk_ids):
        payload = layer_pool[name][blk_ids]  # (B, bs, Hkv, hd)
        if kv_mode == "int8":
            scale_blk = layer_pool[f"{name}_scale"][blk_ids]
            return block_dequantize_int8(
                payload, scale_blk[..., None], block=d
            )
        return payload

    def body(i, carry):
        m, l, acc = carry
        blk_ids = tables[:, i]  # (B,)
        k_blk = gather("k", blk_ids)
        v_blk = gather("v", blk_ids)
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, k_blk, preferred_element_type=f32
        ) * f32(scale)
        kpos = i * block_size + jnp.arange(block_size, dtype=jnp.int32)
        # (B, C, bs): per-sequence causal mask over the timeline
        mask = kpos[None, None, :] <= qpos[:, :, None]
        s = jnp.where(mask[:, None, None, :, :], s, f32(NEG_INF))
        # online softmax; block 0 always holds kpos 0 <= qpos, so m is
        # finite after the first iteration (decode.py's invariant)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=f32,
        )
        return m_new, l, acc * corr[..., None] + pv

    m0 = jnp.full((b, hkv, group, c), NEG_INF, f32)
    l0 = jnp.zeros((b, hkv, group, c), f32)
    acc0 = jnp.zeros((b, hkv, group, c, d), f32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    out = acc / l[..., None]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, c, hq * d)
    return out.astype(q.dtype)


def paged_forward(params, pool_arrays, tokens, pos, tables, config, *,
                  block_size, kv_mode="native", rope_len=None):
    """Run ``tokens`` (B, C) with row ``r`` at absolute positions
    ``[pos[r], pos[r]+C)`` against the paged pool; returns ``(logits,
    pool_arrays)`` — logits (B, C, vocab) fp32, the pool updated at the
    written positions. ``C`` is static; ``pos`` and the tables are
    traced, so one compiled program serves every mix of fills.

    MoE models decode no-drop exactly like ``decode_forward`` (capacity
    raised to the per-token point), so chunked serving cannot diverge
    from the training forward's routing.
    """
    cfg = config
    if cfg.n_experts > 0:
        cfg = dataclasses.replace(
            cfg, moe_capacity_factor=float(cfg.n_experts)
        )
    cdt = resolve_dtype(cfg.compute_dtype)
    b, c = tokens.shape
    hd = cfg.head_dim
    qpos = pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]

    cos_all, sin_all = precompute_rope(
        hd, int(rope_len or cfg.max_seq_len), cfg.rope_theta
    )
    cos, sin = cos_all[qpos], sin_all[qpos]  # (B, C, hd/2)
    scale = 1.0 / (hd**0.5)
    phys, off = _scatter_positions(tables, qpos, block_size)

    x = params["tok_embed"].astype(cdt)[tokens]

    def body(x, scanned):
        layer, layer_pool = scanned
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = qkv_proj(h, layer, cfg, cos, sin)
        # write the chunk BEFORE attending — queries see their own and
        # earlier chunk positions through the pool, exactly like the
        # lockstep cache update
        layer_pool = _append_block_kv(layer_pool, k, v, phys, off, kv_mode)
        attn = paged_attention(
            q, layer_pool, tables, qpos, scale, block_size, kv_mode
        )
        x = attn_residual(x, attn, layer, cfg)
        x, _ = ffn_sublayer(x, layer, cfg)
        return x, layer_pool

    x, new_pool = jax.lax.scan(body, x, (params["layers"], pool_arrays))
    hidden = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(
        "bcd,dv->bcv", hidden, params["output"].astype(cdt),
        preferred_element_type=jnp.float32,
    )
    return logits, new_pool
