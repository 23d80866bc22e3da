"""Ring attention: sequence/context parallelism over the ``sequence`` mesh axis.

The reference has NO sequence parallelism of any kind — long context is
handled only by per-device flash attention (SURVEY §2.2: seq_len is a plain
flag, utils.py:119-123). This module is the TPU-native long-context design
the rebuild owes as a first-class capability: activations are sharded along
the sequence dimension, and attention is computed by rotating KV chunks
around the ring of devices with ``lax.ppermute`` (ICI neighbor exchange)
while accumulating an online softmax — compute overlaps the rotation, HBM
never holds more than one remote chunk, and max context scales linearly
with the number of devices on the ``sequence`` axis.

Scaling design (this is the v2 the long contexts it exists for need):

  * The ring loop is a ``lax.scan`` — one compiled body regardless of ring
    size, no unrolled per-step HLO.
  * The inner update is blockwise (flash-style): the rotating KV chunk is
    consumed in ``block_kv``-sized sub-blocks under a second ``lax.scan``,
    so the transient score block is (Sq_local × block_kv) f32 — never the
    full (Sq_local × Sk_local) matrix.
  * A custom VJP: the forward saves only (out, LSE) per query — the
    standard flash-attention residuals — and the backward runs a second
    ring pass that RECOMPUTES each chunk's scores. dK/dV accumulators
    rotate with their KV chunks and arrive home after the full ring.
    Plain AD through the forward would instead retain every rotated KV
    copy per step (ring × KV memory — exactly what kills long contexts).

Causality is handled with *global* position indices (each device knows its
ring index via ``lax.axis_index``), so the math is identical to full causal
attention — verified against the XLA SDPA path in tests (fwd AND grads).
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from pyrecover_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP, AXIS_SEQ, AXIS_TENSOR

_NEG_INF = -1e30


def _score_mask(seg_q, seg_k, q_start, k_start, sq, sk, causal):
    """Combined causal + packed-segment validity mask, or None. Causal is
    (sq, sk) positional; segments add a batch-dependent (B, sq, sk) term
    (queries attend only within their own document)."""
    mask = None
    if causal:
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        mask = (qpos >= kpos)[None]  # (1, sq, sk)
    if seg_q is not None:
        seg = seg_q[:, :, None] == seg_k[:, None, :]  # (B, sq, sk)
        mask = seg if mask is None else jnp.logical_and(mask, seg)
    return mask


def _block_update(qg, k, v, seg_q, seg_k, q_start, k_start, scale, causal,
                  m, l, acc, k_len=None):
    """One online-softmax update of local q against one KV sub-block.
    Shapes: qg (B, Sq, Hkv, G, D); k/v (B, Sk, Hkv, D); seg_q/seg_k
    (B, Sq)/(B, Sk) int32 or None. State m/l: (B, Hkv, G, Sq, 1) f32;
    acc: (B, Sq, Hkv, G, D) f32. ``k_len`` (traced scalar) masks the
    ragged tail of a padded sub-block: entries at local index >= k_len are
    invalid (their padded global positions would alias the NEXT chunk's,
    so the causal mask alone cannot exclude them)."""
    sq, sk = qg.shape[1], k.shape[1]
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * jnp.float32(scale)
    mask = _score_mask(seg_q, seg_k, q_start, k_start, sq, sk, causal)
    if mask is not None:
        s = jnp.where(mask[:, None, None], s, jnp.float32(_NEG_INF))
    if k_len is not None:
        kidx = jnp.arange(sk, dtype=jnp.int32)
        s = jnp.where(
            (kidx < k_len)[None, None, None, None, :], s,
            jnp.float32(_NEG_INF),
        )
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    upd = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    # corr: (B,Hkv,G,Sq,1) → align to acc (B,Sq,Hkv,G,D)
    corr_acc = jnp.moveaxis(corr, 3, 1)  # (B,Sq,Hkv,G,1)
    acc_new = acc * corr_acc + upd
    return m_new, l_new, acc_new


def _split_blocks(x, block):
    """(B, S, ...) → (nb, B, block, ...), padding a non-divisible S up to a
    whole number of blocks (the flash kernel's ragged-edge pattern,
    ops/flash_attention.py): the blockwise (Sq × block_kv) memory bound
    holds for ANY per-device chunk size. Padded tail entries are masked by
    the caller via each sub-block's valid length (``k_len``). S <= block
    stays a single unpadded block."""
    s = x.shape[1]
    if not block or s <= block:
        return x[None]
    nb = -(-s // block)
    if s % block:
        x = jnp.pad(
            x, ((0, 0), (0, nb * block - s)) + ((0, 0),) * (x.ndim - 2)
        )
    return jnp.moveaxis(x.reshape(x.shape[0], nb, block, *x.shape[2:]), 1, 0)


def _chunk_update(qg, k, v, seg_q, seg_k, q_start, k_start, scale, causal,
                  m, l, acc, block_kv):
    """Consume one rotating KV chunk in flash-style sub-blocks (inner scan):
    the transient score block is (Sq × block_kv), not (Sq × Sk_chunk)."""
    kb = _split_blocks(k, block_kv)
    vb = _split_blocks(v, block_kv)
    sb = None if seg_k is None else _split_blocks(seg_k, block_kv)
    blk = kb.shape[2]
    sk_real = k.shape[1]
    ragged = sk_real % blk != 0  # static: only then is a tail mask needed

    def body(carry, inp):
        m, l, acc = carry
        if sb is None:
            i, kk, vv = inp
            ss = None
        else:
            i, kk, vv, ss = inp
        k_len = jnp.minimum(sk_real - i * blk, blk) if ragged else None
        m, l, acc = _block_update(
            qg, kk, vv, seg_q, ss, q_start, k_start + i * blk, scale,
            causal, m, l, acc, k_len=k_len,
        )
        return (m, l, acc), None

    xs = (
        (jnp.arange(kb.shape[0]), kb, vb)
        if sb is None
        else (jnp.arange(kb.shape[0]), kb, vb, sb)
    )
    (m, l, acc), _ = jax.lax.scan(body, (m, l, acc), xs)
    return m, l, acc


def _ring_fwd_local(q, k, v, seg, *, axis_name, causal, scale, block_kv):
    """Per-shard forward (runs under shard_map): q/k/v hold THIS device's
    sequence chunk. Rotates KV around the ring via a scanned ppermute;
    returns (out, lse) — lse is the flash-attention residual the backward
    needs. KV is rotated on every step (incl. the last), so it arrives back
    home after the scan — the backward relies on the same full rotation."""
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    ring = jax.lax.axis_size(axis_name)
    # positions only feed the causal mask (segment masks compare ids, the
    # ragged-tail mask uses local indices): without causality there is no
    # use for axis_index, so it is not traced at all
    my = jax.lax.axis_index(axis_name) if causal else 0
    q_start = my * sq

    qg = q.reshape(b, sq, hkv, g, d)
    m0 = jnp.full((b, hkv, g, sq, 1), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq, 1), dtype=jnp.float32)
    acc0 = jnp.zeros((b, sq, hkv, g, d), dtype=jnp.float32)
    perm = [(i, (i + 1) % ring) for i in range(ring)]

    def ring_step(carry, step):
        if seg is None:
            k_cur, v_cur, m, l, acc = carry
            seg_cur = None
        else:
            k_cur, v_cur, seg_cur, m, l, acc = carry
        src = (my - step) % ring  # whose chunk we currently hold
        m, l, acc = _chunk_update(
            qg, k_cur, v_cur, seg, seg_cur, q_start, src * sk, scale,
            causal, m, l, acc, block_kv,
        )
        # neighbor exchange over ICI; overlaps the next step's compute
        # under XLA's async collective scheduling (the segment chunk — a
        # tiny (B, Sk) int32 — rides the same rotation when packing)
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        if seg is None:
            return (k_cur, v_cur, m, l, acc), None
        seg_cur = jax.lax.ppermute(seg_cur, axis_name, perm)
        return (k_cur, v_cur, seg_cur, m, l, acc), None

    carry0 = (
        (k, v, m0, l0, acc0) if seg is None else (k, v, seg, m0, l0, acc0)
    )
    out_carry, _ = jax.lax.scan(ring_step, carry0, jnp.arange(ring))
    m, l, acc = out_carry[-3], out_carry[-2], out_carry[-1]

    l_safe = jnp.where(l > 0, l, 1.0)
    out = (acc / jnp.moveaxis(l_safe, 3, 1)).reshape(b, sq, hq, d)
    lse = m + jnp.log(l_safe)  # (B,Hkv,G,Sq,1)
    return out.astype(q.dtype), lse


def _block_bwd(qg, k, v, seg_q, seg_k, do_g, delta, lse, q_start, k_start,
               scale, causal, k_len=None):
    """Recompute one KV sub-block's probabilities from (q, k, lse) and
    return (dq_contrib, dk_block, dv_block) — flash-attention backward
    algebra. ``k_len`` masks a padded ragged tail exactly as in the
    forward (p = 0 there, so dk/dv tail rows come out zero)."""
    sq, sk = qg.shape[1], k.shape[1]
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * jnp.float32(scale)
    mask = _score_mask(seg_q, seg_k, q_start, k_start, sq, sk, causal)
    if mask is not None:
        s = jnp.where(mask[:, None, None], s, jnp.float32(_NEG_INF))
    if k_len is not None:
        kidx = jnp.arange(sk, dtype=jnp.int32)
        s = jnp.where(
            (kidx < k_len)[None, None, None, None, :], s,
            jnp.float32(_NEG_INF),
        )
    p = jnp.exp(s - lse)  # (B,Hkv,G,Sq,Sk); masked entries exp(-inf)=0
    dv = jnp.einsum("bkgqs,bqkgd->bskd", p, do_g,
                    preferred_element_type=jnp.float32)
    dp = jnp.einsum("bqkgd,bskd->bkgqs", do_g, v,
                    preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * jnp.float32(scale)
    dq = jnp.einsum("bkgqs,bskd->bqkgd", ds, k,
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("bkgqs,bqkgd->bskd", ds, qg,
                    preferred_element_type=jnp.float32)
    return dq, dk, dv


def _chunk_bwd(qg, k, v, seg_q, seg_k, do_g, delta, lse, q_start, k_start,
               scale, causal, block_kv):
    """Backward over one rotating KV chunk in flash-style sub-blocks (inner
    scan), mirroring ``_chunk_update``: the transient score/prob/ds tensors
    are (Sq × block_kv) f32 — never the full (Sq × Sk_chunk) matrices,
    which matters most here because training's memory peak IS the backward."""
    kb = _split_blocks(k, block_kv)
    vb = _split_blocks(v, block_kv)
    sb = None if seg_k is None else _split_blocks(seg_k, block_kv)
    nb, blk = kb.shape[0], kb.shape[2]
    sk_real = k.shape[1]
    ragged = sk_real % blk != 0

    def body(dq, inp):
        if sb is None:
            i, kk, vv = inp
            ss = None
        else:
            i, kk, vv, ss = inp
        k_len = jnp.minimum(sk_real - i * blk, blk) if ragged else None
        dq_c, dk_b, dv_b = _block_bwd(
            qg, kk, vv, seg_q, ss, do_g, delta, lse, q_start,
            k_start + i * blk, scale, causal, k_len=k_len,
        )
        return dq + dq_c, (dk_b, dv_b)

    xs = (
        (jnp.arange(nb), kb, vb) if sb is None else (jnp.arange(nb), kb, vb, sb)
    )
    dq, (dk_b, dv_b) = jax.lax.scan(
        body, jnp.zeros(qg.shape, dtype=jnp.float32), xs,
    )
    # (nb, B, blk, Hkv, D) → (B, Sk_chunk, Hkv, D); a padded tail block's
    # zero rows are sliced back off
    dk = jnp.moveaxis(dk_b, 0, 1).reshape(
        k.shape[0], nb * blk, *k.shape[2:]
    )[:, :sk_real]
    dv = jnp.moveaxis(dv_b, 0, 1).reshape(
        v.shape[0], nb * blk, *v.shape[2:]
    )[:, :sk_real]
    return dq, dk, dv


def _ring_bwd_local(q, k, v, seg, out, lse, do, *, axis_name, causal, scale,
                    block_kv):
    """Second ring pass: dK/dV accumulators travel WITH their KV chunks and
    are home after the full rotation; dQ accumulates locally."""
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    ring = jax.lax.axis_size(axis_name)
    # same PartitionId-avoidance as the forward: positions are
    # causal-mask-only inputs
    my = jax.lax.axis_index(axis_name) if causal else 0
    q_start = my * sq

    qg = q.reshape(b, sq, hkv, g, d)
    do_g = do.reshape(b, sq, hkv, g, d)
    # delta_i = Σ_d dO·O per query — (B,Sq,Hq) → (B,Hkv,G,Sq,1)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.moveaxis(
        delta.reshape(b, sq, hkv, g), (1, 2, 3), (3, 1, 2)
    )[..., None]

    dq0 = jnp.zeros((b, sq, hkv, g, d), dtype=jnp.float32)
    dk0 = jnp.zeros((b, sk, hkv, d), dtype=jnp.float32)
    dv0 = jnp.zeros((b, sk, hkv, d), dtype=jnp.float32)
    perm = [(i, (i + 1) % ring) for i in range(ring)]

    def ring_step(carry, step):
        if seg is None:
            k_cur, v_cur, dk_cur, dv_cur, dq = carry
            seg_cur = None
        else:
            k_cur, v_cur, seg_cur, dk_cur, dv_cur, dq = carry
        src = (my - step) % ring
        dq_c, dk_c, dv_c = _chunk_bwd(
            qg, k_cur, v_cur, seg, seg_cur, do_g, delta, lse, q_start,
            src * sk, scale, causal, block_kv,
        )
        dq = dq + dq_c
        dk_cur = dk_cur + dk_c
        dv_cur = dv_cur + dv_c
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
        if seg is None:
            return (k_cur, v_cur, dk_cur, dv_cur, dq), None
        seg_cur = jax.lax.ppermute(seg_cur, axis_name, perm)
        return (k_cur, v_cur, seg_cur, dk_cur, dv_cur, dq), None

    carry0 = (
        (k, v, dk0, dv0, dq0) if seg is None
        else (k, v, seg, dk0, dv0, dq0)
    )
    out_carry, _ = jax.lax.scan(ring_step, carry0, jnp.arange(ring))
    dk, dv, dq = out_carry[-3], out_carry[-2], out_carry[-1]
    return (
        dq.reshape(b, sq, hq, d).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ring_attention_local(q, k, v, seg, axis_name, causal, scale, block_kv):
    out, _ = _ring_fwd_local(
        q, k, v, seg, axis_name=axis_name, causal=causal, scale=scale,
        block_kv=block_kv,
    )
    return out


def _ring_vjp_fwd(q, k, v, seg, axis_name, causal, scale, block_kv):
    out, lse = _ring_fwd_local(
        q, k, v, seg, axis_name=axis_name, causal=causal, scale=scale,
        block_kv=block_kv,
    )
    return out, (q, k, v, seg, out, lse)


def _ring_vjp_bwd(axis_name, causal, scale, block_kv, res, do):
    import numpy as np

    q, k, v, seg, out, lse = res
    dq, dk, dv = _ring_bwd_local(
        q, k, v, seg, out, lse, do, axis_name=axis_name, causal=causal,
        scale=scale, block_kv=block_kv,
    )
    dseg = None if seg is None else np.zeros(seg.shape, jax.dtypes.float0)
    return dq, dk, dv, dseg


_ring_attention_local.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(q, k, v, *, causal=True, scale=None, axis_name=AXIS_SEQ,
                   block_kv=512, segment_ids=None):
    """Drop-in for ``sdpa_attention``: shards the sequence dimension over the
    ``sequence`` mesh axis via shard_map + a scanned ppermute ring. Falls
    back to the XLA path when no mesh / a size-1 sequence axis is in scope.
    ``segment_ids`` (batch, seq) enables packed-sequence masking: the
    sequence-sharded segment chunk rotates around the ring alongside its
    KV chunk (a tiny int32 array on the same ICI hops), so packing and
    sequence parallelism compose."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or mesh.shape.get(axis_name, 1) == 1:
        from pyrecover_tpu.ops.attention import sdpa_attention

        return sdpa_attention(q, k, v, causal=causal, scale=scale,
                              segment_ids=segment_ids)

    batch_axes = tuple(a for a in (AXIS_DATA, AXIS_FSDP) if a in mesh.axis_names)
    head_axis = AXIS_TENSOR if AXIS_TENSOR in mesh.axis_names else None
    spec = P(batch_axes or None, axis_name, head_axis, None)

    body = functools.partial(
        _ring_attention_local, axis_name=axis_name, causal=causal,
        scale=scale, block_kv=block_kv,
    )
    if segment_ids is None:
        return jax.shard_map(
            lambda q, k, v: body(q, k, v, None),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)
    seg_spec = P(batch_axes or None, axis_name)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec, seg_spec),
        out_specs=spec, check_vma=False,
    )(q, k, v, segment_ids.astype(jnp.int32))
