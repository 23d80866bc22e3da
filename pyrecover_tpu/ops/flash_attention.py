"""Pallas flash attention (causal, GQA-aware, packing-aware) with custom VJP.

This is the TPU-native equivalent of the reference's external CUDA
flash-attention dependency (`setup_flashattention.sh` builds Dao-AILab's
Hopper kernels; `model.py:180-190` adapts them) — except implemented
in-repo as Mosaic/Pallas kernels rather than consumed as a wheel, because
Pallas is the TPU kernel path (SURVEY §2: "the one native component
equivalent the build owes").

Algorithm: classic blockwise online-softmax (flash) forward; backward
recomputes per-block probabilities from the saved logsumexp and accumulates
dq / dk / dv in separate kernels (dk/dv with a kv-major grid so each block
is written once). All softmax math in fp32; matmuls hit the MXU with
``preferred_element_type=float32``.

Layout: grid (batch, q_heads, q_blocks, kv_blocks), kv innermost so VMEM
scratch (running max / denominator / accumulator) persists across the kv
sweep of one q block — TPU grids execute sequentially, which is what makes
this accumulator pattern legal. GQA is expressed in the BlockSpec index
maps (kv head = q head // group) so repeated KV heads are never
materialized (unlike the reference's repeat_kv, model.py:130-139).

The kernel is TOTAL over shapes: non-divisible sequence lengths get masked
tail blocks (the ragged edge is iota-masked exactly like the causal
boundary; Mosaic drops out-of-range stores), any head_dim compiles (Mosaic
pads the lane dimension — 64/96/128/... all work), and packed sequences are
supported via per-position ``segment_ids`` folded into the same score mask.
The only remaining fallback is a malformed GQA config (q heads not a
multiple of kv heads), and it is LOUD (log_host0), never silent.

Set ``PYRECOVER_PALLAS_INTERPRET=1`` to run in the Pallas interpreter
(CPU tests — SURVEY §4's fake-backend role). On any other backend the
variable is an ERROR: an interpreted kernel on a chip is a silent 100×
slowdown under the kernel's name.
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from pyrecover_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_TENSOR,
    nonmanual_axes,
)
from pyrecover_tpu.utils.remat import FLASH_LSE, FLASH_OUT

NEG_INF = -1e30
LANES = 128  # TPU lane width: scratch vectors are (bq, 128) replicated
# logsumexp is one number per (batch, head, position); the kernels write
# and read it with a minor dimension of 8 (the f32 sublane count) because
# a (bq, 1) block is no legal store. That saves nothing in HBM: the chip
# pads a minor dimension of 8 to the 128-lane tile all the same (256 MiB a
# Mistral layer for 2 MiB of numbers), and every lane holds the same
# value. So where a remat policy KEEPS the residual for the backward
# (utils/remat.py saves `flash_lse`) it is lane 0 alone, (b, h, s)
# (`_flash_fwd`, `slim_lse`), broadcast back to this shape for the backward
# kernels; everywhere else it stays as the kernel wrote it.
LSE_LANES = 8

# (the two residuals the forward kernel writes carry `checkpoint_name`
# tags, FLASH_OUT and FLASH_LSE of utils/remat.py: under `jax.checkpoint`
# a policy that saves them keeps the backward sweep from running the
# forward kernel a second time)


def _interpret():
    on = os.environ.get("PYRECOVER_PALLAS_INTERPRET", "0") == "1"
    backend = jax.default_backend()
    if on and backend != "cpu":
        raise RuntimeError(
            f"PYRECOVER_PALLAS_INTERPRET=1 on the {backend!r} backend: "
            "interpret mode is the CPU test aid; on an accelerator the "
            "flash kernel must compile (unset the variable)"
        )
    return on


def _score_mask(iq, ik, *, block_q, block_kv, causal, seq_q, seq_kv,
                sq_ref, sk_ref, mask_q_bound):
    """(block_q, block_kv) boolean mask of VALID score positions, or None
    when statically every position in the block is valid. Folds together
    the causal boundary, the ragged sequence tails (when block size does
    not divide the length), and packed-sequence segment equality. The
    q-bound term is only needed where out-of-range q rows would CONTRIBUTE
    to an accumulation (the dk/dv kernel) — elsewhere their garbage stays
    in rows whose stores Mosaic drops."""
    conds = []
    if causal or seq_kv % block_kv or (mask_q_bound and seq_q % block_q):
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0
        )
        kpos = ik * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1
        )
        if causal:
            conds.append(qpos >= kpos)
        if seq_kv % block_kv:
            conds.append(kpos < seq_kv)
        if mask_q_bound and seq_q % block_q:
            conds.append(qpos < seq_q)
    if sq_ref is not None:
        seg_q = sq_ref[...].reshape(block_q, 1)
        seg_k = sk_ref[...].reshape(1, block_kv)
        conds.append(seg_q == seg_k)
    if not conds:
        return None
    mask = conds[0]
    for c in conds[1:]:
        mask = mask & c
    return mask


def _zero_oob_rows(x, block_start, valid_len, block):
    """Zero rows of a (block, d) tile whose global row index falls beyond
    ``valid_len``. Ragged-tail loads are padding-filled by Mosaic/the
    interpreter with UNSPECIFIED values (NaN in interpret mode), and a NaN
    survives multiplication by a zero probability — so any tile that feeds
    a CONTRACTION over its rows must have its out-of-range rows zeroed
    explicitly; score masking alone cannot save those products."""
    if valid_len % block == 0:
        return x  # statically no ragged tail
    rows = block_start + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    return jnp.where(rows < valid_len, x, 0.0)


# =========================== forward kernel ================================


def _fwd_kernel(*args, scale, block_q, block_kv, causal, num_kv_blocks,
                seq_q, seq_kv, has_segments):
    if has_segments:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = args
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = args
        sq_ref = sk_ref = None
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: skip kv blocks strictly above the diagonal band
    run = True
    if causal:
        run = ik * block_kv <= iq * block_q + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, d)
        # v feeds the p·v contraction over kv rows: zero its ragged tail
        v = _zero_oob_rows(v, ik * block_kv, seq_kv, block_kv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk)

        mask = _score_mask(
            iq, ik, block_q=block_q, block_kv=block_kv, causal=causal,
            seq_q=seq_q, seq_kv=seq_kv, sq_ref=sq_ref, sk_ref=sk_ref,
            mask_q_bound=False,
        )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk)
        corr = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # logsumexp for the backward pass
        lse_ref[0, 0] = (
            m_scr[:, :LSE_LANES] + jnp.log(jnp.broadcast_to(l_safe, (l_safe.shape[0], LSE_LANES)))
        ).astype(jnp.float32)


def _fwd(q, k, v, seg, *, causal, scale, block_q, block_kv):
    b, s, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    group = hq // hkv
    bq = min(block_q, s)
    bk = min(block_kv, sk)
    nq = pl.cdiv(s, bq)
    nk = pl.cdiv(sk, bk)
    has_segments = seg is not None

    # (b, h, s, d) layout for clean 2D blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=bq, block_kv=bk,
        causal=causal, num_kv_blocks=nk, seq_q=s, seq_kv=sk,
        has_segments=has_segments,
    )
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
    ]
    inputs = [qt, kt, vt]
    if has_segments:
        # (b, 1, s): Mosaic requires the last-two block dims to divide
        # (8, 128) or equal the array dims — a (1, bq) block over (b, s)
        # fails that on real TPU (the sublane dim 1 vs b); the dummy
        # middle axis makes the trailing block dims (1, bq) legal.
        seg3 = seg.reshape(b, 1, seg.shape[1])
        in_specs += [
            pl.BlockSpec((1, 1, bq), lambda bi, hi, qi, ki: (bi, 0, qi)),
            pl.BlockSpec((1, 1, bk), lambda bi, hi, qi, ki: (bi, 0, ki)),
        ]
        inputs += [seg3, seg3]
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, LSE_LANES),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, s, LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(*inputs)
    return out.transpose(0, 2, 1, 3), lse


# =========================== backward kernels ==============================


def _bwd_dq_kernel(*args, scale, block_q, block_kv, causal, num_kv_blocks,
                   seq_q, seq_kv, has_segments):
    if has_segments:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, sq_ref, sk_ref,
         dq_ref, acc_scr, delta_scr) = args
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dq_ref, acc_scr, delta_scr) = args
        sq_ref = sk_ref = None
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        # delta_i = rowsum(do·out): same for every kv block of this q block
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        delta_scr[:] = jnp.broadcast_to(
            jnp.sum(do * o, axis=-1, keepdims=True), delta_scr.shape
        )

    run = True
    if causal:
        run = ik * block_kv <= iq * block_q + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        # k and v feed contractions over kv rows (ds·k and do·v): zero
        # their ragged tails so 0-probability NaN products can't leak in
        k = _zero_oob_rows(k, ik * block_kv, seq_kv, block_kv)
        v = _zero_oob_rows(v, ik * block_kv, seq_kv, block_kv)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_scr[:, :1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _score_mask(
            iq, ik, block_q=block_q, block_kv=block_kv, causal=causal,
            seq_q=seq_q, seq_kv=seq_kv, sq_ref=sq_ref, sk_ref=sk_ref,
            mask_q_bound=False,
        )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        acc_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = acc_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*args, scale, block_q, block_kv, causal, num_q_blocks,
                    group, seq_q, seq_kv, has_segments):
    if has_segments:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, sq_ref, sk_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = args
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = args
        sq_ref = sk_ref = None
    ik = pl.program_id(2)  # kv-major: kv block is the outer loop dim
    t = pl.program_id(3)  # sweeps (q_block, group member): iq = t // group
    iq = t // group

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = ik * block_kv <= iq * block_q + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        # q and do feed the dk/dv contractions over q rows: zero their
        # ragged tails (a zeroed p alone cannot kill 0·NaN products)
        q = _zero_oob_rows(q, iq * block_q, seq_q, block_q)
        do = _zero_oob_rows(do, iq * block_q, seq_q, block_q)
        lse = lse_ref[0, 0][:, :1]
        delta = jnp.sum(do * o, axis=-1, keepdims=True)  # (bq, 1)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        # q-bound masking matters HERE: out-of-range q rows would otherwise
        # accumulate into dk/dv through garbage lse/delta reads. p and ds
        # are zeroed through `where` (not via s=-inf alone) because
        # exp(-inf - garbage_lse) is not reliably zero.
        mask = _score_mask(
            iq, ik, block_q=block_q, block_kv=block_kv, causal=causal,
            seq_q=seq_q, seq_kv=seq_kv, sq_ref=sq_ref, sk_ref=sk_ref,
            mask_q_bound=True,
        )
        p = jnp.exp(s - lse)  # (bq, bk)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        if mask is not None:
            ds = jnp.where(mask, ds, 0.0)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(t == num_q_blocks * group - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(causal, scale, block_q, block_kv, res, g):
    q, k, v, seg, out, lse = res
    do, _ = g  # gradient wrt (out, lse); lse grad unused
    b, s, hq, d = q.shape
    if lse.ndim == 3:  # kept as lane 0: back to the kernels' layout
        lse = jnp.broadcast_to(lse[..., None], (*lse.shape, LSE_LANES))
    _, sk, hkv, _ = k.shape
    group = hq // hkv
    bq = min(block_q, s)
    bk = min(block_kv, sk)
    nq = pl.cdiv(s, bq)
    nk = pl.cdiv(sk, bk)
    has_segments = seg is not None

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    outt = out.transpose(0, 2, 1, 3)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, block_q=bq, block_kv=bk,
        causal=causal, num_kv_blocks=nk, seq_q=s, seq_kv=sk,
        has_segments=has_segments,
    )
    dq_in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
        pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, bq, LSE_LANES),
                     lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
    ]
    dq_inputs = [qt, kt, vt, dot, outt, lse]
    if has_segments:
        # (b, 1, s) for Mosaic block-shape legality — see _fwd
        seg3 = seg.reshape(b, 1, seg.shape[1])
        dq_in_specs += [
            pl.BlockSpec((1, 1, bq), lambda bi, hi, qi, ki: (bi, 0, qi)),
            pl.BlockSpec((1, 1, bk), lambda bi, hi, qi, ki: (bi, 0, ki)),
        ]
        dq_inputs += [seg3, seg3]
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, hq, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_dq",
    )(*dq_inputs)

    # dk/dv: grid dim 3 sweeps (q_block × GQA group member) so the whole
    # group's contribution accumulates in VMEM scratch and each output
    # block is written once, directly at kv-head granularity — no
    # (b, q_heads, s, d) f32 intermediates (2×2.1G at the 1B bench point)
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, block_q=bq, block_kv=bk,
        causal=causal, num_q_blocks=nq, group=group, seq_q=s, seq_kv=sk,
        has_segments=has_segments,
    )
    qhead = lambda hi, t, g=group: hi * g + t % g  # noqa: E731
    qblock = lambda t, g=group: t // g  # noqa: E731
    dkv_in_specs = [
        pl.BlockSpec((1, 1, bq, d),
                     lambda bi, hi, ki, t: (bi, qhead(hi, t), qblock(t), 0)),
        pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki, t: (bi, hi, ki, 0)),
        pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki, t: (bi, hi, ki, 0)),
        pl.BlockSpec((1, 1, bq, d),
                     lambda bi, hi, ki, t: (bi, qhead(hi, t), qblock(t), 0)),
        pl.BlockSpec((1, 1, bq, d),
                     lambda bi, hi, ki, t: (bi, qhead(hi, t), qblock(t), 0)),
        pl.BlockSpec((1, 1, bq, LSE_LANES),
                     lambda bi, hi, ki, t: (bi, qhead(hi, t), qblock(t), 0)),
    ]
    dkv_inputs = [qt, kt, vt, dot, outt, lse]
    if has_segments:
        dkv_in_specs += [
            pl.BlockSpec((1, 1, bq), lambda bi, hi, ki, t: (bi, 0, qblock(t))),
            pl.BlockSpec((1, 1, bk), lambda bi, hi, ki, t: (bi, 0, ki)),
        ]
        dkv_inputs += [seg3, seg3]
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, hkv, nk, nq * group),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki, t: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki, t: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_dkv",
    )(*dkv_inputs)

    return (
        dq.transpose(0, 2, 1, 3),
        dk.transpose(0, 2, 1, 3),
        dv.transpose(0, 2, 1, 3),
    )


# =========================== public API ====================================

# Per-device-kind default (block_q, block_kv) tilings, measured with
# tools/bench_flash_blocks.py at the flagship bench shape (seq 2048,
# head_dim 128, bf16, fwd+bwd). The other generations are seeded from the
# v5e row scaled by their VMEM headroom — REPLACE a row by re-running the
# sweep on that hardware, then pin it in
# tests/test_flash_attention.py::test_default_blocks_table. Matched by
# substring against the lowered jax ``device_kind`` (the tpu_peak_flops
# convention); a kind the table does not know is an error, like a kind
# the peak table does not know — not a default.
DEFAULT_BLOCKS = {
    "v3": (256, 512),       # 16G HBM, small VMEM: conservative tiles
    "v4": (512, 1024),
    "v5e": (1024, 1024),
    "v5litepod": (1024, 1024),
    "v5 lite": (1024, 1024),
    "v5p": (1024, 1024),
    "v6e": (1024, 2048),    # Trillium: 2× VMEM of v5e, deeper kv tiles
    "cpu": (512, 512),      # interpret mode — tile size is test speed
}


def default_blocks(device_kind=None):
    """``(block_q, block_kv)`` for a device kind (the local device's when
    None). Consumed by the model's attention builder whenever
    ``flash_block_q/kv`` is 0 (= auto); explicit values always win. An
    unknown kind raises: a tile nobody measured on that hardware may not
    even fit its VMEM."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    kind = str(device_kind).lower()
    for key, blocks in DEFAULT_BLOCKS.items():
        if key in kind:
            return blocks
    raise ValueError(
        f"device kind {device_kind!r} has no row in the flash-attention "
        "DEFAULT_BLOCKS table: pass explicit --flash-block-q/--flash-block-kv "
        "or add a row measured with tools/bench_flash_blocks.py"
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, seg, causal, scale, block_q, block_kv, slim_lse):
    out, _ = _fwd(q, k, v, seg, causal=causal, scale=scale,
                  block_q=block_q, block_kv=block_kv)
    return out


def _flash_fwd(q, k, v, seg, causal, scale, block_q, block_kv, slim_lse):
    out, lse = _fwd(q, k, v, seg, causal=causal, scale=scale,
                    block_q=block_q, block_kv=block_kv)
    if slim_lse:  # every lane holds the same number: lane 0 is kept
        lse = lse[..., 0]
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, seg, out, lse)


def _flash_bwd(causal, scale, block_q, block_kv, slim_lse, res, g):
    dq, dk, dv = _bwd(causal, scale, block_q, block_kv, res, (g, None))
    seg = res[3]
    # segment ids are integral: their cotangent type is float0
    dseg = (
        None if seg is None else np.zeros(seg.shape, jax.dtypes.float0)
    )
    return dq, dk, dv, dseg


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal=True, scale=None,
                    block_q=512, block_kv=512, segment_ids=None,
                    slim_lse=False):
    """Drop-in replacement for ``sdpa_attention`` (same signature/shapes),
    backed by the Pallas kernels. Total over sequence lengths and head
    dims (masked tail blocks / lane padding); ``segment_ids`` (batch,
    seq) restricts attention to within-segment for packed sequences.
    There is NO silent fallback: every valid GQA config runs in the
    kernel, and a malformed one (q heads not a multiple of kv heads)
    raises exactly like ``sdpa_attention`` does.

    ``slim_lse``: the row statistics kept for the backward are lane 0 of
    what the kernel writes, (b, h, s) and not (b, h, s, 8) padded to 128
    lanes: a 128th of the bytes while the residual lives through the
    forward sweep, for one slice and one broadcast a call. For a caller
    whose ``jax.checkpoint`` policy saves ``flash_lse``; where nothing
    keeps the residual the pair only costs time (0.5 % of a looped step;
    PERF.md section 6, PR 31).

    Under a mesh the kernel runs PER SHARD inside a ``shard_map`` over the
    batch axes (data, fsdp) and the head axis (tensor) — the layout the
    model constrains q/k/v to. The compiled Mosaic call is opaque to the
    SPMD partitioner: on a real multi-chip mesh the bare call does not
    lower at all ("Mosaic kernels cannot be automatically partitioned.
    Please wrap the call in a shard_map" — the CPU tests never saw it,
    the interpreted kernel being ordinary HLO). Attention is independent
    per (batch row, kv-head group), so the manual region needs no
    collective."""
    b, s, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if scale is None:
        scale = 1.0 / (d**0.5)
    if hq % hkv:
        # same contract as sdpa_attention — there is no path that can run
        # a non-multiple GQA config, so fail loudly rather than degrade
        raise ValueError(f"n_heads={hq} not divisible by n_kv_heads={hkv}")
    if segment_ids is not None:
        if s != sk:
            raise ValueError("segment_ids requires q_len == kv_len")
        segment_ids = segment_ids.astype(jnp.int32)
    bq = min(block_q, s)
    bk = min(block_kv, sk)

    def local(q, k, v, seg):
        return _flash(q, k, v, seg, causal, scale, bq, bk, slim_lse)

    spec = _shard_spec(b, hq, hkv)
    if spec is None:
        return local(q, k, v, segment_ids)
    mesh, qkv_spec, seg_spec = spec
    # segment_ids=None is an empty pytree: its spec then binds nothing
    return jax.shard_map(
        local, mesh=mesh, in_specs=(qkv_spec,) * 3 + (seg_spec,),
        out_specs=qkv_spec, check_vma=False,
    )(q, k, v, segment_ids)


def _shard_spec(batch, hq, hkv):
    """``(mesh, qkv_spec, seg_spec)`` for the per-shard kernel call, or
    None when there is nothing to shard over: no mesh in scope, no
    batch/head axis larger than 1, a dimension the axes do not divide
    (GSPMD keeps such a value replicated; the call stays global), or an
    enclosing manual region (the pipeline stage ``shard_map`` — its
    values are already per-stage local)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    if len(nonmanual_axes(mesh)) != len(mesh.axis_names):
        return None
    batch_axes = tuple(
        a for a in (AXIS_DATA, AXIS_FSDP) if mesh.shape.get(a, 1) > 1
    )
    if batch % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = ()
    tp = mesh.shape.get(AXIS_TENSOR, 1)
    head_axis = AXIS_TENSOR if tp > 1 and not (hq % tp or hkv % tp) else None
    if not batch_axes and head_axis is None:
        return None
    return (
        mesh,
        P(batch_axes or None, None, head_axis, None),
        P(batch_axes or None, None),
    )
