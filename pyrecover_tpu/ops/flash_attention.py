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

Layout: grid (batch, q_heads, pairs). The two block axes are folded into
ONE axis over the (q block, kv block) pairs a call needs (``flash_plan``),
q block outermost and kv innermost, so VMEM scratch (running max /
denominator / accumulator) persists across the kv sweep of one q block —
TPU grids execute sequentially, which is what makes this accumulator
pattern legal. Under ``causal`` a pair wholly above the diagonal is not in
the plan: it costs no grid step and no block copy (at sequence 4096 under
1024 x 1024 blocks six pairs of sixteen; in ``flash_dkv`` such a step used
to fetch q, do, o and the lse block, which the chip pads from 8 lanes to
128: 512 KB for 32 KB of numbers). The pair -> (q block, kv block, flags)
tables reach the index maps and the body as prefetched scalars
(``pltpu.PrefetchScalarGridSpec``; ``_step_tables``); the flags say where a
row's run begins and ends (init / finalize) and which of two bodies the
step runs: an *interior* step, every position of whose tile passes every
positional condition, builds no iota, no compare and (without segment ids)
no ``where``; an *edge* step (the diagonal, a ragged tail) runs the masked
body. The two differ in nothing else, and a ``where`` whose mask is all
true returns its operand: the outputs are the masked-everywhere kernels' to
the last bit. ``flash_dkv`` walks the same pairs kv block outermost, with
the q heads of the kv head's group as the innermost grid axis. GQA is
expressed in the BlockSpec index maps (kv head = q head // group) so
repeated KV heads are never materialized (unlike the reference's
repeat_kv, model.py:130-139).

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
import operator
import os
from typing import NamedTuple

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


class FlashPlan(NamedTuple):
    """What one (batch row, head) of a call walks: ``flash_plan``."""

    steps_visited: int   # grid steps taken: interior + edge
    steps_interior: int  # every position passes every positional condition
    steps_edge: int      # the diagonal or a ragged kv tail crosses the tile
    steps_above: int     # wholly above the diagonal: no step, no copy
    pairs: tuple         # ((q block, kv block, interior), ...) in q-major order

    def counts(self):
        """The four numbers, as the ``flash_plan`` event carries them."""
        return {n: getattr(self, n) for n in self._fields if n != "pairs"}


@functools.lru_cache(maxsize=64)
def flash_plan(seq_q, seq_kv, block_q, block_kv, causal):
    """The (q block, kv block) pairs a call needs, q block outermost and kv
    ascending inside it (the order of the forward's accumulation). Under
    ``causal`` a pair whose kv block begins after the q block's last row is
    *above* and is not in the plan at all. A pair is *interior* when the kv
    block ends at or before the q block's first row (or the call is not
    causal) and the kv block holds no ragged tail: no position of the tile
    needs a positional mask. Every other pair is an *edge*. Pure and static:
    the kernels' grids and step tables (``_step_tables``) are built from it,
    and the ``flash_plan`` telemetry event carries its four counts."""
    bq, bk = min(block_q, seq_q), min(block_kv, seq_kv)
    nq, nk = -(-seq_q // bq), -(-seq_kv // bk)
    pairs = []
    for iq in range(nq):
        for ik in range(nk):
            if causal and ik * bk > iq * bq + bq - 1:
                break  # this kv block and every later one lie above
            interior = (ik + 1) * bk <= seq_kv and (
                not causal or (ik + 1) * bk - 1 <= iq * bq
            )
            pairs.append((iq, ik, interior))
    interior = sum(p[2] for p in pairs)
    return FlashPlan(
        steps_visited=len(pairs), steps_interior=interior,
        steps_edge=len(pairs) - interior, steps_above=nq * nk - len(pairs),
        pairs=tuple(pairs),
    )


# what a grid step is, as its entry of a kernel's flag table says it
FIRST, LAST, INTERIOR, EDGE = 1, 2, 4, 8


def _step_tables(plan, seq_q, block_q, num_kv_blocks, *, kv_major):
    """``(q block, kv block, flags)`` int32 tables of a kernel's folded
    pair axis, one entry a grid step, handed to the index maps and the body
    as prefetched scalars; and the OR of all the flags (which bodies a
    kernel has to hold). ``flash_fwd`` / ``flash_dq`` walk the plan as it
    stands; ``flash_dkv`` (``kv_major``) walks it kv block outermost, q
    blocks ascending inside, and accumulates over q ROWS, so there a tile on
    a ragged q tail is an edge too. FIRST / LAST mark the ends of a row's
    run (init / finalize). A kv block no q block needs (causal, more keys
    than queries) still has its zeros written: one step that is both ends
    and runs neither body."""
    steps = [(iq, ik, INTERIOR if interior else EDGE)
             for iq, ik, interior in plan.pairs]
    if kv_major:
        q_tail = (seq_q - 1) // block_q if seq_q % block_q else -1
        rows = {ik: [] for ik in range(num_kv_blocks)}
        for iq, ik, kind in steps:
            rows[ik].append((iq, ik, EDGE if iq == q_tail else kind))
        steps = [s for ik, row in rows.items() for s in (row or [(0, ik, 0)])]
    row = 1 if kv_major else 0  # the block a run of steps accumulates for
    flags = [
        kind
        | (FIRST if i == 0 or steps[i - 1][row] != step[row] else 0)
        | (LAST if i + 1 == len(steps) or steps[i + 1][row] != step[row] else 0)
        for i, (*step, kind) in enumerate(steps)
    ]
    tables = tuple(
        np.asarray(col, np.int32)
        for col in ([s[0] for s in steps], [s[1] for s in steps], flags)
    )
    return tables, functools.reduce(operator.or_, flags)


def _index_maps(q_head, kv_head):
    """Index maps of a kernel's q-side blocks, kv-side blocks and their
    segment ids, over a grid (batch, head, step, *inner axes) followed by
    the three step tables. ``q_head`` / ``kv_head`` take the grid's head and
    inner axes to the operand's head (GQA lives here)."""
    def q_map(bi, hi, t, *rest):
        *inner, iq, _, _ = rest
        return bi, q_head(hi, *inner), iq[t], 0

    def kv_map(bi, hi, t, *rest):
        *inner, _, ik, _ = rest
        return bi, kv_head(hi, *inner), ik[t], 0

    def seg_q_map(bi, hi, t, *rest):
        return bi, 0, rest[-3][t]

    def seg_k_map(bi, hi, t, *rest):
        return bi, 0, rest[-2][t]

    return q_map, kv_map, seg_q_map, seg_k_map


def _run_step(flags, kinds, compute):
    """One grid step's work: ``compute(False)`` where the tables call the
    step interior, ``compute(True)`` where an edge crosses it. The two
    bodies differ in the positional mask alone; one the plan never asks for
    is not traced."""
    for kind, positional in ((INTERIOR, False), (EDGE, True)):
        if kinds & kind:
            pl.when((flags & kind) != 0)(
                functools.partial(compute, positional)
            )


_plans_told = set()


def _tell_plan(plan, **call):
    """The ``flash_plan`` telemetry event: the plan's four counts with the
    call's blocks and shape, once a traced shape and process — at trace
    time, so nothing of it is in a step. A shape traced before any sink
    listened is told when it is traced again."""
    key = tuple(call.items())
    if key in _plans_told:
        return
    from pyrecover_tpu import telemetry

    if telemetry.emit("flash_plan", **plan.counts(), **call) is not None:
        _plans_told.add(key)


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
                sq_ref, sk_ref, mask_q_bound, positional):
    """(block_q, block_kv) boolean mask of VALID score positions, or None
    when statically every position in the block is valid. Folds together
    the causal boundary, the ragged sequence tails (when block size does
    not divide the length), and packed-sequence segment equality. The
    q-bound term is only needed where out-of-range q rows would CONTRIBUTE
    to an accumulation (the dk/dv kernel) — elsewhere their garbage stays
    in rows whose stores Mosaic drops. ``positional`` False is an interior
    step's mask: the step tables vouch for every position's place, so no
    iota and no compare is built and the segment equality alone is left."""
    conds = []
    if positional and (
        causal or seq_kv % block_kv or (mask_q_bound and seq_q % block_q)
    ):
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


def _fwd_kernel(iq_tab, ik_tab, flag_tab, *args, scale, block_q, block_kv,
                causal, seq_q, seq_kv, has_segments, kinds):
    if has_segments:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = args
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = args
        sq_ref = sk_ref = None
    t = pl.program_id(2)  # the folded (q block, kv block) pair axis
    iq, ik, flags = iq_tab[t], ik_tab[t], flag_tab[t]

    @pl.when((flags & FIRST) != 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(positional):
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, d)
        if positional:
            # v feeds the p·v contraction over kv rows: zero its ragged tail
            v = _zero_oob_rows(v, ik * block_kv, seq_kv, block_kv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk)

        mask = _score_mask(
            iq, ik, block_q=block_q, block_kv=block_kv, causal=causal,
            seq_q=seq_q, seq_kv=seq_kv, sq_ref=sq_ref, sk_ref=sk_ref,
            mask_q_bound=False, positional=positional,
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

    _run_step(flags, kinds, _compute)

    @pl.when((flags & LAST) != 0)
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
    has_segments = seg is not None
    tables, kinds = _step_tables(
        flash_plan(s, sk, bq, bk, causal), s, bq, pl.cdiv(sk, bk),
        kv_major=False,
    )

    # (b, h, s, d) layout for clean 2D blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=bq, block_kv=bk,
        causal=causal, seq_q=s, seq_kv=sk,
        has_segments=has_segments, kinds=kinds,
    )
    q_map, kv_map, seg_q_map, seg_k_map = _index_maps(
        lambda hi: hi, lambda hi: hi // group)
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
    ]
    inputs = [qt, kt, vt]
    if has_segments:
        # (b, 1, s): Mosaic requires the last-two block dims to divide
        # (8, 128) or equal the array dims — a (1, bq) block over (b, s)
        # fails that on real TPU (the sublane dim 1 vs b); the dummy
        # middle axis makes the trailing block dims (1, bq) legal.
        seg3 = seg.reshape(b, 1, seg.shape[1])
        in_specs += [
            pl.BlockSpec((1, 1, bq), seg_q_map),
            pl.BlockSpec((1, 1, bk), seg_k_map),
        ]
        inputs += [seg3, seg3]
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(b, hq, len(tables[0])),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, bq, d), q_map),
                pl.BlockSpec((1, 1, bq, LSE_LANES), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, s, LSE_LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(*tables, *inputs)
    return out.transpose(0, 2, 1, 3), lse


# =========================== backward kernels ==============================


def _bwd_dq_kernel(iq_tab, ik_tab, flag_tab, *args, scale, block_q, block_kv,
                   causal, seq_q, seq_kv, has_segments, kinds):
    if has_segments:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, sq_ref, sk_ref,
         dq_ref, acc_scr, delta_scr) = args
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dq_ref, acc_scr, delta_scr) = args
        sq_ref = sk_ref = None
    t = pl.program_id(2)  # the folded (q block, kv block) pair axis
    iq, ik, flags = iq_tab[t], ik_tab[t], flag_tab[t]

    @pl.when((flags & FIRST) != 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        # delta_i = rowsum(do·out): same for every kv block of this q block
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        delta_scr[:] = jnp.broadcast_to(
            jnp.sum(do * o, axis=-1, keepdims=True), delta_scr.shape
        )

    def _compute(positional):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        if positional:
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
            mask_q_bound=False, positional=positional,
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

    _run_step(flags, kinds, _compute)

    @pl.when((flags & LAST) != 0)
    def _finalize():
        dq_ref[0, 0] = acc_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(iq_tab, ik_tab, flag_tab, *args, scale, block_q, block_kv,
                    causal, group, seq_q, seq_kv, has_segments, kinds):
    if has_segments:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, sq_ref, sk_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = args
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = args
        sq_ref = sk_ref = None
    # kv-major: the folded pair axis walks a kv block's q blocks, and the
    # innermost axis the members of the kv head's group inside each pair
    t = pl.program_id(2)
    member = pl.program_id(3)
    iq, ik, flags = iq_tab[t], ik_tab[t], flag_tab[t]

    @pl.when(((flags & FIRST) != 0) & (member == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(positional):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        if positional:
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
            mask_q_bound=True, positional=positional,
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

    _run_step(flags, kinds, _compute)

    @pl.when(((flags & LAST) != 0) & (member == group - 1))
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
    has_segments = seg is not None
    plan = flash_plan(s, sk, bq, bk, causal)

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    outt = out.transpose(0, 2, 1, 3)

    tables, kinds = _step_tables(plan, s, bq, pl.cdiv(sk, bk), kv_major=False)
    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, block_q=bq, block_kv=bk,
        causal=causal, seq_q=s, seq_kv=sk,
        has_segments=has_segments, kinds=kinds,
    )
    q_map, kv_map, seg_q_map, seg_k_map = _index_maps(
        lambda hi: hi, lambda hi: hi // group)
    dq_in_specs = [
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bq, LSE_LANES), q_map),
    ]
    dq_inputs = [qt, kt, vt, dot, outt, lse]
    if has_segments:
        # (b, 1, s) for Mosaic block-shape legality — see _fwd
        seg3 = seg.reshape(b, 1, seg.shape[1])
        dq_in_specs += [
            pl.BlockSpec((1, 1, bq), seg_q_map),
            pl.BlockSpec((1, 1, bk), seg_k_map),
        ]
        dq_inputs += [seg3, seg3]
    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(b, hq, len(tables[0])),
            in_specs=dq_in_specs,
            out_specs=pl.BlockSpec((1, 1, bq, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        interpret=_interpret(),
        name="flash_dq",
    )(*tables, *dq_inputs)

    # dk/dv: per kv block the grid sweeps (needed q block × GQA group
    # member) so the whole group's contribution accumulates in VMEM scratch
    # and each output block is written once, directly at kv-head
    # granularity — no (b, q_heads, s, d) f32 intermediates (2×2.1G at the
    # 1B bench point)
    tables, kinds = _step_tables(plan, s, bq, pl.cdiv(sk, bk), kv_major=True)
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, block_q=bq, block_kv=bk,
        causal=causal, group=group, seq_q=s, seq_kv=sk,
        has_segments=has_segments, kinds=kinds,
    )
    # the grid's head is the kv head, its innermost axis the group member
    q_map, kv_map, seg_q_map, seg_k_map = _index_maps(
        lambda hi, m: hi * group + m, lambda hi, m: hi)
    dkv_in_specs = [
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bq, LSE_LANES), q_map),
    ]
    dkv_inputs = [qt, kt, vt, dot, outt, lse]
    if has_segments:
        dkv_in_specs += [
            pl.BlockSpec((1, 1, bq), seg_q_map),
            pl.BlockSpec((1, 1, bk), seg_k_map),
        ]
        dkv_inputs += [seg3, seg3]
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(b, hkv, len(tables[0]), group),
            in_specs=dkv_in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, bk, d), kv_map),
                pl.BlockSpec((1, 1, bk, d), kv_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, sk, d), v.dtype),
        ],
        interpret=_interpret(),
        name="flash_dkv",
    )(*tables, *dkv_inputs)

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
    _tell_plan(
        flash_plan(s, sk, bq, bk, causal), seq_q=s, seq_kv=sk, block_q=bq,
        block_kv=bk, causal=bool(causal), batch=b, heads=hq, kv_heads=hkv,
        head_dim=d, segments=segment_ids is not None,
    )

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
