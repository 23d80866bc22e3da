"""Mixture-of-Experts SwiGLU FFN with expert parallelism.

The reference is dense-only (SURVEY §2.2: "Expert parallel (EP/MoE): No —
dense SwiGLU only, model.py:233-269"). This is the TPU-native MoE
construction — rank-and-scatter dispatch over static shapes:

  * Each (token, top-k slot) pick's capacity-queue position is an
    exclusive cumsum over a small (B, S·K, E) one-hot in (s, k) flat
    order — first-come-first-served, no sorting networks. Dispatch is one
    row scatter-add and combine one row gather — O(S·K·D) data movement.
    The masked-einsum formulation (Switch-style one-hot (B,S,K,E,C) slot
    tensors) costs O(S·E·C·D) with C ∝ S — quadratic in sequence length
    in time AND memory; the rank form leaves the MXU only the real
    expert FLOPs.
  * All shapes are static (ranks, fixed capacity C): XLA sees a fixed
    program regardless of routing; dropped tokens keep a clamped slot but
    a zeroed payload/gate, so they contribute exactly nothing.
  * Expert-stacked weights ``(E, D, F)`` are sharded on their expert axis
    over the ``expert`` mesh axis; annotating the ``(B, E, C, D)`` expert
    inputs with the same axis turns the dispatch/combine transfers into
    all-to-alls over ICI, inserted by the compiler.
  * Each batch row is a routing group: capacity and the load-balance aux
    loss are computed per row, which keeps every statistic local under
    data sharding AND under pipeline microbatching (a microbatch is a
    subset of rows, so per-row aux values are identical either way).

Top-k routing renormalizes the selected gate probabilities (Mixtral-style);
the aux loss is the Switch load-balance loss ``E · Σ_e f_e·p_e`` per row.

Four dispatch backends share these semantics (pinned equal by tests):

  * ``_moe_ffn_grouped`` — the MXU path: ALL (token, slot) picks are
    flattened into one pool, sorted by expert, and the expert FFNs run as
    ragged grouped matmuls (``jax.lax.ragged_dot``, whose 2-D lhs is the
    one form TPU's native ragged-dot lowering accepts) over contiguous
    expert groups. No capacity-padded slot tensor, no scatter
    serialization — the MXU sees one dense GEMM per expert sized by its
    actual load. Default when batch and expert axes are both unsharded
    (the flat sort is batch-global, so a sharded batch would gather).
  * ``_moe_ffn_grouped_ep`` — the MXU path composed with sharding: an
    explicitly-SPMD shard_map where each shard flat-sorts its LOCAL batch
    rows, ragged-GEMMs only its local experts' picks (static bound
    B_loc·E_loc·C rows) and one psum over (expert, tensor) plays both the
    combine exchange and the row-parallel reduction. Selected for
    ``moe_dispatch='grouped'`` whenever the batch or expert axis is
    sharded (ep ≥ 1), and by ``auto`` for sharded-batch ep == 1 meshes.
  * ``_moe_ffn_impl`` (rank-and-scatter) — the default EP path: static
    (B,E,C,D) dispatch whose ``expert``-axis constrain turns into
    all-to-alls.
  * ``_moe_ffn_einsum`` (masked one-hot einsums) — inside manual regions
    (pipeline stages), where the partitioner cannot handle batch-sharded
    index ops; and small-shape EP, where 0/1 dispatch einsums beat
    scatters.

``moe_ffn`` picks automatically.
"""

import math

import jax
import jax.numpy as jnp

from pyrecover_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_SEQ,
    AXIS_TENSOR,
    constrain,
)
from pyrecover_tpu.telemetry.stepscopes import MOE_FFN


_warned_grouped_sp = False  # once-per-process guard for the sp>1 warning


def moe_capacity(seq_len, n_experts, top_k, capacity_factor):
    """Per-row expert capacity: ceil(S·k·cf / E), min 1. Static."""
    return max(1, int(math.ceil(seq_len * top_k * capacity_factor / n_experts)))


def _route(h, router_w, E, K, C):
    """THE routing definition every dispatch backend shares — f32 softmax,
    Mixtral-renormalized top-k gates, first-come-first-served capacity in
    (s, k) flat pick order. One definition makes the backends' pinned
    equality structural instead of five hand-synchronized copies.

    Returns ``(probs, eids, gvals, onehot, rank, valid)``:
      probs (B,S,E) f32; eids/gvals/rank/valid (B,N) with N = S·K in
      (s, k) flat order; onehot (B,N,E) int32.
    """
    B, S, _ = h.shape
    N = S * K
    f32 = jnp.float32
    logits = jnp.einsum("bsd,de->bse", h.astype(f32), router_w.astype(f32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)  # (B,S,K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    eids = gate_idx.reshape(B, N)
    gvals = gate_vals.reshape(B, N)
    onehot = (
        eids[:, :, None] == jnp.arange(E, dtype=eids.dtype)[None, None, :]
    ).astype(jnp.int32)
    # queue position within the pick's expert: exclusive cumsum over the
    # small (B,N,E) one-hot — FCFS, no sort, no C-sized slot tensor
    prio = jnp.cumsum(onehot, axis=1) - onehot
    rank = jnp.sum(prio * onehot, axis=-1)
    valid = rank < C
    return probs, eids, gvals, onehot, rank, valid


def _switch_aux(probs, onehot, E, N):
    """Switch load-balance aux loss per row: E · Σ_e f_e·p_e, where f_e is
    the pre-capacity fraction of picks routed to e and p_e the mean router
    probability. Minimized (=1) by a uniform router."""
    f_e = jnp.sum(onehot, axis=1).astype(jnp.float32) / N  # (B,E)
    p_e = probs.mean(axis=1)  # (B,E)
    return E * jnp.sum(f_e * p_e, axis=-1)  # (B,) f32


def moe_ffn(h, router_w, w1, w3, w2, config):
    """MoE SwiGLU: route each token to its top-k experts, run the expert
    FFNs at fixed capacity, combine weighted outputs.

    Picks a dispatch backend per context (see module docstring): the
    masked-einsum form inside manual regions — XLA's SPMD partitioner
    CHECK-fails (spmd_partitioner_util.cc device-group computation) on
    gathers whose indices derive from batch-sharded operands there, and
    einsums are the one form every partitioner handles; grouped ragged
    GEMMs when the expert axis is unsharded (the MXU path); otherwise
    einsum-vs-scatter by the estimated per-device slot-tensor size, whose
    (B,E,C,D) constrain turns dispatch into all-to-alls over the
    ``expert`` axis.

    Args:
      h: (B, S, D) activations (compute dtype).
      router_w: (D, E) router weights.
      w1, w3: (E, D, F) expert gate/up projections; w2: (E, F, D) down.
      config: ModelConfig with n_experts / moe_top_k / moe_capacity_factor.

    Returns:
      (y, aux): y (B, S, D) same dtype as h; aux (B,) f32 per-row
      load-balance loss (caller scales by ``moe_aux_weight``).
    """
    with jax.named_scope(MOE_FFN):
        return _moe_ffn_dispatch(h, router_w, w1, w3, w2, config)


def _moe_ffn_dispatch(h, router_w, w1, w3, w2, config):
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is not None and not mesh.empty:
        from pyrecover_tpu.parallel.mesh import nonmanual_axes

        if len(nonmanual_axes(mesh)) != len(mesh.axis_names):
            # Inside a manual region (the pipeline stage shard_map): XLA's
            # SPMD partitioner CHECK-fails on gathers whose indices derive
            # from batch-sharded operands under partial-manual meshes, and
            # Shardy rejects the nested-shard_map alternative (manual axes
            # must precede free axes in dim shardings — violated by the AD
            # residuals of stage-sharded layers). Use the masked-einsum
            # dispatch there: expressible entirely as einsums, compiles
            # everywhere, numerically pinned to the scatter path by tests.
            return _moe_ffn_einsum(h, router_w, w1, w3, w2, config)
    ep = batch_shards = sp = 1
    if mesh is not None and not mesh.empty:
        ep = mesh.shape.get(AXIS_EXPERT, 1)
        batch_shards = mesh.shape.get(AXIS_DATA, 1) * mesh.shape.get(
            AXIS_FSDP, 1
        )
        sp = mesh.shape.get(AXIS_SEQ, 1)
    choice = config.moe_dispatch
    if choice == "auto" and ep == 1:
        # Grouped ragged GEMMs whenever the expert axis is unsharded: the
        # expert FFNs run as dense per-expert matmuls on the MXU. The flat
        # sort is batch-global, so on a sharded batch the shard-local
        # manual form is used instead (same math, sort/gather stay on-
        # shard; ep=1 degenerates its expert split away) — EXCEPT under
        # sequence sharding, which that form cannot express (it would
        # un-shard the activations): there the scatter/einsum choice below
        # keeps sp intact. With ep > 1 the auto pick also stays with
        # scatter/einsum until grouped-EP is measured on real multichip.
        if batch_shards == 1 and sp == 1:
            return _moe_ffn_grouped(h, router_w, w1, w3, w2, config)
        if sp == 1:
            return _moe_ffn_grouped_ep(h, router_w, w1, w3, w2, config, mesh)
        # sp > 1 falls through: both grouped forms would gather the
        # seq-sharded activations their flat sort flattens over
    if choice == "grouped":
        if ep > 1 or (batch_shards > 1 and sp == 1):
            return _moe_ffn_grouped_ep(h, router_w, w1, w3, w2, config, mesh)
        # fully-local mesh — or sp > 1 with ep == 1, where the manual form
        # is inexpressible and the batch-global sort's gathers are the
        # price of an explicit 'grouped' request under sequence sharding.
        # Loud (the repo's fallback convention, cf. ring attention), but
        # once per process — moe_ffn traces once per layer per retrace,
        # and 32 identical lines bury the signal.
        global _warned_grouped_sp
        if sp > 1 and not _warned_grouped_sp:
            _warned_grouped_sp = True
            import logging

            from pyrecover_tpu.utils.logging import log_host0

            log_host0(
                "moe_dispatch='grouped' with a sharded sequence axis "
                "(sp=%d): the batch-global sort re-gathers the "
                "seq-sharded activations every MoE layer; "
                "'scatter'/'einsum' keep sp intact",
                sp, level=logging.WARNING,
            )
        return _moe_ffn_grouped(h, router_w, w1, w3, w2, config)
    if choice == "auto":
        # Measured on v5e (8x150m, S=1024, fwd+bwd per MoE layer): einsum
        # 5.3 ms vs scatter 7.5 ms — 0/1 dispatch einsums ride the MXU at
        # near-peak while TPU scatters serialize on the vector units. But
        # the einsum form's (B,S,K,E,C) slot tensor and O(S·E·C·D) dispatch
        # FLOPs are quadratic in S (C ∝ S), so past a size threshold the
        # O(S·K·D) scatter wins. Crossover set where the slot tensor
        # reaches ~64M elements (≈256 MB f32).
        B, S = h.shape[0], h.shape[1]
        C = moe_capacity(
            S, config.n_experts, config.moe_top_k, config.moe_capacity_factor
        )
        slot_elems = B * S * config.moe_top_k * config.n_experts * C
        # the slot tensor is batch-sharded over data×fsdp: compare the
        # PER-DEVICE size to the threshold, or large meshes flip to the
        # slower-at-that-scale scatter path long before ~256 MB/device
        if mesh is not None and not mesh.empty:
            slot_elems //= max(
                mesh.shape.get(AXIS_DATA, 1) * mesh.shape.get(AXIS_FSDP, 1), 1
            )
        choice = "einsum" if slot_elems <= 64 * 1024 * 1024 else "scatter"
    if choice == "einsum":
        return _moe_ffn_einsum(h, router_w, w1, w3, w2, config)
    return _moe_ffn_impl(h, router_w, w1, w3, w2, config)


def _moe_ffn_impl(h, router_w, w1, w3, w2, config):
    """Rank-and-scatter dispatch backend (see module docstring)."""
    cfg = config
    B, S, D = h.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    C = moe_capacity(S, E, K, cfg.moe_capacity_factor)
    N = S * K

    probs, eids, gvals, onehot, rank, valid = _route(h, router_w, E, K, C)
    # overflow entries: clamp to a real slot but zero their payload — a
    # scatter-ADD of zeros is a no-op, and in-capacity slots are unique so
    # add ≡ set. (Out-of-range "drop"/"fill" modes CHECK-fail in XLA's SPMD
    # partitioner under a partial-manual mesh.)
    slot = jnp.clip(eids * C + rank, 0, E * C - 1)  # (B,N)

    # --- dispatch: one row scatter-add, O(S·K·D); the K copies of each
    # token are a contiguous repeat, not a gather ---
    cdt = h.dtype
    brange = jnp.arange(B)[:, None]
    rows = jnp.repeat(h, K, axis=1)  # (B,N,D): entry n ← token n // K
    rows = rows * valid[..., None].astype(cdt)
    xin = (
        jnp.zeros((B, E * C, D), cdt)
        .at[brange, slot]
        .add(rows)
        .reshape(B, E, C, D)
    )
    xin = constrain(xin, (AXIS_DATA, AXIS_FSDP), AXIS_EXPERT, None, None)

    # --- expert compute at fixed capacity (the real MoE FLOPs) ---
    gate = jax.nn.silu(jnp.einsum("becd,edf->becf", xin, w1.astype(cdt)))
    up = jnp.einsum("becd,edf->becf", xin, w3.astype(cdt))
    out = jnp.einsum("becf,efd->becd", gate * up, w2.astype(cdt))
    out = constrain(out, (AXIS_DATA, AXIS_FSDP), AXIS_EXPERT, None, None)
    out_flat = out.reshape(B, E * C, D)

    # --- combine: gather each pick's slot result, weight by its gate
    # (dropped entries read a clamped slot but their gate weight is 0) ---
    gathered = out_flat[brange, slot]  # (B,N,D)
    w = jnp.where(valid, gvals, 0.0).astype(cdt)
    y = jnp.sum((gathered * w[..., None]).reshape(B, S, K, D), axis=2)

    return y.astype(h.dtype), _switch_aux(probs, onehot, E, N)


def _flat_pick_sort(h2d, ids_flat, keep_flat, M_cap, N, S, K, cdt):
    """Shared dispatch front half of both grouped backends: stably sort the
    flattened (rows·N,) pick pool by group id, gather each pick's token row
    from the flattened (rows·S, D) activations (flat pick m = (row m // N,
    slot m % N) → token row (m // N)·S + (m % N)//K), truncate to the
    static bound ``M_cap``, and zero picks whose keep flag is off. One
    definition keeps the grouped backends' pinned equality structural
    (the same principle as ``_route``). Returns ``(x, order)`` with ``x``
    (M_cap, D) in group-sorted order and ``order`` the full (rows·N,)
    permutation (``_flat_pick_combine`` inverts it)."""
    order = jnp.argsort(ids_flat, stable=True)
    order_c = order[:M_cap]
    tok = (order_c // N) * S + (order_c % N) // K
    x = jnp.take(h2d, tok, axis=0)
    keep = jnp.take(keep_flat, order_c)
    return x * keep[:, None].astype(cdt), order


def _flat_pick_combine(out, order, wgt, rows, S, K, cdt):
    """Shared combine back half: pad the (M_cap, D) group-sorted expert
    outputs back to the full pool length (truncated picks land in the zero
    padding), invert the sort permutation, weight each pick by its gate
    (zeroed for dropped/non-local picks), and sum the K picks per token."""
    D = out.shape[-1]
    Ml = order.shape[0]
    if out.shape[0] < Ml:
        out = jnp.pad(out, ((0, Ml - out.shape[0]), (0, 0)))
    y_picks = jnp.take(out, jnp.argsort(order), axis=0)  # flat pick order
    return jnp.sum(
        y_picks.reshape(rows, S, K, D) * wgt.reshape(rows, S, K, 1), axis=2
    )


def _moe_ffn_grouped(h, router_w, w1, w3, w2, config):
    """Grouped-GEMM dispatch: expert-sorted tokens through ragged matmuls.

    ALL B·S·K (token, slot) picks are flattened into one pool and stably
    argsorted by expert id, giving contiguous per-expert runs whose
    lengths (the batch-global pre-capacity routing histogram) are the
    ragged ``group_sizes``. The three expert projections then run as
    ``jax.lax.ragged_dot`` calls — one dense MXU GEMM per expert, sized by
    that expert's actual load, with no (B,E,C,D) capacity padding and no
    serializing scatters. The lhs is 2-D ``(B·N, D)`` BY REQUIREMENT, not
    style: TPU's native ragged-dot lowering (RaggedConvSpec) accepts
    exactly one lhs non-contracting dimension — the rank-3 per-row form
    with (B,E) group sizes runs on the CPU backend but fails TPU
    compilation ("number of lhs non-contracting dimensions should be 1,
    got 2"; first seen on-chip in the round-5 bench campaign). Flattening
    also feeds the MXU B×-larger per-expert GEMMs. Dropped picks (rank ≥
    C, still per-row FCFS capacity — routing semantics are unchanged) keep
    their sorted position but are zeroed: a zero row through SwiGLU is
    exactly zero (silu(0)·0 = 0), and their gate weight is zeroed in the
    combine, so semantics stay identical to the other backends
    (equality-pinned by tests). The batch-global sort mixes rows, so under
    a data/fsdp-sharded batch GSPMD inserts gathers across the batch
    shards — the auto pick therefore prefers this path on unsharded-batch
    meshes and per-device-batch regimes; expert-sharded meshes use
    ``_moe_ffn_grouped_ep``, whose sort is shard-local by construction.
    """
    cfg = config
    B, S, D = h.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    C = moe_capacity(S, E, K, cfg.moe_capacity_factor)
    N = S * K
    M = B * N

    probs, eids, gvals, onehot, rank, valid = _route(h, router_w, E, K, C)

    # --- expert-sort the flattened pick pool; group sizes = batch-global
    # routing histogram (pre-capacity: overflow picks stay in their group
    # as zero rows, so the sizes sum to M exactly) ---
    cdt = h.dtype
    x, order = _flat_pick_sort(
        h.reshape(B * S, D), eids.reshape(M), valid.reshape(M), M, N, S, K, cdt
    )  # (M, D) in expert-sorted order
    group_sizes = jnp.sum(onehot, axis=(0, 1)).astype(jnp.int32)  # (E,)

    gate = jax.nn.silu(jax.lax.ragged_dot(x, w1.astype(cdt), group_sizes))
    up = jax.lax.ragged_dot(x, w3.astype(cdt), group_sizes)
    out = jax.lax.ragged_dot(
        gate * up, w2.astype(cdt), group_sizes
    )  # (M, D), still in expert-sorted order

    # --- unsort and combine with renormalized gates ---
    w = jnp.where(valid, gvals, 0.0).astype(cdt)
    y = _flat_pick_combine(out, order, w, B, S, K, cdt)

    return y.astype(h.dtype), _switch_aux(probs, onehot, E, N)


def _moe_ffn_grouped_ep(h, router_w, w1, w3, w2, config, mesh):
    """Grouped ragged-GEMM dispatch under an expert-sharded mesh (ep > 1):
    the MXU MoE path composed with expert parallelism.

    Written as an explicitly-SPMD ``jax.shard_map`` manual over EVERY mesh
    axis — the partial-manual partitioner CHECK-fails on gathers whose
    indices derive from sharded operands (see ``moe_ffn``), so nothing is
    left to it. The EP data flow exploits that activations are replicated
    along the expert axis (batch shards over data×fsdp only): instead of a
    materialized all-to-all exchange, every expert shard routes its OWN
    batch rows, keeps only the picks owned by its local experts, runs the
    ragged GEMMs over those, and one all-reduce over (expert, tensor) sums
    the disjoint per-shard partial outputs — each valid pick contributes on
    exactly one expert shard. The exchange all-to-all and the combine
    reduction collapse into that single psum; compute per shard is bounded
    by the static slice M_cap = B_loc·E_loc·C rows (the capacity bound), so EP
    divides the expert FLOPs by ep exactly like the scatter path's
    (B,E,C,D) form, with dense contiguous GEMMs instead of scatters.

    Routing math (full-E softmax/top-k/FCFS capacity on whole rows) is
    bit-identical to every other backend — rows are never split, so
    capacity and the aux loss are exact, and the backends stay
    equality-pinned. fsdp-sharded weight dims are all-gathered on entry
    (ZeRO-3; transposes to reduce-scatter under AD).

    Constraints (ValueError otherwise): n_experts % ep == 0, and the
    sequence axis must be unsharded — this path would silently un-shard a
    sequence-parallel activation at the shard_map boundary; use
    scatter/einsum dispatch with sp > 1.
    """
    cfg = config
    B, S, D = h.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    ep = mesh.shape.get(AXIS_EXPERT, 1)
    if E % ep != 0:
        raise ValueError(
            f"moe_dispatch='grouped' with ep={ep} needs n_experts % ep == 0 "
            f"(got E={E})"
        )
    if mesh.shape.get(AXIS_SEQ, 1) > 1:
        raise ValueError(
            "moe_dispatch='grouped' with ep > 1 does not compose with a "
            "sharded sequence axis (it would un-shard the activations); "
            "use moe_dispatch='scatter' or 'einsum' under sp > 1."
        )
    E_loc = E // ep
    C = moe_capacity(S, E, K, cfg.moe_capacity_factor)
    N = S * K
    from jax.sharding import PartitionSpec as P

    def _vary(x, names):
        # pcast one axis at a time; only over axes the value is still
        # invariant on (pcast rejects already-varying axes)
        for n in names:
            x = jax.lax.pcast(x, (n,), to="varying")
        return x

    def local_fn(h_loc, rw, w1_loc, w3_loc, w2_loc):
        f32 = jnp.float32
        cdt = h_loc.dtype
        Bl = h_loc.shape[0]
        # AD-CORRECTNESS, not style: every value the y path differentiates
        # is pcast to varying over the axes its in_spec leaves it invariant
        # on. Leaving them invariant MISCOMPILES the backward pass — the
        # vma system drops/misplaces the invariant→varying transition's
        # hidden psum once the sorted keep-mask multiply appears between
        # the two index-gathers (measured: dh off by ~30% vs finite
        # differences, same wrong value for ragged and dense-einsum expert
        # compute; pcast-at-entry restores AD == FD). Same hazard family
        # as the pipeline's stage-divergent lax.cond rule
        # (parallel/pipeline.py).
        h_v = _vary(h_loc, (AXIS_EXPERT, AXIS_TENSOR))
        rw_v = _vary(rw, (AXIS_EXPERT, AXIS_TENSOR, AXIS_DATA, AXIS_FSDP))
        # ZeRO-3: gather the fsdp-sharded weight dims for compute
        w1g = jax.lax.all_gather(
            _vary(w1_loc, (AXIS_DATA,)), AXIS_FSDP, axis=1, tiled=True
        )
        w3g = jax.lax.all_gather(
            _vary(w3_loc, (AXIS_DATA,)), AXIS_FSDP, axis=1, tiled=True
        )
        w2g = jax.lax.all_gather(
            _vary(w2_loc, (AXIS_DATA,)), AXIS_FSDP, axis=2, tiled=True
        )

        # --- routing: the shared definition, on the VARYING values ---
        _, eids, gvals, _, _, valid = _route(h_v, rw_v, E, K, C)

        # --- picks owned by THIS expert shard; sentinel E_loc sorts
        # non-local and capacity-dropped picks to the tail. The pick pool
        # is flattened across the local batch before sorting: TPU's
        # ragged-dot lowering requires a 2-D lhs (exactly one
        # non-contracting dim — the rank-3 per-row form is CPU-only; see
        # _moe_ffn_grouped), and the flat sort is still shard-local ---
        Ml = Bl * N
        M_cap = min(Ml, Bl * E_loc * C)  # ≤ C valid picks per (row, expert)
        e0 = jax.lax.axis_index(AXIS_EXPERT) * E_loc
        local = valid & (eids >= e0) & (eids < e0 + E_loc)
        lids_f = jnp.where(local, eids - e0, E_loc).reshape(Ml)
        x, order = _flat_pick_sort(
            h_v.reshape(Bl * S, D), lids_f, local.reshape(Ml),
            M_cap, N, S, K, cdt,
        )  # (M_cap, D) in local-expert-sorted order
        sizes = jnp.sum(
            (lids_f[:, None] == jnp.arange(E_loc, dtype=lids_f.dtype)).astype(
                jnp.int32
            ),
            axis=0,
        )  # (E_loc,): shard-global valid pick counts, each ≤ Bl·C

        gate = jax.nn.silu(jax.lax.ragged_dot(x, w1g.astype(cdt), sizes))
        up = jax.lax.ragged_dot(x, w3g.astype(cdt), sizes)
        out = jax.lax.ragged_dot(
            gate * up, w2g.astype(cdt), sizes
        )  # (M_cap, D) in local-expert-sorted order
        # rows past the group total belong to NO group — their content is
        # unspecified; zero them before the combine gather
        row_ok = jnp.arange(M_cap) < jnp.sum(sizes)
        out = out * row_ok[:, None].astype(cdt)

        # --- combine (non-local picks land in the zero padding / tail) ---
        wgt = jnp.where(local, gvals, 0.0).astype(cdt)
        y_part = _flat_pick_combine(out, order, wgt, Bl, S, K, cdt)
        # ONE all-reduce: sums the disjoint expert-shard contributions AND
        # the row-parallel w2 partials over tensor. f32: sub-f32
        # all-reduces CHECK-fail on the CPU backend (tests/virtual mesh).
        y = jax.lax.psum(
            y_part.astype(f32), (AXIS_EXPERT, AXIS_TENSOR)
        ).astype(h_loc.dtype)

        # aux from a SEPARATE routing graph on the un-pcast (invariant)
        # values: numerically identical, but its cotangent flows once —
        # through the varying graph it would arrive pre-psum'd over
        # (expert, tensor), i.e. scaled by ep·tp — and the invariant aux
        # satisfies its out_spec without a reduction.
        probs_i, _, _, onehot_i, _, _ = _route(h_loc, rw, E, K, C)
        aux = _switch_aux(probs_i, onehot_i, E, N)
        return y, aux

    batch = (AXIS_DATA, AXIS_FSDP)
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(batch, None, None),
            P(None, None),
            P(AXIS_EXPERT, AXIS_FSDP, AXIS_TENSOR),
            P(AXIS_EXPERT, AXIS_FSDP, AXIS_TENSOR),
            P(AXIS_EXPERT, AXIS_TENSOR, AXIS_FSDP),
        ),
        out_specs=(P(batch, None, None), P(batch)),
        axis_names=set(mesh.axis_names),
    )(h, router_w, w1, w3, w2)


def _moe_ffn_einsum(h, router_w, w1, w3, w2, config):
    """Masked-einsum (Switch-style one-hot) dispatch: O(S·E·C) memory and
    mostly-zero MXU work, but expressible entirely as einsums — the form
    every partitioner handles. Used only inside manual regions (see
    ``moe_ffn``); semantics are identical to ``_moe_ffn_impl`` (same
    first-come-first-served capacity in (s, k) flat order, renormalized
    gates, zero contribution for dropped tokens)."""
    cfg = config
    B, S, D = h.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    C = moe_capacity(S, E, K, cfg.moe_capacity_factor)
    N = S * K

    probs, _, gvals, onehot, rank, valid = _route(h, router_w, E, K, C)

    # Build the (B,S,K,E,C) slot one-hot directly in the compute dtype:
    # every (e, c) slot has exactly one contributor, so the K-sums below
    # have no accumulation — bf16 here is exact 0/1 and halves the VPU
    # traffic on the largest tensors of the dispatch. Only the SELECTED
    # expert's queue position matters (keep masks the rest), so the slot
    # one-hot comes straight from the shared rank.
    cdt = h.dtype
    keep = (
        onehot.reshape(B, S, K, E).astype(cdt)
        * valid.reshape(B, S, K, 1).astype(cdt)
    )  # drop overflow tokens
    slot = keep[..., None] * jax.nn.one_hot(
        rank.reshape(B, S, K), C, dtype=cdt
    )[..., None, :]  # (B,S,K,E,C)
    dispatch = slot.sum(axis=2)  # (B,S,E,C) ∈ {0,1}
    combine = (slot * gvals.reshape(B, S, K).astype(cdt)[..., None, None]).sum(
        axis=2
    )

    xin = jnp.einsum("bsec,bsd->becd", dispatch, h)
    xin = constrain(xin, (AXIS_DATA, AXIS_FSDP), AXIS_EXPERT, None, None)
    gate = jax.nn.silu(jnp.einsum("becd,edf->becf", xin, w1.astype(cdt)))
    up = jnp.einsum("becd,edf->becf", xin, w3.astype(cdt))
    out = jnp.einsum("becf,efd->becd", gate * up, w2.astype(cdt))
    out = constrain(out, (AXIS_DATA, AXIS_FSDP), AXIS_EXPERT, None, None)
    y = jnp.einsum("bsec,becd->bsd", combine, out)

    return y.astype(h.dtype), _switch_aux(probs, onehot, E, N)
