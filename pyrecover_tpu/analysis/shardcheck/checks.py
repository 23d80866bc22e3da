"""shardcheck rule implementations: spec consistency + per-device memory.

Everything here is pure metadata math — inputs are ``(path, shape,
dtype)`` triples (from ``jax.eval_shape`` upstream), PartitionSpecs, and
a resolved mesh shape dict. No arrays are ever materialized, so checking
the 8B flagship costs the same as checking a test config.
"""

import dataclasses

import numpy as np

from pyrecover_tpu.analysis.engine import Finding
from pyrecover_tpu.parallel.mesh import AXIS_FSDP, AXIS_TENSOR

# check id -> (kebab-case name, severity, one-line summary). Mirrors the
# jaxlint rule catalog; ids share the report/suppression machinery but
# live in their own SCxx namespace.
CHECKS = {
    "SC01": ("axis-indivisible", "error",
             "a sharded dimension is not divisible by its mesh-axis product"),
    "SC02": ("unknown-mesh-axis", "error",
             "a PartitionSpec names an axis absent from the resolved mesh"),
    "SC03": ("mesh-axis-double-use", "error",
             "the same mesh axis appears in two entries of one spec"),
    "SC04": ("oversized-replicated-leaf", "warning",
             "a leaf above the size threshold is fully replicated although "
             "a parameter-sharding axis (fsdp/tensor) is >1"),
    "SC05": ("hbm-over-budget", "error",
             "the per-device memory estimate exceeds the device HBM budget"),
    "SC06": ("full-param-gather", "warning",
             "the traced step all-gathers a full parameter-sized tensor"),
    "SC07": ("manifest-leaf-mismatch", "error",
             "checkpoint and model manifests disagree on the leaf set"),
    "SC08": ("manifest-shape-drift", "error",
             "a leaf changed shape between checkpoint and model"),
    "SC09": ("manifest-dtype-drift", "error",
             "a leaf changed dtype between checkpoint and model"),
    "SC10": ("manifest-pspec-drift", "warning",
             "a leaf changed partition spec between checkpoint and model "
             "(restore reshards, but the layout intent drifted)"),
    "SC11": ("reshard-infeasible", "error",
             "an elastic-resume reshard plan cannot be expressed on the "
             "target mesh (indivisible leaf dim, unresolvable mesh, or a "
             "data pipeline that cannot rescale to the new replica count)"),
    "SC12": ("full-precision-collective", "error",
             "the bandwidth-lean update path is configured (zero1 / "
             "quantized gradient collectives) but the traced step or the "
             "resolved specs still move/hold full-precision replicated "
             "state — the configuration is not actually wired in"),
    "SC13": ("overlap-not-survived", "error",
             "gradient bucketing is configured (--grad-bucket-mb) but the "
             "traced step issues fewer data-axis gradient collectives "
             "than the resolved bucket layout — the sync collapsed back "
             "into a single tail-of-backward blob (or serialized behind "
             "the full gradient materialization), so nothing overlaps"),
}


@dataclasses.dataclass(frozen=True)
class ShardcheckConfig:
    """Knobs the CLI exposes; defaults are the CI-gate settings."""

    # check selection (ids or names); None selects everything
    select: frozenset = None
    ignore: frozenset = frozenset()
    # SC04: leaves at or above this many bytes must not be fully
    # replicated when fsdp/tensor shard params (64 MiB ~= the point where
    # a replicated table starts to matter against 16G HBM)
    replicated_threshold_bytes: int = 64 * 2**20
    # SC05: flag when the estimate exceeds this fraction of capacity
    # (leave headroom for XLA scratch/fragmentation)
    hbm_budget_fraction: float = 0.9
    # device kind for the HBM budget ("v5e", "v5p", ...); None = report
    # the table without judging it (the CPU-only CI mode)
    device_kind: str = None

    def check_enabled(self, check_id):
        name = CHECKS[check_id][0]
        if check_id in self.ignore or name in self.ignore:
            return False
        if self.select is None:
            return True
        return check_id in self.select or name in self.select


DEFAULT_CONFIG = ShardcheckConfig()


def make_finding(check_id, locus, message):
    name, severity, _ = CHECKS[check_id]
    return Finding(
        rule=name, rule_id=check_id, severity=severity, path=locus,
        line=0, col=0, message=message,
    )


def _spec_entries(spec):
    """Spec entries normalized to tuples of axis names (None -> ())."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(())
        elif isinstance(entry, (tuple, list)):
            out.append(tuple(entry))
        else:
            out.append((entry,))
    return out


def spec_shard_factor(spec, mesh_shape):
    """Number of shards the spec splits a leaf into on this mesh
    (unknown axes count as 1 — SC02 reports them separately)."""
    factor = 1
    for axes in _spec_entries(spec):
        for a in axes:
            factor *= mesh_shape.get(a, 1)
    return factor


def leaf_nbytes(shape, dtype):
    count = int(np.prod(shape, dtype=np.int64)) if len(shape) else 1
    return count * np.dtype(dtype).itemsize


def spec_findings(leaves, specs, mesh_shape, config=None, locus="config"):
    """Check 1 — spec consistency over abstract leaves.

    ``leaves``: list of ``(path_str, shape, dtype)``; ``specs``: aligned
    list of PartitionSpecs; ``mesh_shape``: dict axis name -> size (the
    resolved virtual mesh). Returns a list of Findings.
    """
    config = config or DEFAULT_CONFIG
    out = []
    shard_axes_live = any(
        mesh_shape.get(a, 1) > 1 for a in (AXIS_FSDP, AXIS_TENSOR)
    )
    for (path, shape, dtype), spec in zip(leaves, specs):
        entries = _spec_entries(spec)
        if len(entries) != len(shape):
            # param_pspecs raises on rank mismatch before we get here;
            # guard anyway for hand-built specs
            out.append(make_finding(
                "SC01", locus,
                f"{path}: spec {spec} has {len(entries)} entries for rank-"
                f"{len(shape)} leaf {tuple(shape)}",
            ))
            continue
        seen = {}
        for dim, axes in enumerate(entries):
            for a in axes:
                if a not in mesh_shape:
                    if config.check_enabled("SC02"):
                        out.append(make_finding(
                            "SC02", locus,
                            f"{path}: spec names mesh axis '{a}' which is "
                            f"absent from the mesh {dict(mesh_shape)} — at "
                            "runtime the axis would be silently dropped and "
                            "the dimension fully replicated",
                        ))
                    continue
                if a in seen and config.check_enabled("SC03"):
                    out.append(make_finding(
                        "SC03", locus,
                        f"{path}: mesh axis '{a}' used on dims {seen[a]} "
                        f"and {dim} of the same spec — a mesh axis can "
                        "shard at most one dimension",
                    ))
                seen.setdefault(a, dim)
            dim_factor = 1
            for a in axes:
                dim_factor *= mesh_shape.get(a, 1)
            if dim_factor > 1 and shape[dim] % dim_factor != 0 and (
                config.check_enabled("SC01")
            ):
                out.append(make_finding(
                    "SC01", locus,
                    f"{path}: dim {dim} of {tuple(shape)} not divisible by "
                    f"{'×'.join(axes)}={dim_factor}",
                ))
        if not config.check_enabled("SC04"):
            continue
        nbytes = leaf_nbytes(shape, dtype)
        if (
            shard_axes_live
            and nbytes >= config.replicated_threshold_bytes
            and spec_shard_factor(spec, mesh_shape) == 1
        ):
            out.append(make_finding(
                "SC04", locus,
                f"{path}: {nbytes / 2**20:.0f} MiB leaf is fully replicated "
                f"(spec {spec}) although fsdp/tensor shard parameters on "
                "this mesh — every device pays the full copy",
            ))
    return out


# ---- check 2: per-device memory model ---------------------------------------


# bytes a token and channel of d_inner that one Mamba layer's mixer holds
# in the backward sweep (memory_budget, hybrid stacks)
MAMBA_MIXER_BYTES = 28


def _bucket_of(path):
    if path.startswith(".params"):
        return "params"
    if path.startswith(".opt_state"):
        return "optimizer"
    return "counters"


def memory_budget(leaves, specs, mesh_shape, model_config, *, batch_size,
                  seq_len, loss_chunk_size=0, config=None, locus="config"):
    """Check 2 — per-device HBM budget of the train step.

    Exact terms: params and optimizer state are summed leaf-by-leaf at
    their sharded sizes (metadata math, no estimation). Modelled terms,
    held by tests/test_remat_ladder.py to the v5e compiler's own peaks
    (``compiled.memory_analysis()``; tools/remat_ladder.py) at the two
    benchmark cells' shapes, every remat rung, to within -1.5 % / +4 %:

    * gradients: one param-sized set, at param dtype;
    * ``activations_bytes``: what the forward sweep SAVES for the
      backward — without remat a layer pass's whole set of
      intermediates, under remat its carry plus the named values the
      policy keeps (utils/remat.py ``named_bytes``), times the layer
      passes; a looped stack also keeps every pass's normed state;
    * ``working_bytes``: what the backward sweep holds BESIDE that while
      it is in one layer pass (the pass recomputed from its carry and its
      cotangents, ~6.5 model widths + 4 FFN widths a token), or the loss
      head's float32 logits where those are larger (the two never live
      together). A looped stack adds the inner scan's saved set copied
      into the outer scan's once a pass, each pass's boundary values and
      cotangents, and the pass's own layer gradients beside the
      accumulator.

    Returns ``(rows, findings)`` where ``rows`` is the budget table the
    reporter renders.
    """
    from pyrecover_tpu.utils.dtypes import resolve_dtype
    from pyrecover_tpu.utils.remat import named_bytes, saved_names

    config = config or DEFAULT_CONFIG
    cfg = model_config
    mesh = mesh_shape
    buckets = {"params": 0, "optimizer": 0, "counters": 0}
    layer_params = 0
    for (path, shape, dtype), spec in zip(leaves, specs):
        nbytes = leaf_nbytes(shape, dtype) // spec_shard_factor(spec, mesh)
        buckets[_bucket_of(path)] += nbytes
        if path.startswith(".params") and "layers" in path:
            layer_params += nbytes
    rows = {
        "params_bytes": buckets["params"],
        "optimizer_bytes": buckets["optimizer"] + buckets["counters"],
        # grads live once, at param dtype, between backward and update
        "gradients_bytes": buckets["params"],
    }

    itemsize = np.dtype(resolve_dtype(cfg.compute_dtype)).itemsize
    batch_shards = mesh.get("data", 1) * mesh.get("fsdp", 1)
    tensor = max(mesh.get("tensor", 1), 1)
    b_loc = max(batch_size // batch_shards, 1)
    s_loc = max(seq_len // mesh.get("sequence", 1), 1)
    tokens = b_loc * s_loc
    # saved activations count layer PASSES: a looped stack saves a carry
    # for each of its loop_steps sweeps over the held layers
    layers_held = max(cfg.n_layers // mesh.get("pipeline", 1), 1)
    passes = layers_held * cfg.loop_steps
    ffn = cfg.expert_hidden_dim if cfg.n_experts > 0 else cfg.ffn_hidden_dim
    carry = tokens * cfg.dim * itemsize
    # one layer pass in the backward sweep: its recomputed forward and
    # the cotangents, in model and FFN widths a token (fitted to the
    # compiler's peak at batch 2 and 4 of both cells)
    work = int(tokens * itemsize * (6.5 * cfg.dim + 4 * ffn // tensor))

    def kept(names):
        return carry + named_bytes(
            cfg, names, tokens=tokens, itemsize=itemsize, tensor=tensor)

    if cfg.remat:
        per_pass = kept(saved_names(cfg))
    else:
        # no remat: every pass keeps its whole set of intermediates, and
        # the backward's working set is one pass's cotangents
        per_pass = int(tokens * itemsize * (7.5 * cfg.dim + 4 * ffn // tensor))
        # the flash kernel's row statistics as it writes them: one float a
        # (head, position), padded to the 128-lane tile
        per_pass += 128 * named_bytes(
            cfg, ("flash_lse",), tokens=tokens, itemsize=itemsize,
            tensor=tensor,
        )
    rows["activations_bytes"] = per_pass * passes
    if cfg.hybrid:
        # two kinds of layer pass: an attention layer keeps the attention
        # names of the save-set, a Mamba layer the mixer's, both the
        # SwiGLU's; the backward sweep's working set is the larger kind's,
        # a Mamba layer's mixer (u and z, the convolved u, the float32
        # step, u, y and the gated y, each with its cotangent) counted in
        # bytes a channel of d_inner (fitted, like the rest, to the v5e
        # compiler's peak at the benchmark's shape at batch 1 and 2)
        attn_passes = max(passes // cfg.attn_layer_period, 1)
        names = saved_names(cfg) if cfg.remat else ()
        ssm = tuple(n for n in names if n.startswith("ssm_"))
        shared = tuple(n for n in names if n.startswith("ffn_"))
        mixer = int(tokens * cfg.d_inner // tensor * MAMBA_MIXER_BYTES)
        if cfg.remat:
            attn_pass = kept(tuple(n for n in names if n not in ssm))
            mamba_pass = kept(ssm + shared)
            work += mixer
        else:
            attn_pass, mamba_pass = per_pass, per_pass + mixer
        rows["activations_bytes"] = (
            attn_pass * attn_passes + mamba_pass * (passes - attn_passes))
    if cfg.loop_steps > 1:
        # every pass's normed state is kept for the exit loss
        rows["activations_bytes"] += cfg.loop_steps * carry
        # the inner scan's saved set is copied into the outer scan's, the
        # pass boundaries hold ~6 carries more (state in and out, the normed
        # state, their cotangents), and a pass's layer gradients stand
        # beside the accumulator
        work += per_pass * layers_held + 6 * cfg.loop_steps * carry
        work += layer_params
    chunk = loss_chunk_size if 0 < loss_chunk_size < s_loc else s_loc
    vocab_loc = cfg.vocab_size // tensor
    # logits + logprobs, f32 (train_state.chunked_ce)
    rows["logits_bytes"] = 2 * b_loc * chunk * vocab_loc * 4
    rows["working_bytes"] = max(work, rows["logits_bytes"])
    rows["total_bytes"] = sum(
        v for k, v in rows.items()
        if k.endswith("_bytes") and k != "logits_bytes"
    )

    findings = []
    capacity = None
    if config.device_kind is not None:
        from pyrecover_tpu.utils.perf import tpu_hbm_bytes

        capacity = tpu_hbm_bytes(config.device_kind)
    rows["device_kind"] = config.device_kind
    rows["hbm_capacity_bytes"] = capacity
    if capacity is not None:
        budget = int(capacity * config.hbm_budget_fraction)
        rows["hbm_budget_bytes"] = budget
        if rows["total_bytes"] > budget and config.check_enabled("SC05"):
            findings.append(make_finding(
                "SC05", locus,
                f"estimated {rows['total_bytes'] / 2**30:.2f} GiB/device "
                f"exceeds the {config.hbm_budget_fraction:.0%} budget of "
                f"{config.device_kind} HBM ({capacity / 2**30:.0f} GiB) — "
                "raise fsdp/tensor, enable --remat, or shrink the batch",
            ))
    return rows, findings
