"""Parameter and batch partition rules.

The reference's only parallelism is DDP — params replicated, batch sharded
(`train.py:107-115`, SURVEY §2.2). Here the same intent is expressed as
PartitionSpecs over the 4-axis mesh, which also unlocks tensor parallelism
(Megatron-style column/row sharding of attention + FFN) and fsdp (ZeRO-3)
with zero changes to the model code: XLA inserts the collectives.

Rules are path-based over the parameter pytree produced by
``pyrecover_tpu.models.llama.init_params``.
"""

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from pyrecover_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_PIPE,
    AXIS_SEQ,
    AXIS_TENSOR,
)

# name of final pytree leaf key -> spec factory, keyed on leaf ndim.
# Layer-stacked leaves (L, ...) put the leading (layer) axis on the pipeline
# mesh axis: each pipeline stage physically holds its contiguous L/S slice
# (parallel.pipeline); with pipeline=1 that entry is inert.
_RULES = {
    # embeddings: vocab replicated, model dim sharded over tensor×fsdp. A
    # vocab-sharded table would need a masked-gather+psum per lookup, which
    # XLA's SPMD partitioner handles by full rematerialization (observed:
    # "Involuntary full rematerialization" on the embedding gather); a
    # dim-sharded table makes the gather local and the later allgather tiny.
    "tok_embed": P(None, (AXIS_TENSOR, AXIS_FSDP)),
    # attention projections, stacked over layers at dim 0:
    #   wq/wk/wv (L, D, heads*hd): column parallel — output dim on tensor
    "wq": P(AXIS_PIPE, AXIS_FSDP, AXIS_TENSOR),
    "wk": P(AXIS_PIPE, AXIS_FSDP, AXIS_TENSOR),
    "wv": P(AXIS_PIPE, AXIS_FSDP, AXIS_TENSOR),
    #   wo (L, heads*hd, D): row parallel — input dim on tensor
    "wo": P(AXIS_PIPE, AXIS_TENSOR, AXIS_FSDP),
    # SwiGLU FFN (reference model.py:233-269 semantics):
    "w1": P(AXIS_PIPE, AXIS_FSDP, AXIS_TENSOR),
    "w3": P(AXIS_PIPE, AXIS_FSDP, AXIS_TENSOR),
    "w2": P(AXIS_PIPE, AXIS_TENSOR, AXIS_FSDP),
    # MoE (models/moe.py): experts on the expert axis, then the usual
    # column/row split of each expert's SwiGLU over fsdp×tensor
    "router": P(AXIS_PIPE, None, None),
    "moe_w1": P(AXIS_PIPE, AXIS_EXPERT, AXIS_FSDP, AXIS_TENSOR),
    "moe_w3": P(AXIS_PIPE, AXIS_EXPERT, AXIS_FSDP, AXIS_TENSOR),
    "moe_w2": P(AXIS_PIPE, AXIS_EXPERT, AXIS_TENSOR, AXIS_FSDP),
    # norms: replicated within a stage (tiny), layer axis on pipeline
    "attn_norm": P(AXIS_PIPE, None),
    "ffn_norm": P(AXIS_PIPE, None),
    "attn_post_norm": P(AXIS_PIPE, None),
    "ffn_post_norm": P(AXIS_PIPE, None),
    "final_norm": P(None),
    # Mamba layers of a hybrid stack (models/mamba.py), stacked like the
    # others: the two wide products split as the SwiGLU's are, the rest of
    # a mixer (convolution, inner norms, step and state leaves) is small
    # and replicated within a stage
    "mixer_norm": P(AXIS_PIPE, None),
    "in_proj": P(AXIS_PIPE, AXIS_FSDP, AXIS_TENSOR),
    "out_proj": P(AXIS_PIPE, AXIS_TENSOR, AXIS_FSDP),
    "conv_w": P(AXIS_PIPE, None, None),
    "conv_b": P(AXIS_PIPE, None),
    "x_proj": P(AXIS_PIPE, None, None),
    "dt_norm": P(AXIS_PIPE, None),
    "b_norm": P(AXIS_PIPE, None),
    "c_norm": P(AXIS_PIPE, None),
    "dt_proj": P(AXIS_PIPE, None, None),
    "dt_bias": P(AXIS_PIPE, None),
    "a_log": P(AXIS_PIPE, None, None),
    "d_skip": P(AXIS_PIPE, None),
    # exit gate Linear(D -> 1) of a looped model: tiny, replicated
    "exit_gate_w": P(None, None),
    "exit_gate_b": P(None),
    # untied output projection (D, V) (reference model.py:367)
    "output": P(AXIS_FSDP, AXIS_TENSOR),
}


def _leaf_rule(path):
    for part in reversed(path):
        key = str(getattr(part, "key", getattr(part, "name", "")))
        if key in _RULES:
            return _RULES[key]
    return None


_KEYSTR_TOKEN = None  # compiled lazily; regex import kept off the hot path


def spec_for_manifest_path(path_str, ndim):
    """Target PartitionSpec for a checkpoint-manifest leaf path.

    The string twin of ``_leaf_rule`` + ``train.state_pspecs``: manifest
    paths are ``jax.tree_util.keystr`` strings (``.params['layers']['wq']``,
    ``.opt_state[0].mu['wq']``), so the same innermost-key-wins rule lookup
    resolves them without a live pytree — which is what lets a reshard
    plan be computed from a manifest alone, no devices, no model build.
    Falls back to fully replicated when no rule matches or the rule's rank
    disagrees with the leaf (exactly the ``state_pspecs`` behavior).
    """
    global _KEYSTR_TOKEN
    if _KEYSTR_TOKEN is None:
        import re

        # concur: disable-next=unguarded-shared-state -- benign race: a
        # lazy one-time compile of a constant pattern; two roots (resume
        # main vs the hot-swap watcher placing params) racing the None
        # check both assign the identical compiled regex
        _KEYSTR_TOKEN = re.compile(r"\['([^']+)'\]|\.([A-Za-z_]\w*)|\[(\d+)\]")
    keys = [a or b or c for a, b, c in _KEYSTR_TOKEN.findall(path_str or "")]
    if "grad_residual" in keys:
        # per-replica error-feedback residual (quantized grad collectives):
        # leading replica dim on the data axis, payload dims replicated
        return grad_residual_spec(ndim)
    for key in reversed(keys):
        rule = _RULES.get(key)
        if rule is not None:
            return rule if len(rule) == ndim else P(*([None] * ndim))
    return P(*([None] * ndim))


# ---- ZeRO-1 cross-replica optimizer sharding (arxiv 2004.13336) -------------
#
# The data axis replicates parameters, so without help it also replicates
# the AdamW moments — 2× param bytes of optimizer state on EVERY replica.
# ZeRO-1 shards the weight-update computation across the data axis
# instead: moments carry the param rule PLUS the data axis on the first
# dimension it divides, the train step constrains gradients to the same
# specs before the optax update (XLA turns the DP allreduce into a
# reduce-scatter), the update runs shard-local, and the updates are
# constrained back to the param rules (the allgather). Per-device
# optimizer bytes drop by the data-axis size; the program semantics are
# unchanged, which is what makes the zero1-fp32 parity gate bit-exact.


def _rule_entries(rule, ndim):
    """Rule entries normalized to per-dim axis tuples, length ``ndim``."""
    entries = []
    for e in rule:
        if e is None:
            entries.append(())
        elif isinstance(e, (tuple, list)):
            entries.append(tuple(e))
        else:
            entries.append((e,))
    entries += [()] * (ndim - len(entries))
    return entries


def _entries_to_spec(entries):
    return P(*[
        (e[0] if len(e) == 1 else e) if e else None for e in entries
    ])


def zero1_leaf_spec(rule, shape, mesh_shape):
    """The zero1 spec for an optimizer-moment leaf: ``rule`` with the
    data axis appended to the first dimension whose size the combined
    axis product divides. Falls back to ``rule`` unchanged when no
    dimension divides (the leaf stays replicated over data — graceful,
    and shardcheck's SC12 reports a zero1 config where NOTHING sharded).
    """
    data = int(mesh_shape.get(AXIS_DATA, 1))
    if rule is None:
        rule = P(*([None] * len(shape)))
    if data <= 1:
        return rule
    entries = _rule_entries(rule, len(shape))
    if any(AXIS_DATA in e for e in entries):
        return rule  # already data-sharded; nothing to add
    for dim, axes in enumerate(entries):
        factor = 1
        for a in axes:
            factor *= int(mesh_shape.get(a, 1))
        if shape[dim] % (factor * data) == 0:
            entries[dim] = tuple(axes) + (AXIS_DATA,)
            return _entries_to_spec(entries)
    return rule


def grad_residual_spec(ndim=2):
    """Spec for the error-feedback residual carried by the quantized
    gradient path (parallel/collectives.py): shape ``(replicas, L)``
    with the leading per-replica dim on the data axis."""
    return P(AXIS_DATA, *([None] * (ndim - 1)))


def _ambient_mesh_shape():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return {str(k): int(v) for k, v in dict(mesh.shape).items()}


def zero1_constrain(tree):
    """Constrain a param-shaped tree (gradients) to the zero1 specs under
    the ambient mesh — the reduce-scatter half of the decomposed update.
    No-op without a mesh or with a trivial data axis."""
    mesh_shape = _ambient_mesh_shape()
    if mesh_shape is None or mesh_shape.get(AXIS_DATA, 1) <= 1:
        return tree

    def f(path, leaf):
        rule = _leaf_rule(path)
        if rule is None or len(rule) != leaf.ndim:
            rule = P(*([None] * leaf.ndim))
        return jax.lax.with_sharding_constraint(
            leaf, zero1_leaf_spec(rule, leaf.shape, mesh_shape)
        )

    return jax.tree_util.tree_map_with_path(f, tree)


def rules_constrain(tree):
    """Constrain a param-shaped tree (updates) back to the base param
    rules — the allgather half of the decomposed update."""
    mesh_shape = _ambient_mesh_shape()
    if mesh_shape is None or mesh_shape.get(AXIS_DATA, 1) <= 1:
        return tree

    def f(path, leaf):
        rule = _leaf_rule(path)
        if rule is None or len(rule) != leaf.ndim:
            rule = P(*([None] * leaf.ndim))
        return jax.lax.with_sharding_constraint(leaf, rule)

    return jax.tree_util.tree_map_with_path(f, tree)


def param_pspecs(params):
    """PartitionSpec pytree matching ``params``' structure."""

    def spec_for(path, leaf):
        rule = _leaf_rule(path)
        if rule is None:
            return P(*([None] * leaf.ndim))
        if len(rule) != leaf.ndim:
            raise ValueError(
                f"Partition rule {rule} rank-mismatches leaf {path} with shape {leaf.shape}"
            )
        return rule

    return jax.tree_util.tree_map_with_path(spec_for, params)


def batch_pspec():
    """Token batches: (batch, seq) sharded over (data+fsdp, sequence).

    fsdp participates in batch sharding — ZeRO shards both data and params —
    matching the standard TPU recipe (scaling-book: dp×fsdp both consume the
    batch axis).
    """
    return P((AXIS_DATA, AXIS_FSDP), AXIS_SEQ)


def shard_params(params, mesh):
    """Place a parameter pytree onto ``mesh`` per the partition rules."""
    specs = param_pspecs(params)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


def replicated(mesh):
    return NamedSharding(mesh, P())
