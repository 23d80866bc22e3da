"""Rotary position embeddings (RoPE).

Same math as the reference's complex-number formulation
(`model.py:52-127`: `precompute_freqs_cis` / `apply_rotary_emb`), expressed
with real cos/sin tables — the TPU-friendly form (no complex dtypes, which
XLA on TPU lowers poorly). The reference pairs *adjacent* elements
(`view_as_complex` of a `(..., d/2, 2)` reshape); we keep that interleaved
convention so head-dim semantics match.

The table is a function of (head_dim, max_seq_len, theta) only — it is
recomputed at trace time and never stored in checkpoints, matching the
reference's *non-persistent* `freqs_cis` buffer (`model.py:357-359`).

How a pair is rotated: the head dimension is the TPU's 128-lane axis, so
splitting it by stride 2 (``x[..., 0::2]``) and interleaving the halves back
(``stack(..., -1).reshape``) are lane shuffles, which the compiler answered
with relayout copies and transposes (PR 39: ≈ 8 ms a layer pass in Ouro's
shape, about as long as the q/k/v products take). Instead every lane is
rotated in place against per-lane tables — the pair's cosine on both lanes,
its sine with the sign folded in (−s even, +s odd) — and the partner lane
comes from one product with a constant 0/1 permutation, which is exact:
``out = x·C + swap(x)·S``. Per lane that is the interleaved formula's own
arithmetic (``x₀c + x₁(−s) = x₀c − x₁s``), so every output bit is the same.

The forward swaps the float32 operand, not the bfloat16 q or k: where the
projection's product is converted to float32 inside its own fusion, the TPU
compiler hands over the unrounded accumulator (excess precision), and the
interleaved form rotated those values. A bfloat16 swap would read them
rounded and move the first loss at the 1e-5 level.
"""

import jax
import jax.numpy as jnp
import numpy as np

#: the ``form`` the ``rope_plan`` event names
FORM = "pair_swap_product"


def precompute_rope(head_dim, max_seq_len, theta=500000.0, dtype=jnp.float32):
    """Returns (cos, sin), each of shape (max_seq_len, head_dim // 2)."""
    if head_dim % 2 != 0:
        raise ValueError(f"head_dim must be even, got {head_dim}")
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = jnp.outer(jnp.arange(max_seq_len, dtype=jnp.float32), freqs)
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def _swap_pairs(x):
    """``x`` in float32 with lanes 2i and 2i+1 exchanged: one product with a
    constant permutation. Each output is one operand times 1 plus zeros, so
    it is exact; a float32 operand asks for ``HIGHEST``, which the TPU's
    default would round to bfloat16."""
    hd = x.shape[-1]
    perm = np.eye(hd, dtype=np.float32)[np.arange(hd) ^ 1]
    precision = None if x.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST
    return jnp.matmul(
        x, jnp.asarray(perm, x.dtype), precision=precision,
        preferred_element_type=jnp.float32,
    )


@jax.custom_vjp
def _rotate(x, c, s):
    # the barrier keeps XLA from reading the float32 operand as an upcast of
    # bfloat16 and the HIGHEST product as a bfloat16 one: the projection then
    # hands over float32, as it did to the interleaved form (module doc)
    xf = jax.lax.optimization_barrier(x.astype(jnp.float32))
    return (xf * c + _swap_pairs(xf) * s).astype(x.dtype)


def _rotate_fwd(x, c, s):
    return _rotate(x, c, s), (c, s)


def _rotate_bwd(res, g):
    # the transpose of the rotation, swapping the cotangent in its own dtype
    # (autodiff would swap the float32 g·S through the product's transpose,
    # which the TPU's default precision rounds; g comes off the attention
    # kernels in bfloat16, so its swap is exact): even lanes g₀c + g₁s, odd
    # g₁c − g₀s, the interleaved formula's backward bit for bit. The tables
    # are constants of the positions: no gradient flows into them.
    c, s = res
    dx = g.astype(jnp.float32) * c - _swap_pairs(g) * s
    return dx.astype(g.dtype), None, None


_rotate.defvjp(_rotate_fwd, _rotate_bwd)

_plans_told = set()


def _tell_plan(x):
    """The ``rope_plan`` telemetry event: which form rotated a traced shape,
    once a shape and process — at trace time, so nothing of it is in a step.
    A shape traced before any sink listened is told when it is traced
    again."""
    *batch, seq, heads, head_dim = x.shape
    key = (heads, head_dim, seq, tuple(batch))
    if key in _plans_told:
        return
    from pyrecover_tpu import telemetry

    if telemetry.emit("rope_plan", form=FORM, heads=heads, head_dim=head_dim,
                      seq=seq, batch_dims=list(batch)) is not None:
        _plans_told.add(key)


def apply_rope(x, cos, sin):
    """Rotate q or k. ``x``: (..., seq, heads, head_dim); cos/sin:
    (seq, head_dim//2), or (..., seq, head_dim//2) with leading batch dims
    when each batch row sits at its own absolute positions (the paged
    decode path gathers a per-sequence position table).

    Interleaved-pair convention: elements (2i, 2i+1) form the complex pair,
    matching reference `model.py:101-127`. Computed in fp32, cast back.
    """
    _tell_plan(x)
    c = jnp.repeat(cos.astype(jnp.float32), 2, axis=-1)
    s = jnp.stack([-sin, sin], axis=-1).reshape(c.shape).astype(jnp.float32)
    # broadcast over (leading dims and) heads: (..., seq, 1, head_dim)
    return _rotate(x, c[..., :, None, :], s[..., :, None, :])
