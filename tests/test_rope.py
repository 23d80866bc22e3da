"""RoPE's rotation held to the interleaved formula it replaced (PR 39):
the same output and VJP bit for bit, no lane shuffle of the head axis, and
the ``rope_plan`` event once a traced shape."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyrecover_tpu import telemetry
from pyrecover_tpu.models.llama import ModelConfig, init_params, qkv_proj
from pyrecover_tpu.ops import rope
from pyrecover_tpu.ops.rope import apply_rope, precompute_rope


def interleaved_rope(x, cos, sin):
    """The oracle: the parent's formula — strided halves, re-interleaved."""
    xf = x.astype(jnp.float32)
    x1 = xf[..., 0::2]
    x2 = xf[..., 1::2]
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    r1 = x1 * c - x2 * s
    r2 = x2 * c + x1 * s
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape).astype(x.dtype)


def _tables(shape, paged):
    """cos/sin for x of ``shape`` (..., seq, heads, hd): shared positions, or
    (the paged decode path) each batch row at its own gathered positions."""
    *_, seq, _, hd = shape
    cos, sin = precompute_rope(hd, 8192, theta=500000.0)
    if not paged:
        return cos[:seq], sin[:seq]
    pos = jax.random.randint(jax.random.key(7), (shape[0], seq), 0, 8192)
    return cos[pos], sin[pos]


CASES = {
    "mha_hd64": ((2, 24, 4, 64), False),
    "mha_hd128": ((2, 24, 4, 128), False),
    "gqa_q32": ((1, 24, 32, 128), False),
    "gqa_kv8": ((1, 24, 8, 128), False),
    "paged": ((3, 5, 4, 128), True),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(CASES))
def test_rotation_and_vjp_are_the_interleaved_formulas_bits(case, dtype):
    """Op by op, not jitted: inside a fusion XLA's CPU backend contracts a
    multiply and an add into one rounding where it sees fit, on either side
    differently, so a jitted comparison here would read the CPU compiler's
    choices. The v5e has no fused multiply-add (its bundles hold
    ``vmul.f32`` and ``vadd.f32``); the compiled programs are compared on
    the chip."""
    shape, paged = CASES[case]
    cos, sin = _tables(shape, paged)
    kx, kg = jax.random.split(jax.random.key(11))
    x = (3 * jax.random.normal(kx, shape, jnp.float32)).astype(dtype)
    g = jax.random.normal(kg, shape, jnp.float32).astype(dtype)

    def run(f):
        out, vjp = jax.vjp(lambda x: f(x, cos, sin), x)
        return out, vjp(g)[0]

    out, dx = run(apply_rope)
    want_out, want_dx = run(interleaved_rope)
    assert out.dtype == dx.dtype == dtype
    assert np.array_equal(np.asarray(out), np.asarray(want_out))
    assert np.array_equal(np.asarray(dx), np.asarray(want_dx))


def _eqns(jaxpr):
    """Every equation, sub-jaxprs included, in program order."""
    for eqn in jaxpr.eqns:
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's own
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)
        yield eqn


def _shuffles(jaxpr, lead):
    """Equations that shuffle the head axis of a tensor whose leading dims
    are ``lead`` (x's, heads included — the tables' heads axis is 1): a
    strided slice or a gather (jnp's strided indexing) and their transposes
    (an interior pad, a scatter), a concatenate or a reshape of a trailing
    size-2 axis. Sub-jaxprs included."""
    found = []
    for eqn in _eqns(jaxpr):
        shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)
                  if hasattr(v.aval, "shape")]
        if not any(tuple(s[:len(lead)]) == lead for s in shapes):
            continue
        name = eqn.primitive.name
        if name in ("gather", "scatter", "scatter-add"):
            found.append(name)
        elif name == "slice" and any(
            st not in (None, 1) for st in (eqn.params["strides"] or ())
        ):
            found.append(name)
        elif name == "pad" and any(
            interior for _, _, interior in eqn.params["padding_config"]
        ):
            found.append(name)
        elif name in ("concatenate", "reshape") and any(
            len(s) > len(lead) + 1 and s[-1] == 2 for s in shapes
        ):
            found.append(name)
    return found


@pytest.mark.parametrize("fn, shuffles", [
    (apply_rope, False),
    (interleaved_rope, True),  # the guard sees the form it guards against
], ids=["apply_rope", "oracle"])
def test_no_lane_shuffle_of_the_head_axis(fn, shuffles):
    shape = (2, 16, 4, 128)
    cos, sin = _tables(shape, False)
    x = jnp.ones(shape, jnp.bfloat16)

    def forward_and_vjp(x, g):
        out, vjp = jax.vjp(lambda x: fn(x, cos, sin), x)
        return out, vjp(g)

    closed = jax.make_jaxpr(forward_and_vjp)(x, x)
    found = _shuffles(closed.jaxpr, shape[:-1])
    assert bool(found) == shuffles, found


def test_forward_swaps_the_float32_operand_behind_a_barrier():
    """The forward's swap reads x as float32 at ``HIGHEST``, behind an
    ``optimization_barrier`` (the TPU compiler hands the projection's
    unrounded float32 to the rotation, as it did to the interleaved form;
    a bfloat16 swap would read it rounded). The backward swaps the bfloat16
    cotangent at the default precision."""
    shape = (2, 16, 4, 128)
    cos, sin = _tables(shape, False)
    x = jnp.ones(shape, jnp.bfloat16)

    def forward_and_vjp(x, g):
        out, vjp = jax.vjp(lambda x: apply_rope(x, cos, sin), x)
        return out, vjp(g)

    eqns = list(_eqns(jax.make_jaxpr(forward_and_vjp)(x, x).jaxpr))
    names = [e.primitive.name for e in eqns]
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert [str(e.invars[0].aval.dtype) for e in dots] == ["float32",
                                                            "bfloat16"]
    assert dots[0].params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
    assert names.index("optimization_barrier") < names.index("dot_general")


def test_rope_plan_once_a_traced_shape(monkeypatch):
    """A traced ``qkv_proj`` tells which form rotated q and k, one event a
    shape (GQA: q's and k's heads differ), at trace time only."""
    monkeypatch.setattr(rope, "_plans_told", set())
    cfg = ModelConfig().tiny()
    layer = jax.tree.map(lambda a: a[0], init_params(jax.random.key(0),
                                                     cfg)["layers"])
    assert cfg.n_heads != cfg.n_kv_heads
    cos, sin = precompute_rope(cfg.head_dim, 16, cfg.rope_theta)
    h = jnp.ones((2, 16, cfg.dim), jnp.bfloat16)

    def proj(h):
        return qkv_proj(h, layer, cfg, cos, sin)

    sink = telemetry.add_sink(telemetry.MemorySink())
    try:
        for _ in range(2):  # the loop: one trace, two runs
            jax.block_until_ready(jax.jit(proj)(h))
        plans = [e for e in sink.events if e["event"] == "rope_plan"]
        assert [(e["form"], e["heads"], e["head_dim"], e["seq"],
                 e["batch_dims"]) for e in plans] == [
            (rope.FORM, cfg.n_heads, cfg.head_dim, 16, [2]),
            (rope.FORM, cfg.n_kv_heads, cfg.head_dim, 16, [2]),
        ]
        jax.jit(lambda h: proj(h)[0] * 2)(h)  # the same shapes traced again
        assert len([e for e in sink.events if e["event"] == "rope_plan"]) == 2
    finally:
        telemetry.remove_sink(sink)
