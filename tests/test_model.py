"""Model unit tests: shapes, causality, GQA semantics, RoPE, determinism.

The reference has no pytest suite (SURVEY §4) — its only model check is a
param-count print (test_model.py:6-25). These tests are the golden-value
coverage the rebuild owes for RMSNorm/RoPE/GQA/SwiGLU semantics
(reference model.py:25-139).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyrecover_tpu.models import ModelConfig, forward, init_params
from pyrecover_tpu.ops.attention import sdpa_attention
from pyrecover_tpu.ops.rope import apply_rope, precompute_rope
from pyrecover_tpu.models.llama import rms_norm

CFG = ModelConfig().tiny()


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def test_forward_shape_and_dtype(params):
    tokens = jnp.zeros((2, 16), dtype=jnp.int32)
    logits = forward(params, tokens, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert logits.dtype == jnp.float32


def test_param_count(params):
    hd = CFG.head_dim
    ffn = CFG.ffn_hidden_dim
    expected = (
        CFG.vocab_size * CFG.dim  # embed
        + CFG.n_layers
        * (
            2 * CFG.dim  # two norms
            + CFG.dim * CFG.n_heads * hd  # wq
            + 2 * CFG.dim * CFG.n_kv_heads * hd  # wk, wv
            + CFG.n_heads * hd * CFG.dim  # wo
            + 3 * CFG.dim * ffn  # w1, w2, w3
        )
        + CFG.dim  # final norm
        + CFG.dim * CFG.vocab_size  # output
    )
    total = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    assert total == expected


def test_causality(params):
    """Perturbing token t must not change logits at positions < t."""
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (1, 16)), dtype=jnp.int32)
    logits_a = forward(params, tokens, CFG)
    perturbed = tokens.at[0, 10].set((tokens[0, 10] + 1) % CFG.vocab_size)
    logits_b = forward(params, perturbed, CFG)
    np.testing.assert_array_equal(
        np.asarray(logits_a[0, :10]), np.asarray(logits_b[0, :10])
    )
    assert not np.allclose(np.asarray(logits_a[0, 10:]), np.asarray(logits_b[0, 10:]))


def test_determinism(params):
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % CFG.vocab_size
    f = jax.jit(lambda p, t: forward(p, t, CFG))
    a = f(params, tokens)
    b = f(params, tokens)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gqa_matches_materialized_mha():
    """GQA via grouped einsum == repeat_kv then plain MHA
    (reference model.py:130-139 repeat_kv semantics)."""
    key = jax.random.key(1)
    b, s, hq, hkv, d = 2, 8, 4, 2, 16
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, hq, d), dtype=jnp.float32)
    k = jax.random.normal(kk, (b, s, hkv, d), dtype=jnp.float32)
    v = jax.random.normal(kv, (b, s, hkv, d), dtype=jnp.float32)

    out_gqa = sdpa_attention(q, k, v, causal=True)
    # materialize: each kv head repeated hq//hkv times
    k_rep = jnp.repeat(k, hq // hkv, axis=2)
    v_rep = jnp.repeat(v, hq // hkv, axis=2)
    out_mha = sdpa_attention(q, k_rep, v_rep, causal=True)
    np.testing.assert_allclose(
        np.asarray(out_gqa), np.asarray(out_mha), rtol=1e-5, atol=1e-5
    )


def test_attention_against_naive():
    """sdpa_attention == explicit softmax(QK^T/sqrt(d))V with causal mask."""
    key = jax.random.key(2)
    b, s, h, d = 1, 8, 2, 4
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype=jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), dtype=jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), dtype=jnp.float32)

    out = sdpa_attention(q, k, v, causal=True)

    qt = np.asarray(q).transpose(0, 2, 1, 3)  # b h s d
    kt = np.asarray(k).transpose(0, 2, 1, 3)
    vt = np.asarray(v).transpose(0, 2, 1, 3)
    scores = qt @ kt.transpose(0, 1, 3, 2) / np.sqrt(d)
    mask = np.tril(np.ones((s, s), dtype=bool))
    scores = np.where(mask, scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    ref = (probs @ vt).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_rope_properties():
    cos, sin = precompute_rope(8, 16, theta=10000.0)
    assert cos.shape == (16, 4) and sin.shape == (16, 4)
    x = jax.random.normal(jax.random.key(3), (1, 16, 2, 8), dtype=jnp.float32)
    rotated = apply_rope(x, cos, sin)
    # norm-preserving per pair
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(rotated), axis=-1),
        rtol=1e-5,
    )
    # position 0 is identity (angle 0)
    np.testing.assert_allclose(
        np.asarray(x[:, 0]), np.asarray(rotated[:, 0]), rtol=1e-6, atol=1e-6
    )
    # relative-position property: <rope(q,m), rope(k,n)> depends on m-n only
    q = jax.random.normal(jax.random.key(4), (1, 16, 1, 8))
    k = jax.random.normal(jax.random.key(5), (1, 16, 1, 8))
    rq, rk = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    dots = np.einsum("bshd,bshd->bsh", np.asarray(rq[:, 1:]), np.asarray(rk[:, :-1]))
    # shift both by +3 positions: dot of (m+3, n+3) must equal dot of (m, n)
    q2 = jnp.roll(jnp.zeros_like(q).at[:, 3:].set(q[:, :-3]), 0)
    # simpler: compare dot(rope(q)@pos m, rope(k)@pos m-1) across m — all equal
    # only if q,k constant across positions; use constant vectors:
    qc = jnp.broadcast_to(q[:, :1], q.shape)
    kc = jnp.broadcast_to(k[:, :1], k.shape)
    rqc, rkc = apply_rope(qc, cos, sin), apply_rope(kc, cos, sin)
    d1 = np.einsum("bshd,bshd->bs", np.asarray(rqc[:, 1:]), np.asarray(rkc[:, :-1]))
    assert np.allclose(d1, d1[0, 0], rtol=1e-4), "relative-position invariance broken"


def test_rms_norm():
    x = jax.random.normal(jax.random.key(6), (2, 8), dtype=jnp.bfloat16)
    scale = jnp.full((8,), 2.0, dtype=jnp.float32)
    out = rms_norm(x, scale, 1e-5)
    assert out.dtype == jnp.bfloat16
    xf = np.asarray(x, dtype=np.float32)
    ref = xf / np.sqrt((xf**2).mean(-1, keepdims=True) + 1e-5) * 2.0
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32), ref, rtol=2e-2, atol=2e-2)


def test_ffn_hidden_dim_formula():
    """Reference model.py:258-262 with the 8B defaults resolves to 14336."""
    cfg = ModelConfig(dim=4096, ffn_dim_multiplier=1.3, multiple_of=1024)
    assert cfg.ffn_hidden_dim == 14336


def _remat_cases():
    from pyrecover_tpu.utils.remat import LADDER

    # the two explicit policies, then every rung of the ladder that
    # rematerializes, each under both attention paths (the flash names
    # exist only in the kernel's own forward rule)
    cases = [("sdpa", dict(remat_policy="full")),
             ("sdpa", dict(remat_policy="save-attn")),
             ("flash", dict(remat_policy="full")),
             ("flash", dict(remat_policy="save-attn"))]
    for rung, names in LADDER.items():
        if rung not in ("none", "full", "save-attn"):
            cases += [("sdpa", dict(remat_save=names)),
                      ("flash", dict(remat_save=names))]
    return cases


@pytest.mark.parametrize(
    "attention,how", _remat_cases(),
    ids=lambda v: v if isinstance(v, str) else "+".join(
        v.get("remat_save") or (v["remat_policy"],)),
)
def test_remat_policies_match_no_remat(monkeypatch, attention, how):
    """remat=True, whatever the layer scan keeps (the explicit policies
    "full" and "save-attn", every rung of utils/remat.py's ladder), must
    produce the same loss AND gradients as remat=False — rematerialization
    is a memory strategy, never a numerics change."""
    import dataclasses

    from pyrecover_tpu.models.llama import forward_hidden_with_aux

    monkeypatch.setenv("PYRECOVER_PALLAS_INTERPRET", "1")
    base = dataclasses.replace(
        ModelConfig().tiny(max_seq_len=32, vocab_size=128, n_layers=2),
        attention_impl=attention,
    )
    params = init_params(jax.random.key(0), base)
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, 128, (2, 32)), dtype=jnp.int32
    )

    def loss(p, cfg):
        h, aux = forward_hidden_with_aux(p, tokens, cfg)
        return jnp.sum(h.astype(jnp.float32) ** 2) + jnp.sum(aux)

    ref_cfg = dataclasses.replace(base, remat=False)
    ref_val, ref_grads = jax.jit(
        jax.value_and_grad(lambda p: loss(p, ref_cfg))
    )(params)

    cfg = dataclasses.replace(base, remat=True, **how)
    val, grads = jax.jit(
        jax.value_and_grad(lambda p: loss(p, cfg))
    )(params)
    np.testing.assert_allclose(np.asarray(val), np.asarray(ref_val),
                               rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(ref_grads),
                    jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_invalid_remat_policy_rejected():
    import dataclasses

    import pytest

    with pytest.raises(ValueError, match="remat_policy"):
        dataclasses.replace(ModelConfig().tiny(), remat_policy="attn")
