"""Plain float32 reference for the Mistral family of decoders.

One file for the family: the dense block of Mistral-7B (arXiv:2310.06825:
pre-norm RMSNorm, grouped-query attention with rotary positions, SwiGLU) and
the sparse block of Mixtral-8x7B (arXiv:2401.04088: the same attention, the
feed-forward replaced by 8 SwiGLU experts of which a softmax router picks
two per token, their gates renormalised over the chosen two). Forward, loss,
gradients and the AdamW update, in straightforward ``jax.numpy`` float32 with
every product at ``Precision.HIGHEST``. No kernel, no cache, no batching
beyond a Python loop over the rows of the batch; it imports nothing of the
program under test and takes nothing the program has made: the weights are
drawn here from the seed, the only inputs are the token rows the step was fed.

Departures from the published descriptions, each because the system under
test trains that way and the comparison is of the same mathematics:

* Weights are drawn N(0, 0.02) (residual outputs ``wo``/``w2`` scaled by
  ``1/sqrt(2 L)``) from ``jax.random`` keys split off the seed in the order of
  the program's initialiser, one draw per layer, rounded to the storage type
  the configuration states (bfloat16; the router is then widened to float32).
* Rotary pairs are adjacent elements ``(2i, 2i+1)`` (the original Meta
  layout; the Hugging Face checkpoints permute to half-split pairs, which is
  the same function under a fixed permutation of each head).
* The 4096-token sliding window is not applied: every cell keeps sequences
  at or under 4096, where the window is full causal attention.
* The load-balancing loss is the Switch form per row of the batch,
  ``E * sum_e f_e p_e`` with ``f_e`` the share of the row's ``S*K`` picks and
  ``p_e`` the row's mean router probability, averaged over rows and summed
  over layers (Mixtral's is taken over all tokens of the batch at once).
  No token is dropped (the configuration sets the capacity at which none can
  be).
* Storage is what the configuration states: parameters and both Adam moments
  are kept in bfloat16 between steps (every update is computed in float32 and
  rounded once when stored). That rounding is part of the training the cell
  times, not of the arithmetic under test.

Memory: one row of the batch at a time, layer by layer, with a hand-rolled
backward sweep (``jax.vjp`` per layer, attention in blocks of query rows,
experts one after another), gradients summed in float32. So the reference
fits beside nothing else on the chip: run it after the program's state is
freed. On several devices every weight is split along its widest free
dimension (heads, feed-forward width, vocabulary) and the tokens are
replicated, which shards the memory and the products four ways and changes
no value beyond the order of a sum.

``precision`` other than ``"f32"`` turns this file into the *control*: every
product's operands are rounded to that type first (straight-through for the
gradient), products still accumulate in float32. ``"fp8"`` is e4m3 with one
scale per tensor, the usual recipe, and is the step below the bfloat16 the
configurations state.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

HI = jax.lax.Precision.HIGHEST
IGNORE = -100
QBLOCK = 1024  # attention is computed in blocks of this many query rows

_STORE = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _round_to(x, kind):
    """Round a product's operand to ``kind`` (straight-through gradient)."""
    if kind == "f32":
        return x
    if kind == "bf16":
        r = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif kind == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    else:
        raise ValueError(f"unknown precision {kind!r}")
    return x + jax.lax.stop_gradient(r - x)


def _mm(a, b, kind):
    return jnp.matmul(_round_to(a, kind), _round_to(b, kind), precision=HI)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x: (S, heads, hd); adjacent pairs rotated by position * theta^(-2i/hd)."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * sn, x2 * c + x1 * sn], -1).reshape(x.shape)


def _attention(q, k, v, kind):
    """Causal grouped-query attention of one row. q: (S, KV, G, hd)."""
    s, _, _, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    kq, vq = _round_to(k, kind), _round_to(v, kind)

    @jax.checkpoint
    def block(qb, start):
        sc = jnp.einsum("skgd,tkd->kgst", _round_to(qb, kind), kq,
                        precision=HI) * scale
        rows = start + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= rows, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgst,tkd->skgd", _round_to(p, kind), vq,
                          precision=HI)

    qb = min(QBLOCK, s)
    outs = [block(q[i:i + qb], i) for i in range(0, s, qb)]
    return jnp.concatenate(outs, axis=0)


def _swiglu(h, w1, w3, w2, kind):
    return _mm(jax.nn.silu(_mm(h, w1, kind)) * _mm(h, w3, kind), w2, kind)


def _layer(x, lp, m, kind):
    """One decoder block on one row x: (S, D), weights ``lp`` in float32.
    Returns (x, aux of the row)."""
    f32 = jnp.float32
    s = x.shape[0]
    H, KV, hd = m["heads"], m["kv_heads"], m["head_dim"]
    h = _rms(x, lp["attn_norm"], m["eps"])
    q = _rope(_mm(h, lp["wq"], kind).reshape(s, H, hd), m["theta"])
    k = _rope(_mm(h, lp["wk"], kind).reshape(s, KV, hd), m["theta"])
    v = _mm(h, lp["wv"], kind).reshape(s, KV, hd)
    a = _attention(q.reshape(s, KV, H // KV, hd), k, v, kind)
    x = x + _mm(a.reshape(s, H * hd), lp["wo"], kind)
    h = _rms(x, lp["ffn_norm"], m["eps"])
    E, K = m["experts"], m["top_k"]
    if not E:
        return x + _swiglu(h, lp["w1"], lp["w3"], lp["w2"], kind), f32(0)
    probs = jax.nn.softmax(jnp.matmul(h, lp["router"], precision=HI), axis=-1)
    topv, topi = jax.lax.top_k(probs, K)
    gates = topv / jnp.sum(topv, axis=-1, keepdims=True)
    picked = topi[:, :, None] == jnp.arange(E)[None, None, :]  # (S, K, E)
    weight = jnp.sum(jnp.where(picked, gates[:, :, None], 0.0), axis=1)
    y = jnp.zeros_like(x)
    expert = jax.checkpoint(functools.partial(_swiglu, kind=kind))
    for e in range(E):
        y = y + weight[:, e:e + 1] * expert(
            h, lp["moe_w1"][e], lp["moe_w3"][e], lp["moe_w2"][e])
    share = jnp.sum(picked, axis=(0, 1)).astype(f32) / (s * K)
    aux = E * jnp.sum(share * jnp.mean(probs, axis=0))
    return x + y, aux


def _head(x, final_norm, output, labels, m, kind):
    """Summed next-token cross-entropy of one row over its unmasked labels."""
    h = _rms(x, final_norm, m["eps"])
    logp = jax.nn.log_softmax(_mm(h, output, kind), -1)
    valid = labels != IGNORE
    ll = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[:, None], 1)
    return -jnp.sum(jnp.where(valid, ll[:, 0], 0.0))


def model_dims(cfg):
    """The sizes this file needs, from a configuration file's (Hugging Face
    named) keys."""
    heads = cfg["num_attention_heads"]
    return {
        "dim": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "heads": heads, "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim", cfg["hidden_size"] // heads),
        "ffn": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
        "experts": cfg.get("num_local_experts", 0),
        "top_k": cfg.get("num_experts_per_tok", 0),
        "aux_weight": cfg.get("router_aux_loss_coef", 0.0),
    }


def leaf_shapes(m):
    """name -> (shape, init std or None for ones, the dimension split over
    devices). Names are the program's tree paths joined by '/'."""
    D, L, F, V = m["dim"], m["layers"], m["ffn"], m["vocab"]
    qd, kd = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    std, rstd = 0.02, 0.02 / math.sqrt(2 * L)
    out = {
        "tok_embed": ((V, D), std, 1),
        "layers/attn_norm": ((L, D), None, None),
        "layers/wq": ((L, D, qd), std, 2),
        "layers/wk": ((L, D, kd), std, 2),
        "layers/wv": ((L, D, kd), std, 2),
        "layers/wo": ((L, qd, D), rstd, 1),
        "layers/ffn_norm": ((L, D), None, None),
        "final_norm": ((D,), None, None),
        "output": ((D, V), std, 1),
    }
    E = m["experts"]
    if E:
        out.update({
            "layers/router": ((L, D, E), std, None),
            "layers/moe_w1": ((L, E, D, F), std, 3),
            "layers/moe_w3": ((L, E, D, F), std, 3),
            "layers/moe_w2": ((L, E, F, D), rstd, 2),
        })
    else:
        out.update({
            "layers/w1": ((L, D, F), std, 2),
            "layers/w3": ((L, D, F), std, 2),
            "layers/w2": ((L, F, D), rstd, 1),
        })
    return out


# the program's initialiser splits the seed's key in ten and hands them out so
_KEY_OF = {
    "tok_embed": 0, "layers/wq": 1, "layers/wk": 2, "layers/wv": 3,
    "layers/wo": 4, "layers/router": 5, "layers/moe_w1": 6,
    "layers/moe_w3": 7, "layers/moe_w2": 9, "layers/w1": 5, "layers/w3": 6,
    "layers/w2": 7, "output": 8,
}


class Reference:
    """Weights from a seed, then ``step(inputs, labels)`` as the trainer's
    step: loss, clipped gradients, AdamW, storage rounding."""

    def __init__(self, cfg, optim, devices, precision="f32"):
        self.m = model_dims(cfg)
        self.o = dict(optim)
        self.kind = precision
        self.mesh = Mesh(np.array(list(devices)), ("t",))
        self.shapes = leaf_shapes(self.m)
        self.store = _STORE[self.o["param_dtype"]]
        self.count = 0
        self._jit = {}

    # -- placement ----------------------------------------------------------
    def _sharding(self, name):
        shape, _, split = self.shapes[name]
        spec = [None] * len(shape)
        n = self.mesh.devices.size
        if split is not None and n > 1 and shape[split] % n == 0:
            spec[split] = "t"
        return NamedSharding(self.mesh, P(*spec))

    def _replicated(self):
        return NamedSharding(self.mesh, P())

    def _dtype(self, name):
        return jnp.float32 if name == "layers/router" else self.store

    # -- weights ------------------------------------------------------------
    def _draw(self, seed):
        L, store = self.m["layers"], self.store
        keys = jax.random.split(jax.random.key(seed), 10)

        def normal(key, shape, std):
            z = jax.random.normal(key, shape, dtype=jnp.float32) * std
            return z.astype(store)

        out = {}
        for name, (shape, std, _) in self.shapes.items():
            if std is None:
                out[name] = jnp.ones(shape, store)
            elif name.startswith("layers/"):
                ks = jax.random.split(keys[_KEY_OF[name]], L)
                out[name] = jnp.stack(
                    [normal(k, shape[1:], std) for k in ks]
                ).astype(self._dtype(name))
            else:
                out[name] = normal(keys[_KEY_OF[name]], shape, std)
        return out

    def _fresh(self, seed):
        # the seed is an argument, not a constant of the program: one
        # compilation serves every seed (and every later run, from the cache)
        sh = {n: self._sharding(n) for n in self.shapes}
        draw = self._fn("draw", lambda: jax.jit(self._draw, out_shardings=sh))
        return draw(jnp.int32(seed))

    def init(self, seed):
        self.seed = int(seed)
        self.p = self._fresh(self.seed)
        zeros = jax.jit(
            lambda: {n: jnp.zeros(s[0], self._dtype(n))
                     for n, s in self.shapes.items()},
            out_shardings={n: self._sharding(n) for n in self.shapes},
        )
        self.mu, self.nu = zeros(), zeros()
        self.count = 0

    # -- jitted pieces (built once) -----------------------------------------
    def _fn(self, key, build):
        if key not in self._jit:
            self._jit[key] = build()
        return self._jit[key]

    def _layer_params(self, l):
        take = self._fn("take", lambda: jax.jit(
            lambda p, i: {k[len("layers/"):]: jax.lax.dynamic_index_in_dim(
                a, i, 0, keepdims=False)
                for k, a in p.items() if k.startswith("layers/")}))
        return take(self.p, l)

    def _grads(self, inputs, labels):
        """Loss and float32 gradients of one batch, a row at a time."""
        m, kind = self.m, self.kind
        rep = self._replicated()
        layer = functools.partial(_layer, m=m, kind=kind)

        def wide(tree):  # gradients are taken in float32, not in storage
            return jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), tree)

        fwd = self._fn("fwd", lambda: jax.jit(
            lambda x, lp: layer(x, wide(lp)), out_shardings=(rep, rep)))

        def bwd_fn(x, lp, dy, daux):
            _, vjp = jax.vjp(layer, x, wide(lp))
            return vjp((dy, daux))

        bwd = self._fn("bwd", lambda: jax.jit(bwd_fn))

        def head_fn(x, fn, out, lab, scale):
            ce, vjp = jax.vjp(
                lambda a, b, c: _head(a, b, c, lab, m, kind),
                x, wide(fn), wide(out))
            return (ce,) + vjp(scale)

        head = self._fn("head", lambda: jax.jit(head_fn))
        embed = self._fn("embed", lambda: jax.jit(
            lambda t, toks: t.astype(jnp.float32)[toks], out_shardings=rep))

        def add_layer(acc, dlp, l):
            return {k: (a.at[l].add(dlp[k[len("layers/"):]])
                        if k.startswith("layers/") else a)
                    for k, a in acc.items()}

        add_layer = self._fn("add_layer", lambda: jax.jit(
            add_layer, donate_argnums=0))

        def add_ends(acc, toks, dx, dfn, dout):
            acc = dict(acc)
            acc["tok_embed"] = acc["tok_embed"].at[toks].add(dx)
            acc["final_norm"] = acc["final_norm"] + dfn
            acc["output"] = acc["output"] + dout
            return acc

        add_ends = self._fn("add_ends", lambda: jax.jit(
            add_ends, donate_argnums=0))
        acc = self._fn("zeros32", lambda: jax.jit(
            lambda: {n: jnp.zeros(s[0], jnp.float32)
                     for n, s in self.shapes.items()},
            out_shardings={n: self._sharding(n) for n in self.shapes}))()

        B = inputs.shape[0]
        n_valid = max(int(np.sum(labels != IGNORE)), 1)
        L = m["layers"]
        ce_total = 0.0
        daux = jnp.float32(m["aux_weight"] / B)
        for b in range(B):
            toks = jnp.asarray(inputs[b], jnp.int32)
            lab = jnp.asarray(labels[b], jnp.int32)
            xs = [embed(self.p["tok_embed"], toks)]
            for l in range(L):
                x, _ = fwd(xs[-1], self._layer_params(l))
                xs.append(x)
            ce, dx, dfn, dout = head(
                xs[-1], self.p["final_norm"], self.p["output"], lab,
                jnp.float32(1.0 / n_valid))
            ce_total += float(ce)
            for l in reversed(range(L)):
                dx, dlp = bwd(xs[l], self._layer_params(l), dx, daux)
                acc = add_layer(acc, dlp, l)
            acc = add_ends(acc, toks, dx, dfn, dout)
        return ce_total / n_valid, acc

    def _lr(self, count):
        base, w = self.o["learning_rate"], self.o["lr_warmup_steps"]
        ramp = max(w - 1, 1)
        if count >= ramp:
            return base
        first = base / max(w, 1)
        return first + (base - first) * count / ramp

    def step(self, inputs, labels):
        """One training step. Returns the loss (mean cross-entropy, as the
        trainer logs it), the global gradient norm before clipping, and the
        norm of each leaf's gradient as the optimizer gets it (clipped)."""
        o = self.o
        loss, g = self._grads(np.asarray(inputs), np.asarray(labels))
        sq = self._fn("sq", lambda: jax.jit(leaf_sq_norms))(g)
        sq = {k: np.asarray(v, np.float64) for k, v in sq.items()}
        gnorm = math.sqrt(sum(float(v.sum()) for v in sq.values()))
        clip = 1.0
        if o["grad_clipping"] and o["grad_max_norm"] > 0:
            clip = min(1.0, o["grad_max_norm"] / max(gnorm, 1e-30))
        t = self.count + 1
        b1, b2 = o["adam_b1"], o["adam_b2"]

        def update(p, mu, nu, g, clip, lr, c1, c2):
            out_p, out_mu, out_nu = {}, {}, {}
            for k in p:
                f32 = jnp.float32
                gk = g[k] * clip
                m_ = b1 * mu[k].astype(f32) + (1 - b1) * gk
                v_ = b2 * nu[k].astype(f32) + (1 - b2) * gk * gk
                u = (m_ / c1) / (jnp.sqrt(v_ / c2) + o["adam_eps"])
                u = u + o["weight_decay"] * p[k].astype(f32)
                out_p[k] = (p[k].astype(f32) - lr * u).astype(p[k].dtype)
                out_mu[k] = m_.astype(mu[k].dtype)
                out_nu[k] = v_.astype(nu[k].dtype)
            return out_p, out_mu, out_nu

        upd = self._fn("update", lambda: jax.jit(
            update, donate_argnums=(0, 1, 2)))
        self.p, self.mu, self.nu = upd(
            self.p, self.mu, self.nu, g, jnp.float32(clip),
            jnp.float32(self._lr(self.count)),
            jnp.float32(1 - b1 ** t), jnp.float32(1 - b2 ** t))
        self.count = t
        return {
            "loss": loss, "grad_norm": gnorm,
            "grad_leaf_norms": {k: np.sqrt(v) * clip for k, v in sq.items()},
        }

    def change_norms(self):
        """Norm of each leaf's change since the seed's weights (drawn again,
        so no second copy is held through the steps)."""
        p0 = self._fresh(self.seed)
        sq = self._fn("dsq", lambda: jax.jit(
            lambda a, b: leaf_sq_norms({
                k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32)
                for k in a})))(self.p, p0)
        return {k: np.sqrt(np.asarray(v, np.float64)) for k, v in sq.items()}

    def weight_norms(self):
        sq = self._fn("sq", lambda: jax.jit(leaf_sq_norms))(self.p)
        return {k: np.sqrt(np.asarray(v, np.float64)) for k, v in sq.items()}


def leaf_sq_norms(tree):
    """Sum of squares of each leaf in float32; leaves stacked over layers
    (``layers/...``) give one number per layer."""
    out = {}
    for k, a in tree.items():
        a = a.astype(jnp.float32)
        axes = tuple(range(1, a.ndim)) if k.startswith("layers/") else None
        out[k] = jnp.sum(a * a, axis=axes)
    return out
