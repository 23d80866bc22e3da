"""Plain float32 reference for Jamba, a hybrid of Mamba-1 and attention layers.

AI21-Jamba2-3B (``model_type`` ``jamba``; the public ``modeling_jamba.py``
beside its ``config.json``): layer i is an attention layer where
``i % attn_layer_period == attn_layer_offset`` and a Mamba-1 layer otherwise;
every layer's feed-forward is a dense SwiGLU (``num_experts`` 1). With N an
RMSNorm of its own scale:

    every layer:   x <- x + mixer(N_in(x));  x <- x + W2(silu(W1 h) * W3 h),
                   h = N_ff(x);  after the last layer N_final;
                   logits = h E^T with E the embedding (tied)
    attention:     q = h Wq (heads x hd), k = h Wk, v = h Wv (kv heads x hd),
                   NO rotary positions, causal softmax at scale hd^-1/2,
                   out = concat Wo; no biases
    Mamba:         [u, z] = h W_in;  u <- silu(conv1d(u) + b_conv) (depthwise,
                   causal, kernel 4);  [dt, B, C] = u W_x;  each through an
                   RMSNorm of its own;  Dt = softplus(dt W_dt + b_dt);
                   A = -exp(A_log);  for every token t, channel c, state n:
                     s_t[c,n] = exp(Dt_t[c] A[c,n]) s_{t-1}[c,n] + Dt_t[c] B_t[n] u_t[c]
                     y_t[c]   = sum_n C_t[n] s_t[c,n] + D[c] u_t[c],   s_0 = 0
                   y <- y * silu(z);  out = y W_out

Forward, loss (cross-entropy averaged over the valid tokens), the gradient of
every leaf and the AdamW update, in straightforward ``jax.numpy`` float32 with
every product at ``Precision.HIGHEST``. The recurrence is a plain ``lax.scan``
over tokens carrying the whole state; no kernel, no cache, no batching beyond
a Python loop over the rows of the batch. It imports nothing of the program
under test and takes nothing the program has made: the weights are drawn here
from the seed, the only inputs are the token rows the step was fed.

Departures from the published description, each because the system under test
trains that way and the comparison is of the same mathematics:

* Weights are random, from ``jax.random`` keys derived from the seed in the
  order of the program's initialiser (the embedding from the first of ten
  splits of the seed's key; group g of layers — ``mamba_pre``, ``attn``,
  ``mamba_post`` — from ``fold_in(key, 100 + g)``, one split a drawn leaf, one
  draw a layer): matrices N(0, 0.02), the residual outputs ``out_proj``,
  ``wo``, ``w2`` scaled by ``1/sqrt(2 L)``, the convolution uniform in
  +-k^-1/2 with bias nought, ``b_dt`` the inverse softplus of a log-uniform
  step in [1e-3, 1e-1], ``A_log`` = log(1..d_state), ``D`` and the norms one.
* Storage is what the configuration states: parameters and both Adam moments
  in bfloat16 between steps, except ``A_log``, ``D``, ``b_dt`` and their
  moments, which are float32 (every update is computed in float32 and rounded
  once when stored). Every leaf takes the weight decay: the trainer's AdamW
  has no mask.
* The leaves of a layer group are stacked on a leading axis and named by the
  program's tree paths (``layers/mamba_pre/in_proj`` ...), so the comparison
  is leaf by leaf and layer by layer.

Memory: the stored state is 9.6 GB at the benchmark's size and a float32
gradient of every leaf 6.4 GB more, which one chip does not hold beside a
layer's work. So **the Adam moments live on the host while the sweep runs**
(they are not read until the update, which takes them leaf by leaf), the
gradient accumulator is float32 on the device, and one row of the batch is
swept at a time: the forward keeps each layer's input, the backward takes the
layers last to first (``jax.vjp`` per layer, the recurrence in checkpointed
blocks of tokens, attention in blocks of query rows, the head in blocks of
tokens). Run it after the program's state is freed. One device only.

``precision`` other than ``"f32"`` turns this file into the *control*: every
product's operands are rounded to that type first (straight-through for the
gradient), products still accumulate in float32. ``"fp8"`` is e4m3 with one
scale per tensor, the step below the bfloat16 the configuration states.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
IGNORE = -100
QBLOCK = 1024  # attention, the head and the recurrence in blocks of tokens
SBLOCK = 256

_STORE = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
FLOAT32_LEAVES = ("a_log", "d_skip", "dt_bias")
# drawn leaves of a group, in the order the group's key is split
MAMBA_DRAWN = ("in_proj", "conv_w", "x_proj", "dt_proj", "dt_bias",
               "out_proj", "w1", "w3", "w2")
ATTN_DRAWN = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")
GROUP_FOLD = 100
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


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


def _attention(q, k, v, kind):
    """Causal grouped-query attention of one row, no positions applied.
    q: (S, KV, G, hd); k, v: (S, KV, hd)."""
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
    return jnp.concatenate(
        [block(q[i:i + qb], i) for i in range(0, s, qb)], axis=0)


def _swiglu(x, lp, m, kind):
    h = _rms(x, lp["ffn_norm"], m["eps"])
    f = _mm(jax.nn.silu(_mm(h, lp["w1"], kind)) * _mm(h, lp["w3"], kind),
            lp["w2"], kind)
    return x + f


def _attn_layer(x, lp, m, kind):
    s = x.shape[0]
    H, KV, hd = m["heads"], m["kv_heads"], m["head_dim"]
    h = _rms(x, lp["attn_norm"], m["eps"])
    q = _mm(h, lp["wq"], kind).reshape(s, KV, H // KV, hd)
    k = _mm(h, lp["wk"], kind).reshape(s, KV, hd)
    v = _mm(h, lp["wv"], kind).reshape(s, KV, hd)
    a = _attention(q, k, v, kind).reshape(s, H * hd)
    return _swiglu(x + _mm(a, lp["wo"], kind), lp, m, kind)


def _conv(u, w, b):
    """Depthwise causal convolution: out_t = b + sum_j w[j] u_{t-(k-1)+j}."""
    k, s = w.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), u.dtype), u])
    return b + sum(padded[j:j + s] * w[j] for j in range(k))


def recurrence(u, dt, a, b, c):
    """The selective recurrence of one row, token by token with the whole
    state carried: u, dt (S, Di), a (Di, N), b, c (S, N) -> y (S, Di) without
    the skip. Blocks of ``SBLOCK`` tokens are checkpointed so that a gradient
    keeps one block's states and the block boundaries."""

    a_t = a.T  # the state is carried (N, Di): channels on the minor axis

    def step(state, x):
        u_t, dt_t, b_t, c_t = x
        state = (jnp.exp(dt_t[None, :] * a_t) * state
                 + (dt_t * u_t)[None, :] * b_t[:, None])
        return state, jnp.sum(state * c_t[:, None], axis=0)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    s = u.shape[0]
    state = jnp.zeros(a_t.shape, a.dtype)
    ys = []
    for i in range(0, s, SBLOCK):
        state, y = block(state, tuple(x[i:i + SBLOCK] for x in (u, dt, b, c)))
        ys.append(y)
    return jnp.concatenate(ys)


def _mamba_layer(x, lp, m, kind):
    di, n, r = m["d_inner"], m["d_state"], m["dt_rank"]
    h = _rms(x, lp["mixer_norm"], m["eps"])
    xz = _mm(h, lp["in_proj"], kind)
    u, z = xz[:, :di], xz[:, di:]
    u = jax.nn.silu(_conv(u, lp["conv_w"], lp["conv_b"]))
    dbc = _mm(u, lp["x_proj"], kind)
    dt = _rms(dbc[:, :r], lp["dt_norm"], m["eps"])
    b = _rms(dbc[:, r:r + n], lp["b_norm"], m["eps"])
    c = _rms(dbc[:, r + n:], lp["c_norm"], m["eps"])
    dt = jax.nn.softplus(_mm(dt, lp["dt_proj"], kind) + lp["dt_bias"])
    a = -jnp.exp(lp["a_log"])
    y = recurrence(u, dt, a, b, c) + lp["d_skip"] * u
    y = y * jax.nn.silu(z)
    return _swiglu(x + _mm(y, lp["out_proj"], kind), lp, m, kind)


LAYER_FN = {"attn": _attn_layer, "mamba": _mamba_layer}


def _token_ce(h, embed, labels, kind):
    """Cross-entropy of every token of one row against its label through the
    tied head, (S,), nought where the label is masked; blocks of tokens."""
    @jax.checkpoint
    def block(hb, lab):
        logp = jax.nn.log_softmax(_mm(hb, embed.T, kind), -1)
        valid = lab != IGNORE
        ll = jnp.take_along_axis(logp, jnp.where(valid, lab, 0)[:, None], 1)
        return jnp.where(valid, -ll[:, 0], 0.0)

    qb = min(QBLOCK, h.shape[0])
    return jnp.concatenate([block(h[i:i + qb], labels[i:i + qb])
                            for i in range(0, h.shape[0], qb)])


def _close(x, final_norm, embed, labels, m, kind):
    """Final norm, tied head, summed cross-entropy of one row."""
    return jnp.sum(_token_ce(_rms(x, final_norm, m["eps"]), embed, labels,
                             kind))


def model_dims(cfg):
    """The sizes this file needs, from a configuration file's keys."""
    heads, d = cfg["num_attention_heads"], cfg["hidden_size"]
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    layers = cfg["num_hidden_layers"]
    if layers % period:
        raise ValueError("layers must be whole periods")
    return {
        "dim": d, "layers": layers, "heads": heads,
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim", d // heads),
        "ffn": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"], "period": period, "offset": offset,
        "periods": layers // period,
        "d_inner": cfg["mamba_expand"] * d, "d_state": cfg["mamba_d_state"],
        "d_conv": cfg["mamba_d_conv"], "dt_rank": cfg["mamba_dt_rank"],
    }


def groups(m):
    """[(group name, kind, layers a period)] in the order a period runs."""
    pre, post = m["offset"], m["period"] - 1 - m["offset"]
    out = [("mamba_pre", "mamba", pre), ("attn", "attn", 1),
           ("mamba_post", "mamba", post)]
    return [g for g in out if g[2] > 0]


def layer_order(m):
    """[(group, kind, index in the group's stack)] for the whole stack."""
    return [(name, kind, p * per + i)
            for p in range(m["periods"])
            for name, kind, per in groups(m) for i in range(per)]


def layer_shapes(m, kind):
    d, f = m["dim"], m["ffn"]
    ffn = {"ffn_norm": (d,), "w1": (d, f), "w3": (d, f), "w2": (f, d)}
    if kind == "attn":
        qd, kd = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
        return {"attn_norm": (d,), "wq": (d, qd), "wk": (d, kd),
                "wv": (d, kd), "wo": (qd, d), **ffn}
    di, n, r, k = m["d_inner"], m["d_state"], m["dt_rank"], m["d_conv"]
    return {"mixer_norm": (d,), "in_proj": (d, 2 * di), "conv_w": (k, di),
            "conv_b": (di,), "x_proj": (di, r + 2 * n), "dt_norm": (r,),
            "b_norm": (n,), "c_norm": (n,), "dt_proj": (r, di),
            "dt_bias": (di,), "a_log": (di, n), "d_skip": (di,),
            "out_proj": (di, d), **ffn}


def leaf_shapes(m):
    """name -> shape of every leaf, layer leaves stacked over their group."""
    out = {"tok_embed": (m["vocab"], m["dim"]), "final_norm": (m["dim"],)}
    for name, kind, per in groups(m):
        for leaf, shape in layer_shapes(m, kind).items():
            out[f"layers/{name}/{leaf}"] = (per * m["periods"],) + shape
    return out


def store_dtype(name, store):
    return jnp.float32 if name.rsplit("/", 1)[-1] in FLOAT32_LEAVES else store


def _draw_layer(leaf, key, shape, m):
    if leaf == "conv_w":
        lim = m["d_conv"] ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -lim, lim)
    if leaf == "dt_bias":
        span = jnp.log(DT_MAX) - jnp.log(DT_MIN)
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * span
                       + jnp.log(DT_MIN))
        step = jnp.maximum(step, DT_FLOOR)
        return step + jnp.log(-jnp.expm1(-step))
    std = 0.02
    if leaf in ("out_proj", "wo", "w2"):
        std = 0.02 / math.sqrt(2 * m["layers"])
    return jax.random.normal(key, shape, jnp.float32) * std


def draw_weights(seed, m, store):
    """Every leaf from the seed, in its storage type."""
    root = jax.random.key(seed)
    out = {
        "tok_embed": (jax.random.normal(
            jax.random.split(root, 10)[0], (m["vocab"], m["dim"]),
            jnp.float32) * 0.02).astype(store),
        "final_norm": jnp.ones((m["dim"],), store),
    }
    for g, (name, kind, per) in enumerate(groups(m)):
        count = per * m["periods"]
        drawn = MAMBA_DRAWN if kind == "mamba" else ATTN_DRAWN
        keys = dict(zip(drawn, jax.random.split(
            jax.random.fold_in(root, GROUP_FOLD + g), len(drawn))))
        for leaf, shape in layer_shapes(m, kind).items():
            full = f"layers/{name}/{leaf}"
            dtype = store_dtype(full, store)
            if leaf in keys:
                out[full] = jnp.stack([
                    _draw_layer(leaf, k, shape, m).astype(dtype)
                    for k in jax.random.split(keys[leaf], count)])
            elif leaf == "a_log":
                row = jnp.log(jnp.arange(1, m["d_state"] + 1,
                                         dtype=jnp.float32))
                out[full] = jnp.broadcast_to(
                    row, (count,) + shape).astype(dtype)
            elif leaf == "conv_b":
                out[full] = jnp.zeros((count,) + shape, dtype)
            else:
                out[full] = jnp.ones((count,) + shape, dtype)
    return out


def layer_params(p, group, index):
    """One layer's leaves by their short names, float32."""
    prefix = f"layers/{group}/"
    return {k[len(prefix):]: a[index].astype(jnp.float32)
            for k, a in p.items() if k.startswith(prefix)}


def forward_row(p, tokens, m, kind="f32"):
    """The whole model on one row, nothing hand-rolled: the normed final
    state (S, D) and the logits (S, V). ``p``: weights by leaf name. For tests
    at small sizes."""
    x = p["tok_embed"].astype(jnp.float32)[tokens]
    for group, layer_kind, index in layer_order(m):
        x = LAYER_FN[layer_kind](x, layer_params(p, group, index), m, kind)
    h = _rms(x, p["final_norm"].astype(jnp.float32), m["eps"])
    return h, _mm(h, p["tok_embed"].astype(jnp.float32).T, kind)


def batch_loss(p, inputs, labels, m, kind="f32"):
    """Mean cross-entropy over the valid tokens of a batch, as the equations
    give it (``jax.grad`` of it is every leaf's gradient). Small sizes only."""
    n_valid = max(int(np.sum(np.asarray(labels) != IGNORE)), 1)
    total = 0.0
    for b in range(inputs.shape[0]):
        lab = jnp.asarray(labels[b], jnp.int32)
        h, _ = forward_row(p, jnp.asarray(inputs[b], jnp.int32), m, kind)
        total = total + jnp.sum(_token_ce(
            h, p["tok_embed"].astype(jnp.float32), lab, kind))
    return total / n_valid


class Reference:
    """Weights from a seed, then ``step(inputs, labels)`` as the trainer's
    step: loss, clipped gradients, AdamW, storage rounding."""

    def __init__(self, cfg, optim, devices, precision="f32"):
        if len(list(devices)) != 1:
            raise ValueError("the Jamba reference runs on one device")
        self.m = model_dims(cfg)
        self.o = dict(optim)
        self.kind = precision
        self.shapes = leaf_shapes(self.m)
        self.store = _STORE[self.o["param_dtype"]]
        self.count = 0
        self._jit = {}

    def _fn(self, key, build):
        if key not in self._jit:
            self._jit[key] = build()
        return self._jit[key]

    # -- weights ------------------------------------------------------------
    def _fresh(self, seed):
        # the seed is an argument, not a constant of the program: one
        # compilation serves every seed (and every later run, from the cache)
        draw = self._fn("draw", lambda: jax.jit(
            lambda s: draw_weights(s, self.m, self.store)))
        return draw(jnp.int32(seed))

    def init(self, seed):
        self.seed = int(seed)
        self.p = self._fresh(self.seed)
        # the moments wait on the HOST between updates (module docstring)
        self.mu = {n: np.zeros(s, store_dtype(n, self.store))
                   for n, s in self.shapes.items()}
        self.nu = {n: np.zeros(s, store_dtype(n, self.store))
                   for n, s in self.shapes.items()}
        self.count = 0

    # -- the sweep ------------------------------------------------------------
    def _grads(self, inputs, labels):
        """Mean loss and float32 gradients of one batch, a row at a time."""
        m, kind = self.m, self.kind
        f32 = jnp.float32

        def wide(tree):
            return jax.tree_util.tree_map(lambda a: a.astype(f32), tree)

        def take(p, group, i):
            prefix = f"layers/{group}/"
            return {k[len(prefix):]: jax.lax.dynamic_index_in_dim(
                a, i, 0, keepdims=False)
                for k, a in p.items() if k.startswith(prefix)}

        fwd, bwd, add = {}, {}, {}
        for layer_kind, fn in LAYER_FN.items():
            layer = functools.partial(fn, m=m, kind=kind)
            fwd[layer_kind] = self._fn(("fwd", layer_kind), lambda layer=layer:
                                       jax.jit(lambda x, lp: layer(x, wide(lp))))
            bwd[layer_kind] = self._fn(("bwd", layer_kind), lambda layer=layer:
                                       jax.jit(lambda x, lp, dy: jax.vjp(
                                           layer, x, wide(lp))[1](dy)))
        take_j = self._fn("take", lambda: jax.jit(take, static_argnums=1))

        def add_layer(acc, dlp, group, i):
            prefix = f"layers/{group}/"
            return {k: (a.at[i].add(dlp[k[len(prefix):]])
                        if k.startswith(prefix) else a)
                    for k, a in acc.items()}

        add_j = self._fn("add", lambda: jax.jit(
            add_layer, static_argnums=2, donate_argnums=0))
        close = functools.partial(_close, m=m, kind=kind)
        close_vg = self._fn("close", lambda: jax.jit(
            lambda x, fnorm, emb, lab: jax.value_and_grad(
                lambda x, fnorm, emb: close(x, fnorm, emb, lab),
                argnums=(0, 1, 2))(x, fnorm.astype(f32), emb.astype(f32))))
        embed = self._fn("embed", lambda: jax.jit(
            lambda t, toks: t.astype(f32)[toks]))

        def add_ends(acc, dfnorm, demb, toks, dx, scale):
            out = dict(acc)
            out["final_norm"] = acc["final_norm"] + dfnorm * scale
            out["tok_embed"] = (acc["tok_embed"] + demb * scale).at[toks].add(dx)
            return out

        add_ends_j = self._fn("add_ends", lambda: jax.jit(
            add_ends, donate_argnums=0))
        acc = self._fn("zeros", lambda: jax.jit(
            lambda: {n: jnp.zeros(s, f32) for n, s in self.shapes.items()}))()

        n_valid = max(int(np.sum(labels != IGNORE)), 1)
        scale = jnp.float32(1.0 / n_valid)
        order = layer_order(m)
        total = 0.0
        for b in range(inputs.shape[0]):
            toks = jnp.asarray(inputs[b], jnp.int32)
            lab = jnp.asarray(labels[b], jnp.int32)
            xs = [embed(self.p["tok_embed"], toks)]
            for group, layer_kind, i in order:
                xs.append(fwd[layer_kind](xs[-1], take_j(self.p, group, i)))
            ce, (dx, dfnorm, demb) = close_vg(
                xs[-1], self.p["final_norm"], self.p["tok_embed"], lab)
            total += float(ce)
            dx = dx * scale
            for l in reversed(range(len(order))):
                group, layer_kind, i = order[l]
                dx, dlp = bwd[layer_kind](
                    xs[l], take_j(self.p, group, i), dx)
                acc = add_j(acc, dlp, group, i)
                xs[l + 1] = None
            acc = add_ends_j(acc, dfnorm, demb, toks, dx, scale)
        return total / n_valid, acc

    def _lr(self, count):
        base, w = self.o["learning_rate"], self.o["lr_warmup_steps"]
        ramp = max(w - 1, 1)
        if count >= ramp:
            return base
        first = base / max(w, 1)
        return first + (base - first) * count / ramp

    def step(self, inputs, labels):
        """One training step. Returns the loss, the global gradient norm
        before clipping, and the norm of each leaf's gradient as the optimizer
        gets it (clipped)."""
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

        def update(p, mu, nu, gk, clip, lr, c1, c2):
            f32 = jnp.float32
            gk = gk * clip
            m_ = b1 * mu.astype(f32) + (1 - b1) * gk
            v_ = b2 * nu.astype(f32) + (1 - b2) * gk * gk
            u = (m_ / c1) / (jnp.sqrt(v_ / c2) + o["adam_eps"])
            u = u + o["weight_decay"] * p.astype(f32)
            return ((p.astype(f32) - lr * u).astype(p.dtype),
                    m_.astype(mu.dtype), v_.astype(nu.dtype))

        upd = self._fn("update", lambda: jax.jit(
            update, donate_argnums=(0, 1, 2)))
        scalars = (jnp.float32(clip), jnp.float32(self._lr(self.count)),
                   jnp.float32(1 - b1 ** t), jnp.float32(1 - b2 ** t))
        # leaf by leaf: a leaf's moments come up from the host, are used
        # once, and go back; the gradient leaf is freed by its donation
        for k in list(self.p):
            self.p[k], mu, nu = upd(
                self.p[k], jnp.asarray(self.mu[k]), jnp.asarray(self.nu[k]),
                g.pop(k), *scalars)
            self.mu[k], self.nu[k] = np.asarray(mu), np.asarray(nu)
            del mu, nu
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
