"""Plain float32 reference for Ouro, a looped language model.

Ouro-2.6B (ByteDance; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741; the public ``modeling_ouro.py`` beside its ``config.json``)
runs ONE stack of L decoder layers T = ``total_ut_steps`` times over the same
weights. With h(0) = E[x], for t = 1..T:

    u <- h(t-1);  for l = 1..L:  a = u + N2_l(Attn_l(N1_l(u)))
                                 u = a + N4_l(MLP_l(N3_l(a)))
    h(t) = N_f(u);   logits(t) = h(t) W_out;   lam_t = sigmoid(w_g . h(t) + b_g)

N are RMSNorms with their own scales, four a layer (the block is "sandwich"
normed: one before and one after each sublayer, the second inside the residual
branch); Attn is causal multi-head attention with rotary positions and no
biases; MLP is W2(silu(W1 x) * W3 x). The final norm N_f closes EVERY pass:
its output is what the head reads and what the next pass starts from. Per
token the exit distribution is p_1 = lam_1, p_t = lam_t prod_{j<t}(1 - lam_j)
for t < T, and p_T = prod_{j<T}(1 - lam_j), the remainder. The pre-training
loss (the paper's first stage) is, averaged over the valid tokens,

    sum_t p_t CE(logits(t), y)  -  beta H(p),     H(p) = -sum_t p_t log p_t.

Forward, loss, the gradient of every leaf (the gate and the four norms
included) and the AdamW update, in straightforward ``jax.numpy`` float32 with
every product at ``Precision.HIGHEST``. No kernel, no cache, no batching
beyond a Python loop over the rows of the batch; it imports nothing of the
program under test and takes nothing the program has made: the weights are
drawn here from the seed, the only inputs are the token rows the step was fed.

Departures from the published description, each because the system under test
trains that way and the comparison is of the same mathematics:

* Weights are drawn N(0, 0.02) (residual outputs ``wo``/``w2`` scaled by
  ``1/sqrt(2 L)``, the gate's weight N(0, 0.02), its bias nought) from
  ``jax.random`` keys split off the seed in the order of the program's
  initialiser, one draw per layer, the gate's key folded in beside them;
  rounded to the storage type the configuration states (bfloat16).
* Rotary pairs are adjacent elements ``(2i, 2i+1)`` (the original Meta
  layout; the Hugging Face checkpoints use half-split pairs, which is the same
  function under a fixed permutation of each head).
* ``loss`` as returned is the expected cross-entropy ``sum_t p_t CE_t``
  without the entropy bonus, because that is what the trainer logs; the
  gradients are of the whole objective.
* The paper's second stage (the gate trained alone on a frozen model) and
  exits before the last pass at inference are not here: the cell is of
  first-stage pre-training.
* Storage is what the configuration states: parameters and both Adam moments
  are kept in bfloat16 between steps (every update is computed in float32 and
  rounded once when stored).

Memory: one row of the batch at a time. The forward keeps the state each pass
starts from (T + 1 rows); the backward takes the passes last to first and
RECOMPUTES each pass's L layer inputs before sweeping it in reverse
(``jax.vjp`` per layer, attention in blocks of query rows, the head in blocks
of tokens), gradients summed in float32 — so L + T rows are held, not T L, and
the reference fits beside nothing else on the chip: run it after the program's
state is freed. On several devices every weight is split along its widest
free dimension and the tokens are replicated.

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
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

HI = jax.lax.Precision.HIGHEST
IGNORE = -100
QBLOCK = 1024  # attention, and the head, in blocks of this many token rows

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


def _layer(x, lp, m, kind):
    """One sandwich-normed decoder block on one row x: (S, D), weights ``lp``
    in float32."""
    s = x.shape[0]
    H, KV, hd = m["heads"], m["kv_heads"], m["head_dim"]
    h = _rms(x, lp["attn_norm"], m["eps"])
    q = _rope(_mm(h, lp["wq"], kind).reshape(s, H, hd), m["theta"])
    k = _rope(_mm(h, lp["wk"], kind).reshape(s, KV, hd), m["theta"])
    v = _mm(h, lp["wv"], kind).reshape(s, KV, hd)
    a = _attention(q.reshape(s, KV, H // KV, hd), k, v, kind)
    a = _mm(a.reshape(s, H * hd), lp["wo"], kind)
    x = x + _rms(a, lp["attn_post_norm"], m["eps"])
    h = _rms(x, lp["ffn_norm"], m["eps"])
    f = _mm(jax.nn.silu(_mm(h, lp["w1"], kind)) * _mm(h, lp["w3"], kind),
            lp["w2"], kind)
    return x + _rms(f, lp["ffn_post_norm"], m["eps"])


def _token_ce(h, output, labels, kind):
    """Cross-entropy of every token of one row against its label, (S,), nought
    where the label is masked; the head's product in blocks of tokens."""
    @jax.checkpoint
    def block(hb, lab):
        logp = jax.nn.log_softmax(_mm(hb, output, kind), -1)
        valid = lab != IGNORE
        ll = jnp.take_along_axis(logp, jnp.where(valid, lab, 0)[:, None], 1)
        return jnp.where(valid, -ll[:, 0], 0.0)

    qb = min(QBLOCK, h.shape[0])
    return jnp.concatenate([block(h[i:i + qb], labels[i:i + qb])
                            for i in range(0, h.shape[0], qb)])


def _close(u, final_norm, output, gate_w, gate_b, labels, m, kind):
    """What closes a pass: h = N_f(u), then the head's cross-entropy of every
    token and the gate's logit, both read from h. Returns (h, ce, g)."""
    h = _rms(u, final_norm, m["eps"])
    g = _mm(h, gate_w, kind)[:, 0] + gate_b[0]
    return h, _token_ce(h, output, labels, kind), g


def exit_probs(g):
    """(T, S) gate logits -> (T, S) exit probabilities, by the equations:
    lam = sigmoid(g); p_t = lam_t prod_{j<t}(1 - lam_j); the last pass takes
    the remainder prod_{j<T}(1 - lam_j)."""
    lam = jax.nn.sigmoid(g)
    rest = jnp.ones_like(lam[0])
    out = []
    for t in range(g.shape[0] - 1):
        out.append(lam[t] * rest)
        rest = rest * (1.0 - lam[t])
    out.append(rest)
    return jnp.stack(out)


def _combine(ce, g, labels, beta):
    """Summed over the row's valid tokens: (objective, expected CE,
    per-pass CE (T,), exit mass (T,), entropy)."""
    valid = (labels != IGNORE)[None, :]
    p = jnp.where(valid, exit_probs(g), 0.0)
    expected = jnp.sum(p * ce)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                                 0.0))
    return expected - beta * entropy, (
        expected, jnp.sum(ce, axis=1), jnp.sum(p, axis=1), entropy)


def forward_row(p, tokens, labels, m, kind="f32"):
    """The whole model on one row, nothing hand-rolled: logits of every pass
    (T, S, V), gate logits (T, S), per-token CE (T, S). ``p``: float32
    weights by leaf name. For tests at small sizes."""
    h = p["tok_embed"][tokens]
    logits, gs, ces = [], [], []
    for _ in range(m["loops"]):
        u = h
        for l in range(m["layers"]):
            lp = {k[len("layers/"):]: a[l] for k, a in p.items()
                  if k.startswith("layers/")}
            u = _layer(u, lp, m, kind)
        h, ce, g = _close(u, p["final_norm"], p["output"], p["exit_gate_w"],
                          p["exit_gate_b"], labels, m, kind)
        logits.append(_mm(h, p["output"], kind))
        gs.append(g)
        ces.append(ce)
    return jnp.stack(logits), jnp.stack(gs), jnp.stack(ces)


def batch_loss(p, inputs, labels, m, kind="f32"):
    """The objective of a batch, as the equations give it: mean over the valid
    tokens of sum_t p_t CE_t - beta H(p). Returns (objective, expected CE).
    For tests at small sizes (``jax.grad`` of it is every leaf's gradient)."""
    n_valid = max(int(np.sum(np.asarray(labels) != IGNORE)), 1)
    obj = exp = 0.0
    for b in range(inputs.shape[0]):
        lab = jnp.asarray(labels[b], jnp.int32)
        _, g, ce = forward_row(p, jnp.asarray(inputs[b], jnp.int32), lab, m,
                               kind)
        o, (e, *_) = _combine(ce, g, lab, m["beta"])
        obj, exp = obj + o, exp + e
    return obj / n_valid, exp / n_valid


def model_dims(cfg):
    """The sizes this file needs, from a configuration file's keys (Hugging
    Face names; ``exit_beta`` from the file's ``trainer_model``, since the
    published config has no such key)."""
    heads = cfg["num_attention_heads"]
    return {
        "dim": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "heads": heads, "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim", cfg["hidden_size"] // heads),
        "ffn": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
        "loops": cfg["total_ut_steps"],
        "beta": float(cfg.get("trainer_model", {}).get("exit_beta", 0.1)),
    }


def leaf_shapes(m):
    """name -> (shape, init std | None for ones | 0.0 for noughts, the
    dimension split over devices). Names are the program's tree paths joined
    by '/'."""
    D, L, F, V = m["dim"], m["layers"], m["ffn"], m["vocab"]
    qd, kd = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    std, rstd = 0.02, 0.02 / math.sqrt(2 * L)
    return {
        "tok_embed": ((V, D), std, 1),
        "layers/attn_norm": ((L, D), None, None),
        "layers/wq": ((L, D, qd), std, 2),
        "layers/wk": ((L, D, kd), std, 2),
        "layers/wv": ((L, D, kd), std, 2),
        "layers/wo": ((L, qd, D), rstd, 1),
        "layers/attn_post_norm": ((L, D), None, None),
        "layers/ffn_norm": ((L, D), None, None),
        "layers/w1": ((L, D, F), std, 2),
        "layers/w3": ((L, D, F), std, 2),
        "layers/w2": ((L, F, D), rstd, 1),
        "layers/ffn_post_norm": ((L, D), None, None),
        "final_norm": ((D,), None, None),
        "output": ((D, V), std, 1),
        "exit_gate_w": ((D, 1), std, None),
        "exit_gate_b": ((1,), 0.0, None),
    }


# the program's initialiser splits the seed's key in ten and hands them out so;
# the gate's key is the seed's key with 10 folded in
_KEY_OF = {
    "tok_embed": 0, "layers/wq": 1, "layers/wk": 2, "layers/wv": 3,
    "layers/wo": 4, "layers/w1": 5, "layers/w3": 6, "layers/w2": 7,
    "output": 8,
}
_GATE_FOLD = 10


def draw_weights(seed, m, store):
    """Every leaf from the seed, in the storage type."""
    L = m["layers"]
    root = jax.random.key(seed)
    keys = jax.random.split(root, 10)

    def normal(key, shape, std):
        z = jax.random.normal(key, shape, dtype=jnp.float32) * std
        return z.astype(store)

    out = {}
    for name, (shape, std, _) in leaf_shapes(m).items():
        if std is None:
            out[name] = jnp.ones(shape, store)
        elif std == 0.0:
            out[name] = jnp.zeros(shape, store)
        elif name == "exit_gate_w":
            out[name] = normal(jax.random.fold_in(root, _GATE_FOLD), shape, std)
        elif name.startswith("layers/"):
            ks = jax.random.split(keys[_KEY_OF[name]], L)
            out[name] = jnp.stack([normal(k, shape[1:], std) for k in ks])
        else:
            out[name] = normal(keys[_KEY_OF[name]], shape, std)
    return out


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
        self.last = {}  # the last step's per-pass readings, for a by-hand look
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

    # -- weights ------------------------------------------------------------
    def _fresh(self, seed):
        # the seed is an argument, not a constant of the program: one
        # compilation serves every seed (and every later run, from the cache)
        sh = {n: self._sharding(n) for n in self.shapes}
        draw = self._fn("draw", lambda: jax.jit(
            lambda s: draw_weights(s, self.m, self.store), out_shardings=sh))
        return draw(jnp.int32(seed))

    def _zeros(self, dtype):
        make = self._fn(("zeros", dtype), lambda: jax.jit(
            lambda: {n: jnp.zeros(s[0], dtype) for n, s in self.shapes.items()},
            out_shardings={n: self._sharding(n) for n in self.shapes}))
        return make()

    def init(self, seed):
        self.seed = int(seed)
        self.p = self._fresh(self.seed)
        self.mu, self.nu = self._zeros(self.store), self._zeros(self.store)
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
        """Expected loss and float32 gradients of one batch, a row at a time,
        the passes of a row last to first, each recomputed before its sweep."""
        m, kind = self.m, self.kind
        rep = self._replicated()
        layer = functools.partial(_layer, m=m, kind=kind)
        close = functools.partial(_close, m=m, kind=kind)
        ends = ("final_norm", "output", "exit_gate_w", "exit_gate_b")

        def wide(tree):  # gradients are taken in float32, not in storage
            return jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), tree)

        fwd = self._fn("fwd", lambda: jax.jit(
            lambda x, lp: layer(x, wide(lp)), out_shardings=rep))
        bwd = self._fn("bwd", lambda: jax.jit(
            lambda x, lp, dy: jax.vjp(layer, x, wide(lp))[1](dy)))
        close_fwd = self._fn("close", lambda: jax.jit(
            lambda u, e, lab: close(u, *wide(e), lab),
            out_shardings=(rep, rep, rep)))

        def close_bwd(u, e, lab, dh, dce, dg):
            _, vjp = jax.vjp(lambda u, *w: close(u, *w, lab), u, *wide(e))
            du, *dends = vjp((dh, dce, dg))
            return du, dends

        close_bwd = self._fn("close_bwd", lambda: jax.jit(close_bwd))

        def combine(ce, g, lab, scale):
            (_, extras), vjp = jax.vjp(
                lambda c, gg: _combine(c, gg, lab, m["beta"]), ce, g)
            zero = jax.tree_util.tree_map(jnp.zeros_like, extras)
            return extras, vjp((scale, zero))

        combine = self._fn("combine", lambda: jax.jit(combine))
        embed = self._fn("embed", lambda: jax.jit(
            lambda t, toks: t.astype(jnp.float32)[toks], out_shardings=rep))

        def add_layer(acc, dlp, l):
            return {k: (a.at[l].add(dlp[k[len("layers/"):]])
                        if k.startswith("layers/") else a)
                    for k, a in acc.items()}

        add_layer = self._fn("add_layer", lambda: jax.jit(
            add_layer, donate_argnums=0))

        def add_ends(acc, dends):
            return {**acc, **{k: acc[k] + d for k, d in zip(ends, dends)}}

        add_ends = self._fn("add_ends", lambda: jax.jit(
            add_ends, donate_argnums=0))
        add_embed = self._fn("add_embed", lambda: jax.jit(
            lambda acc, toks, dx: {
                **acc, "tok_embed": acc["tok_embed"].at[toks].add(dx)},
            donate_argnums=0))
        acc = self._zeros(jnp.float32)

        B = inputs.shape[0]
        n_valid = max(int(np.sum(labels != IGNORE)), 1)
        L, T = m["layers"], m["loops"]
        scale = jnp.float32(1.0 / n_valid)
        e = tuple(self.p[k] for k in ends)
        total = None
        for b in range(B):
            toks = jnp.asarray(inputs[b], jnp.int32)
            lab = jnp.asarray(labels[b], jnp.int32)
            hs, ces, gs = [embed(self.p["tok_embed"], toks)], [], []
            for _ in range(T):
                u = hs[-1]
                for l in range(L):
                    u = fwd(u, self._layer_params(l))
                h, ce, g = close_fwd(u, e, lab)
                hs.append(h), ces.append(ce), gs.append(g)
            extras, (dce, dg) = combine(
                jnp.stack(ces), jnp.stack(gs), lab, scale)
            extras = [np.asarray(x, np.float64) for x in extras]
            total = extras if total is None else [
                a + x for a, x in zip(total, extras)]
            dh = jnp.zeros_like(hs[-1])  # nothing reads the last pass's state
            for t in reversed(range(T)):
                xs = [hs[t]]
                for l in range(L):
                    xs.append(fwd(xs[-1], self._layer_params(l)))
                dx, dends = close_bwd(xs[-1], e, lab, dh, dce[t], dg[t])
                acc = add_ends(acc, dends)
                for l in reversed(range(L)):
                    dx, dlp = bwd(xs[l], self._layer_params(l), dx)
                    acc = add_layer(acc, dlp, l)
                dh = dx
            acc = add_embed(acc, toks, dh)
        expected, loop_ce, mass, entropy = (x / n_valid for x in total)
        self.last = {"loop_ce": loop_ce, "exit_mass": mass,
                     "exit_entropy": float(entropy)}
        return float(expected), acc

    def _lr(self, count):
        base, w = self.o["learning_rate"], self.o["lr_warmup_steps"]
        ramp = max(w - 1, 1)
        if count >= ramp:
            return base
        first = base / max(w, 1)
        return first + (base - first) * count / ramp

    def step(self, inputs, labels):
        """One training step. Returns the loss (the expected cross-entropy
        over the exits, as the trainer logs it), the global gradient norm
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
