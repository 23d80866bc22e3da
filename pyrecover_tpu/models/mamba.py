"""The Mamba-1 layer of a hybrid stack (AI21 Jamba's form of it), as pure
functions over stacked leaves like the attention layer in ``llama.py``.

With ``h = N_in(x)``: ``[u, z] = h W_in``; ``u <- silu(conv1d(u) + b_conv)``
(depthwise, causal, kernel ``mamba_d_conv``); ``[dt, B, C] = u W_x``
(``dt_rank``, ``d_state``, ``d_state``), each through an RMSNorm of its own
(Jamba's three inner norms); ``Dt = softplus(dt W_dt + b_dt)``;
``A = -exp(A_log)``; the selective scan (``ops/selective_scan.py``) gives
``y``; ``y <- y * silu(z)``; ``out = y W_out``; ``x <- x + out``, then the
SwiGLU sublayer every layer of the stack has. ``Dt``, ``A``, the recurrence
and ``y`` are float32 whatever the compute dtype, as the published kernels
compute them; ``A_log``, ``D`` and ``b_dt`` are HELD in float32 whatever
the parameter dtype (81,920 + 10,240 numbers a layer): a step of 3e-4 on
``log 16`` is under bfloat16's spacing and would be rounded away.
"""

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from pyrecover_tpu.ops.selective_scan import causal_conv1d, selective_scan
from pyrecover_tpu.telemetry.stepscopes import MAMBA_MIXER
from pyrecover_tpu.utils.dtypes import resolve_dtype

# the mixer's intermediates a remat policy may keep (utils/remat.py names
# them; on no rung of the ladder yet): the input product (u and z), the
# convolved and activated u, the float32 step, the scan's output
SSM_NAMES = ("ssm_in", "ssm_conv", "ssm_dt", "ssm_y")

# leaves drawn N(0, std) from the group's key, in this order (the plain
# reference draws them in the same order: benchmark/references/jamba.py)
MAMBA_DRAWN = ("in_proj", "conv_w", "x_proj", "dt_proj", "dt_bias",
               "out_proj", "w1", "w3", "w2")
FLOAT32_LEAVES = ("a_log", "d_skip", "dt_bias")
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


def mamba_leaf_shapes(cfg):
    """name -> shape of ONE Mamba layer's leaves."""
    d, di, n = cfg.dim, cfg.d_inner, cfg.mamba_d_state
    r, k, f = cfg.dt_rank, cfg.mamba_d_conv, cfg.ffn_hidden_dim
    return {
        "mixer_norm": (d,), "in_proj": (d, 2 * di), "conv_w": (k, di),
        "conv_b": (di,), "x_proj": (di, r + 2 * n), "dt_norm": (r,),
        "b_norm": (n,), "c_norm": (n,), "dt_proj": (r, di),
        "dt_bias": (di,), "a_log": (di, n), "d_skip": (di,),
        "out_proj": (di, d), "ffn_norm": (d,), "w1": (d, f), "w3": (d, f),
        "w2": (f, d),
    }


def init_mamba_layers(key, cfg, count, pdt, std, resid_std):
    """``count`` Mamba layers stacked on axis 0, one independent draw a
    layer and leaf. ``conv_w`` is uniform in +-k^-1/2 (torch's default for
    a depthwise kernel), ``dt_bias`` the inverse softplus of a step drawn
    log-uniform in [1e-3, 1e-1], ``a_log`` log(1..d_state), ``d_skip`` and
    the norms one, ``conv_b`` nought."""
    shapes = mamba_leaf_shapes(cfg)
    keys = dict(zip(MAMBA_DRAWN, jax.random.split(key, len(MAMBA_DRAWN))))
    n = cfg.mamba_d_state

    def conv_kernel(k, shape):
        lim = cfg.mamba_d_conv ** -0.5
        return jax.random.uniform(k, shape, jnp.float32, -lim, lim)

    def step_bias(k, shape):
        span = jnp.log(DT_MAX) - jnp.log(DT_MIN)
        step = jnp.exp(
            jax.random.uniform(k, shape, jnp.float32) * span
            + jnp.log(DT_MIN))
        step = jnp.maximum(step, DT_FLOOR)
        return step + jnp.log(-jnp.expm1(-step))

    def matrix(scale):
        return lambda k, shape: jax.random.normal(k, shape, jnp.float32) * scale

    drawers = {name: matrix(std) for name in MAMBA_DRAWN}
    drawers.update(conv_w=conv_kernel, dt_bias=step_bias,
                   out_proj=matrix(resid_std), w2=matrix(resid_std))

    def draw(name, k):
        return drawers[name](k, shapes[name])

    out = {}
    for name, shape in shapes.items():
        dtype = jnp.float32 if name in FLOAT32_LEAVES else pdt
        if name in keys:
            ks = jax.random.split(keys[name], count)
            out[name] = jnp.stack([draw(name, k).astype(dtype) for k in ks])
        elif name == "a_log":
            row = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
            out[name] = jnp.broadcast_to(row, (count,) + shape).astype(dtype)
        elif name == "conv_b":
            out[name] = jnp.zeros((count,) + shape, dtype)
        else:  # the norms and the skip
            out[name] = jnp.ones((count,) + shape, dtype)
    return out


def mamba_mixer(h, layer, config):
    """The mixer on a normed state ``h`` (batch, seq, dim)."""
    from pyrecover_tpu.models.llama import rms_norm

    cfg = config
    cdt = resolve_dtype(cfg.compute_dtype)
    f32 = jnp.float32
    di, n, r = cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank
    xz = checkpoint_name(h @ layer["in_proj"].astype(cdt), "ssm_in")
    u, z = xz[..., :di], xz[..., di:]
    u = checkpoint_name(
        jax.nn.silu(causal_conv1d(u, layer["conv_w"], layer["conv_b"])),
        "ssm_conv")
    dbc = u @ layer["x_proj"].astype(cdt)
    dt = rms_norm(dbc[..., :r], layer["dt_norm"], cfg.norm_eps)
    b = rms_norm(dbc[..., r:r + n], layer["b_norm"], cfg.norm_eps)
    c = rms_norm(dbc[..., r + n:], layer["c_norm"], cfg.norm_eps)
    dt = jnp.einsum(
        "bsr,rd->bsd", dt, layer["dt_proj"].astype(cdt),
        preferred_element_type=f32)
    dt = checkpoint_name(
        jax.nn.softplus(dt + layer["dt_bias"].astype(f32)), "ssm_dt")
    a = -jnp.exp(layer["a_log"].astype(f32))
    y = selective_scan(u, dt, a, b, c, layer["d_skip"])
    y = checkpoint_name(y, "ssm_y")
    y = (y * jax.nn.silu(z.astype(f32))).astype(cdt)
    return y @ layer["out_proj"].astype(cdt)


def mamba_block(x, layer, config):
    """One pre-norm Mamba layer: the mixer, then the feed-forward sublayer
    the attention layers have too. Returns ``(x, aux)`` as ``_block``."""
    from pyrecover_tpu.models.llama import ffn_sublayer, rms_norm
    from pyrecover_tpu.parallel.mesh import (
        AXIS_DATA, AXIS_FSDP, AXIS_SEQ, constrain,
    )

    with jax.named_scope(MAMBA_MIXER):
        h = rms_norm(x, layer["mixer_norm"], config.norm_eps)
        x = x + mamba_mixer(h, layer, config)
    x = constrain(x, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ, None)
    x, aux = ffn_sublayer(x, layer, config)
    return constrain(x, (AXIS_DATA, AXIS_FSDP), AXIS_SEQ, None), aux
