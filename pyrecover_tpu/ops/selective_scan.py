"""The selective state-space recurrence of a Mamba-1 mixer, and the
depthwise causal convolution that feeds it.

For every token t, channel c and state n (``a`` negative, all in float32
whatever the model computes in, as the published kernels do):

    s_t[c, n] = exp(dt_t[c] a[c, n]) s_{t-1}[c, n] + dt_t[c] b_t[n] u_t[c]
    y_t[c]    = sum_n c_t[n] s_t[c, n] + d[c] u_t[c],        s_0 = 0

The state is ``d_inner x d_state`` floats a TOKEN (81,920 at Jamba's
widths): ``[batch, seq, d_inner, d_state]`` is 2.7 GB a layer at 8,192
tokens and is never held. The sequence is cut in chunks of ``chunk``
tokens; the forward sweep keeps the state each chunk starts from
(``seq / chunk`` states, 10 MB a layer at 8,192 tokens and 256) and the
backward sweep, chunks last to first, recomputes the states inside one
chunk from its boundary before it runs the recurrence's adjoint over
them. One ``jax.custom_vjp`` holds that contract for both formulations:

``pallas``  the kernel pair ``ssm_scan_fwd`` / ``ssm_scan_bwd``: grid
            (batch, channel block, chunk), the chunks innermost and
            sequential with the state ``(d_state, channel block)`` in
            VMEM scratch, channels on the lanes and states on the
            sublanes, the tokens of a chunk in a loop of 8-token tiles.
``xla``     a ``lax.scan`` over chunks round a ``lax.scan`` over tokens,
            the chunk's adjoint by ``jax.vjp`` of the inner scan. What
            the CPU runs, what a mesh of several devices runs (a Mosaic
            call is not partitioned automatically), and the oracle the
            kernels are tested against (itself held to the recurrence
            token by token in tests/test_hybrid.py).

Document boundaries (``segment_ids``) are not here: the state would have
to be zeroed where a document starts.
"""

import functools
import os

import jax
import jax.numpy as jnp

IMPLS = ("auto", "xla", "pallas")
# tokens held at once: the backward sweep keeps one state a chunk and
# recomputes inside it. 128 / 256 / 512 read within 2 % on the v5e at
# Jamba's widths (PERF.md section 6), so it is no option of the model
SCAN_CHUNK = 256
# the XLA formulation unrolls this many tokens a loop trip (fewer, larger
# fusions; measured on the v5e, PERF.md section 6)
XLA_UNROLL = 8


def causal_conv1d(u, weight, bias):
    """Depthwise causal convolution over the sequence: ``u`` (b, s, d),
    ``weight`` (k, d) with ``weight[k-1]`` on the current token, ``bias``
    (d,): ``out_t = bias + sum_j weight[j] u_{t-(k-1)+j}``, tokens before
    the first read as nought."""
    k = weight.shape[0]
    s = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias.astype(u.dtype)
    for j in range(k):
        out = out + padded[:, j:j + s, :] * weight[j].astype(u.dtype)
    return out


def resolve_impl(impl, d, n, chunk):
    """``auto``: the kernels where one accelerator runs the program (or the
    Pallas interpreter was asked for) and they tile the shapes, else the XLA
    formulation. Kernels asked for by name on shapes they do not tile are an
    error, never a silent change of formulation."""
    if impl not in IMPLS:
        raise ValueError(f"selective scan impl {impl!r}: expected {IMPLS}")
    tiled = pallas_supported(d, n, chunk)
    if impl == "pallas" and not tiled:
        raise ValueError(
            f"selective scan impl 'pallas': the kernels tile whole lane "
            f"tiles of channels ({LANES}), whole sublane tiles of states "
            f"({GROUP}) and chunks of whole lane tiles of tokens; got "
            f"d_inner={d}, d_state={n}, chunk={chunk}"
        )
    if impl != "auto":
        return impl
    if not tiled:
        return "xla"  # the tests' toy widths
    if os.environ.get("PYRECOVER_PALLAS_INTERPRET", "") == "1":
        return "pallas"
    if jax.default_backend() != "tpu":
        return "xla"
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is not None and not mesh.empty and mesh.size > 1:
        return "xla"  # a Mosaic call is not partitioned automatically
    return "pallas"


# ---- the XLA formulation ----------------------------------------------------


def _chunk_sweep(h, xs, a_t):
    """One chunk, token by token. ``h`` (b, n, d) the state the chunk
    starts from; ``xs`` = (u, dt, b, c) time-major (l, b, ...), float32;
    ``a_t`` (n, d). Returns (state after the chunk, y (l, b, d))."""

    def step(h, x):
        u_t, dt_t, b_t, c_t = x
        decay = jnp.exp(dt_t[:, None, :] * a_t[None])
        h = decay * h + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(c_t[:, :, None] * h, axis=1)

    return jax.lax.scan(step, h, xs, unroll=XLA_UNROLL)


@jax.custom_vjp
def _scan_xla(u, dt, b, c, a_t):
    """Chunked arrays (nc, l, batch, ...) float32 -> y (nc, l, batch, d)."""
    return _scan_xla_fwd(u, dt, b, c, a_t)[0]


def _scan_xla_fwd(u, dt, b, c, a_t):
    h0 = jnp.zeros((u.shape[2], a_t.shape[0], a_t.shape[1]), jnp.float32)

    def chunk(h, xs):
        h_next, y = _chunk_sweep(h, xs, a_t)
        return h_next, (y, h)

    _, (y, bounds) = jax.lax.scan(chunk, h0, (u, dt, b, c))
    return y, (u, dt, b, c, a_t, bounds)


def _scan_xla_bwd(res, dy):
    u, dt, b, c, a_t, bounds = res

    def chunk(carry, xs):
        dh, da = carry
        h, dy_c, *ins = xs
        _, vjp = jax.vjp(_chunk_sweep, h, tuple(ins), a_t)
        dh, dins, da_c = vjp((dh, dy_c))
        return (dh, da + da_c), dins

    zero = jnp.zeros_like(bounds[0])
    (_, da), (du, ddt, db, dc) = jax.lax.scan(
        chunk, (zero, jnp.zeros_like(a_t)), (bounds, dy, u, dt, b, c),
        reverse=True,
    )
    return du, ddt, db, dc, da


_scan_xla.defvjp(_scan_xla_fwd, _scan_xla_bwd)


def _selective_scan_xla(u, dt, a, b, c, chunk):
    """u, dt (batch, s, d), b, c (batch, s, n), all float32, s a multiple
    of ``chunk``; a (d, n). Returns y (batch, s, d) without the skip."""
    bsz, s, d = u.shape
    nc = s // chunk

    def chunked(x):  # (batch, s, w) -> (nc, chunk, batch, w)
        return jnp.moveaxis(x, 0, 1).reshape(nc, chunk, bsz, x.shape[-1])

    y = _scan_xla(chunked(u), chunked(dt), chunked(b), chunked(c), a.T)
    return jnp.moveaxis(y.reshape(s, bsz, d), 0, 1)


# ---- the entry --------------------------------------------------------------


def selective_scan(u, dt, a, b, c, d_skip, *, chunk=None, impl="auto"):
    """The recurrence above. ``u`` (batch, seq, d) any float type, ``dt``
    (batch, seq, d) the positive step, ``a`` (d, n) negative, ``b`` / ``c``
    (batch, seq, n), ``d_skip`` (d,). Returns ``y`` (batch, seq, d) in
    float32. ``chunk`` defaults to ``SCAN_CHUNK``; a sequence that it does
    not divide is padded with tokens of step nought, which leave the state
    as it is."""
    f32 = jnp.float32
    u, dt, b, c = (x.astype(f32) for x in (u, dt, b, c))
    a, d_skip = a.astype(f32), d_skip.astype(f32)
    s = u.shape[1]
    chunk = max(min(int(chunk or SCAN_CHUNK), s), 1)
    pad = -s % chunk
    if pad:
        u, dt, b, c = (
            jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (u, dt, b, c)
        )
    impl = resolve_impl(impl, u.shape[-1], a.shape[-1], chunk)
    with jax.named_scope("ssm_scan"):
        if impl == "pallas":
            y = _scan_pallas(u, dt, b, c, a.T, chunk)
        else:
            y = _selective_scan_xla(u, dt, a, b, c, chunk)
    return y[:, :s] + d_skip * u[:, :s]


# ---- the kernel pair ---------------------------------------------------------
#
# Layout inside a kernel: channels on the 128 lanes, states on the
# sublanes, so the state of a channel block is (n, block_d) and every
# per-token row (u, dt, dy: (1, 128) a lane tile) broadcasts down the
# sublanes. B and C come in spread over a lane tile, (s, n, 128), so that a
# token's (n, 128) tile multiplies a lane tile of the state as it is: no
# transpose and no lane broadcast inside the loop, at 8 KB a token of extra
# reads. Tokens run in tiles of 8 (one float32 sublane tile): a tile of u,
# dt is loaded, its 8 rows are walked in order, and the 8 result rows are
# merged by sublane into one tile that is stored whole.

LANES = 128
GROUP = 8
# channels a kernel instance holds: measured on the v5e at Jamba's widths
# (tools/bench_selective_scan.py, PERF.md section 6: 256 / 512 / 1024 give
# 9.59 / 8.15 / 7.19 ms forward + backward)
DEFAULT_BLOCK_D = 1024
VMEM_LIMIT_BYTES = 64 * 2**20


def pallas_supported(d, n, chunk):
    """Shapes the kernels tile: whole lane tiles of channels, whole sublane
    tiles of states, chunks of whole lane tiles of tokens (the backward
    kernel lays a chunk's dB, dC out by token on the lanes)."""
    return d % LANES == 0 and n % GROUP == 0 and chunk % LANES == 0


def _block_d(d):
    bd = min(DEFAULT_BLOCK_D, d)
    while d % bd:
        bd -= LANES
    return bd


def _lane(j):
    return slice(j * LANES, (j + 1) * LANES)


def _advance(h, j, dt_r, u_r, a_ref, bt):
    """One token of one lane tile: the state after it."""
    return (jnp.exp(dt_r * a_ref[:, _lane(j)]) * h
            + (dt_r * u_r) * bt)


def _fwd_kernel(u_ref, dt_ref, bx_ref, cx_ref, a_ref, y_ref, hb_ref, h_scr,
                *, chunk, tiles):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    hb_ref[...] = h_scr[...]  # the state this chunk starts from
    sub = jax.lax.broadcasted_iota(jnp.int32, (GROUP, LANES), 0)

    def group(g, h):
        t0 = pl.multiple_of(g * GROUP, GROUP)
        u8 = u_ref[pl.ds(t0, GROUP), :]
        dt8 = dt_ref[pl.ds(t0, GROUP), :]
        h = list(h)
        y8 = [jnp.zeros((GROUP, LANES), jnp.float32)] * tiles
        for i in range(GROUP):
            bt, ct = bx_ref[t0 + i], cx_ref[t0 + i]
            for j in range(tiles):
                dt_r, u_r = dt8[i:i + 1, _lane(j)], u8[i:i + 1, _lane(j)]
                h[j] = _advance(h[j], j, dt_r, u_r, a_ref, bt)
                y_r = jnp.sum(ct * h[j], axis=0, keepdims=True)
                y8[j] = jnp.where(sub == i, y_r, y8[j])
        for j in range(tiles):
            y_ref[pl.ds(t0, GROUP), _lane(j)] = y8[j]
        return tuple(h)

    h = jax.lax.fori_loop(
        0, chunk // GROUP, group,
        tuple(h_scr[:, _lane(j)] for j in range(tiles)))
    for j in range(tiles):
        h_scr[:, _lane(j)] = h[j]


def _bwd_kernel(u_ref, dt_ref, bx_ref, cx_ref, a_ref, hb_ref, dy_ref,
                du_ref, ddt_ref, dbt_ref, dct_ref, da_ref, hs_scr, dh_scr,
                *, chunk, tiles):
    """Chunks arrive last to first. ``hs_scr[t + 1]`` is the state after
    token t of the chunk (``hs_scr[0]`` the boundary), recomputed here;
    ``dh_scr`` carries the state's adjoint into the chunk before."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    n = a_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    hs_scr[0] = hb_ref[...]

    def recompute(g, h):
        t0 = pl.multiple_of(g * GROUP, GROUP)
        u8 = u_ref[pl.ds(t0, GROUP), :]
        dt8 = dt_ref[pl.ds(t0, GROUP), :]
        h = list(h)
        for i in range(GROUP):
            bt = bx_ref[t0 + i]
            for j in range(tiles):
                dt_r, u_r = dt8[i:i + 1, _lane(j)], u8[i:i + 1, _lane(j)]
                h[j] = _advance(h[j], j, dt_r, u_r, a_ref, bt)
                hs_scr[t0 + i + 1, :, _lane(j)] = h[j]
        return tuple(h)

    jax.lax.fori_loop(
        0, chunk // GROUP, recompute,
        tuple(hb_ref[:, _lane(j)] for j in range(tiles)))

    sub = jax.lax.broadcasted_iota(jnp.int32, (GROUP, LANES), 0)
    lane_id = jax.lax.broadcasted_iota(jnp.int32, (n, LANES), 1)
    groups = LANES // GROUP
    dh = tuple(dh_scr[:, _lane(j)] for j in range(tiles))
    for q in reversed(range(chunk // LANES)):

        def group(k, carry, q=q):
            dh, dbt, dct = carry
            g = groups - 1 - k
            t0 = pl.multiple_of(q * LANES + g * GROUP, GROUP)
            u8 = u_ref[pl.ds(t0, GROUP), :]
            dt8 = dt_ref[pl.ds(t0, GROUP), :]
            dy8 = dy_ref[pl.ds(t0, GROUP), :]
            dh = list(dh)
            du8 = [jnp.zeros((GROUP, LANES), f32)] * tiles
            ddt8 = [jnp.zeros((GROUP, LANES), f32)] * tiles
            for i in reversed(range(GROUP)):
                t = t0 + i
                bt, ct = bx_ref[t], cx_ref[t]
                db_acc = jnp.zeros((n, LANES), f32)
                dc_acc = jnp.zeros((n, LANES), f32)
                for j in range(tiles):
                    sl = _lane(j)
                    dt_r, u_r = dt8[i:i + 1, sl], u8[i:i + 1, sl]
                    dy_r = dy8[i:i + 1, sl]
                    a_j = a_ref[:, sl]
                    # adjoint of the state after token t, all its uses in
                    dh_j = dh[j] + ct * dy_r
                    dc_acc = dc_acc + hs_scr[t + 1, :, sl] * dy_r
                    decay = jnp.exp(dt_r * a_j)
                    through = dh_j * hs_scr[t, :, sl] * decay
                    da_ref[:, sl] += through * dt_r
                    fed = jnp.sum(dh_j * bt, axis=0, keepdims=True)
                    ddt_r = jnp.sum(
                        through * a_j, axis=0, keepdims=True) + fed * u_r
                    db_acc = db_acc + dh_j * (dt_r * u_r)
                    dh[j] = decay * dh_j
                    du8[j] = jnp.where(sub == i, fed * dt_r, du8[j])
                    ddt8[j] = jnp.where(sub == i, ddt_r, ddt8[j])
                col = g * GROUP + i  # the token's lane in its 128-token tile
                dbt = jnp.where(
                    lane_id == col,
                    jnp.sum(db_acc, axis=1, keepdims=True), dbt)
                dct = jnp.where(
                    lane_id == col,
                    jnp.sum(dc_acc, axis=1, keepdims=True), dct)
            for j in range(tiles):
                du_ref[pl.ds(t0, GROUP), _lane(j)] = du8[j]
                ddt_ref[pl.ds(t0, GROUP), _lane(j)] = ddt8[j]
            return tuple(dh), dbt, dct

        zero = jnp.zeros((n, LANES), f32)
        dh, dbt, dct = jax.lax.fori_loop(0, groups, group, (dh, zero, zero))
        dbt_ref[q] = dbt
        dct_ref[q] = dct
    for j in range(tiles):
        dh_scr[:, _lane(j)] = dh[j]


def _interpret():
    from pyrecover_tpu.ops.flash_attention import _interpret as flag

    return flag()


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
    )


def _spread(x):
    """(batch, s, n) -> (batch, s, n, 128): a token's B or C as a lane tile."""
    return jnp.broadcast_to(x[..., None], (*x.shape, LANES))


def _pallas_fwd_call(u, dt, b, c, a_t, chunk):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, d = u.shape
    n = a_t.shape[0]
    bd = _block_d(d)
    nc, nd = s // chunk, d // bd
    rows = pl.BlockSpec((None, chunk, bd), lambda bi, di, ci: (bi, ci, di))
    tiles_bc = pl.BlockSpec(
        (None, chunk, n, LANES), lambda bi, di, ci: (bi, ci, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, tiles=bd // LANES),
        grid=(bsz, nd, nc),
        in_specs=[
            rows, rows, tiles_bc, tiles_bc,
            pl.BlockSpec((n, bd), lambda bi, di, ci: (0, di)),
        ],
        out_specs=[
            rows,
            pl.BlockSpec((None, None, n, bd),
                         lambda bi, di, ci: (bi, ci, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nc, n, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="ssm_scan_fwd",
    )(u, dt, _spread(b), _spread(c), a_t)


def _pallas_bwd_call(u, dt, b, c, a_t, bounds, dy, chunk):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, d = u.shape
    n = a_t.shape[0]
    bd = _block_d(d)
    nc, nd = s // chunk, d // bd
    last = nc - 1
    rows = pl.BlockSpec(
        (None, chunk, bd), lambda bi, di, ci: (bi, last - ci, di))
    tiles_bc = pl.BlockSpec(
        (None, chunk, n, LANES), lambda bi, di, ci: (bi, last - ci, 0, 0))
    by_token = pl.BlockSpec(
        (None, None, chunk // LANES, n, LANES),
        lambda bi, di, ci: (bi, di, last - ci, 0, 0))
    f32 = jnp.float32
    du, ddt, dbt, dct, da = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, tiles=bd // LANES),
        grid=(bsz, nd, nc),
        in_specs=[
            rows, rows, tiles_bc, tiles_bc,
            pl.BlockSpec((n, bd), lambda bi, di, ci: (0, di)),
            pl.BlockSpec((None, None, n, bd),
                         lambda bi, di, ci: (bi, last - ci, 0, di)),
            rows,
        ],
        out_specs=[
            rows, rows, by_token, by_token,
            pl.BlockSpec((None, n, bd), lambda bi, di, ci: (bi, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), f32),
            jax.ShapeDtypeStruct((bsz, s, d), f32),
            jax.ShapeDtypeStruct((bsz, nd, s // LANES, n, LANES), f32),
            jax.ShapeDtypeStruct((bsz, nd, s // LANES, n, LANES), f32),
            jax.ShapeDtypeStruct((bsz, n, d), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((chunk + 1, n, bd), f32),
            pltpu.VMEM((n, bd), f32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="ssm_scan_bwd",
    )(u, dt, _spread(b), _spread(c), a_t, bounds, dy)

    def by_row(x):  # (batch, nd, s/128, n, 128) -> (batch, s, n)
        x = jnp.sum(x, axis=1)
        return jnp.swapaxes(x, 2, 3).reshape(bsz, s, n)

    return du, ddt, by_row(dbt), by_row(dct), jnp.sum(da, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan_pallas(u, dt, b, c, a_t, chunk):
    return _pallas_fwd_call(u, dt, b, c, a_t, chunk)[0]


def _scan_pallas_fwd(u, dt, b, c, a_t, chunk):
    y, bounds = _pallas_fwd_call(u, dt, b, c, a_t, chunk)
    return y, (u, dt, b, c, a_t, bounds)


def _scan_pallas_bwd(chunk, res, dy):
    return _pallas_bwd_call(*res, dy, chunk)


_scan_pallas.defvjp(_scan_pallas_fwd, _scan_pallas_bwd)
