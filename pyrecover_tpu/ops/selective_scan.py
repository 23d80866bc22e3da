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
            (batch, chunk, channel block), the chunks sequential with
            every block's state in VMEM scratch; a token's channel
            block fills whole vector registers and every state has
            registers of its own, the tokens of a chunk in a loop of
            8-token tiles.
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

from pyrecover_tpu.telemetry.stepscopes import SSM_SCAN

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
            f"selective scan impl 'pallas': the kernels tile whole registers "
            f"of channels ({REGISTER}), a power of two of states up to "
            f"{LANES // 2} and chunks of whole {GROUP}-token tiles; got "
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
    dt, b, c = (x.astype(f32) for x in (dt, b, c))
    a, d_skip = a.astype(f32), d_skip.astype(f32)
    s = u.shape[1]
    chunk = max(min(int(chunk or SCAN_CHUNK), s), 1)
    pad = -s % chunk
    if pad:
        u, dt, b, c = (
            jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (u, dt, b, c)
        )
    impl = resolve_impl(impl, u.shape[-1], a.shape[-1], chunk)
    with jax.named_scope(SSM_SCAN):
        if impl == "pallas":
            # the kernels read a bfloat16 u at its own width and widen it
            wide = u if u.dtype == jnp.bfloat16 else u.astype(f32)
            y = _scan_pallas(wide, dt, b, c, a.T, chunk)
        else:
            y = _selective_scan_xla(u.astype(f32), dt, a, b, c, chunk)
    return y[:, :s] + d_skip * u[:, :s].astype(f32)


# ---- the kernel pair ---------------------------------------------------------
#
# Layout inside a kernel: a token's channel block fills whole vector
# registers and every state has registers of its own. With the channel axis
# cut in lane tiles, a block of ``tiles`` lane tiles is one ``(tiles, 128)``
# value a token for each of u, dt, dy, y (one float32 register at 1024
# channels) and the state is ``d_state`` such values. So the token loop is
# the recurrence and nothing else: dt, dt u and dy are registers as loaded,
# the sums over states are adds of whole registers, a token's y, du, ddt are
# stored whole. B and C enter at their own size, ``(chunk, d_state)``; the
# cross-lane unit spreads a chunk of them over the lanes ONCE for all its
# channel blocks (``_spread``: the grid walks the channel blocks innermost,
# the state of every block in scratch) and a token's ``b[t, k]`` is then a
# load that broadcasts one sublane.
#
# In HBM nothing moves for it. A ``(seq, d)`` array is held in tiles of 8
# tokens by 128 channels (at 2 bytes too), so a token's lane tiles lie one
# tile apart: ``_tiled`` relabels the array as (seq / 8, d / 128 * 8, 128),
# rows (lane tile, token of the tile), which is the same bytes (XLA compiles
# it to a bitcast), and a token's value is a load of every 8th row.
#
# The backward kernel's sums over CHANNELS (dB, dC: one number a token and
# state, 32 whole registers a token to be summed) are what this layout has
# to pay for. The token loop stores the products as they are; after ``TRIP``
# tiles of 8 tokens, loads of every 4th row bring the tokens back onto the
# sublanes, so that ONE cross-lane add sums a product of 8 tokens
# (``_channel_sums``). What was measured on the v5e and shaped this
# (PERF.md section 6): a cross-lane operation answers ~110 cycles late and
# takes ~6 cycles of its unit, a rotate of the lanes is far dearer than the
# one-operation add, strided loads and stores cost what plain ones do.

LANES = 128
GROUP = 8       # tokens a tile: the sublanes of a float32 tile in HBM
REGISTER = GROUP * LANES  # channels that fill a float32 register a token
# channels a kernel instance holds: 1024 fill a register a token; twice that
# are two registers a token and twice the states in flight (Jamba's 5120 do
# not divide by it). tools/bench_selective_scan.py, PERF.md section 6
DEFAULT_BLOCK_D = 1024
VMEM_LIMIT_BYTES = 64 * 2**20
# tiles whose sums over channels the backward kernel takes together, their
# chains side by side: 2 / 4 / 8 read 4.10 / 4.02 / 3.96 ms a call (section 6)
TRIP = 8
LOG2_E = 1.4426950408889634
LN_2 = 0.6931471805599453


def pallas_supported(d, n, chunk):
    """Shapes the kernels tile: channels that fill whole registers a token
    (8 lane tiles), chunks of whole 8-token tiles, and a power of two of
    states whose dB and dC fit the lanes of one register."""
    return (d % REGISTER == 0 and chunk % GROUP == 0
            and n & (n - 1) == 0 and 1 <= n <= LANES // 2)


def _trip(chunk):
    """Tiles of 8 tokens whose sums over channels the backward kernel takes
    together."""
    return next(t for t in (TRIP, 4, 2, 1) if chunk // GROUP % t == 0)


def _block_d(d):
    bd = min(DEFAULT_BLOCK_D, d)
    while d % bd:
        bd -= REGISTER
    return bd


def _tiled(x):
    """(batch, s, d) -> (batch, s / 8, d / 128 * 8, 128), rows (lane tile,
    token of the tile): the bytes XLA holds the array in, relabelled."""
    bsz, s, d = x.shape
    x = x.reshape(bsz, s // GROUP, GROUP, d // LANES, LANES)
    return jnp.swapaxes(x, 2, 3).reshape(bsz, s // GROUP, d // LANES * GROUP, LANES)


def _untiled(x):
    bsz, groups, rows, _ = x.shape
    x = x.reshape(bsz, groups, rows // GROUP, GROUP, LANES)
    return jnp.swapaxes(x, 2, 3).reshape(bsz, groups * GROUP, rows // GROUP * LANES)


def _token(ref, g, i, tiles):
    """Token ``i`` of tile ``g``: its ``tiles`` lane tiles as one value."""
    from jax.experimental import pallas as pl

    return ref[g, pl.ds(i, tiles, stride=GROUP), :]


def _token_bf16(ref, g, i, tiles):
    """The same of a bfloat16 array, whose 32-bit rows hold tokens 2j and
    2j + 1 of a tile in their low and high halves; widened."""
    from jax.experimental import pallas as pl

    rows = pl.ds(i // 2, tiles, stride=GROUP // 2)
    w = ref.bitcast(jnp.uint32)[0, g, rows, :]
    w = w << 16 if i % 2 == 0 else w & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(w, jnp.float32)


def _put_token(ref, g, i, tiles, value):
    from jax.experimental import pallas as pl

    ref[g, pl.ds(i, tiles, stride=GROUP), :] = value


def _spread(ref, scr, chunk):
    """A chunk of B or C, (chunk, n), over the lanes: row 8 k + i of
    ``scr[g]`` holds ``x[8 g + i, k]`` in every lane. The cross-lane unit
    does it, 8 tokens a broadcast, once a chunk for all its channel blocks."""
    from jax.experimental import pallas as pl

    def tile(g, carry):
        rows = ref[pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP), :]
        for k in range(ref.shape[1]):
            scr[g, pl.ds(k * GROUP, GROUP), :] = jnp.broadcast_to(
                rows[:, k:k + 1], (GROUP, LANES))
        return carry

    jax.lax.fori_loop(0, chunk // GROUP, tile, 0)


def _splat(scr, k, g, i, tiles):
    """``x[8 g + i, k]`` of the chunk spread in ``scr`` in every place of a
    token's value (a load that broadcasts one sublane, at a fixed distance
    from the tile's first row)."""
    from jax.experimental import pallas as pl

    return jnp.broadcast_to(scr[g, pl.ds(k * GROUP + i, 1), :], (tiles, LANES))


def _advance(h, dt, dtu, a2, bs_scr, k, g, i, tiles):
    """State ``k`` after token ``i`` of tile ``g``: exp(dt a) as 2^(dt a2),
    ``a2`` = a log2 e (the product with log2 e once a chunk, not a token)."""
    return jnp.exp2(dt * a2) * h + dtu * _splat(bs_scr, k, g, i, tiles)


def _fwd_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, hb_ref,
                h_scr, bs_scr, cs_scr, *, chunk, tiles, token):
    from jax.experimental import pallas as pl

    n = a_ref.shape[0]
    di = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_scr[di] = jnp.zeros(h_scr.shape[1:], jnp.float32)

    @pl.when(di == 0)
    def _():
        _spread(b_ref, bs_scr, chunk)
        _spread(c_ref, cs_scr, chunk)

    hb_ref[...] = h_scr[di]  # the state this chunk starts from
    a2 = [a_ref[k] * LOG2_E for k in range(n)]

    def group(g, h):
        h = list(h)
        for i in range(GROUP):
            u, dt = token(u_ref, g, i, tiles), _token(dt_ref, g, i, tiles)
            dtu = dt * u
            y = None
            for k in range(n):
                h[k] = _advance(h[k], dt, dtu, a2[k], bs_scr, k, g, i, tiles)
                y_k = h[k] * _splat(cs_scr, k, g, i, tiles)
                y = y_k if y is None else y + y_k
            _put_token(y_ref, g, i, tiles, y)
        return tuple(h)

    h = jax.lax.fori_loop(
        0, chunk // GROUP, group, tuple(h_scr[di, k] for k in range(n)))
    for k in range(n):
        h_scr[di, k] = h[k]


def _put_product(p_scr, j, m, i, value):
    """Token ``i``'s product ``m`` in tile ``j`` of the trip (a token's
    value, to be summed over its channels), its registers added into one."""
    from jax.experimental import pallas as pl

    regs = value.reshape(-1, GROUP, LANES)
    p_scr[j, m, pl.ds(i * GROUP, GROUP), :] = _tree_sum(
        [regs[k] for k in range(regs.shape[0])])


def _channel_sums(p_scr, j, dbc_ref, g):
    """The sums over channels of the products in tile ``j`` of the trip,
    into tile ``g``'s rows of ``dbc_ref``. A load of every 4th row brings 4
    tokens' lane tiles s and s + 4 onto sublanes 2t and 2t + 1, four such
    loads added hold their two half sums each; a sublane's neighbour added,
    the even sublanes hold tokens 0-3 and the odd ones, from the other half,
    tokens 4-7: one register a product, whose lanes the cross-lane unit adds
    in ONE operation (it takes ~6 cycles of the unit, so a register is
    filled first), the answer in every lane. A tree of selects puts product
    m's in lane m. Rows (t, 4 + t) for t = 0..3: XLA reads them back. All
    products at once, as (products, 8, 128) arrays: the trace stays short."""
    from jax.experimental import pallas as pl

    count = p_scr.shape[1]
    sub = jax.lax.broadcasted_iota(jnp.int32, (count, GROUP, LANES), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (GROUP, LANES), 1)

    def tokens(half):  # 4 tokens: (token, half sum) on the sublanes
        x = _tree_sum([
            p_scr[j, :, pl.ds(half * 4 * GROUP + s, GROUP, stride=4), :]
            for s in range(4)])
        # a token's whole sum: tokens 0-3 on the even sublanes (the half sum
        # below comes up), tokens 4-7 on the odd ones (the one above down)
        return x + _roll(x, 1 if half else -1, 1)

    both = jnp.where((sub & 1) == 0, tokens(0), tokens(1))
    sums = jnp.sum(both, axis=2, keepdims=True)
    totals = [sums[m] for m in range(count)]
    bit = 1
    while len(totals) > 1:  # bit b of the lane picks at level b
        totals = [jnp.where((lane & bit) == 0, a, b)
                  for a, b in zip(totals[::2], totals[1::2])]
        bit *= 2
    at = pl.multiple_of(g * GROUP, GROUP)
    dbc_ref[pl.ds(at, GROUP), :] = jnp.broadcast_to(totals[0], (GROUP, LANES))


def _roll(x, shift, axis):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, shift % x.shape[axis], axis)


def _tree_sum(terms):
    terms = list(terms)
    while len(terms) > 1:
        terms = [a + b for a, b in zip(terms[::2], terms[1::2])] + (
            [terms[-1]] if len(terms) % 2 else [])
    return terms[0]


def _bwd_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, hb_ref, dy_ref,
                du_ref, ddt_ref, dbc_ref, da_ref,
                hs_scr, dh_scr, bs_scr, cs_scr, p_scr, *, chunk, tiles, token):
    """Chunks arrive last to first. ``hs_scr[t + 1]`` is the state after
    token t of the chunk (``hs_scr[0]`` the boundary), recomputed here;
    ``dh_scr`` carries the state's adjoint into the chunk before."""
    from jax.experimental import pallas as pl

    n = a_ref.shape[0]
    groups = chunk // GROUP
    di = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dh_scr[di] = jnp.zeros(dh_scr.shape[1:], jnp.float32)

    @pl.when((pl.program_id(1) == 0) & (di == 0))
    def _():
        da_ref[...] = jnp.zeros_like(da_ref)

    @pl.when(di == 0)
    def _():
        _spread(b_ref, bs_scr, chunk)
        _spread(c_ref, cs_scr, chunk)

    a2 = [a_ref[k] * LOG2_E for k in range(n)]
    hs_scr[0] = hb_ref[...]

    def recompute(g, h):
        h = list(h)
        for i in range(GROUP):
            t = g * GROUP + i
            u, dt = token(u_ref, g, i, tiles), _token(dt_ref, g, i, tiles)
            dtu = dt * u
            for k in range(n):
                h[k] = _advance(h[k], dt, dtu, a2[k], bs_scr, k, g, i, tiles)
                hs_scr[t + 1, k] = h[k]
        return tuple(h)

    jax.lax.fori_loop(0, groups, recompute, tuple(hb_ref[k] for k in range(n)))

    trip = p_scr.shape[0]

    def group(j, carry, q):
        g = groups - 1 - (q * trip + j)
        dh, da = (list(x) for x in carry)
        for i in reversed(range(GROUP)):
            t = g * GROUP + i
            u, dt = token(u_ref, g, i, tiles), _token(dt_ref, g, i, tiles)
            dy = _token(dy_ref, g, i, tiles)
            dtu = dt * u
            fed, through_a = [], []
            for k in range(n):
                # adjoint of the state after token t, all its uses in
                dh_k = dh[k] + _splat(cs_scr, k, g, i, tiles) * dy
                _put_product(p_scr, j, k, i, dh_k * dtu)                # dB
                _put_product(p_scr, j, n + k, i, hs_scr[t + 1, k] * dy)  # dC
                dh[k] = dh_k * jnp.exp2(dt * a2[k])
                through = dh[k] * hs_scr[t, k]
                da[k] = da[k] + through * dt
                fed.append(dh_k * _splat(bs_scr, k, g, i, tiles))
                through_a.append(through * a2[k])
            fed = _tree_sum(fed)
            _put_token(du_ref, g, i, tiles, fed * dt)
            _put_token(ddt_ref, g, i, tiles,
                       _tree_sum(through_a) * LN_2 + fed * u)
        return tuple(dh), tuple(da)

    def tiles_of_a_trip(q, carry):
        # the cross-lane unit answers ~110 cycles late: the sums over
        # channels of `trip` tiles are taken together, their chains side by
        # side, where one tile's alone would leave the loop waiting
        carry = jax.lax.fori_loop(
            0, trip, functools.partial(group, q=q), carry)
        for j in range(trip):
            _channel_sums(p_scr, j, dbc_ref, groups - 1 - (q * trip + j))
        return carry

    zero = jnp.zeros((tiles, LANES), jnp.float32)
    dh, da = jax.lax.fori_loop(
        0, groups // trip, tiles_of_a_trip,
        (tuple(dh_scr[di, k] for k in range(n)), (zero,) * n))
    for k in range(n):
        dh_scr[di, k] = dh[k]
        da_ref[k, pl.ds(pl.multiple_of(di * tiles, tiles), tiles), :] += da[k]


def _interpret():
    from pyrecover_tpu.ops.flash_attention import _interpret as flag

    return flag()


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _operands(u, dt, b, c, a_t, chunk, order):
    """What both calls share: the operands every call starts with (u at its
    own width, dt, B, C, A by lane tile), their blocks, the block of one
    more token array and of a chunk's state, a token array's shape, and
    the reader of a token of u. ``order`` maps the grid's chunk index to the chunk (the backward call
    walks them last to first). Grid (batch, chunk, channel block)."""
    from jax.experimental import pallas as pl

    n, d = a_t.shape
    tiles = _block_d(d) // LANES
    narrow = u.dtype == jnp.bfloat16

    def rows(lead=None):
        return pl.BlockSpec(
            (lead, chunk // GROUP, tiles * GROUP, LANES),
            lambda bi, ci, di: (bi, order(ci), di, 0))

    bc = pl.BlockSpec((None, chunk, n), lambda bi, ci, di: (bi, order(ci), 0))
    state = pl.BlockSpec((None, None, n, tiles, LANES),
                         lambda bi, ci, di: (bi, order(ci), 0, di, 0))
    specs = [
        # a bfloat16 block keeps its batch axis: a ref is bitcast with the
        # rank it has
        rows(1) if narrow else rows(), rows(), bc, bc,
        pl.BlockSpec((n, tiles, LANES), lambda bi, ci, di: (0, di, 0)),
    ]
    arrays = (_tiled(u), _tiled(dt), b, c, a_t.reshape(n, d // LANES, LANES))
    tokens = jax.ShapeDtypeStruct(arrays[1].shape, jnp.float32)
    return (arrays, specs, rows(), state, tokens,
            _token_bf16 if narrow else _token)


def _pallas_fwd_call(u, dt, b, c, a_t, chunk):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, d = dt.shape
    n = a_t.shape[0]
    tiles = _block_d(d) // LANES
    nc, nd = s // chunk, d // (tiles * LANES)
    f32 = jnp.float32
    arrays, specs, rows, state, tokens, token = _operands(
        u, dt, b, c, a_t, chunk, lambda ci: ci)
    y, bounds = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, tiles=tiles, token=token),
        grid=(bsz, nc, nd),
        in_specs=specs,
        out_specs=[rows, state],
        out_shape=[
            tokens,
            jax.ShapeDtypeStruct((bsz, nc, n, d // LANES, LANES), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nd, n, tiles, LANES), f32),
            pltpu.VMEM((chunk // GROUP, n * GROUP, LANES), f32),
            pltpu.VMEM((chunk // GROUP, n * GROUP, LANES), f32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="ssm_scan_fwd",
    )(*arrays)
    return _untiled(y), bounds


def _pallas_bwd_call(u, dt, b, c, a_t, bounds, dy, chunk):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, d = dt.shape
    n = a_t.shape[0]
    tiles = _block_d(d) // LANES
    last, nd = s // chunk - 1, d // (tiles * LANES)
    f32 = jnp.float32
    arrays, specs, rows, state, tokens, token = _operands(
        u, dt, b, c, a_t, chunk, lambda ci: last - ci)
    du, ddt, dbc, da = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, tiles=tiles, token=token),
        grid=(bsz, last + 1, nd),
        in_specs=[*specs, state, rows],
        out_specs=[
            rows, rows,
            pl.BlockSpec((None, None, chunk, LANES),
                         lambda bi, ci, di: (bi, di, last - ci, 0)),
            pl.BlockSpec((None, n, d // LANES, LANES),
                         lambda bi, ci, di: (bi, 0, 0, 0)),
        ],
        out_shape=[
            tokens, tokens,
            jax.ShapeDtypeStruct((bsz, nd, s, LANES), f32),
            jax.ShapeDtypeStruct((bsz, n, d // LANES, LANES), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((chunk + 1, n, tiles, LANES), f32),
            pltpu.VMEM((nd, n, tiles, LANES), f32),
            pltpu.VMEM((chunk // GROUP, n * GROUP, LANES), f32),
            pltpu.VMEM((chunk // GROUP, n * GROUP, LANES), f32),
            pltpu.VMEM((_trip(chunk), 2 * n, GROUP * GROUP, LANES), f32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="ssm_scan_bwd",
    )(*arrays, bounds, _tiled(dy))
    # a token's 2n sums over a block's channels, product m in lane m, a
    # tile's rows in the order (t, 4 + t) `_channel_sums` leaves them in
    dbc = jnp.sum(dbc, axis=1).reshape(bsz, s // GROUP, GROUP // 2, 2, LANES)
    dbc = jnp.swapaxes(dbc, 2, 3).reshape(bsz, s, LANES)
    return (_untiled(du).astype(u.dtype), _untiled(ddt), dbc[..., :n],
            dbc[..., n:2 * n], jnp.sum(da, axis=0).reshape(n, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan_pallas(u, dt, b, c, a_t, chunk):
    return _pallas_fwd_call(u, dt, b, c, a_t, chunk)[0]


def _scan_pallas_fwd(u, dt, b, c, a_t, chunk):
    y, bounds = _pallas_fwd_call(u, dt, b, c, a_t, chunk)
    return y, (u, dt, b, c, a_t, bounds)


def _scan_pallas_bwd(chunk, res, dy):
    return _pallas_bwd_call(*res, dy, chunk)


_scan_pallas.defvjp(_scan_pallas_fwd, _scan_pallas_bwd)
