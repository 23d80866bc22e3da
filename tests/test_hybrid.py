"""A hybrid stack of Mamba-1 and attention layers (Jamba's shape) through the
program's normal path: the selective scan against the token-by-token
recurrence, the model against the plain reference in
``benchmark/references/jamba.py`` on seeded weights over two optimizer
steps, the tied head, remat, save and resume, the plain decoder left bit for
bit what it was, and a refusal in words wherever a path cannot hold a
recurrent state or split a period. Tiny widths, two periods, so that the
period boundary is crossed."""

import dataclasses
import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from pyrecover_tpu.models.llama import (  # noqa: E402
    ModelConfig,
    forward,
    init_params,
)
from pyrecover_tpu.ops.selective_scan import (  # noqa: E402
    causal_conv1d,
    selective_scan,
)
from pyrecover_tpu.train_state import (  # noqa: E402
    IGNORE_INDEX,
    make_train_step,
    model_loss,
)

PERIOD, OFFSET, L, B, S, V = 4, 2, 8, 2, 48, 256


def hybrid(**kw):
    base = dict(
        n_layers=L, n_kv_heads=1, vocab_size=V, max_seq_len=S,
        attn_layer_period=PERIOD, attn_layer_offset=OFFSET, rope=False,
        tie_embeddings=True, mamba_d_state=4, mamba_dt_rank=4,
        norm_eps=1e-6, param_dtype="float32", compute_dtype="float32")
    base.update(kw)
    return ModelConfig().tiny(**base)


@pytest.fixture(scope="module", autouse=True)
def short_chunks():
    """The scan's chunk is a constant of its module (256 tokens); the toy
    sequences here are 16 and 48 tokens, so it is shortened to 8 for the
    module: every model below crosses chunk boundaries as the cell does."""
    import pyrecover_tpu.ops.selective_scan as ss

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ss, "SCAN_CHUNK", 8)
        yield


def flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


@pytest.fixture(scope="module")
def batch():
    tok = jax.random.randint(jax.random.key(5), (B, S), 0, V)
    lab = jnp.roll(tok, -1, axis=1).at[:, -1].set(IGNORE_INDEX)
    return tok, lab.at[1, :5].set(IGNORE_INDEX)


# ---- the scan and the convolution ---------------------------------------------

def recurrence_token_by_token(u, dt, a, b, c, d_skip):
    """The equations as written, the whole state carried, no chunks and no
    custom rule: what the chunked formulation is held to (and far too large
    to differentiate at a real width)."""
    def step(h, x):
        u_t, dt_t, b_t, c_t = x
        h = (jnp.exp(dt_t[..., None] * a) * h
             + (dt_t * u_t)[..., None] * b_t[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, c_t) + d_skip * u_t

    h0 = jnp.zeros((u.shape[0], *a.shape), jnp.float32)
    _, y = jax.lax.scan(
        step, h0, tuple(jnp.moveaxis(x, 1, 0) for x in (u, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def scan_operands(seed, b=2, s=37, d=24, n=4, u_dtype=jnp.float32):
    k = jax.random.split(jax.random.key(seed), 7)
    return (
        jax.random.normal(k[0], (b, s, d)).astype(u_dtype),
        jax.nn.softplus(jax.random.normal(k[1], (b, s, d)) - 1.0),
        -jnp.exp(jax.random.normal(k[2], (d, n))),
        jax.random.normal(k[3], (b, s, n)), jax.random.normal(k[4], (b, s, n)),
        jax.random.normal(k[5], (d,)),
    ), jax.random.normal(k[6], (b, s, d))


@pytest.mark.parametrize("chunk", [1, 8, 16, 37, 64],
                         ids=lambda c: f"chunk{c}")
def test_chunked_scan_matches_the_token_by_token_recurrence(chunk):
    """Forward and every operand's gradient, at chunk sizes that do (1, 37)
    and do not (8, 16) divide the 37 tokens, and one longer than them (64).
    Float32 on both sides: what is left is the order of sums."""
    ops, w = scan_operands(0)
    y, g = jax.value_and_grad(
        lambda *xs: jnp.sum(selective_scan(*xs, chunk=chunk, impl="xla") * w),
        argnums=range(6))(*ops)
    y0, g0 = jax.value_and_grad(
        lambda *xs: jnp.sum(recurrence_token_by_token(*xs) * w),
        argnums=range(6))(*ops)
    assert abs(y - y0) <= 2e-6 * abs(y0)
    for got, want, name in zip(g, g0, ("u", "dt", "a", "b", "c", "d")):
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(got - want))) <= 5e-6 * scale, name


@pytest.mark.parametrize("s,chunk,d,n,block,u_dtype", [
    (128, 64, 2048, 8, 1024, jnp.float32),
    (150, 128, 1024, 8, 1024, jnp.float32),
    (64, 32, 1024, 16, 1024, jnp.float32),
    (64, 32, 2048, 8, 2048, jnp.float32),
    (64, 32, 1024, 8, 1024, jnp.bfloat16),
    (100, 48, 1024, 8, 1024, jnp.bfloat16),
], ids=["divides", "padded", "jamba-states", "two-registers-a-token",
        "bf16-u", "bf16-u-padded"])
def test_kernel_pair_matches_the_xla_formulation(
        monkeypatch, s, chunk, d, n, block, u_dtype):
    """``ssm_scan_fwd`` / ``ssm_scan_bwd`` in the Pallas interpreter against
    the XLA formulation under the same custom rule, more than one chunk in
    every case: two channel blocks of a register a token (8 lane tiles), a
    sequence the chunk does not divide, Jamba's own 16 states, a block of
    two registers a token, u as the model holds it (bfloat16, widened in
    the kernels), and 100 tokens in chunks of 48 in that layout. The
    backward kernel sums over channels 8, 4 and 2 tiles at a time in them."""
    import pyrecover_tpu.ops.selective_scan as ss

    monkeypatch.setenv("PYRECOVER_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(ss, "DEFAULT_BLOCK_D", block)
    ops, w = scan_operands(1, b=2, s=s, d=d, n=n, u_dtype=u_dtype)

    def weighed(impl, chunk):
        def f(*xs):
            y = selective_scan(*xs, chunk=chunk, impl=impl)
            return jnp.sum(y * w), y
        return jax.value_and_grad(f, argnums=range(6), has_aux=True)(*ops)

    (_, y), g = weighed("pallas", chunk)
    (_, y0), g0 = weighed("xla", 32)
    # every y, not their weighed sum: a million terms cancel to a sum whose
    # own rounding is over the tolerance at these widths
    assert float(jnp.max(jnp.abs(y - y0))) <= 2e-6 * float(jnp.max(jnp.abs(y0)))
    for got, want, name in zip(g, g0, ("u", "dt", "a", "b", "c", "d")):
        assert got.dtype == want.dtype, name
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        scale = float(jnp.max(jnp.abs(want)))
        # a bfloat16 u's cotangent is rounded to bfloat16 on both sides: two
        # float32 values 1e-7 apart may round to neighbours (2^-8 apart)
        tol = 2.0**-8 if (name == "u" and u_dtype == jnp.bfloat16) else 5e-6
        assert float(jnp.max(jnp.abs(got - want))) <= tol * scale, name


def test_kernels_leave_shapes_they_do_not_tile_to_xla(monkeypatch):
    from pyrecover_tpu.ops.selective_scan import pallas_supported, resolve_impl

    monkeypatch.delenv("PYRECOVER_PALLAS_INTERPRET", raising=False)
    assert pallas_supported(5120, 16, 256)
    assert pallas_supported(5120, 16, 48)       # whole 8-token tiles
    assert not pallas_supported(24, 4, 16)      # the tests' toy width
    assert not pallas_supported(5120 + 512, 16, 256)  # half a register a token
    assert not pallas_supported(5120, 16, 36)   # half a tile of tokens
    assert not pallas_supported(5120, 12, 256)  # states no power of two
    assert resolve_impl("auto", 5120, 16, 256) == "xla"   # the CPU
    monkeypatch.setenv("PYRECOVER_PALLAS_INTERPRET", "1")
    assert resolve_impl("auto", 5120, 16, 256) == "pallas"
    assert resolve_impl("auto", 24, 4, 16) == "xla"
    with pytest.raises(ValueError, match="selective scan impl"):
        resolve_impl("cuda", 5120, 16, 256)
    # kernels asked for by name on shapes they do not tile: an error, not
    # another formulation in silence
    ops, _ = scan_operands(0)
    with pytest.raises(ValueError, match="whole registers of channels"):
        selective_scan(*ops, chunk=16, impl="pallas")


def test_causal_conv_reads_no_token_ahead():
    u = jax.random.normal(jax.random.key(2), (2, 9, 6))
    w = jax.random.normal(jax.random.key(3), (4, 6))
    bias = jax.random.normal(jax.random.key(4), (6,))
    got = np.asarray(causal_conv1d(u, w, bias))
    un, wn = np.asarray(u), np.asarray(w)
    for t in range(9):
        want = np.asarray(bias).copy()
        for j in range(4):
            if t - 3 + j >= 0:
                want = want + wn[j] * un[:, t - 3 + j]
        np.testing.assert_allclose(got[:, t], want, rtol=2e-6, atol=2e-6)
    # a change to a later token moves no earlier output
    later = causal_conv1d(u.at[:, 5:].add(1.0), w, bias)
    np.testing.assert_array_equal(np.asarray(later[:, :5]), got[:, :5])


# ---- the model against the plain reference, two optimizer steps -----------------

def toy_cfg():
    """The harness's toy width with two periods of (Mamba, Mamba, attention,
    Mamba) and the configuration's own trainer_model."""
    from benchmark.lib.manifest import Manifest
    from benchmark.run import REHEARSAL

    man = Manifest()
    cfg = man.config("jamba2-3b")
    small = {"num_hidden_layers": L, "attn_layer_period": PERIOD,
             "attn_layer_offset": OFFSET, "mamba_d_state": 4,
             "mamba_dt_rank": 4, "num_key_value_heads": 1}
    tm = {**cfg["trainer_model"], **REHEARSAL["cfg"]["trainer_model"],
          "attn_layer_period": PERIOD, "attn_layer_offset": OFFSET,
          "mamba_d_state": 4, "mamba_dt_rank": 4}
    return man, {**cfg, **REHEARSAL["cfg"], **small, "trainer_model": tm}


# float32 on the CPU on both sides, so what is left between the program and
# the reference is the order of float32 sums: the scan in chunks against
# blocks, the chunked head against the whole, a stacked scan against Python
# loops. The readings are ~1e-7 on the losses and ~1e-5 on the norms; the
# limits leave ten times that and are a hundred times under what the
# recurrence computed in bfloat16 reads (below).
TOLERANCES = {"weights_gap": 1e-6, "loss1_gap": 3e-6, "loss2_gap": 3e-6,
              "gnorm1_gap": 1e-4, "grad_leaf_gap": 2e-4,
              "change_leaf_gap": 1e-3}


@pytest.fixture(scope="module")
def followed():
    from benchmark.runners import train_window as tw
    from benchmark.tests.test_reference import program_steps, rows_for

    man, cfg = toy_cfg()
    rows = rows_for(3, 2, batch=2, seq=48)
    prog, config = program_steps(cfg, 11, rows)
    ref = man.reference(cfg["reference"]).Reference(
        cfg, tw.optimizer_facts(config), jax.devices()[:1])
    out = tw.follow(ref, 11, rows)
    return {"cfg": cfg, "rows": rows, "prog": prog, "ref": out,
            "config": config, "compare": tw.compare}


def test_reference_follows_the_program_over_two_steps(followed):
    prog, ref = followed["prog"], followed["ref"]
    assert set(prog["grad_leaf_norms"]) == set(ref["grad_leaf_norms"])
    names = set(ref["grad_leaf_norms"])
    assert {"layers/mamba_pre/a_log", "layers/mamba_post/in_proj",
            "layers/attn/wq", "tok_embed"} <= names
    assert "output" not in names  # tied
    # per layer: two periods of (2 Mamba, attention, 1 Mamba)
    assert ref["grad_leaf_norms"]["layers/mamba_pre/in_proj"].shape == (4,)
    assert ref["grad_leaf_norms"]["layers/attn/wq"].shape == (2,)
    got = followed["compare"](prog, ref)
    for name, limit in TOLERANCES.items():
        assert got[name] < limit, (name, got)


def test_recurrence_in_bfloat16_fails_the_tolerances(followed, monkeypatch):
    """The same two steps with the recurrence (and only it) computed in
    bfloat16: at least one of the tolerances above must refuse it."""
    import pyrecover_tpu.models.mamba as mamba
    from benchmark.tests.test_reference import program_steps

    bf = jnp.bfloat16

    def scan_bf16(u, dt, a, b, c, d_skip):
        u, dt, a, b, c = (x.astype(bf) for x in (u, dt, a, b, c))

        def step(h, x):
            u_t, dt_t, b_t, c_t = x
            h = (jnp.exp(dt_t[..., None] * a) * h
                 + (dt_t * u_t)[..., None] * b_t[:, None, :]).astype(bf)
            return h, jnp.einsum("bdn,bn->bd", h, c_t)

        h0 = jnp.zeros((u.shape[0], *a.shape), bf)
        _, y = jax.lax.scan(
            step, h0, tuple(jnp.moveaxis(x, 1, 0) for x in (u, dt, b, c)))
        return (jnp.moveaxis(y, 0, 1) + d_skip.astype(bf) * u).astype(
            jnp.float32)

    monkeypatch.setattr(mamba, "selective_scan", scan_bf16)
    prog, _ = program_steps(followed["cfg"], 11, followed["rows"])
    got = followed["compare"](prog, followed["ref"])
    over = {k: got[k] / v for k, v in TOLERANCES.items() if got[k] >= v}
    assert over, got
    assert max(over.values()) > 10, got


def test_tied_head_gradient_is_the_sum_of_both_uses(batch):
    tok, lab = batch
    cfg = hybrid()
    params = init_params(jax.random.key(4), cfg)
    assert "output" not in params
    tied = jax.grad(lambda p: model_loss(p, tok, lab, None, cfg, 16)[0])(params)
    # the same model with the head as a leaf of its own, holding E^T
    untied_cfg = dataclasses.replace(cfg, tie_embeddings=False)
    untied = dict(params, output=params["tok_embed"].T)
    g = jax.grad(
        lambda p: model_loss(p, tok, lab, None, untied_cfg, 16)[0])(untied)
    want = g["tok_embed"] + g["output"].T
    assert float(jnp.max(jnp.abs(g["output"]))) > 0
    assert float(jnp.max(jnp.abs(g["tok_embed"]))) > 0
    np.testing.assert_allclose(tied["tok_embed"], want, rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("chunked", [0, 16], ids=["whole-head", "chunked"])
def test_remat_full_equals_no_remat(batch, chunked):
    tok, lab = batch
    cfg = hybrid()
    params = init_params(jax.random.key(9), cfg)
    full = dataclasses.replace(cfg, remat=True, remat_policy="full")

    def vg(c):
        return jax.value_and_grad(
            lambda p: model_loss(p, tok, lab, None, c, chunked)[0])(params)

    (l0, g0), (l1, g1) = vg(cfg), vg(full)
    assert float(l0) == float(l1)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(g0),
                                 jax.tree_util.tree_leaves_with_path(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9,
                                   err_msg=jax.tree_util.keystr(path))


def test_period_of_one_period_is_the_loop_of_two(batch):
    """One period runs without the outer loop; two run as a scan over
    periods. The first period of a two-period stack, taken alone with its
    leaves, gives what the two-period forward computes on the way."""
    tok, _ = batch
    two = hybrid()
    params = init_params(jax.random.key(2), two)
    one = dataclasses.replace(two, n_layers=PERIOD)
    first = dict(params, layers=jax.tree_util.tree_map(
        lambda a: a[:a.shape[0] // 2], params["layers"]))
    second = dict(params, layers=jax.tree_util.tree_map(
        lambda a: a[a.shape[0] // 2:], params["layers"]))
    from pyrecover_tpu.models.llama import _stack

    carry, run = _stack(first, tok, one, None)
    mid = run(carry)
    _, run2 = _stack(second, tok, one, None)
    end = run2(mid)
    carry, run_all = _stack(params, tok, two, None)
    np.testing.assert_allclose(run_all(carry)["x"], end["x"], rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("period,offset", [(4, 2), (2, 1), (1, 0)],
                         ids=["2-mamba+attn+1-mamba", "1-mamba+attn", "plain"])
def test_runner_walks_the_layers_in_stack_order(batch, period, offset):
    """One runner for every stack: layer i is an attention layer where
    i % period == offset. Against the layers applied one by one in a Python
    loop, each taken from its group by its place in the stack."""
    from pyrecover_tpu.models.llama import _block, _stack, sdpa_attention
    from pyrecover_tpu.models.mamba import mamba_block

    tok, _ = batch
    cfg = hybrid(attn_layer_period=period, attn_layer_offset=offset,
                 n_layers=4)
    assert [g[0] for g in cfg.layer_groups()] == {
        4: ["mamba_pre", "attn", "mamba_post"], 2: ["mamba_pre", "attn"],
        1: ["attn"]}[period]
    params = init_params(jax.random.key(6), cfg)
    groups = params["layers"] if cfg.hybrid else {"attn": params["layers"]}
    carry, run = _stack(params, tok, cfg, None)
    x, seen = carry["x"], {name: 0 for name in groups}
    for i in range(cfg.n_layers):
        at = i % period
        name = ("attn" if at == offset else
                "mamba_pre" if at < offset else "mamba_post")
        layer = jax.tree_util.tree_map(lambda a: a[seen[name]], groups[name])
        seen[name] += 1
        if name == "attn":
            x, _ = _block(x, layer, None, None, cfg, sdpa_attention)
        else:
            x, _ = mamba_block(x, layer, cfg)
    assert seen == {name: leaves["ffn_norm"].shape[0]
                    for name, leaves in groups.items()}
    np.testing.assert_allclose(run(carry)["x"], x, rtol=1e-5, atol=1e-6)


# ---- the plain decoder is what it was --------------------------------------------

# logits: computed on the parent commit of PR 32 (PR 31). Gradients: since
# PR 39, whose RoPE backward is the interleaved formula's arithmetic lane by
# lane (tests/test_rope.py, op by op) but which XLA's CPU backend fuses into
# one multiply-add rounding where the interleaved form's scatters kept two
FORWARD_DIGESTS = {
    "dense": (
        "894387a677f91833e39ae22ed395c63ee4a171efd855eefda4a3048ec47c0f1c",
        "2510333c5f2db1cadb5ffbda2a3096b118c0c7301cee900801d046dbaf9e4e30"),
    "dense_bf16_remat": (
        "f2c88a3d976b694970a0287218b837d58115771e1351d890246c1ec70d3dab44",
        "5cc2d65416a3fbe66473951dd0a34c437ee2df03eaabfc9659f8b461046e7485"),
    "moe": (
        "c12f4459b81cfc8cdd73beba5f69030cb777a3b3293df4b870259d1881141495",
        "c7ee5e62791fce0c9798655fcea73393ae383660a741d9e36f694312e0223dc6"),
}


@pytest.mark.parametrize("name,kw", [
    ("dense", dict(param_dtype="float32", compute_dtype="float32")),
    ("dense_bf16_remat", dict(param_dtype="bfloat16", remat=True,
                              remat_policy="full")),
    ("moe", dict(n_experts=4, param_dtype="float32",
                 compute_dtype="float32")),
])
def test_homogeneous_stack_is_bit_for_bit_the_parents(name, kw):
    """A stack of one layer kind is the period of one: its logits and the
    gradient of its loss, byte for byte what the commits named above
    computed."""
    def digest(*arrays):
        h = hashlib.sha256()
        for a in arrays:
            h.update(np.asarray(a).tobytes())
        return h.hexdigest()

    cfg = ModelConfig().tiny(**kw)
    assert not cfg.hybrid and cfg.rope and not cfg.tie_embeddings
    toks = jax.random.randint(jax.random.key(1), (2, 32), 0, 256)
    labs = jax.random.randint(jax.random.key(2), (2, 32), 0, 256)
    p = init_params(jax.random.key(7), cfg)
    logits = jax.jit(lambda p: forward(p, toks, cfg))(p)
    grads = jax.jit(jax.grad(
        lambda p: model_loss(p, toks, labs, None, cfg, 16)[0]))(p)
    assert (digest(logits), digest(*jax.tree_util.tree_leaves(grads))) == \
        FORWARD_DIGESTS[name]


# ---- the trainer's normal path: counters, save, resume ---------------------------

class _Events:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(dict(rec))

    def close(self):
        pass


def _train(tmp_path, **overrides):
    from pyrecover_tpu import telemetry
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.train import train

    base = dict(
        sequence_length=16, batch_size=8, training_samples=64,
        training_steps=4, learning_rate=1e-3, lr_warmup_steps=1, seed=13,
        checkpoint_dir=str(tmp_path), checkpoint_frequency=2,
        experiment_name="hybrid", logging_frequency=2, model_dtype="fp32",
        checkpoint_engine="sharded", async_checkpoint=False,
        loss_chunk_size=8, remat=True,
        model=hybrid(max_seq_len=16, vocab_size=128),
    )
    base.update(overrides)
    sink = _Events()
    telemetry.add_sink(sink)
    try:
        state, step, _ = train(TrainConfig(**base))
    finally:
        telemetry.remove_sink(sink)
    return state, step, sink.records


@pytest.fixture(scope="module")
def straight_run(tmp_path_factory):
    return _train(tmp_path_factory.mktemp("straight"))


def test_run_start_carries_the_hybrid_counters(straight_run):
    _, step, events = straight_run
    assert step == 4
    start = next(e for e in events if e["event"] == "run_start")
    assert (start["mamba_layers"], start["attn_layers"]) == (6, 2)
    assert start["ssm_state_elems"] == 2 * 64 * 4
    assert start["scan_chunk"] == 8
    assert start["loop_steps"] == 1 and start["layer_passes"] == L


def test_plain_model_run_start_reads_no_recurrent_state(tmp_path):
    _, _, events = _train(
        tmp_path, training_steps=2, checkpoint_frequency=-1,
        model=ModelConfig().tiny(max_seq_len=16, vocab_size=128))
    start = next(e for e in events if e["event"] == "run_start")
    assert (start["mamba_layers"], start["attn_layers"]) == (0, 2)
    assert start["ssm_state_elems"] == 0 and start["scan_chunk"] == 0


def test_sharded_save_kill_resume_bit_exact(tmp_path, straight_run):
    """Four steps straight against two steps, a new trainer, two more: the
    whole state, leaf for leaf, and by the tool the operator would use."""
    import check_equality

    straight, _, _ = straight_run
    _train(tmp_path, training_steps=2)
    resumed, step, _ = _train(tmp_path, resume_from_checkpoint="latest")
    assert step == 4
    a = jax.tree_util.tree_leaves_with_path(straight)
    b = jax.tree_util.tree_leaves_with_path(resumed)
    assert [p for p, _ in a] == [p for p, _ in b]
    names = {jax.tree_util.keystr(p) for p, _ in a}
    assert any("mamba_pre" in n and "a_log" in n for n in names)
    assert any("mamba_post" in n and "conv_w" in n for n in names)
    for (path, x), (_, y) in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=jax.tree_util.keystr(path))
    # the resumed run's final checkpoint against a straight run's, on disk
    other = tmp_path / "again"
    _train(other)
    finals = [sorted((d / "hybrid").glob("ckpt_4*"))[-1]
              for d in (tmp_path, other)]
    assert check_equality.main(
        [str(finals[0]), str(finals[1]), "--all-state"]) == 0


# ---- refusals, each in words, none inside a trace --------------------------------

def _train_config(**kw):
    from pyrecover_tpu.config import TrainConfig

    return lambda: TrainConfig(model=hybrid(), **kw)


def _pp(schedule):
    from pyrecover_tpu.parallel.mesh import MeshConfig

    return _train_config(mesh=MeshConfig(pipeline=2), pp_schedule=schedule)


def _engine():
    from pyrecover_tpu.serving.engine import ServingEngine

    cfg = hybrid()
    return ServingEngine(init_params(jax.random.key(0), cfg), cfg)


def _pool(cfg):
    from pyrecover_tpu.serving.kvpool import BlockPool

    return lambda: BlockPool(cfg, 4, 8)


def _decode():
    from pyrecover_tpu.models.decode import generate_tokens

    cfg = hybrid()
    return generate_tokens(init_params(jax.random.key(0), cfg), cfg, [1, 2], 2)


def _packed_forward():
    cfg = hybrid()
    tok = jnp.zeros((1, 8), jnp.int32)
    return forward(init_params(jax.random.key(0), cfg), tok, cfg,
                   segment_ids=jnp.zeros((1, 8), jnp.int32))


HYBRID_REFUSED = r"cannot run a hybrid stack \(attn_layer_period=4: 6 Mamba layers\)"


@pytest.mark.parametrize("build,sentence", [
    (_pp("gpipe"), r"pipeline parallelism \(--pp > 1\) " + HYBRID_REFUSED),
    (_pp("1f1b"), r"pipeline parallelism \(--pp > 1\) " + HYBRID_REFUSED),
    (_engine, r"paged serving engine \(BlockPool\) " + HYBRID_REFUSED),
    (_pool(hybrid()), r"paged serving engine \(BlockPool\) " + HYBRID_REFUSED),
    (_decode, r"key/value-cached decoder \(models/decode.py\) " + HYBRID_REFUSED),
    (_train_config(pack_sequences=True),
     r"--pack-sequences cannot train a hybrid stack"),
    (_packed_forward, r"packed sequences .* cannot run through a hybrid stack"),
    (lambda: hybrid(n_experts=4),
     r"mixture of experts with Mamba layers is not supported"),
    (lambda: hybrid(loop_steps=2), r"looped, sandwich-normed or gated hybrid"),
    (lambda: hybrid(n_layers=6), r"scanned by period: n_layers=6 is not a multiple"),
    (lambda: hybrid(attn_layer_offset=4), r"offset inside it"),
    (lambda: make_train_step(
        dataclasses.replace(hybrid(), pp_schedule="1f1b"), optax.sgd(1.0)),
     r"--pp-schedule 1f1b hands the schedule an embedding"),
    (lambda: make_train_step(
        ModelConfig().tiny(tie_embeddings=True, pp_schedule="1f1b"),
        optax.sgd(1.0)),
     r"--pp-schedule 1f1b hands the schedule an embedding"),
    (_pool(ModelConfig().tiny(tie_embeddings=True)),
     r"tie_embeddings or without rope is not served"),
    (_pool(ModelConfig().tiny(rope=False)),
     r"tie_embeddings or without rope is not served"),
], ids=["pp-gpipe", "pp-1f1b", "serving-engine", "kv-pool", "decode",
        "pack-sequences", "segment-ids", "hybrid-moe", "hybrid-looped",
        "ragged-periods", "offset-outside", "1f1b-hybrid", "1f1b-tied",
        "paged-tied", "paged-no-rope"])
def test_refusals_name_the_path(build, sentence):
    with pytest.raises(ValueError, match=sentence):
        build()


def test_tied_plain_decoder_still_decodes():
    """Tying the head or dropping rope alone is no reason to refuse the
    lockstep decoder: it shares the projections with the training forward."""
    from pyrecover_tpu.models.decode import generate_tokens

    cfg = ModelConfig().tiny(tie_embeddings=True, rope=False,
                             compute_dtype="float32")
    params = init_params(jax.random.key(3), cfg)
    out = generate_tokens(params, cfg, [5, 6, 7], 4)
    assert out[:3] == [5, 6, 7] and len(out) == 7
    logits = forward(params, jnp.asarray([out[:-1]]), cfg)
    assert int(jnp.argmax(logits[0, -1])) == out[-1]


# ---- the meters and the byte model know the stack ----------------------------------

def test_param_count_flop_meter_and_flags():
    from pyrecover_tpu.config import get_args
    from pyrecover_tpu.metrics import ThroughputMeter
    from pyrecover_tpu.models.presets import analytic_param_count
    from pyrecover_tpu.utils.perf import get_num_params

    cfg = hybrid()
    params = init_params(jax.random.key(0), cfg)
    assert analytic_param_count(cfg) == get_num_params(params)
    assert analytic_param_count(cfg, exclude_embedding=True) == \
        get_num_params(params, exclude_embedding=True)
    # the published shape: 1,598,556,096 held at one period (ISSUE 32)
    jamba = ModelConfig(
        dim=2560, n_layers=14, n_heads=20, n_kv_heads=1, vocab_size=65536,
        ffn_dim_multiplier=1.2, multiple_of=256, attn_layer_period=14,
        attn_layer_offset=7, rope=False, tie_embeddings=True,
        mamba_dt_rank=160)
    assert jamba.ffn_hidden_dim == 8192 and jamba.head_dim == 128
    assert (jamba.n_mamba_layers, jamba.n_attn_layers) == (13, 1)
    assert jamba.ssm_state_elems == 81920 and jamba.dt_rank == 160
    assert analytic_param_count(jamba) == 1_598_556_096
    # the meter: 6 N over the weights a token meets (the tied table once, as
    # the head), attention on the ATTENTION layers alone, the recurrence
    n = 1000
    meter = ThroughputMeter(cfg, n, S, 1)
    attn = 12 * 2 * cfg.n_heads * cfg.head_dim * S
    scan = 3 * 6 * cfg.d_inner * (6 * 4 + 2 * 4)
    assert meter.flop_per_token == 6 * (n + V * cfg.dim) + attn + scan
    got = get_args([
        "--model-attn-period", "14", "--model-attn-offset", "7",
        "--model-no-rope", "--model-tie-embeddings", "--model-layers", "28",
        "--model-dim", "2560"]).model
    assert (got.attn_layer_period, got.attn_layer_offset, got.rope,
            got.tie_embeddings, got.dt_rank, got.mamba_d_state) == (
        14, 7, False, True, 160, 16)
    plain = get_args([]).model
    assert (plain.hybrid, plain.rope, plain.tie_embeddings) == (
        False, True, False)


def test_new_leaves_have_partition_specs_and_names_reach_the_program(batch):
    from jax.sharding import PartitionSpec as P

    from pyrecover_tpu.models.mamba import SSM_NAMES, mamba_leaf_shapes
    from pyrecover_tpu.parallel.sharding import spec_for_manifest_path

    cfg = hybrid()
    for leaf, shape in mamba_leaf_shapes(cfg).items():
        spec = spec_for_manifest_path(
            f".params['layers']['mamba_pre']['{leaf}']", len(shape) + 1)
        assert len(spec) == len(shape) + 1 and spec[0] == "pipeline", leaf
    assert spec_for_manifest_path(
        ".opt_state[0].mu['layers']['mamba_post']['in_proj']", 3) == P(
        "pipeline", "fsdp", "tensor")
    tok, lab = batch
    params = init_params(jax.random.key(0), cfg)
    text = jax.jit(
        lambda p: model_loss(p, tok, lab, None, cfg, 16)[0]
    ).lower(params).as_text(debug_info=True)
    assert "mamba_mixer" in text and "ssm_scan" in text
    jaxpr = str(jax.make_jaxpr(
        lambda p: model_loss(p, tok, lab, None, cfg, 16)[0])(params))
    for name in SSM_NAMES:
        assert f"name={name}" in jaxpr, name
