"""A looped, sandwich-normed, exit-gated stack (Ouro's shape) through the
program's normal path: against the plain reference in
``benchmark/references/ouro.py`` on seeded weights, against an unshared stack
of tied copies, through remat / accumulation / save-resume / the KV cache,
and refused by name where the program sweeps the layers once."""

import dataclasses
import hashlib
import importlib.util
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pyrecover_tpu.models.llama import (
    ModelConfig,
    _attention_fn,
    _block,
    forward,
    forward_passes_with_aux,
    init_params,
    project_vocab,
    rms_norm,
)
from pyrecover_tpu.ops.rope import precompute_rope
from pyrecover_tpu.train_state import (
    IGNORE_INDEX,
    create_train_state,
    exit_distribution,
    exit_stats_fields,
    make_train_step,
    model_loss,
)

REPO = Path(__file__).resolve().parent.parent
T, L, B, S, V = 3, 2, 4, 32, 256

# float32 on the CPU, where a float32 product is a float32 product on both
# sides: what is left between the program and the reference is the order of
# float32 sums (scan against Python loops, chunked against whole heads)


def looped(**kw):
    base = dict(loop_steps=T, post_norms=True, exit_gate=True, n_layers=L,
                n_kv_heads=4, vocab_size=V, param_dtype="float32",
                compute_dtype="float32", max_seq_len=S)
    base.update(kw)
    return ModelConfig().tiny(**base)


def flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


@pytest.fixture(scope="module")
def ouro():
    spec = importlib.util.spec_from_file_location(
        "ref_ouro", REPO / "benchmark" / "references" / "ouro.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def batch():
    tok = jax.random.randint(jax.random.key(5), (B, S), 0, V)
    lab = jnp.roll(tok, -1, axis=1).at[:, -1].set(IGNORE_INDEX)
    lab = lab.at[1, :5].set(IGNORE_INDEX)  # a masked stretch inside a row
    return tok, lab


@pytest.fixture(scope="module")
def both(ouro, batch):
    """The program's and the reference's readings of one seeded batch."""
    cfg = looped()
    tok, lab = batch
    m = {"dim": cfg.dim, "layers": L, "heads": cfg.n_heads,
         "kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
         "ffn": cfg.ffn_hidden_dim, "vocab": V, "eps": cfg.norm_eps,
         "theta": cfg.rope_theta, "loops": T, "beta": cfg.exit_beta}
    params = init_params(jax.random.key(11), cfg)
    # the reference draws its own weights from the seed: they must be the
    # program's, leaf for leaf, or nothing below compares the same model
    ref_p = ouro.draw_weights(11, m, jnp.float32)
    mine = flat(params)
    assert set(mine) == set(ref_p)
    for k in mine:
        np.testing.assert_array_equal(np.asarray(mine[k]), np.asarray(ref_p[k]))

    hiddens, gates, _ = forward_passes_with_aux(params, tok, cfg)
    logits = jnp.stack([project_vocab(params, h, cfg) for h in hiddens])

    def objective(p):
        obj, ce, _, _, stats = model_loss(p, tok, lab, None, cfg, 8)
        return obj, (ce, stats)

    (obj, (ce, stats)), grads = jax.value_and_grad(
        objective, has_aux=True)(params)
    (r_obj, r_ce), r_grads = jax.value_and_grad(
        lambda p: ouro.batch_loss(p, np.asarray(tok), np.asarray(lab), m),
        has_aux=True)(ref_p)
    rows = [ouro.forward_row(ref_p, tok[b], lab[b], m) for b in range(B)]
    return {
        "cfg": cfg, "m": m, "logits": logits, "gates": gates,
        "obj": obj, "ce": ce, "stats": stats, "grads": flat(grads),
        "r_logits": jnp.stack([r[0] for r in rows], axis=1),
        "r_gates": jnp.stack([r[1] for r in rows], axis=1),
        "r_obj": r_obj, "r_ce": r_ce, "r_grads": r_grads, "ouro": ouro,
    }


# ---- the program against the plain reference --------------------------------

@pytest.mark.parametrize("t", range(T))
def test_pass_logits_match_reference(both, t):
    # logits are O(1) at this width; 2e-5 absolute is ~100 float32 ulp of the
    # largest, the room the differing summation orders of a 2-layer, 3-pass
    # stack need (measured ~3e-6)
    np.testing.assert_allclose(both["logits"][t], both["r_logits"][t],
                               atol=2e-5, rtol=0)


def test_gate_and_exit_probabilities_match_reference(both):
    np.testing.assert_allclose(both["gates"], both["r_gates"], atol=1e-5)
    p, _ = exit_distribution(both["gates"])
    r_p = both["ouro"].exit_probs(both["r_gates"])
    np.testing.assert_allclose(p, r_p, atol=1e-6)


def test_loss_matches_reference(both):
    # a mean over ~120 tokens of O(5) terms: 1e-6 relative is float32's own
    np.testing.assert_allclose(both["ce"], both["r_ce"], rtol=2e-6)
    np.testing.assert_allclose(both["obj"], both["r_obj"], rtol=2e-6)


LEAVES = ["tok_embed", "final_norm", "output", "exit_gate_w", "exit_gate_b"] + [
    f"layers/{k}" for k in ("attn_norm", "wq", "wk", "wv", "wo",
                            "attn_post_norm", "ffn_norm", "w1", "w3", "w2",
                            "ffn_post_norm")]


@pytest.mark.parametrize("leaf", LEAVES)
def test_leaf_gradient_matches_reference(both, leaf):
    assert set(both["grads"]) == set(LEAVES)
    g, r = np.asarray(both["grads"][leaf]), np.asarray(both["r_grads"][leaf])
    # against the leaf's own largest entry: float32 sums in another order,
    # through three passes of backward (measured ~1e-6 of the largest)
    assert np.max(np.abs(g - r)) <= 2e-5 * np.max(np.abs(r)), leaf
    assert np.max(np.abs(r)) > 0  # the gate and every norm DO get gradient


# ---- exit distribution -------------------------------------------------------

def test_exit_distribution_sums_to_one_and_last_is_the_remainder():
    g = jax.random.normal(jax.random.key(0), (5, 7, 9)) * 4.0
    p, log_p = exit_distribution(g)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(g)
    np.testing.assert_allclose(p[0], lam[0], atol=1e-6)
    np.testing.assert_allclose(p[-1], jnp.prod(1 - lam[:-1], axis=0), atol=1e-6)
    np.testing.assert_allclose(jnp.exp(log_p), p, atol=1e-7)
    # far-out logits neither overflow nor give a nan entropy
    p, log_p = exit_distribution(jnp.array([[80.0], [-80.0], [0.0]]))
    assert np.isfinite(np.asarray(p * log_p)).all()


# ---- one pass, no post-norms: today's model ----------------------------------

INIT_DIGESTS = {  # computed on the parent commit (PR 26)
    "dense": "827a62ae4ace62fa128e5836704625da21d691d08cbd971e6a4d1a32d66598ee",
    "dense_bf16": "0f25919987a3abda9e49c9b9820c98592c0a94be9606dba5e1ec84707c21c0b3",
    "moe": "2a233557938e3b869924e6db845d1bd89464db0993daa3f42f6787f98de6610f",
}


@pytest.mark.parametrize("name,kw", [
    ("dense", dict(param_dtype="float32")),
    ("dense_bf16", dict(param_dtype="bfloat16")),
    ("moe", dict(n_experts=4, param_dtype="float32")),
])
def test_plain_init_params_bit_identical_to_parent(name, kw):
    p = init_params(jax.random.key(7), ModelConfig().tiny(**kw))
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_leaves_with_path(p),
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(leaf.dtype).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == INIT_DIGESTS[name]


def test_looped_init_keeps_the_ten_keys(batch):
    """The shared leaves of a looped model are the plain model's, bit for
    bit: the gate's key is derived beside the ten, not split with them."""
    plain = flat(init_params(jax.random.key(3), looped(
        loop_steps=1, post_norms=False, exit_gate=False)))
    loop = flat(init_params(jax.random.key(3), looped()))
    for k, v in plain.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(loop[k]))
    assert set(loop) - set(plain) == {
        "layers/attn_post_norm", "layers/ffn_post_norm", "exit_gate_w",
        "exit_gate_b"}


def _stack_by_hand(params, tokens, cfg, passes=1):
    """The parent's forward, composed by hand: embed, a scan of ``_block``
    over the layers it is given, the final norm — ``passes`` times."""
    cos, sin = precompute_rope(cfg.head_dim, tokens.shape[1], cfg.rope_theta)
    block = partial(_block, cos=cos, sin=sin, config=cfg,
                    attn_fn=_attention_fn(cfg))
    x = params["tok_embed"][tokens]
    per_pass = jax.tree_util.tree_map(
        lambda a: a.reshape(passes, -1, *a.shape[1:]), params["layers"])
    for t in range(passes):
        layers = jax.tree_util.tree_map(lambda a: a[t], per_pass)
        x, _ = jax.lax.scan(lambda c, l: (block(c, l)[0], None), x, layers)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x


def test_one_pass_no_post_norms_is_todays_forward(batch):
    cfg = looped(loop_steps=1, post_norms=False, exit_gate=False)
    params = init_params(jax.random.key(2), cfg)
    tok, _ = batch
    want = jax.jit(lambda p: project_vocab(
        p, _stack_by_hand(p, tok, cfg), cfg))(params)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda p: forward(p, tok, cfg))(params)),
        np.asarray(want))
    # and the scan over passes, at one pass, is that forward too
    hiddens, gates, _ = forward_passes_with_aux(params, tok, cfg)
    assert hiddens.shape[0] == 1 and gates is None
    np.testing.assert_allclose(project_vocab(params, hiddens[0], cfg), want,
                               atol=1e-6)


# ---- shared weights: an unshared stack of tied copies -------------------------

def test_looped_stack_equals_untied_copies_and_sums_their_gradients(batch):
    cfg = looped(exit_gate=False)
    params = init_params(jax.random.key(4), cfg)
    tok, _ = batch
    untied = dict(params, layers=jax.tree_util.tree_map(
        lambda a: jnp.tile(a, (T,) + (1,) * (a.ndim - 1)), params["layers"]))

    def looped_out(p):
        return forward_passes_with_aux(p, tok, cfg)[0][-1]

    def untied_out(p):
        return _stack_by_hand(p, tok, cfg, passes=T)

    np.testing.assert_allclose(looped_out(params), untied_out(untied),
                               atol=1e-5)
    probe = jax.random.normal(jax.random.key(9), (B, S, cfg.dim))
    g_loop = jax.grad(lambda p: jnp.sum(looped_out(p) * probe))(params)
    g_untied = jax.grad(lambda p: jnp.sum(untied_out(p) * probe))(untied)
    for k, g in g_loop["layers"].items():
        per_pass = g_untied["layers"][k].reshape(T, L, *g.shape[1:])
        np.testing.assert_allclose(
            g, jnp.sum(per_pass, axis=0), rtol=1e-4,
            atol=1e-5 * float(jnp.max(jnp.abs(g))), err_msg=k)
        # every pass contributes: no pass's copy has a nought gradient
        assert all(float(jnp.max(jnp.abs(per_pass[t]))) > 0 for t in range(T))


# ---- remat, accumulation ------------------------------------------------------

def _one_sgd_step(cfg, batch, accum):
    """Parameters after one step of plain SGD at rate 1: their change IS the
    gradient the step computed."""
    tok, lab = batch
    opt = optax.sgd(1.0)
    state = create_train_state(jax.random.key(0), cfg, opt)
    step = make_train_step(cfg, opt, donate=False, loss_chunk_size=8,
                           grad_accumulation_steps=accum)
    new, metrics = step(state, {"inputs": tok, "labels": lab})
    grads = jax.tree_util.tree_map(lambda a, b: a - b, state.params, new.params)
    return flat(grads), metrics


@pytest.mark.parametrize("remat,accum", [(True, 1), (False, 2), (True, 2)])
def test_remat_and_accumulation_give_the_same_gradients(batch, remat, accum):
    base, base_m = _one_sgd_step(looped(), batch, 1)
    got, got_m = _one_sgd_step(looped(remat=remat), batch, accum)
    for k in base:
        scale = float(jnp.max(jnp.abs(base[k])))
        np.testing.assert_allclose(got[k], base[k], atol=2e-5 * scale,
                                   rtol=0, err_msg=k)
    # `loss` is the expected cross-entropy on every path, the statistics ride
    # beside it: [loss, loop_ce (T), exit_mass (T), exit_entropy]
    np.testing.assert_allclose(got_m["loss"], base_m["loss"], rtol=1e-6)
    np.testing.assert_allclose(got_m["exit_stats"], base_m["exit_stats"],
                               rtol=1e-5)
    stats = np.asarray(got_m["exit_stats"])
    assert stats.shape == (2 * T + 2,) and stats[0] == float(got_m["loss"])
    np.testing.assert_allclose(stats[1 + T:1 + 2 * T].sum(), 1.0, atol=1e-5)


def test_chunked_exit_loss_equals_the_unchunked(batch):
    cfg, (tok, lab) = looped(), batch
    params = init_params(jax.random.key(1), cfg)
    whole = model_loss(params, tok, lab, None, cfg, 0)
    chunked = model_loss(params, tok, lab, None, cfg, 8)
    np.testing.assert_allclose(whole[0], chunked[0], rtol=1e-6)
    np.testing.assert_allclose(whole[1], chunked[1], rtol=1e-6)
    # the objective is the expected CE less beta x the entropy; the vector
    # of statistics opens with the expected CE and names its parts
    stats = exit_stats_fields(np.asarray(chunked[4]).tolist())
    assert float(chunked[4][0]) == float(chunked[1])
    np.testing.assert_allclose(
        chunked[0], chunked[1] - cfg.exit_beta * stats["exit_entropy"],
        rtol=1e-5)
    assert len(stats["loop_ce"]) == len(stats["exit_mass"]) == T
    assert exit_stats_fields(None) == {}


# ---- the trainer's normal path: telemetry, save, resume ------------------------

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
        experiment_name="looped", logging_frequency=2, model_dtype="fp32",
        checkpoint_engine="sharded", async_checkpoint=False,
        loss_chunk_size=8, remat=True,
        model=looped(max_seq_len=16, vocab_size=128, n_kv_heads=2),
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


def test_run_start_and_train_sync_carry_the_loop_counters(straight_run):
    _, step, events = straight_run
    assert step == 4
    start = next(e for e in events if e["event"] == "run_start")
    assert start["loop_steps"] == T and start["layer_passes"] == T * L
    syncs = [e for e in events if e["event"] == "train_sync"]
    assert len(syncs) == 2
    for e in syncs:
        assert len(e["loop_ce"]) == T and len(e["exit_mass"]) == T
        assert abs(sum(e["exit_mass"]) - 1.0) < 1e-4
        assert 0.0 < e["exit_entropy"] <= np.log(T) + 1e-6
        # `loss` is the expected cross-entropy: inside the passes' own range
        assert min(e["loop_ce"]) - 1e-4 <= e["loss"] <= max(e["loop_ce"]) + 1e-4


def test_plain_model_train_sync_has_no_loop_fields(tmp_path):
    _, _, events = _train(
        tmp_path, training_steps=2, checkpoint_frequency=-1,
        model=ModelConfig().tiny(max_seq_len=16, vocab_size=128))
    start = next(e for e in events if e["event"] == "run_start")
    assert start["loop_steps"] == 1 and start["layer_passes"] == 2
    sync = next(e for e in events if e["event"] == "train_sync")
    assert not {"loop_ce", "exit_mass", "exit_entropy"} & set(sync)


def test_sharded_save_resume_bit_exact_with_the_new_leaves(tmp_path, straight_run):
    straight, _, _ = straight_run
    _train(tmp_path, training_steps=2)
    resumed, step, _ = _train(tmp_path, resume_from_checkpoint="latest")
    assert step == 4
    a = jax.tree_util.tree_leaves_with_path(straight)
    b = jax.tree_util.tree_leaves_with_path(resumed)
    assert [p for p, _ in a] == [p for p, _ in b]
    names = {jax.tree_util.keystr(p) for p, _ in a}
    assert any("exit_gate_w" in n for n in names)
    assert any("ffn_post_norm" in n for n in names)
    for (path, x), (_, y) in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=jax.tree_util.keystr(path))


# ---- decoding through the (T . L) cache ----------------------------------------

def test_prefill_then_decode_equals_the_last_pass_of_the_full_forward(batch):
    from pyrecover_tpu.models.decode import decode_forward, init_kv_cache

    cfg = looped()
    params = init_params(jax.random.key(6), cfg)
    tok = batch[0][:2]
    full = forward(params, tok, cfg)  # logits of the LAST pass
    hiddens, _, _ = forward_passes_with_aux(params, tok, cfg)
    np.testing.assert_array_equal(
        np.asarray(full), np.asarray(project_vocab(params, hiddens[-1], cfg)))

    cache = init_kv_cache(cfg, 2, S)
    assert cache["k"].shape[0] == T * L
    n_prompt = 20
    logits, cache = decode_forward(params, cache, tok[:, :n_prompt], 0, cfg)
    np.testing.assert_allclose(logits, full[:, :n_prompt], atol=2e-5)
    for pos in range(n_prompt, S):
        step, cache = decode_forward(
            params, cache, tok[:, pos:pos + 1], pos, cfg)
        np.testing.assert_allclose(step[:, 0], full[:, pos], atol=2e-5)
    # every (pass, layer) pair wrote keys of its own
    k = np.asarray(cache["k"])
    assert len({k[i].tobytes() for i in range(T * L)}) == T * L


def test_greedy_decode_runs_a_looped_model():
    from pyrecover_tpu.models.decode import generate_tokens

    cfg = looped()
    params = init_params(jax.random.key(6), cfg)
    out = generate_tokens(params, cfg, [1, 2, 3, 4], 5)
    assert len(out) == 9 and out[:4] == [1, 2, 3, 4]


# ---- refusals, each in words, none inside a trace -------------------------------

def _pp_config(schedule):
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.parallel.mesh import MeshConfig

    return lambda: TrainConfig(model=looped(), mesh=MeshConfig(pipeline=2),
                               pp_schedule=schedule)


def _engine():
    from pyrecover_tpu.serving.engine import ServingEngine

    cfg = looped()
    return ServingEngine(init_params(jax.random.key(0), cfg), cfg)


def _pool():
    from pyrecover_tpu.serving.kvpool import BlockPool

    return BlockPool(looped(), 4, 8)


def _quantized(**kw):
    return lambda: make_train_step(looped(), optax.sgd(1.0), **kw)


@pytest.mark.parametrize("build,sentence", [
    (_pp_config("gpipe"), r"pipeline parallelism \(--pp > 1\) cannot run a looped model"),
    (_pp_config("1f1b"), r"pipeline parallelism \(--pp > 1\) cannot run a looped model"),
    (_engine, r"paged serving engine \(BlockPool\) cannot run a looped model"),
    (_pool, r"paged serving engine \(BlockPool\) cannot run a looped model"),
    (lambda: looped(n_experts=4), r"looped, sandwich-normed or gated mixture of experts"),
    (lambda: looped(loop_steps=1), r"exit_gate .* needs loop_steps >= 2"),
    (lambda: looped(loop_steps=0), r"loop_steps .* must be >= 1"),
    (_quantized(grad_allreduce="int8"), r"cannot train an exit-gated model"),
    (_quantized(grad_bucket_mb=1.0), r"cannot train an exit-gated model"),
    (_quantized(grad_allreduce="bf16"), r"cannot train an exit-gated model"),
], ids=["pp-gpipe", "pp-1f1b", "serving-engine", "kv-pool", "looped-moe",
        "gate-one-pass", "zero-passes", "int8-sync", "bucketed-sync",
        "step-bf16-sync"])
def test_refusals_name_the_path(build, sentence):
    with pytest.raises(ValueError, match=sentence):
        build()


def test_post_norms_alone_are_served_by_the_paged_engine():
    """Sandwich norms without a loop are no reason to refuse: the paged sweep
    shares the block's two residual halves with the training forward."""
    from pyrecover_tpu.models.decode import generate_tokens
    from pyrecover_tpu.serving.engine import ServingConfig, ServingEngine

    cfg = looped(loop_steps=1, exit_gate=False)
    params = init_params(jax.random.key(8), cfg)
    engine = ServingEngine(params, cfg, ServingConfig(
        max_seqs=2, block_size=8, max_model_len=S))
    prompt = [5, 6, 7, 8, 9]
    rid = engine.submit(prompt, max_new_tokens=6)
    engine.run_until_drained()
    assert engine.result(rid) == generate_tokens(params, cfg, prompt, 6)


# ---- the meters count layer passes ----------------------------------------------

def test_flop_meter_and_hbm_model_count_layer_passes():
    from pyrecover_tpu.analysis.shardcheck.checks import memory_budget
    from pyrecover_tpu.metrics import ThroughputMeter
    from pyrecover_tpu.models.presets import analytic_param_count
    from pyrecover_tpu.utils.perf import get_num_params

    cfg = looped(remat=True)
    plain = dataclasses.replace(cfg, loop_steps=1, exit_gate=False)
    assert cfg.layer_passes == T * L
    n = 1000
    looped_meter = ThroughputMeter(cfg, n, S, 1)
    plain_meter = ThroughputMeter(plain, n, S, 1)
    attn = 12 * L * cfg.n_heads * cfg.head_dim * S
    assert plain_meter.flop_per_token == 6 * n + attn
    assert looped_meter.flop_per_token == T * (6 * n + attn)

    params = init_params(jax.random.key(0), cfg)
    assert analytic_param_count(cfg) == get_num_params(params)

    def saved(c):
        rows, _ = memory_budget([], [], {}, c, batch_size=B, seq_len=S)
        return rows["activations_bytes"]

    carry = B * S * cfg.dim * 4
    assert saved(plain) == L * carry
    assert saved(cfg) == T * L * carry + T * carry  # + the T closed passes


def test_flags_and_the_cell_width():
    from pyrecover_tpu.config import get_args

    # the cell's 5632 comes out of the one formula the model has
    assert ModelConfig(dim=2048, ffn_dim_multiplier=1.0,
                       multiple_of=256).ffn_hidden_dim == 5632
    cfg = get_args([
        "--model-loop-steps", "4", "--model-post-norms", "--model-exit-gate",
        "--model-exit-beta", "0.05"]).model
    assert (cfg.loop_steps, cfg.post_norms, cfg.exit_gate,
            cfg.exit_beta) == (4, True, True, 0.05)
    plain = get_args([]).model
    assert (plain.loop_steps, plain.post_norms, plain.exit_gate) == (1, False, False)


# ---- tracing -------------------------------------------------------------------

def test_named_scopes_reach_the_lowered_program(batch):
    cfg, (tok, lab) = looped(), batch
    params = init_params(jax.random.key(0), cfg)
    text = jax.jit(
        lambda p: model_loss(p, tok, lab, None, cfg, 8)[0]
    ).lower(params).as_text(debug_info=True)
    assert "loop_pass" in text and "exit_head_loss" in text
    plain = dataclasses.replace(cfg, loop_steps=1, exit_gate=False)
    text = jax.jit(
        lambda p: model_loss(init_params(jax.random.key(0), plain), tok, lab,
                             None, plain, 8)[0]
    ).lower(params).as_text(debug_info=True)
    assert "loop_pass" not in text and "exit_head_loss" not in text


def test_new_leaves_have_partition_specs():
    from jax.sharding import PartitionSpec as P

    from pyrecover_tpu.parallel.sharding import spec_for_manifest_path

    assert spec_for_manifest_path(
        ".params['layers']['attn_post_norm']", 2) == P("pipeline", None)
    assert spec_for_manifest_path(
        ".params['layers']['ffn_post_norm']", 2) == P("pipeline", None)
    assert spec_for_manifest_path(".params['exit_gate_w']", 2) == P(None, None)
    assert spec_for_manifest_path(".opt_state[0].mu['exit_gate_b']", 1) == P(None)
