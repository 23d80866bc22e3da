"""What the layer scan keeps under remat (utils/remat.py, ISSUE 31).

  * what is saved is what was named: with the flash residuals kept the
    backward sweep holds no second forward kernel, and the kept row
    statistics are (b, h, s);
  * the resolver at the two benchmark cells' shapes on `TPU v5 lite`
    answers what the chip runs confirmed, and the memory model it sizes
    by stays within its stated error of the v5e compiler's own peaks
    (recorded from tools/remat_ladder.py; nothing compiles here);
  * the compiler keeps the last word once: a refused compile steps down
    one rung, says so in the event, and never loops.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyrecover_tpu.models.llama import ModelConfig, _attention_fn, _block, init_params
from pyrecover_tpu.ops.rope import precompute_rope
from pyrecover_tpu.utils import remat

GIB = 2**30
V5E = "TPU v5 lite"

# compiled.memory_analysis().peak_memory_in_bytes of the train step, v5e
# compiler, one device of v5e:2x2, the cells' own shapes (4 x 4096, loss
# chunk 512): `tools/remat_ladder.py --cell <cell> --topology v5e:2x2`
# (PR 31). A rung the compiler refuses reads what it said it would use.
COMPILER_PEAKS = {
    "mistral-7b.steady": {
        "flash+qkv+w3": 15570013696,
        "qkv+w3": 15041449472,
        "flash+qkv": 13690965504,
        "qkv": 13162401280,
        "save-attn": 12885659136,
        "full": 12357234176,
    },
    "ouro-2.6b.steady": {"full": 14802372608},
    # PR 32: a hybrid stack, 2 x 4096, the scan's kernel pair in the program
    # (`ops.selective_scan.resolve_impl` steered to the kernels, as the chip
    # resolves it; the XLA formulation reads 15629611008 under full).
    # One attention layer of fourteen: what it keeps does not move the peak,
    # which a Mamba layer's backward sets
    # PR 34: the kernels read B and C at their own size and u at 2 bytes
    # (no lane-spread copies, no float32 u among a layer's temporaries):
    # 15420156416 / 15425399296 before
    "jamba2-3b.steady": {
        "flash+qkv": 15278599168,  # the shipped program
        "qkv": 15278599168, "save-attn": 15278599168, "full": 15278599168,
    },
    # chip_smoke.py's shape: llama-1b widths, 20 layers, 8 x 2048
    "llama-1b": {"qkv": 15823074304, "save-attn": 14502003712,
                 "full": 12836791296},
}
# rungs the compiler refuses, by what it said the program would use
# ("Used 21.70G of 15.75G hbm"): a hopeless program's count is loose, so
# these only have to read as not fitting, and no lower than refused
REFUSED_GIB = {
    "mistral-7b.steady": {"none": 22.69},
    "ouro-2.6b.steady": {"save-attn": 17.79, "qkv": 20.0, "flash+qkv": 24.47},
    # (no remat at all reads "Used 43.80G"; the model's 39 GiB is under it by
    # more than this table allows and is not held to it: nobody sizes a
    # hybrid stack without remat)
    "jamba2-3b.steady": {},
    "llama-1b": {"qkv+w3": 18.94, "flash+qkv+w3": 20.32},
}
# the model's stated error against the peaks: it may read up to 4 % high
# and no more than 2 % low (llama-1b's save-sets read 0.27 GiB low; the
# low side is what the last part of the resolver's margin is for)
ERR_LOW, ERR_HIGH = 0.02, 0.04
LLAMA_1B = [  # chip_smoke.py's train_cmd
    "--model-dim", "2048", "--model-layers", "20", "--model-heads", "16",
    "--model-kv-heads", "8", "--vocab-size", "32768",
    "--sequence-length", "2048", "--batch-size", "8",
    "--model-dtype", "bf16", "--param-dtype", "bf16",
    "--use-flash-attention", "--remat", "--loss-chunk-size", "512",
]


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setenv("PYRECOVER_PALLAS_INTERPRET", "1")


def flash_block(names):
    """One remat-wrapped block with the flash kernel, and its inputs."""
    cfg = dataclasses.replace(
        ModelConfig().tiny(max_seq_len=32, n_layers=1),
        attention_impl="flash", remat=True, remat_save=names,
    )
    # (the model's attention builder keeps the statistics slim where the
    # policy saves them, and as the kernel wrote them elsewhere)
    layer = jax.tree_util.tree_map(
        lambda a: a[0], init_params(jax.random.key(0), cfg)["layers"])
    cos, sin = precompute_rope(cfg.head_dim, 32, cfg.rope_theta)
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.dim), jnp.float32)

    def block(x, layer):
        out, _ = _block(x, layer, cos, sin, cfg, _attention_fn(cfg))
        return out

    return cfg, jax.checkpoint(block, policy=remat.checkpoint_policy(cfg)), x, layer


@pytest.mark.parametrize("names,forward_calls", [
    ((), 2),  # full: the forward kernel runs again inside the backward
    (remat.FLASH, 1),  # its residuals kept: once
])
def test_flash_forward_runs_once_when_its_residuals_are_kept(
        interpret, names, forward_calls):
    cfg, block, x, layer = flash_block(names)
    grad = jax.make_jaxpr(jax.grad(lambda x, l: jnp.sum(block(x, l) ** 2)))
    assert str(grad(x, layer)).count("name=flash_fwd") == forward_calls


def test_saved_residuals_are_the_named_ones(interpret):
    from jax._src.ad_checkpoint import saved_residuals  # no public name

    cfg, block, x, layer = flash_block(remat.FLASH)
    saved = [(aval.shape, why) for aval, why in saved_residuals(block, x, layer)]
    kept = [(shape, why) for shape, why in saved
            if "argument" not in why and "constant" not in why]
    b, s, h, d = 2, 32, cfg.n_heads, cfg.head_dim
    # the kernel's output, and lane 0 of its (b, h, s, 8) row statistics,
    # not all 8; besides them only the block's arguments (and the rope
    # tables) are kept
    assert sorted(shape for shape, _ in kept) == [(b, h, s), (b, s, h, d)]
    assert any("named 'flash_lse'" in why for _, why in kept)
    # under full nothing but the arguments is
    _, full, x, layer = flash_block(())
    assert all("argument" in why or "constant" in why
               for _, why in saved_residuals(full, x, layer))


def cell(name, **model):
    import remat_ladder

    from pyrecover_tpu.config import get_args

    config = (get_args(LLAMA_1B) if name == "llama-1b"
              else remat_ladder.cell_config(name, False))
    return config, dataclasses.replace(config.model, **model)


def decide(name, kind=V5E, **model):
    config, model_config = cell(name, **model)
    return remat.resolve_remat_policy(
        model_config, {}, batch_size=config.batch_size,
        seq_len=config.sequence_length,
        loss_chunk_size=config.loss_chunk_size, device_kind=kind,
    )


@pytest.mark.parametrize("name,rung", [
    ("mistral-7b.steady", "flash+qkv+w3"),  # what the chip runs confirmed
    ("ouro-2.6b.steady", "full"),  # 13.79 GiB as it is: nothing more fits
    # the one attention layer's kernel residuals and q, k, v (0.08 GiB);
    # `w3` would be kept in all fourteen layers (1.75 GiB) and does not fit
    ("jamba2-3b.steady", "flash+qkv"),
    ("llama-1b", "qkv"),  # flash+qkv makes the compiler rematerialize
])
def test_auto_at_the_cells_shapes(monkeypatch, name, rung):
    monkeypatch.setenv(remat.DEVICE_KIND_ENV, V5E)  # wins over the live CPU
    d = decide(name, kind="cpu")
    assert (d.rung, d.fits, d.fell_back) == (rung, True, 0)
    assert d.limit_bytes == int(15.75 * GIB)
    assert d.table[rung] <= d.limit_bytes - d.margin_bytes
    built = d.apply(cell(name)[1])
    assert built.remat and built.remat_save == d.saved_names
    assert remat.saved_names(built) == d.saved_names
    event = d.as_event()
    assert event["rung"] == rung and event["modelled_bytes"] == d.table
    assert event["saved_names"] == list(d.saved_names)


def test_auto_without_a_limit_and_without_remat(monkeypatch):
    monkeypatch.delenv(remat.DEVICE_KIND_ENV, raising=False)
    d = decide("mistral-7b.steady", kind="cpu")  # no limit: today's program
    assert (d.rung, d.fits, d.limit_bytes) == ("full", None, None)
    assert d.saved_names == ()
    d = decide("mistral-7b.steady", remat=False)  # auto does not override
    assert d.rung == "none" and not d.remat
    assert not d.apply(cell("mistral-7b.steady")[1]).remat
    # an explicit policy is that rung, whatever fits
    d = decide("ouro-2.6b.steady", remat_policy="save-attn")
    assert d.rung == "save-attn" and d.saved_names == remat.FLASH


@pytest.mark.parametrize("name", sorted(COMPILER_PEAKS))
def test_memory_model_against_the_compilers_peaks(name):
    d = decide(name)
    for rung, peak in COMPILER_PEAKS[name].items():
        got = d.table[rung] / peak
        assert 1 - ERR_LOW <= got <= 1 + ERR_HIGH, (rung, got)
        # never under the compiler's count by more than the margin
        assert d.table[rung] >= peak - remat.MARGIN_BYTES, rung
    for rung, used in REFUSED_GIB[name].items():
        assert d.table[rung] > d.limit_bytes, rung  # reads as not fitting
        assert d.table[rung] >= (used - 0.25) * GIB, rung


def test_ladder_is_ordered_and_names_exist():
    config, model = cell("mistral-7b.steady")
    table = decide("mistral-7b.steady").table
    sizes = [table[rung] for rung in remat.RUNGS]
    assert sizes == sorted(sizes, reverse=True)  # richest first
    assert len(set(sizes)) == len(sizes)  # with the kernel, no two alike
    assert remat.RUNGS[0] == "none" and remat.RUNGS[-1] == "full"
    assert remat.LADDER["save-attn"] == remat.FLASH


def test_every_saved_name_is_a_name_the_program_carries(interpret):
    _, block, x, layer = flash_block(())
    # (differentiated: the flash names sit in the kernel's forward rule)
    program = str(jax.make_jaxpr(
        jax.grad(lambda x, l: jnp.sum(block(x, l) ** 2)))(x, layer))
    for names in remat.LADDER.values():
        for n in names:
            assert f"name={n}]" in program, n


class FakeStep:
    """A jitted step's surface: ``lower().compile()`` and a call."""

    def __init__(self, model_config, refuse):
        self.model_config, self.refuse = model_config, refuse
        self.calls = 0

    def lower(self, state, batch):
        return self

    def compile(self):
        if remat.saved_names(self.model_config) in self.refuse:
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran "
                "out of memory in memory space hbm. Used 16.55G of 15.75G hbm.")
        return self

    def memory_analysis(self):
        return type("M", (), {"peak_memory_in_bytes": 123})()

    def __call__(self, state, batch):
        self.calls += 1
        return state, {"loss": 0.0}


TOP = remat.FLASH + remat.QKV + remat.W3


@pytest.mark.parametrize("refuse,fell_back,rung", [
    ((), 0, "flash+qkv+w3"),
    ((TOP,), 1, "qkv+w3"),
    ((TOP, remat.QKV + remat.W3, remat.FLASH + remat.QKV), 3, "qkv"),
])
def test_a_refused_compile_steps_down_one_rung(refuse, fell_back, rung):
    decision = decide("mistral-7b.steady")
    built, events = [], []

    def build(model_config):
        built.append(FakeStep(model_config, refuse))
        return built[-1]

    step = remat.CompiledOnce(
        build, cell("mistral-7b.steady")[1], decision, events.append)
    for _ in range(3):
        step(None, None)
    assert len(built) == fell_back + 1  # one compile a rung, never a loop
    assert built[-1].calls == 3 and all(s.calls == 0 for s in built[:-1])
    (event,) = events  # emitted once, when the step has compiled
    assert (event.rung, event.fell_back) == (rung, fell_back)
    assert event.compiled_peak_bytes == 123
    assert event.as_event()["fell_back"] == fell_back


def test_a_refusal_that_is_not_the_ladders_to_take():
    decision = decide("mistral-7b.steady")
    model = cell("mistral-7b.steady")[1]
    # nothing leaner than full: the compiler's error is the user's to read
    everything = tuple(remat.LADDER.values())
    step = remat.CompiledOnce(
        lambda mc: FakeStep(mc, everything), model, decision, lambda d: None)
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        step(None, None)
    # an explicit policy does not step down
    explicit = dataclasses.replace(model, remat_policy="save-attn")
    step = remat.CompiledOnce(
        lambda mc: FakeStep(mc, (remat.FLASH,)), explicit,
        decide("mistral-7b.steady", remat_policy="save-attn"), lambda d: None)
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        step(None, None)


def test_step_without_lower_compiles_at_its_first_call():
    """A plain wrapper round the step (benchmark faults, tests) is called
    as it is; the event still goes out, without a compiled peak."""
    events = []
    calls = []

    def plain(state, batch):
        calls.append(1)
        return state, {}

    step = remat.CompiledOnce(
        lambda mc: plain, cell("mistral-7b.steady")[1],
        decide("mistral-7b.steady"), events.append)
    step(None, None)
    step(None, None)
    assert len(calls) == 2 and len(events) == 1
    assert events[0].compiled_peak_bytes is None
    assert np.isfinite(events[0].table["full"])
