"""The jitted step's operations by the program's own scopes (ISSUE 38).

  * ``classify`` reads the phase and the scopes off an instruction's
    ``op_name`` as the compiler prints it;
  * the table of a compiled tiny step (plain, looped, hybrid and
    sparse-expert stacks) holds every sublayer the stack has and every
    phase (without remat no recomputed forward but the chunked head's),
    and leaves next to nothing without phase and scope;
  * a fusion is read by the product inside it, an instruction the compiler
    named nothing by its reader;
  * the table is written and its ONE event emitted only while a sink is
    registered;
  * the scopes are metadata and nothing else: with ``jax.named_scope`` a
    null context the step lowers to the same text.
"""

import contextlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import optax
import pytest

from pyrecover_tpu import telemetry
from pyrecover_tpu.models.llama import ModelConfig
from pyrecover_tpu.telemetry import stepscopes
from pyrecover_tpu.train_state import (
    IGNORE_INDEX,
    create_train_state,
    make_train_step,
)
from pyrecover_tpu.utils import remat

REPO = Path(__file__).resolve().parent.parent
B, S, CHUNK = 2, 32, 16

STACKS = {
    "plain": dict(),
    "looped": dict(loop_steps=3, post_norms=True, exit_gate=True),
    "hybrid": dict(
        n_layers=8, n_kv_heads=1, attn_layer_period=4, attn_layer_offset=2,
        rope=False, tie_embeddings=True, mamba_d_state=4, mamba_dt_rank=4),
    "sparse-expert": dict(n_experts=4, moe_top_k=2),
}
# the sublayers each stack opens, beside embed / attn / optimizer
SUBLAYERS = {
    "plain": {"ffn", "loss_head"},
    "looped": {"ffn", "exit_head_loss"},
    "hybrid": {"ffn", "loss_head", "mamba_mixer"},
    "sparse-expert": {"ffn", "moe_ffn", "loss_head"},
}


@pytest.fixture(scope="module", autouse=True)
def short_chunks():
    """The selective scan's chunk (256 tokens) shortened to 8 for the toy
    sequences here, as tests/test_hybrid.py does."""
    import pyrecover_tpu.ops.selective_scan as ss

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ss, "SCAN_CHUNK", 8)
        yield


def tiny(stack, use_remat):
    cfg = ModelConfig().tiny(
        max_seq_len=S, remat=use_remat, remat_policy="full", **STACKS[stack])
    opt = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3))
    state = create_train_state(jax.random.key(0), cfg, opt)
    tok = jax.random.randint(jax.random.key(5), (B, S), 0, cfg.vocab_size)
    lab = jnp.roll(tok, -1, axis=1).at[:, -1].set(IGNORE_INDEX)
    step = make_train_step(cfg, opt, donate=False, loss_chunk_size=CHUNK)
    return step, state, {"inputs": tok, "labels": lab}


_TABLES = {}


def table_of(stack, use_remat):
    """One compile a (stack, remat) for the module."""
    key = stack, use_remat
    if key not in _TABLES:
        step, state, batch = tiny(stack, use_remat)
        _TABLES[key] = stepscopes.table(step.lower(state, batch).compile())
    return _TABLES[key]


# ---- classify ----------------------------------------------------------------

LAYER = "while/body/closed_call"


@pytest.mark.parametrize("op_name,phase,scopes", [
    (f"jit(train_step)/jvp()/layers/{LAYER}/ffn/dot_general",
     "fwd", ("layers", "ffn")),
    # a scope opened outside the differentiated function lies inside the
    # transform's parentheses
    (f"jit(train_step)/transpose(jvp(layers))/{LAYER}/checkpoint/attn/"
     "flash_attention/flash_dq/pallas_call",
     "bwd", ("layers", "attn", "flash_attention")),
    (f"jit(train_step)/transpose(jvp(layers))/{LAYER}/checkpoint/"
     "rematted_computation/ffn/jit(silu)/mul", "remat", ("layers", "ffn")),
    (f"jit(train_step)/transpose(jvp())/{LAYER}/loop_pass/layers/{LAYER}/"
     "checkpoint/rematted_computation/attn/mul",
     "remat", ("loop_pass", "layers", "attn")),
    ("jit(train_step)/optimizer/jit(_threefry_fold_in)/add",
     "update", ("optimizer",)),
    (f"jit(train_step)/jvp(loss_head)/{LAYER}/bsd,dv->bsv/dot_general",
     "fwd", ("loss_head",)),
    (f"jit(train_step)/jvp(layers)/{LAYER}/ffn/moe_ffn/jit(_take)/gather",
     "fwd", ("layers", "ffn", "moe_ffn")),
    # the compiler merged two instructions: the first part names the result
    (f"jit(train_step)/transpose(jvp())/layers/{LAYER}/checkpoint/ffn/mul;"
     "jit(train_step)/optimizer/add", "bwd", ("layers", "ffn")),
    # a function's name is no scope, whatever it is called
    ("jit(train_step)/jit(embed)/jit(_where)/select_n", "", ()),
    ("jit(train_step)/attn/iota", "", ("attn",)),
    ("state.params['layers']['w1']", "", ()),
    ("", "", ()),
])
def test_classify_reads_phase_and_scopes_off_a_path(op_name, phase, scopes):
    assert stepscopes.classify(op_name) == (phase, scopes)


@pytest.mark.parametrize("scopes,part", [
    (("layers", "ffn"), "ffn"),
    (("layers", "ffn", "moe_ffn"), "moe_ffn"),
    (("layers", "mamba_mixer", "ssm_scan"), "mamba_mixer"),
    (("loop_pass", "layers"), "layer_scan"),
    (("loop_pass",), "layer_scan"),
    ((), ""),
])
def test_an_operation_belongs_to_the_last_sublayer_on_its_path(scopes, part):
    assert stepscopes.sublayer(scopes) == part


def test_the_vocabulary_is_one_tuple_and_the_models_open_its_names():
    assert len(set(stepscopes.SCOPES)) == len(stepscopes.SCOPES)
    assert set(stepscopes.SCOPES) == (
        set(stepscopes.SUBLAYERS) | set(stepscopes.KERNELS)
        | set(stepscopes.GROUPS))
    from pyrecover_tpu.models import llama, mamba, moe
    from pyrecover_tpu.ops import selective_scan

    assert llama.ATTN is stepscopes.ATTN and moe.MOE_FFN is stepscopes.MOE_FFN
    assert mamba.MAMBA_MIXER is stepscopes.MAMBA_MIXER
    assert selective_scan.SSM_SCAN is stepscopes.SSM_SCAN


# ---- the table of a compiled step ----------------------------------------------

def parts(tab):
    rows = tab["instructions"].values()
    phases = {r[0] for r in rows}
    subs = {stepscopes.sublayer(r[1].split("/")) for r in rows}
    unscoped = sum(1 for r in rows if not r[0] and not r[1])
    return phases, subs, unscoped / max(len(tab["instructions"]), 1)


@pytest.mark.parametrize("stack", list(STACKS))
def test_table_holds_every_sublayer_and_phase_of_the_stack(stack):
    tab = table_of(stack, True)
    assert tab["module"] == "jit_train_step"
    phases, subs, unscoped = parts(tab)
    assert {"fwd", "remat", "bwd", "update"} <= phases
    assert {"embed", "attn", "optimizer", "layer_scan"} <= subs
    assert SUBLAYERS[stack] <= subs
    other = set(stepscopes.SUBLAYERS) - SUBLAYERS[stack] - {
        "embed", "attn", "optimizer"}
    assert not other & subs
    assert unscoped < 0.05
    scopes = {s for r in tab["instructions"].values() for s in r[1].split("/")}
    assert ("loop_pass" in scopes) == (stack == "looped")
    assert ("ssm_scan" in scopes) == (stack == "hybrid")
    # instructions that do no work of their own are no events of a trace
    assert not {r[2] for r in tab["instructions"].values()} & {
        "parameter", "constant", "get-tuple-element", "tuple"}
    json.dumps(tab)


@pytest.mark.parametrize("stack", list(STACKS))
def test_no_remat_phase_in_the_layers_without_remat(stack):
    tab = table_of(stack, False)
    phases, subs, unscoped = parts(tab)
    # the layer scan recomputes nothing; the chunked head still does, chunk
    # by chunk, under a checkpoint of its own (train_state.chunked_ce_sum)
    recomputed = {stepscopes.sublayer(r[1].split("/"))
                  for r in tab["instructions"].values() if r[0] == "remat"}
    assert recomputed == SUBLAYERS[stack] & {"loss_head", "exit_head_loss"}
    assert {"fwd", "bwd", "update"} <= phases and SUBLAYERS[stack] <= subs
    assert unscoped < 0.05


@pytest.mark.parametrize("stack", list(STACKS))
def test_the_event_counts_what_the_table_holds(stack):
    tab = table_of(stack, True)
    got = stepscopes.summary(tab)
    assert got["instructions"] == len(tab["instructions"])
    assert sum(got["by_phase"].values()) == got["instructions"]
    assert sum(got["by_scope"].values()) == got["instructions"]
    assert got["unscoped"] <= got["by_phase"].get("none", 0)
    assert got["module"] == "jit_train_step"


HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (p0: f32[4,8,8], p1: f32[8,8], p2: s32[]) -> f32[4,8,8] {
  %p0 = f32[4,8,8]{2,1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %p2 = s32[] parameter(2)
  %convolution.1 = f32[8,8]{1,0} convolution(%p1, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(train_step)/transpose(jvp())/layers/while/body/closed_call/checkpoint/ffn/dot_general"}
  %bitcast.1 = f32[1,8,8]{2,1,0} bitcast(%convolution.1)
  ROOT %dynamic-update-slice.1 = f32[4,8,8]{2,1,0} dynamic-update-slice(%p0, %bitcast.1, %p2, %p2, %p2), metadata={op_name="jit(train_step)/transpose(jvp())/layers/while/body/dynamic_update_slice"}
}

%fused_computation.2 (p0: f32[4,8,8], p1: f32[8,8], p2: s32[]) -> f32[4,8,8] {
  %p0 = f32[4,8,8]{2,1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %p2 = s32[] parameter(2)
  %bitcast.2 = f32[1,8,8]{2,1,0} bitcast(%p1)
  ROOT %dynamic-update-slice.2 = f32[4,8,8]{2,1,0} dynamic-update-slice(%p0, %bitcast.2, %p2, %p2, %p2), metadata={op_name="jit(train_step)/transpose(jvp())/layers/while/body/dynamic_update_slice"}
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%body.1 (arg: (s32[], f32[4,8,8], f32[8,8])) -> (s32[], f32[4,8,8], f32[8,8]) {
  %arg = (s32[], f32[4,8,8]{2,1,0}, f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %g = f32[4,8,8]{2,1,0} get-tuple-element(%arg), index=1
  %x = f32[8,8]{1,0:T(8,128)S(1)} get-tuple-element(%arg), index=2
  %copy.7 = f32[8,8]{1,0} copy(%x)
  %bitcast_dynamic-update-slice_fusion.3 = f32[4,8,8]{2,1,0} fusion(%g, %copy.7, %i), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp())/layers/while/body/dynamic_update_slice"}
  %dynamic-update-slice_fusion.4 = f32[4,8,8]{2,1,0} fusion(%bitcast_dynamic-update-slice_fusion.3, %x, %i), kind=kLoop, calls=%fused_computation.2
  %reduce.5 = f32[] reduce(%x, %i), dimensions={0,1}, to_apply=%region_0.1
  %copy.8 = f32[8,8]{1,0} copy(%x)
  ROOT %tuple.1 = (s32[], f32[4,8,8]{2,1,0}, f32[8,8]{1,0}) tuple(%i, %dynamic-update-slice_fusion.4, %copy.8)
}

%cond.1 (arg: (s32[], f32[4,8,8], f32[8,8])) -> pred[] {
  %arg = (s32[], f32[4,8,8]{2,1,0}, f32[8,8]{1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%arg), index=0
  %c = s32[] constant(4)
  ROOT %compare.1 = pred[] compare(%i.1, %c), direction=LT, metadata={op_name="jit(train_step)/transpose(jvp())/layers/while/cond/lt"}
}

ENTRY %main.1 (p: f32[8,8]) -> f32[4,8,8] {
  %p = f32[8,8]{1,0} parameter(0), metadata={op_name="state.params['w']"}
  %zero = s32[] constant(0)
  %broadcast.1 = f32[4,8,8]{2,1,0} broadcast(%zero), dimensions={}
  %tuple.0 = (s32[], f32[4,8,8]{2,1,0}, f32[8,8]{1,0}) tuple(%zero, %broadcast.1, %p)
  %while.1 = (s32[], f32[4,8,8]{2,1,0}, f32[8,8]{1,0}) while(%tuple.0), condition=%cond.1, body=%body.1, metadata={op_name="jit(train_step)/transpose(jvp())/layers/while"}
  %gte = f32[4,8,8]{2,1,0} get-tuple-element(%while.1), index=1
  %copy.9 = f32[4,8,8]{2,1,0} copy(%gte)
  ROOT %multiply.1 = f32[4,8,8]{2,1,0} multiply(%copy.9, %copy.9), metadata={op_name="jit(train_step)/optimizer/mul;jit(train_step)/jvp()/embed/mul"}
}
"""


def test_a_fusion_is_read_by_its_product_and_a_nameless_copy_by_its_reader():
    tab = stepscopes.table(HLO)
    rows = tab["instructions"]
    assert tab["module"] == "jit_train_step"
    # a weight-gradient product fused with its write into the stacked
    # gradient: the product's scope, the write's opcode
    assert rows["bitcast_dynamic-update-slice_fusion.3"] == [
        "bwd", "layers/ffn", "dynamic-update-slice", "convolution"]
    # a bare write: the scan's plumbing
    assert rows["dynamic-update-slice_fusion.4"] == [
        "bwd", "layers", "dynamic-update-slice", ""]
    # the compiler's own copy takes its reader's name ...
    assert rows["copy.7"][:2] == ["bwd", "layers/ffn"]
    assert rows["copy.9"] == ["update", "optimizer", "copy", ""]
    # ... and one nothing named reads, the name of the loop it runs in
    assert rows["copy.8"] == ["bwd", "layers", "copy", ""]
    assert rows["reduce.5"][:2] == ["bwd", "layers"]
    assert rows["while.1"] == ["bwd", "layers", "while", ""]
    assert rows["compare.1"][:2] == ["bwd", "layers"]
    # a merged name is read by its first part
    assert rows["multiply.1"] == ["update", "optimizer", "multiply", ""]
    # fused bodies, scalar regions and instructions that do no work are
    # no rows
    assert not {"convolution.1", "add.9", "p", "zero", "tuple.0", "gte",
                "dynamic-update-slice.1"} & set(rows)
    assert rows["broadcast.1"][:2] == ["bwd", "layers"]  # the loop reads it


# ---- written once, with a sink -------------------------------------------------

def test_step_scopes_is_emitted_once_with_a_sink_and_nothing_without(tmp_path):
    step, state, batch = tiny("plain", False)
    path = tmp_path / "exp" / stepscopes.FILE_NAME

    quiet = remat.CompiledOnce(
        lambda mc: step, None, None, on_ready=None, scopes_path=path)
    assert not telemetry.enabled()
    quiet(state, batch)
    assert not path.exists() and not path.parent.exists()

    sink = telemetry.add_sink(telemetry.MemorySink())
    try:
        once = remat.CompiledOnce(
            lambda mc: step, None, None, on_ready=None, scopes_path=path)
        for _ in range(3):
            new_state, _ = once(state, batch)
        events = [e for e in sink.events if e["event"] == "step_scopes"]
    finally:
        telemetry.remove_sink(sink)
    (event,) = events
    assert event["path"] == str(path) and event["module"] == "jit_train_step"
    assert event["build_s"] >= 0 and event["unscoped"] < event["instructions"]
    assert {"fwd", "bwd", "update"} <= set(event["by_phase"])
    assert {"attn", "ffn", "loss_head", "optimizer"} <= set(event["by_scope"])
    # the table is in the file, never in the event
    assert "instructions" in json.loads(path.read_text())
    assert len(json.dumps(event)) < 2000
    tab = json.loads(path.read_text())
    assert len(tab["instructions"]) == event["instructions"]
    assert tab["vocabulary"]["sublayers"] == list(stepscopes.SUBLAYERS)
    assert int(new_state.step) == 1


def test_a_table_that_cannot_be_written_does_not_stop_the_step(tmp_path):
    step, state, batch = tiny("plain", False)
    blocked = tmp_path / "file"
    blocked.write_text("not a directory")
    sink = telemetry.add_sink(telemetry.MemorySink())
    try:
        once = remat.CompiledOnce(
            lambda mc: step, None, None, on_ready=None,
            scopes_path=blocked / stepscopes.FILE_NAME)
        new_state, _ = once(state, batch)
        assert not [e for e in sink.events if e["event"] == "step_scopes"]
    finally:
        telemetry.remove_sink(sink)
    assert int(new_state.step) == 1


def test_the_trainer_writes_table_and_profile_under_the_experiment_directory(
        tmp_path):
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.train import train

    sink = telemetry.add_sink(telemetry.MemorySink())
    try:
        train(TrainConfig(
            sequence_length=16, batch_size=8, training_samples=32,
            training_steps=2, learning_rate=1e-3, lr_warmup_steps=1, seed=3,
            checkpoint_dir=str(tmp_path), checkpoint_frequency=-1,
            experiment_name="scopes", logging_frequency=1,
            profile=True, profile_step_start=1, profile_step_end=2,
            profile_dir="prof",
            model=ModelConfig().tiny(max_seq_len=16, vocab_size=128)))
        events = [e for e in sink.events if e["event"] == "step_scopes"]
    finally:
        telemetry.remove_sink(sink)
    (event,) = events  # a run without --remat has its table too
    assert event["path"] == str(tmp_path / "scopes" / stepscopes.FILE_NAME)
    tab = json.loads((tmp_path / "scopes" / stepscopes.FILE_NAME).read_text())
    assert tab["module"] == "jit_train_step"
    assert not [e for e in sink.events if e["event"] == "remat_autosize"]
    # a --profile run leaves the profile beside it: a relative
    # --profile-dir lies under the experiment directory
    assert list((tmp_path / "scopes" / "prof").rglob("*.xplane.pb"))


# ---- the operator's reader -----------------------------------------------------

def test_the_tool_reads_a_recorded_profile_by_a_table(tmp_path, capsys):
    """tools/step_scopes.py on the small profile recorded on the chip
    (benchmark/tests/data: three runs of ``jit_work``, five operations a
    run), under a table made by hand for its instructions."""
    import sys

    sys.path.insert(0, str(REPO / "tools"))
    import step_scopes as tool

    table = {
        "module": "jit_work",
        "vocabulary": {"sublayers": list(stepscopes.SUBLAYERS),
                       "kernels": list(stepscopes.KERNELS),
                       "groups": list(stepscopes.GROUPS)},
        "instructions": {
            "convolution_tanh_fusion.2": [
                "fwd", "layers/ffn", "tanh", "convolution"],
            "fusion.12": ["bwd", "layers/ffn", "reduce", "convolution"],
            "broadcast_add_fusion": ["update", "optimizer", "add", ""],
            "copy-done": ["fwd", "layers", "copy-done", ""],
        },
    }
    path = tmp_path / "step_scopes.json"
    path.write_text(json.dumps(table))
    trace = REPO / "benchmark/tests/data/small_1chip.xplane.pb"
    out = tmp_path / "out" / "split.json"
    assert tool.main([str(trace), str(path), "--json", str(out)]) == 0
    text = capsys.readouterr().out
    got = json.loads(out.read_text())
    assert got["steps"] == 3  # the module's runs in the profile
    assert got["step_ms"] == pytest.approx(0.0244, rel=0.01)
    assert set(got["phase_ms"]) == {"fwd", "bwd", "update"}
    assert got["sublayer_ms"]["ffn"] == pytest.approx(0.02238, rel=0.01)
    assert got["sublayer_ms"]["layer_scan"] > 0  # copy-done: a group alone
    # copy-start is in no table: unscoped, and named so among the heaviest
    assert 0 < got["unscoped_pct"] < 0.1
    assert got["heaviest"][0][0] == "fusion.12"
    assert got["heaviest"][0][2:] == ["bwd", "layers/ffn", "reduce", "convolution"]
    assert "convolution>reduce" in text and "ms a step over 3 steps" in text
    assert "heaviest of 5 operations," in text
    assert sum(got["phase_ms"].values()) == pytest.approx(
        got["step_ms"] * (1 - got["unscoped_pct"] / 100))
    # one scope's operations alone
    assert tool.main([str(trace), str(path), "--scope", "optimizer"]) == 0
    listed = capsys.readouterr().out.split("under scope optimizer")[1]
    assert "broadcast_add_fusion" in listed and "fusion.12" not in listed
    # no table, no run of the module: said in words, exit 2
    assert tool.main([str(trace), str(tmp_path / "none.json")]) == 2
    other = tmp_path / "other.json"
    other.write_text(json.dumps(dict(table, module="jit_train_step")))
    assert tool.main([str(trace), str(other)]) == 2
    assert tool.main([str(trace), str(other), "--steps", "3"]) == 0


# ---- metadata and nothing else -------------------------------------------------

@pytest.mark.parametrize("stack", list(STACKS))
def test_the_step_lowers_to_the_same_text_without_the_scopes(stack, monkeypatch):
    step, state, batch = tiny(stack, True)
    scoped = step.lower(state, batch)
    assert "/attn/" in scoped.as_text(debug_info=True)
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext())
    step, state, batch = tiny(stack, True)
    bare = step.lower(state, batch)
    assert "/attn/" not in bare.as_text(debug_info=True)
    assert bare.as_text() == scoped.as_text()
