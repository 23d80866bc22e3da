"""The looped configuration's own files: its plain reference against the
program at a toy width on the CPU, the lower-precision controls, the looped
count by hand at the cell's shapes, the new per-layer readers on hand-made run
views, and the entries in BENCHMARK.json."""

import json

import numpy as np
import pytest

from benchmark.lib import counts_looped
from benchmark.lib.manifest import Manifest
from benchmark.run import REHEARSAL
from benchmark.runners import train_window as tw
from benchmark.tests.test_reference import program_steps, rows_for

CELL, CONFIG = "ouro-2.6b.steady", "ouro-2.6b"


def toy():
    """The harness's toy width, with the feed-forward width the looped
    configuration's two numbers (multiplier 1.0, rounded up to 32) give at
    hidden 64, and the toy's own head size (the file states the published 128)."""
    man = Manifest()
    cfg = man.config(CONFIG)
    tm = {**cfg["trainer_model"], **REHEARSAL["cfg"]["trainer_model"]}
    return man, {**cfg, **REHEARSAL["cfg"], "trainer_model": tm,
                 "intermediate_size": 192, "head_dim": 16}


# ---- the reference ------------------------------------------------------------

def test_reference_imports_nothing_of_the_program():
    text = Manifest().bench.joinpath("references", "ouro.py").read_text()
    assert "import pyrecover_tpu" not in text
    assert "from pyrecover_tpu" not in text


def test_reference_follows_the_program():
    """Through the harness's own wrapper and comparison: the seed's weights,
    two steps' losses, the first gradient of every leaf (the gate and the four
    norms among them) and the parameters' change under AdamW. Both sides in
    float32 here, so they agree to rounding; the cell's limits are read on
    the chip."""
    import jax

    man, cfg = toy()
    rows = rows_for(3, 2)
    prog, config = program_steps(cfg, 11, rows)
    ref = man.reference(cfg["reference"]).Reference(
        cfg, tw.optimizer_facts(config), jax.devices()[:1])
    out = tw.follow(ref, 11, rows)
    assert set(prog["grad_leaf_norms"]) == set(out["grad_leaf_norms"])
    assert {"exit_gate_w", "exit_gate_b", "layers/attn_post_norm",
            "layers/ffn_post_norm"} <= set(out["grad_leaf_norms"])
    got = tw.compare(prog, out)
    assert got["weights_gap"] < 1e-6  # norms sum in another order
    assert got["loss1_gap"] < 2e-6 and got["loss2_gap"] < 2e-6, got
    # three passes more of backward than Mistral's toy: the same bounds hold
    assert got["gnorm1_gap"] < 1e-4, got
    assert got["grad_leaf_gap"] < 1e-4, got
    assert got["change_leaf_gap"] < 1e-3, got
    # the reference keeps the last step's exit statistics for a by-hand look
    assert ref.last["exit_mass"].shape == (cfg["total_ut_steps"],)
    assert abs(ref.last["exit_mass"].sum() - 1.0) < 1e-6


def test_hand_rolled_sweep_is_the_gradient_of_the_plain_loss():
    """The reference's pass-by-pass, recomputing backward sweep against
    ``jax.grad`` of its own straight-line loss: every leaf."""
    import jax
    import jax.numpy as jnp

    man, cfg = toy()
    mod = man.reference(cfg["reference"])
    rows = rows_for(9, 1, batch=2, seq=32)
    facts = {"learning_rate": 3e-4, "lr_warmup_steps": 1, "adam_b1": 0.9,
             "adam_b2": 0.95, "adam_eps": 1e-8, "weight_decay": 0.1,
             "grad_clipping": False, "grad_max_norm": 1.0,
             "param_dtype": "float32"}
    ref = mod.Reference(cfg, facts, jax.devices()[:1])
    ref.init(5)
    loss, grads = ref._grads(rows[0]["inputs"], rows[0]["labels"])
    (obj, exp), want = jax.value_and_grad(
        lambda p: mod.batch_loss(p, rows[0]["inputs"], rows[0]["labels"], ref.m),
        has_aux=True)(jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), ref.p))
    assert abs(loss - float(exp)) < 2e-6 * float(exp)
    for k, g in grads.items():
        scale = float(jnp.max(jnp.abs(want[k])))
        assert scale > 0, k
        assert float(jnp.max(jnp.abs(g - want[k]))) <= 2e-5 * scale, k


def test_lower_precision_reads_higher():
    """fp8 operands read a wider gap than bfloat16, which reads wider than
    float32's nought: the control can tell the precision below the
    configuration's."""
    import jax

    man, cfg = toy()
    rows = rows_for(7, 2)
    _, config = program_steps(cfg, 17, rows[:1])
    Ref = man.reference(cfg["reference"]).Reference
    facts = tw.optimizer_facts(config)
    out = {k: tw.follow(Ref(cfg, facts, jax.devices()[:1], precision=k), 17, rows)
           for k in ("f32", "bf16", "fp8")}
    bf16 = tw.compare(out["bf16"], out["f32"])
    fp8 = tw.compare(out["fp8"], out["f32"])
    assert fp8["loss1_gap"] > 3 * bf16["loss1_gap"] > 0
    assert fp8["grad_leaf_gap"] > 3 * bf16["grad_leaf_gap"] > 0


# ---- the configuration and the cell -------------------------------------------

def test_configuration_file_states_the_published_widths_and_the_cut():
    cfg = Manifest().config(CONFIG)
    published = {
        "hidden_size": 2048, "intermediate_size": 5632, "head_dim": 128,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "vocab_size": 49152, "total_ut_steps": 4, "early_exit_threshold": 1,
        "rms_norm_eps": 1e-6, "rope_theta": 1000000,
        "max_position_embeddings": 65536, "tie_word_embeddings": False,
    }
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["num_hidden_layers"] == 12
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    for point in ("final_norm_in_loop", "sandwich_norms", "exit_gate",
                  "exit_beta", "exit_gate_init", "head_dim", "weights",
                  "rope_layout"):
        assert point in cfg["assumed"], point
    assert "pipeline stages" in cfg["deployment"]
    # what the trainer is told beyond the Hugging Face keys says the same
    assert cfg["trainer_model"]["loop_steps"] == cfg["total_ut_steps"]


def test_trainer_model_is_the_published_shape():
    mc = tw.model_config(Manifest().config(CONFIG))
    assert (mc.dim, mc.n_layers, mc.n_heads, mc.n_kv_heads, mc.head_dim) == (
        2048, 12, 16, 16, 128)
    assert mc.ffn_hidden_dim == 5632 and mc.vocab_size == 49152
    assert (mc.loop_steps, mc.post_norms, mc.exit_gate, mc.exit_beta) == (
        4, True, True, 0.1)
    assert mc.layer_passes == 48 and mc.norm_eps == 1e-6


def test_the_cell_is_the_issues():
    man = Manifest()
    cell = man.cell(CELL)
    assert (cell["chips"], cell["runner"], cell["rate_metric"]) == (
        1, "train_window", "train_tok_s_per_chip")
    assert cell["sequence_length"] * cell["batch_size"] == 16384
    t = cell["trainer"]
    assert (t["model_dtype"], t["param_dtype"], t["use_flash_attention"],
            t["remat"], t["loss_chunk_size"], t["learning_rate"],
            t["lr_warmup_steps"], t["checkpoint_frequency"]) == (
        "bf16", "bf16", True, True, 512, 3e-4, 1, -1)
    assert t["logging_frequency"] == 3 and cell["mesh"] == {}
    assert cell["window"] == {"open_event": "train_sync", "open_step": 3,
                              "close_event": "train_sync", "trace_steps": 3}
    assert cell["check"]["reference_steps"] in (2, 3)
    entry = man.cells[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "steady", 1)


def test_entries_stand_in_the_manifest_and_nothing_else_moved():
    man = Manifest()
    assert man.problems() == []
    assert sum(w["chips"] == 4 for w in man.doc["workloads"]) == 0
    due = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert {"looped_step_mfu_pct", "loop_layer_pass_ms",
            "loop_exit_head_share_pct", "flash_roofline", "data_wait_ms",
            "host_dispatch_ms", "step_device_ms", "device_idle_pct.train",
            "hbm_peak_gib.train"} <= due
    assert "step_mfu_pct" not in due  # it counts the stack once
    assert [m["name"] for m in man.metrics_of(CELL, "end_to_end")] == [
        "train_tok_s_per_chip", "setup_s"]
    for name in ("looped_step_mfu_pct", "loop_layer_pass_ms",
                 "loop_exit_head_share_pct"):
        m = man.per_layer[name]
        assert m["moves"] == "train_tok_s_per_chip"
        assert m["workloads"] == [CELL]
    # the accepted cells report what they reported
    assert {m["name"] for m in man.metrics_of("mistral-7b.steady", "per_layer")} \
        == {"data_wait_ms", "host_dispatch_ms", "step_mfu_pct", "step_device_ms",
            "flash_roofline", "device_idle_pct.train", "hbm_peak_gib.train"}


# ---- the looped count, by hand --------------------------------------------------

def test_looped_count_by_hand_at_the_cells_shapes():
    cfg = Manifest().config(CONFIG)
    attention = 4 * 2048 * 2048                 # q, k, v, o at 16 heads of 128
    ffn = 3 * 2048 * 5632
    assert counts_looped.layer_params(cfg) == attention + ffn + 4 * 2048
    assert attention + ffn == 51_380_224
    total = 12 * (attention + ffn + 4 * 2048) + 2 * 49152 * 2048 + 2048 + 2049
    assert counts_looped.total_params(cfg) == total
    assert round(total / 1e6, 1) == 818.0
    assert counts_looped.layer_passes(cfg) == 48
    pairs_per_token = 4097 / 2                  # causal half square at 4096
    attn = 48 * 16 * 2 * 2 * 128 * pairs_per_token
    products = 48 * (attention + ffn) + 4 * 2048 * 49152
    want = 3 * (2 * products + attn)
    assert counts_looped.train_flops_per_token(cfg, 4096) == pytest.approx(want)
    assert round(want / 1e9, 1) == 19.6
    assert counts_looped.head_share(cfg, 4096) == pytest.approx(0.123, abs=5e-4)
    # the accepted count reads the stack once: about a quarter
    from benchmark.lib import counts
    assert counts.train_flops_per_token(cfg, 4096) / want == pytest.approx(
        0.25, abs=0.01)


# ---- the readers ---------------------------------------------------------------

class Sink:
    def __init__(self, records):
        self.records = [(float(i), r) for i, r in enumerate(records)]


class Run:
    """What the new readers ask of a run view."""

    def __init__(self, cfg, *, rate=5000.0, busy_s=9.6, steps=3, events=(),
                 records=(), peaks=True, trace=True):
        self.cfg = cfg
        self.cell = {"sequence_length": 4096, "batch_size": 4, "chips": 1}
        self.rate = rate
        self.peaks = {"bf16_flops_per_s": 197e12} if peaks else None
        self.trace = {"busy_s": busy_s, "window_s": busy_s * 1.002,
                      "events": {0: list(events)}} if trace else None
        self.res = {"sink": Sink(records)}
        self._steps = steps

    def traced_steps(self):
        return self._steps if self.trace else 0


def test_looped_mfu_reader():
    man = Manifest()
    read = man.reader("looped_step_mfu_pct")
    cfg = man.config(CONFIG)
    got = read(Run(cfg, rate=5000.0))
    assert got == pytest.approx(100 * 19.63e9 * 5000 / 197e12, rel=1e-3)
    assert 0 < got < 100
    # at the chip's peak it reads 100 and never more from a true rate
    peak_rate = 197e12 / counts_looped.train_flops_per_token(cfg, 4096)
    assert read(Run(cfg, rate=peak_rate)) == pytest.approx(100.0)
    # a configuration that is not looped, or a rehearsal without peaks: nothing
    assert read(Run(man.config("mistral-7b"))) is None
    assert read(Run(cfg, peaks=False)) is None


def test_layer_pass_reader():
    man = Manifest()
    read = man.reader("loop_layer_pass_ms")
    cfg = man.config(CONFIG)
    start = {"event": "run_start", "loop_steps": 4, "layer_passes": 48}
    assert read(Run(cfg, busy_s=9.6, steps=3, records=[start])) == \
        pytest.approx(1e3 * 9.6 / 3 / 48)
    # the parent reports no such counter; an untraced run has no busy time
    assert read(Run(cfg, records=[{"event": "run_start"}])) is None
    assert read(Run(cfg, records=[])) is None
    assert read(Run(cfg, records=[start], trace=False)) is None


def test_exit_head_share_reader():
    man = Manifest()
    read = man.reader("loop_exit_head_share_pct")
    cfg = man.config(CONFIG)
    ms = 1e6  # ns
    logits = "f32[4,512,49152]{2,1,0:T(8,128)}"
    events = [  # (HLO line, start ns, duration ns), sorted by start
        # the head's loop holds its operations: counted through them, not twice
        (f"%while.9 = (s32[], {logits}, bf16[32,4,512,2048]) while(%tuple.1)",
         0.0, 40 * ms),
        (f"%fusion.1 = (f32[4,512], {logits}) fusion(bf16[2048,49152] %w, "
         "bf16[4,512,2048] %h)", 0.0, 10 * ms),
        (f"%fusion.2 = bf16[2048,49152] fusion(bf16[4,512,2048] %h, {logits} "
         "%dlogits)", 10 * ms, 12 * ms),
        (f"%fusion.3 = bf16[4,512,2048] fusion({logits} %dlogits, "
         "bf16[2048,49152] %w)", 22 * ms, 8 * ms),
        ("%copy.4 = bf16[32,4,512,2048] copy(bf16[32,4,512,2048] %x)",
         30 * ms, 10 * ms),
        # a layer's product and the optimizer's pass over the head's weight
        ("%fusion.5 = bf16[4,4096,5632] fusion(bf16[4,4096,2048] %x)",
         40 * ms, 50 * ms),
        ("%fusion.6 = bf16[2048,49152] fusion(bf16[2048,49152] %w, "
         "bf16[2048,49152] %mu)", 90 * ms, 10 * ms),
    ]
    cell = man.cell(CELL)
    run = Run(cfg, busy_s=0.1, events=events)
    run.cell = cell
    assert read(run) == pytest.approx(100 * 30 / 100)
    # no operation of the logits' shape, no trace, or another model: nothing
    run = Run(cfg, busy_s=0.1, events=events[4:])
    run.cell = cell
    assert read(run) is None
    none = Run(cfg, trace=False)
    none.cell = cell
    assert read(none) is None
    other = Run(man.config("mistral-7b"), events=events)
    other.cell = cell
    assert read(other) is None


def test_dumped_numbers_are_json(tmp_path):
    """The configuration's nested groups survive the harness's own loader."""
    cfg = Manifest().config(CONFIG)
    assert json.loads(json.dumps(cfg)) == cfg
    assert np.all(np.array(cfg["layer_types"]) == "full_attention")
