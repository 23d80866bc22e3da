"""The hybrid configuration's own files: its plain reference against finite
differences and against its own straight-line loss, the lower-precision
controls, the hybrid count by hand at the cell's shapes, the three new
per-layer readers on hand-made run views, and the entries in BENCHMARK.json.
(The reference against the PROGRAM, over two optimizer steps, is in
``tests/test_hybrid.py``.)"""

import json

import numpy as np
import pytest

from benchmark.lib import counts, counts_hybrid
from benchmark.lib.manifest import Manifest
from benchmark.run import REHEARSAL
from benchmark.runners import train_window as tw
from benchmark.tests.test_reference import program_steps, rows_for

CELL, CONFIG = "jamba2-3b.steady", "jamba2-3b"
FACTS = {"learning_rate": 3e-4, "lr_warmup_steps": 1, "adam_b1": 0.9,
         "adam_b2": 0.95, "adam_eps": 1e-8, "weight_decay": 0.1,
         "grad_clipping": False, "grad_max_norm": 1.0,
         "param_dtype": "float32"}


def toy(layers=8, period=4, offset=2):
    """The harness's toy width with whole periods of a small pattern."""
    man = Manifest()
    cfg = man.config(CONFIG)
    small = {"num_hidden_layers": layers, "attn_layer_period": period,
             "attn_layer_offset": offset, "mamba_d_state": 4,
             "mamba_dt_rank": 4, "num_key_value_heads": 1}
    tm = {**cfg["trainer_model"], **REHEARSAL["cfg"]["trainer_model"],
          "attn_layer_period": period, "attn_layer_offset": offset,
          "mamba_d_state": 4, "mamba_dt_rank": 4}
    return man, {**cfg, **REHEARSAL["cfg"], **small, "trainer_model": tm}


# ---- the reference ------------------------------------------------------------

def test_reference_imports_nothing_of_the_program():
    text = Manifest().bench.joinpath("references", "jamba.py").read_text()
    assert "import pyrecover_tpu" not in text
    assert "from pyrecover_tpu" not in text
    assert "from benchmark" not in text and "import benchmark" not in text


def test_layer_order_follows_period_and_offset():
    man, cfg = toy()
    mod = man.reference(cfg["reference"])
    m = mod.model_dims(cfg)
    kinds = [kind for _, kind, _ in mod.layer_order(m)]
    assert kinds == ["mamba", "mamba", "attn", "mamba"] * 2
    assert [(g, i) for g, _, i in mod.layer_order(m)] == [
        ("mamba_pre", 0), ("mamba_pre", 1), ("attn", 0), ("mamba_post", 0),
        ("mamba_pre", 2), ("mamba_pre", 3), ("attn", 1), ("mamba_post", 1)]
    # the published pattern: offset 7 of 14
    m = mod.model_dims(man.config(CONFIG))
    kinds = [kind for _, kind, _ in mod.layer_order(m)]
    assert kinds == ["mamba"] * 7 + ["attn"] + ["mamba"] * 6
    with pytest.raises(ValueError, match="whole periods"):
        mod.model_dims({**man.config(CONFIG), "num_hidden_layers": 20})


def test_recurrence_is_the_equations_token_by_token():
    """The reference's scan against the equations written out in numpy."""
    import jax

    mod = Manifest().reference("jamba")
    rng = np.random.default_rng(0)
    s, di, n = 300, 6, 3  # more than one checkpointed block of 256
    u, b, c = (rng.normal(size=sh).astype(np.float32)
               for sh in ((s, di), (s, n), (s, n)))
    dt = np.log1p(np.exp(rng.normal(size=(s, di)))).astype(np.float32)
    a = -np.exp(rng.normal(size=(di, n))).astype(np.float32)
    got = np.asarray(jax.jit(mod.recurrence)(u, dt, a, b, c))
    state = np.zeros((di, n))
    for t in range(s):
        state = (np.exp(dt[t][:, None] * a) * state
                 + (dt[t] * u[t])[:, None] * b[t][None, :])
        np.testing.assert_allclose(got[t], state @ c[t], rtol=2e-5, atol=2e-6)


def test_hand_rolled_sweep_is_the_gradient_of_the_plain_loss():
    """The reference's row-by-row, layer-by-layer backward sweep against
    ``jax.grad`` of its own straight-line loss: every leaf of both kinds of
    layer, the tied embedding fed from both ends."""
    import jax
    import jax.numpy as jnp

    man, cfg = toy()
    mod = man.reference(cfg["reference"])
    rows = rows_for(9, 1, batch=2, seq=32)
    ref = mod.Reference(cfg, FACTS, jax.devices()[:1])
    ref.init(5)
    loss, grads = ref._grads(rows[0]["inputs"], rows[0]["labels"])
    wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), ref.p)
    want_loss, want = jax.value_and_grad(lambda p: mod.batch_loss(
        p, rows[0]["inputs"], rows[0]["labels"], ref.m))(wide)
    assert abs(loss - float(want_loss)) < 2e-6 * float(want_loss)
    assert set(grads) == set(want)
    for k, g in grads.items():
        scale = float(jnp.max(jnp.abs(want[k])))
        assert scale > 0, k
        assert float(jnp.max(jnp.abs(g - want[k]))) <= 3e-5 * scale, k


def test_reference_gradient_against_finite_differences():
    """Central differences of the reference's own loss along random
    directions in a leaf of each kind: the recurrence's A_log and step bias,
    the convolution, a mixer matrix, an attention matrix, the tied table."""
    import jax
    import jax.numpy as jnp

    man, cfg = toy(layers=4)
    mod = man.reference(cfg["reference"])
    m = mod.model_dims(cfg)
    rows = rows_for(4, 1, batch=1, seq=24)
    inputs, labels = rows[0]["inputs"], rows[0]["labels"]
    with jax.enable_x64():
        p = {k: jnp.asarray(np.asarray(v), jnp.float64)
             for k, v in mod.draw_weights(3, m, jnp.float32).items()}

        def loss(p):
            total = 0.0
            for b in range(inputs.shape[0]):
                lab = jnp.asarray(labels[b], jnp.int32)
                x = p["tok_embed"][jnp.asarray(inputs[b], jnp.int32)]
                for group, kind, i in mod.layer_order(m):
                    lp = {k.split("/")[-1]: a[i] for k, a in p.items()
                          if k.startswith(f"layers/{group}/")}
                    x = mod.LAYER_FN[kind](x, lp, m, "f32")
                total = total + mod._close(
                    x, p["final_norm"], p["tok_embed"], lab, m, "f32")
            return total / max(int(np.sum(labels != -100)), 1)

        grads = jax.grad(loss)(p)
        rng = np.random.default_rng(1)
        for leaf in ("layers/mamba_pre/a_log", "layers/mamba_pre/dt_bias",
                     "layers/mamba_post/conv_w", "layers/mamba_pre/x_proj",
                     "layers/attn/wk", "tok_embed"):
            direction = jnp.asarray(rng.normal(size=p[leaf].shape))
            eps = 1e-5
            up = loss({**p, leaf: p[leaf] + eps * direction})
            down = loss({**p, leaf: p[leaf] - eps * direction})
            numeric = float((up - down) / (2 * eps))
            analytic = float(jnp.sum(grads[leaf] * direction))
            # (float64: what is left is the differences' own truncation)
            assert abs(numeric - analytic) <= 1e-5 * max(
                abs(analytic), 1e-3), (leaf, numeric, analytic)


def test_lower_precision_reads_higher():
    """fp8 operands read a wider gap than bfloat16, which reads wider than
    float32's nought: the control can tell the precision below the
    configuration's."""
    import jax

    man, cfg = toy()
    rows = rows_for(7, 2, batch=2, seq=48)
    _, config = program_steps(cfg, 17, rows[:1])
    Ref = man.reference(cfg["reference"]).Reference
    facts = tw.optimizer_facts(config)
    out = {k: tw.follow(Ref(cfg, facts, jax.devices()[:1], precision=k), 17, rows)
           for k in ("f32", "bf16", "fp8")}
    bf16 = tw.compare(out["bf16"], out["f32"])
    fp8 = tw.compare(out["fp8"], out["f32"])
    assert fp8["loss1_gap"] > 3 * bf16["loss1_gap"] > 0
    assert fp8["grad_leaf_gap"] > 3 * bf16["grad_leaf_gap"] > 0


def test_moments_wait_on_the_host():
    """Between updates the Adam moments are host arrays in the storage type
    (bfloat16, float32 for A_log, D and the step bias), the parameters stay
    on the device."""
    import jax
    import jax.numpy as jnp

    man, cfg = toy(layers=4)
    ref = man.reference(cfg["reference"]).Reference(
        cfg, {**FACTS, "param_dtype": "bfloat16"}, jax.devices()[:1])
    ref.init(2)
    rows = rows_for(2, 1, batch=1, seq=16)
    ref.step(rows[0]["inputs"], rows[0]["labels"])
    assert all(isinstance(v, np.ndarray) for v in ref.mu.values())
    assert all(isinstance(v, np.ndarray) for v in ref.nu.values())
    assert ref.mu["layers/mamba_pre/in_proj"].dtype == jnp.bfloat16
    assert ref.mu["layers/mamba_pre/a_log"].dtype == np.float32
    assert ref.p["layers/mamba_pre/a_log"].dtype == jnp.float32
    assert ref.p["tok_embed"].dtype == jnp.bfloat16
    assert float(np.abs(ref.mu["tok_embed"].astype(np.float32)).max()) > 0
    with pytest.raises(ValueError, match="one device"):
        man.reference(cfg["reference"]).Reference(
            cfg, FACTS, jax.devices()[:2])


# ---- the configuration and the cell -------------------------------------------

CATALOG_ROW = {  # the catalog's `config` of AI21-Jamba2-3B, key for key
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": 65536,
}


def test_configuration_file_states_the_published_widths_and_the_cut():
    cfg = Manifest().config(CONFIG)
    differs = {k for k, v in CATALOG_ROW.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 14 == cfg["attn_layer_period"]
    assert cfg["published"] == {"num_hidden_layers": 28}
    assert set(cfg["reduced"]) == {"num_hidden_layers"}
    assert "1,598,556,096" in cfg["reduced"]["num_hidden_layers"]
    for key in ("layer_order", "head_dim", "rope_theta", "inner_norms",
                "dt_proj_bias", "initial_values", "weight_decay", "storage"):
        assert key in cfg["assumed"], key
    assert "unread" in cfg["assumed"]["rope_theta"]
    assert "pipeline stage" in cfg["deployment"]


def test_trainer_model_is_the_published_shape():
    cfg = Manifest().config(CONFIG)
    mc = tw.model_config(cfg)
    assert (mc.dim, mc.n_layers, mc.n_heads, mc.n_kv_heads) == (2560, 14, 20, 1)
    assert mc.head_dim == 128 and mc.ffn_hidden_dim == 8192
    assert (mc.attn_layer_period, mc.attn_layer_offset) == (14, 7)
    assert (mc.n_mamba_layers, mc.n_attn_layers) == (13, 1)
    assert (mc.d_inner, mc.mamba_d_state, mc.dt_rank, mc.mamba_d_conv) == (
        5120, 16, 160, 4)
    assert mc.tie_embeddings and not mc.rope and mc.n_experts == 0
    assert mc.vocab_size == 65536 and mc.norm_eps == 1e-6
    assert [g[0] for g in mc.layer_groups()] == [
        "mamba_pre", "attn", "mamba_post"]
    assert [g[2] for g in mc.layer_groups()] == [7, 1, 6]


def test_the_cell_is_the_issues():
    man = Manifest()
    cell = man.cell(CELL)
    assert (cell["config"], cell["chips"], cell["runner"]) == (
        CONFIG, 1, "train_window")
    assert cell["rate_metric"] == "train_tok_s_per_chip"
    assert cell["sequence_length"] * cell["batch_size"] == 8192
    t = cell["trainer"]
    assert (t["model_dtype"], t["param_dtype"]) == ("bf16", "bf16")
    assert t["use_flash_attention"] and t["remat"]
    assert t["loss_chunk_size"] == 512 and t["learning_rate"] == 3e-4
    assert t["lr_warmup_steps"] == 1 and t["logging_frequency"] == 5
    assert t["checkpoint_frequency"] == -1
    assert cell["window"] == {"open_event": "train_sync", "open_step": 5,
                              "close_event": "train_sync", "trace_steps": 5}
    assert cell["check"]["reference_steps"] == 2
    assert not cell["check"]["readback"]
    limits = cell["check"]["limits"]
    assert limits["rows_repeated"] == 0 and limits["recompiles_in_window"] == 0
    # every number the other steady cells are held to, the second loss too
    assert set(limits) == set(
        man.cell("ouro-2.6b.steady")["check"]["limits"])


def test_entries_stand_in_the_manifest_and_nothing_else_moved():
    man = Manifest()
    assert man.problems() == []
    assert [c["name"] for c in man.doc["configs"]][-1] == CONFIG
    assert [w["name"] for w in man.doc["workloads"]][-1] == CELL
    entry = man.configs[CONFIG]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == man.config(CONFIG)["source"]
    new = ["hybrid_step_mfu_pct", "ssm_scan_share_pct", "ssm_scan_roofline"]
    assert [m["name"] for m in man.doc["per_layer"]][-3:] == new
    for name in new:
        m = man.per_layer[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tok_s_per_chip"
    due = {m["name"] for m in man.metrics_of(CELL, "per_layer")}
    assert due == set(new) | {
        "data_wait_ms", "host_dispatch_ms", "step_device_ms",
        "flash_roofline", "device_idle_pct.train", "hbm_peak_gib.train"}
    assert {m["name"] for m in man.metrics_of(CELL, "end_to_end")} == {
        "train_tok_s_per_chip", "setup_s"}
    # the cells that were there report what they reported
    for cell in ("mistral-7b.steady", "ouro-2.6b.steady",
                 "mistral-7b.save-every-8"):
        assert not {m["name"] for m in man.metrics_of(cell, "per_layer")} & set(new)
    assert man.doc["run_seconds"] == 40
    assert man.end_to_end["train_tok_s_per_chip"]["bound"] == 0.01


# ---- the count -----------------------------------------------------------------

def test_hybrid_count_by_hand_at_the_cells_shapes():
    cfg = Manifest().config(CONFIG)
    mixer_matrices = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert counts_hybrid.mixer_matmul_params(cfg) == mixer_matrices
    mixer = mixer_matrices + 5120 * 4 + 5120 + 5120 + 5120 * 16 + 5120 + 160 + 32
    assert mixer == 41_241_792 == counts_hybrid.mixer_params(cfg)
    ffn = 3 * 2560 * 8192
    assert counts_hybrid.mamba_layer_params(cfg) == mixer + ffn + 5120 == 104_161_472
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert counts_hybrid.attn_layer_params(cfg) == attention + ffn + 5120 == 76_682_240
    period = 13 * 104_161_472 + 76_682_240
    assert period == 1_430_781_376
    assert counts_hybrid.total_params(cfg) == period + 65536 * 2560 + 2560
    assert counts_hybrid.total_params(cfg) == 1_598_556_096
    assert (counts_hybrid.mamba_layers(cfg), counts_hybrid.attn_layers(cfg)) == (13, 1)

    products = 13 * (mixer_matrices + ffn) + (attention + ffn) + 2560 * 65536
    attn = 20 * 2 * 2 * 128 * 4097 / 2          # one layer, the half square
    scan = 13 * (6 * 5120 * 16 + 2 * 4 * 5120)
    want = 3 * (2 * products + attn + scan)
    assert counts_hybrid.train_flops_per_token(cfg, 4096) == pytest.approx(want)
    assert round(want / 1e9, 2) == 9.67
    assert round(want * 8192 / 1e12, 1) == 79.2  # a step
    assert counts_hybrid.scan_share(cfg, 4096) == pytest.approx(0.334, abs=1e-3)
    # the accepted count would read fourteen attention layers and no mixer
    assert counts.train_flops_per_token(cfg, 4096) < 0.9 * want

    # the scan's least bytes and operations, one layer at 8,192 tokens: what
    # the timed operations move (u, Dt, B, C in, y out), not the gate's z
    tokens = 8192
    fwd = tokens * (5120 * (2 + 4) + 2 * 16 * 2 + 5120 * 2)
    assert fwd == tokens * 41_024
    assert counts_hybrid.scan_bytes("fwd", tokens, cfg) == fwd
    bwd = tokens * (2 * (5120 * 6 + 64) + 5120 * 2)
    assert counts_hybrid.scan_bytes("bwd", tokens, cfg) == bwd
    assert counts_hybrid.scan_flops("fwd", tokens, cfg) == tokens * 6 * 81920
    assert counts_hybrid.scan_flops("bwd", tokens, cfg) == 2 * tokens * 6 * 81920
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = counts.roofline_seconds(
        counts_hybrid.scan_flops("fwd", tokens, cfg), fwd, peaks)
    assert bound == "memory" and t == pytest.approx(0.4103e-3, rel=1e-3)
    t, bound = counts.roofline_seconds(
        counts_hybrid.scan_flops("bwd", tokens, cfg), bwd, peaks)
    assert bound == "memory" and t == pytest.approx(0.7183e-3, rel=1e-3)


# ---- the readers ---------------------------------------------------------------

class Sink:
    def __init__(self, records):
        self.records = [(float(i), r) for i, r in enumerate(records)]


class Run:
    """What the new readers ask of a run view."""

    def __init__(self, cfg, cell, *, rate=8000.0, busy_s=5.0, steps=5,
                 events=(), peaks=True, trace=True):
        self.cfg, self.cell, self.rate = cfg, cell, rate
        self.peaks = {"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9} if peaks else None
        self.trace = {"busy_s": busy_s, "window_s": busy_s * 1.002,
                      "events": {0: list(events)}} if trace else None
        self.res = {"sink": Sink([])}
        self.counts = counts
        self._steps = steps

    def traced_steps(self):
        return self._steps if self.trace else 0


MS = 1e6  # ns


def kernel_events(fwd_ms=4.0, bwd_ms=12.0, layers=13, steps=5):
    """A traced window's scan calls as Mosaic custom calls (forward twice a
    layer and step under remat) beside a product and a loop that holds them."""
    out, t = [], 0.0
    span = steps * layers * (2 * fwd_ms + bwd_ms + 10.0) * MS
    out.append(("%while.7 = (s32[], bf16[2,4096,2560]) while(%tuple.3)", 0.0, span))
    for _ in range(steps * layers):
        for name, ms in (
            ('%ssm_scan_fwd.1 = (f32[2,4096,5120], f32[2,16,16,5120]) '
             'custom-call(f32[2,4096,5120] %u), custom_call_target='
             '"tpu_custom_call", metadata={op_name="jit(step)/ssm_scan_fwd"}',
             fwd_ms),
            ('%checkpoint.2 = (f32[2,4096,5120], f32[2,16,16,5120]) '
             'custom-call(f32[2,4096,5120] %u), custom_call_target='
             '"tpu_custom_call", metadata={op_name="jit(step)/remat/'
             'ssm_scan_fwd"}', fwd_ms),
            ('%ssm_scan_bwd.3 = (f32[2,4096,5120], f32[2,4096,5120]) '
             'custom-call(f32[2,4096,5120] %dy), custom_call_target='
             '"tpu_custom_call", metadata={op_name="jit(step)/ssm_scan_bwd"}',
             bwd_ms),
            ("%fusion.9 = bf16[2,4096,10240] fusion(bf16[2,4096,2560] %h)",
             10.0),
        ):
            out.append((name, t, ms * MS))
            t += ms * MS
    return out, span / 1e9


def test_hybrid_mfu_reader():
    man = Manifest()
    read = man.reader("hybrid_step_mfu_pct")
    cfg, cell = man.config(CONFIG), man.cell(CELL)
    per_token = counts_hybrid.train_flops_per_token(cfg, 4096)
    got = read(Run(cfg, cell, rate=8000.0))
    assert got == pytest.approx(100 * per_token * 8000 / 197e12)
    assert 0 < got < 100
    assert read(Run(cfg, cell, rate=197e12 / per_token)) == pytest.approx(100.0)
    # a configuration without the hybrid keys, or a rehearsal without peaks
    assert read(Run(man.config("mistral-7b"), cell)) is None
    assert read(Run(cfg, cell, peaks=False)) is None


def test_scan_share_reader_tells_the_kernels_by_name():
    man = Manifest()
    read = man.reader("ssm_scan_share_pct")
    cfg, cell = man.config(CONFIG), man.cell(CELL)
    events, busy = kernel_events()
    # (4 + 4 + 12) of every 30 ms; the loop that holds them is not counted
    assert read(Run(cfg, cell, busy_s=busy, events=events)) == pytest.approx(
        100 * 20 / 30)
    # nothing of the scan in the trace (the parent), no trace, another model
    rest = [e for e in events if "ssm_scan" not in e[0]]
    assert read(Run(cfg, cell, busy_s=busy, events=rest)) is None
    assert read(Run(cfg, cell, trace=False)) is None
    assert read(Run(man.config("mistral-7b"), cell, events=events)) is None


def test_scan_share_reader_tells_an_xla_formulation_by_the_states_shape():
    man = Manifest()
    read = man.reader("ssm_scan_share_pct")
    cfg, cell = man.config(CONFIG), man.cell(CELL)
    state = "f32[2,16,5120]{2,1,0:T(8,128)}"
    events = [
        (f"%while.4 = (s32[], {state}, f32[256,2,5120]) while(%tuple.9)",
         0.0, 60 * MS),
        (f"%fusion.11 = ({state}, f32[2,5120]) fusion({state} %h, "
         "f32[2,5120] %dt)", 0.0, 25 * MS),
        (f"%fusion.12 = {state} fusion({state} %dh, {state} %h)",
         25 * MS, 35 * MS),
        ("%fusion.13 = bf16[2,4096,8192] fusion(bf16[2,4096,2560] %x)",
         60 * MS, 40 * MS),
    ]
    assert read(Run(cfg, cell, busy_s=0.1, events=events)) == pytest.approx(60.0)


def test_scan_roofline_reader_counts_the_calls_in_the_trace():
    man = Manifest()
    read = man.reader("ssm_scan_roofline")
    cfg, cell = man.config(CONFIG), man.cell(CELL)
    events, busy = kernel_events(fwd_ms=4.0, bwd_ms=12.0)
    peak = 819e9
    fwd_s = counts_hybrid.scan_bytes("fwd", 8192, cfg) / peak
    bwd_s = counts_hybrid.scan_bytes("bwd", 8192, cfg) / peak
    got = read(Run(cfg, cell, busy_s=busy, events=events))
    assert got == pytest.approx(
        100 * 13 * 5 * (2 * fwd_s + bwd_s) / (13 * 5 * 20e-3))
    assert 5 < got < 10  # 0.41 + 0.41 + 0.72 ms of every 20
    # kernels at their bytes bound read 100, and never more from true times
    fast, busy = kernel_events(fwd_ms=fwd_s * 1e3, bwd_ms=bwd_s * 1e3)
    assert read(Run(cfg, cell, busy_s=busy, events=fast)) == pytest.approx(
        100.0, rel=1e-6)
    # a step that keeps the scan's output runs the forward once and is
    # credited with one, whatever the cell's remat flag says
    once = [e for e in events if "remat" not in e[0]]
    assert read(Run(cfg, cell, busy_s=busy, events=once)) == pytest.approx(
        100 * (fwd_s + bwd_s) / 16e-3)
    # a formulation without named kernels: one forward and one backward a
    # layer and traced step are what the step requires
    state = "f32[2,16,5120]{2,1,0:T(8,128)}"
    xla = [(f"%fusion.11 = ({state}, f32[2,5120]) fusion({state} %h)",
            0.0, 500 * MS)]
    assert read(Run(cfg, cell, busy_s=1.0, events=xla)) == pytest.approx(
        100 * 13 * 5 * (fwd_s + bwd_s) / 0.5)
    # nothing to read: the parent's trace, no trace, no traced step
    rest = [e for e in events if "ssm_scan" not in e[0]]
    assert read(Run(cfg, cell, busy_s=busy, events=rest)) is None
    assert read(Run(cfg, cell, trace=False)) is None
    assert read(Run(cfg, cell, steps=0, events=events)) is None


def test_dumped_numbers_are_json():
    cfg = Manifest().config(CONFIG)
    assert json.loads(json.dumps(cfg)) == cfg
