"""The split of a traced window by the program's own scopes
(benchmark/lib/scope_trace.py), on events and a table made by hand."""

import json

import pytest

from benchmark.lib import scope_trace
from benchmark.lib.manifest import Manifest

STEADY = ["mistral-7b.steady", "ouro-2.6b.steady", "jamba2-3b.steady"]
METRICS = {
    "step_fwd_ms": STEADY, "step_remat_ms": STEADY, "step_bwd_ms": STEADY,
    "step_update_ms": STEADY, "step_attn_ms": STEADY, "step_ffn_ms": STEADY,
    "step_head_ms": STEADY, "step_mixer_ms": ["jamba2-3b.steady"],
    "step_layer_scan_ms": STEADY, "step_unscoped_pct": STEADY,
}

TABLE = {
    "module": "jit_train_step",
    "vocabulary": {
        "sublayers": ["embed", "attn", "ffn", "moe_ffn", "mamba_mixer",
                      "loss_head", "exit_head_loss", "optimizer"],
        "kernels": ["flash_attention", "ssm_scan"],
        "groups": ["layers", "loop_pass"]},
    "instructions": {
        "while.1": ["fwd", "layers", "while", ""],
        "fusion.1": ["fwd", "layers/ffn", "convolution", "convolution"],
        "flash_fwd.2": ["fwd", "layers/attn/flash_attention", "custom-call", ""],
        "while.2": ["bwd", "layers", "while", ""],
        "fusion.3": ["remat", "layers/ffn", "convolution", "convolution"],
        "bitcast_dynamic-update-slice_fusion.4": [
            "bwd", "layers/ffn", "dynamic-update-slice", "convolution"],
        "dynamic-update-slice_fusion.5": [
            "bwd", "layers", "dynamic-update-slice", ""],
        "ssm_scan_bwd.6": [
            "bwd", "layers/mamba_mixer/ssm_scan", "custom-call", ""],
        "fusion.7": ["bwd", "loss_head", "convolution", "convolution"],
        "fusion.8": ["fwd", "exit_head_loss", "reduce", ""],
        "fusion.9": ["update", "optimizer", "multiply", ""],
        "iota.10": ["", "attn", "iota", ""],
        "copy.11": ["", "", "copy", ""],
    },
}


def line(name, start, dur):
    """An event as the profile names it: the whole HLO line."""
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop",
            float(start), float(dur))


# one step: a forward loop holding two operations, a backward loop holding
# five, then the head, the update, and three events outside every scope
EVENTS = [
    line("while.1", 0, 100), line("fusion.1", 10, 30),
    line("flash_fwd.2", 50, 40),
    line("while.2", 100, 300), line("fusion.3", 110, 50),
    line("bitcast_dynamic-update-slice_fusion.4", 170, 80),
    line("dynamic-update-slice_fusion.5", 260, 20),
    line("ssm_scan_bwd.6", 290, 60), line("fusion.1", 360, 30),
    line("fusion.7", 400, 40), line("fusion.8", 440, 20),
    line("fusion.9", 460, 25), line("iota.10", 485, 5),
    line("copy.11", 490, 6),  # an entry with neither phase nor scope
    line("fusion.413", 500, 14),  # a name the table lacks: another module's
]
NS = 1e-9


def test_split_by_phase_sublayer_and_kernel():
    got = scope_trace.split(EVENTS, TABLE)
    # the loops keep what nests in them does not take (self time)
    assert got["phase"] == pytest.approx({
        "fwd": (30 + 30 + 30 + 40 + 20) * NS,  # while.1's own 30, fusion.1 twice
        "remat": 50 * NS,
        "bwd": ((300 - 50 - 80 - 20 - 60 - 30) + 80 + 20 + 60 + 40) * NS,
        "update": 25 * NS, "none": 5 * NS})
    assert got["sublayer"] == pytest.approx({
        "layer_scan": (30 + 60 + 20) * NS, "ffn": (60 + 50 + 80) * NS,
        "attn": 45 * NS, "mamba_mixer": 60 * NS, "loss_head": 40 * NS,
        "exit_head_loss": 20 * NS, "optimizer": 25 * NS})
    assert got["kernel"] == pytest.approx({
        "flash_attention": 40 * NS, "ssm_scan": 60 * NS})
    assert got["cross"]["bwd", "ffn"] == pytest.approx(80 * NS)
    assert got["cross"]["remat", "ffn"] == pytest.approx(50 * NS)
    # an entry with neither phase nor scope and a name no entry holds
    assert got["unscoped_s"] == pytest.approx((6 + 14) * NS)
    # nothing is counted twice and nothing lost: the parts are the busy time
    assert got["total_s"] == pytest.approx(510 * NS)
    for kind in ("phase", "sublayer"):
        assert sum(got[kind].values()) + got["unscoped_s"] == pytest.approx(
            got["total_s"])
    name, secs, entry = got["ops"][0]
    assert (name, entry[3]) == (
        "bitcast_dynamic-update-slice_fusion.4", "convolution")
    assert [n for n, _, e in got["ops"] if e is None] == ["fusion.413"]


class Sink:
    def __init__(self, records, steps):
        self.records = [(float(i), r) for i, r in enumerate(records)]
        self.spec = {"trace_steps": steps}


class Run:
    """What a reader sees of a run (benchmark/lib/report.py RunView)."""

    def __init__(self, records, events=EVENTS, steps=2):
        self.res = {"sink": Sink(records, steps)}
        self.trace = {"events": {0: events}, "busy_s": 510 * NS} if (
            events is not None) else None

    def traced_steps(self):
        return self.res["sink"].spec["trace_steps"] if self.trace else 0


@pytest.fixture
def table_event(tmp_path):
    path = tmp_path / "step_scopes.json"
    path.write_text(json.dumps(TABLE))
    return {"event": "step_scopes", "path": str(path),
            "module": "jit_train_step", "instructions": 13, "unscoped": 1,
            "build_s": 0.05}


@pytest.mark.parametrize("metric,value", [
    ("step_fwd_ms", 150 * NS * 1e3 / 2),
    ("step_remat_ms", 50 * NS * 1e3 / 2),
    ("step_bwd_ms", 260 * NS * 1e3 / 2),
    ("step_update_ms", 25 * NS * 1e3 / 2),
    ("step_attn_ms", 45 * NS * 1e3 / 2),
    ("step_ffn_ms", 190 * NS * 1e3 / 2),
    ("step_head_ms", 60 * NS * 1e3 / 2),  # loss_head and exit_head_loss
    ("step_mixer_ms", 60 * NS * 1e3 / 2),
    ("step_layer_scan_ms", 110 * NS * 1e3 / 2),
    ("step_unscoped_pct", 100 * 20 / 510),
])
def test_readers_give_ms_a_traced_step(metric, value, table_event, capsys):
    run = Run([{"event": "run_start"}, table_event])
    assert Manifest().reader(metric)(run) == pytest.approx(value)
    assert "step_scopes: module jit_train_step" in capsys.readouterr().err
    # the split is made once a run, whichever reader asks first
    assert scope_trace.by(run) is run._scope_trace


@pytest.mark.parametrize("metric", list(METRICS))
def test_nothing_to_read_gives_none(metric, table_event, tmp_path):
    read = Manifest().reader(metric)
    # the parent commit: no event; an untraced run; a file that is gone
    assert read(Run([{"event": "run_start"}])) is None
    assert read(Run([table_event], events=None)) is None
    gone = dict(table_event, path=str(tmp_path / "nowhere.json"))
    assert read(Run([gone])) is None


def test_a_sublayer_the_stack_lacks_reports_nothing(table_event):
    events = [e for e in EVENTS if "ssm_scan" not in e[0]]
    run = Run([table_event], events=events)
    assert Manifest().reader("step_mixer_ms")(run) is None
    assert Manifest().reader("step_ffn_ms")(run) is not None
    assert scope_trace.ms_a_step(run, "kernel", "ssm_scan") is None
    assert scope_trace.ms_a_step(
        run, "kernel", "flash_attention") == pytest.approx(40 * NS * 1e3 / 2)


def test_the_entries_stand_in_the_manifest():
    man = Manifest()
    assert man.problems() == []
    by_name = {m["name"]: m for m in man.doc["per_layer"]}
    for metric, cells in METRICS.items():
        assert by_name[metric] == {
            "name": metric, "unit": "%" if metric.endswith("_pct") else "ms",
            "better": "lower", "source": "device_trace",
            "layer": "jitted step", "moves": "train_tok_s_per_chip",
            "workloads": cells}
    save = {m["name"] for m in
            man.metrics_of("mistral-7b.save-every-8", "per_layer")}
    assert not save & set(METRICS)
