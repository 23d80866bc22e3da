"""BENCHMARK.json and the files it names: the contract's rules as far as files
can show them, and that a configuration, a cell and a per-layer metric can each
be added as new files plus entries, with no edit to what is there."""

import json
import shutil

from benchmark.lib.manifest import NAME, UNIT, Manifest


def test_manifest_has_no_problems():
    assert Manifest().problems() == []


def test_names_units_and_files():
    man = Manifest()
    doc = man.doc
    for table in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in doc[table]:
            assert NAME.match(entry["name"]), entry["name"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]) and len(m["unit"]) <= 16, m
    for cell in man.cells:
        spec = man.cell(cell)
        cfg = man.config(spec["config"])
        assert man.cell_file(cell).is_file()
        assert (man.bench / "references" / f"{cfg['reference']}.py").is_file()
        assert (man.bench / "runners" / f"{spec['runner']}.py").is_file()
        assert set(spec["check"]["limits"]), cell
    for m in doc["per_layer"]:
        assert m["workloads"], m["name"]
        moved = man.end_to_end[m["moves"]]
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", list(man.cells))
        assert callable(man.reader(m["name"]))
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= 1
    assert len(json.dumps(doc)) < 64 * 1024


def test_a_dummy_config_cell_and_metric_are_found_without_edits(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(Manifest().bench, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = dict(Manifest().doc)
    bench = root / "benchmark"
    # a configuration: one new file
    cfg = json.loads((bench / "configs" / "mistral-7b.json").read_text())
    cfg["num_hidden_layers"] = 2
    (bench / "configs" / "dummy-2l.json").write_text(json.dumps(cfg))
    doc["configs"] = doc["configs"] + [{
        "name": "dummy-2l", "source": cfg["source"],
        "file": "benchmark/configs/dummy-2l.json",
        "reduced": ["num_hidden_layers"], "why": "a dummy"}]
    # a cell: one new file
    cell = json.loads(
        (bench / "workloads" / "mistral-7b.steady.json").read_text())
    cell["config"] = "dummy-2l"
    (bench / "workloads" / "dummy-2l.steady.json").write_text(json.dumps(cell))
    doc["workloads"] = doc["workloads"] + [{
        "name": "dummy-2l.steady", "config": "dummy-2l", "traffic": "steady",
        "chips": 1, "why": "a dummy"}]
    doc["end_to_end"] = [
        dict(m, workloads=m["workloads"] + ["dummy-2l.steady"])
        if m["name"] == "train_tok_s_per_chip" else m
        for m in doc["end_to_end"]]
    # a per-layer metric: a reader of its own
    (bench / "metrics" / "moe_gemm_share_pct.py").write_text(
        "def read(run):\n    return None\n")
    doc["per_layer"] = doc["per_layer"] + [{
        "name": "moe_gemm_share_pct", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "MoE dispatch",
        "moves": "train_tok_s_per_chip", "workloads": ["dummy-2l.steady"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    man = Manifest(root)
    assert man.problems() == []
    assert man.cell("dummy-2l.steady")["config"] == "dummy-2l"
    assert man.config("dummy-2l")["num_hidden_layers"] == 2
    due = [m["name"] for m in man.metrics_of("dummy-2l.steady", "per_layer")]
    assert due == ["moe_gemm_share_pct"]
    assert man.reader("moe_gemm_share_pct")(None) is None
