"""Find everything by name from ``BENCHMARK.json``.

One file per configuration (``configs/<config>.json``), per cell
(``workloads/<cell>.json``), per per-layer metric (``metrics/<name>.py``, a
reader), per kind of run (``runners/<runner>.py``) and per model family's plain
reference (``references/<family>.py``). A later PR adds files and entries and
edits nothing that is here.
"""

import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """Import a file by path (metric names may hold dots)."""
    path = Path(path)
    name = "bench_" + re.sub(r"[^A-Za-z0-9_]", "_", "_".join(path.parts[-2:]))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    def __init__(self, root=ROOT):
        self.root = Path(root)
        self.bench = self.root / "benchmark"
        self.doc = load_json(self.root / "BENCHMARK.json")
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}

    # -- files by name ------------------------------------------------------
    def cell_file(self, cell):
        return self.bench / "workloads" / f"{cell}.json"

    def config_file(self, config):
        """The file BENCHMARK.json names; for a configuration no cell ships
        yet (its file is kept with what was learned), the place by name."""
        if config in self.configs:
            return self.root / self.configs[config]["file"]
        return self.bench / "configs" / f"{config}.json"

    def metric_file(self, metric):
        return self.bench / "metrics" / f"{metric}.py"

    def cell(self, name):
        if name not in self.cells:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json "
                f"(has: {', '.join(sorted(self.cells))})")
        spec = load_json(self.cell_file(name))
        entry = self.cells[name]
        for key in ("config", "chips"):
            if spec[key] != entry[key]:
                raise ValueError(
                    f"{self.cell_file(name)}: {key}={spec[key]!r} but "
                    f"BENCHMARK.json says {entry[key]!r}")
        return spec

    def config(self, name):
        return load_json(self.config_file(name))

    def runner(self, kind):
        return load_module(self.bench / "runners" / f"{kind}.py")

    def reference(self, family):
        return load_module(self.bench / "references" / f"{family}.py")

    def reader(self, metric):
        return load_module(self.metric_file(metric)).read

    # -- which metrics a cell reports ---------------------------------------
    def metrics_of(self, cell, table):
        """Metrics of ``table`` ('end_to_end' | 'per_layer') due in ``cell``.
        A per-layer metric without a ``workloads`` key is due in every cell
        that reports the end-to-end metric it moves."""
        out = []
        e2e_here = {
            m["name"] for m in self.doc["end_to_end"]
            if cell in m.get("workloads", list(self.cells))
        }
        for m in self.doc[table]:
            if "workloads" in m:
                due = cell in m["workloads"]
            elif table == "per_layer":
                due = m["moves"] in e2e_here
            else:
                due = True
            if due:
                out.append(m)
        return out

    # -- the contract's own rules, as far as a file can show them -----------
    def problems(self):
        d, out = self.doc, []
        want = {"command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"}
        if set(d) != want:
            out.append(f"keys {sorted(set(d) ^ want)} missing or unknown")
        for p in d["paths"]:
            if not re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p):
                out.append(f"path {p!r}")
        for word in d["command"]:
            if word.startswith("/") or ".." in word.split("/"):
                out.append(f"command word {word!r} leaves the repo")
        names = []
        for c in d["configs"]:
            names.append(c["name"])
            if set(c) != {"name", "source", "file", "reduced", "why"}:
                out.append(f"config {c['name']}: keys {sorted(c)}")
            if not any(c["file"].startswith(p + "/") for p in d["paths"]):
                out.append(f"config {c['name']}: file outside paths")
            if not (self.root / c["file"]).is_file():
                out.append(f"config {c['name']}: no file {c['file']}")
            else:
                held = load_json(self.root / c["file"])
                for key in c["reduced"]:
                    if not NAME.match(key):
                        out.append(f"config {c['name']}: reduced key {key!r}")
                    if key not in held.get("reduced", {}):
                        out.append(
                            f"config {c['name']}: {key} reduced but its file "
                            "gives no reason")
                if not (self.bench / "references"
                        / f"{held.get('reference')}.py").is_file():
                    out.append(f"config {c['name']}: no reference file")
        four = 0
        for w in d["workloads"]:
            names.append(w["name"])
            if set(w) != {"name", "config", "traffic", "chips", "why"}:
                out.append(f"workload {w['name']}: keys {sorted(w)}")
            if w["config"] not in self.configs:
                out.append(f"workload {w['name']}: unknown config")
            if w["chips"] not in (1, 4):
                out.append(f"workload {w['name']}: chips {w['chips']}")
            four += w["chips"] == 4
            if len(w["why"]) > 200 or "\n" in w["why"]:
                out.append(f"workload {w['name']}: why too long")
            if not NAME.match(w["traffic"]):
                out.append(f"workload {w['name']}: traffic {w['traffic']!r}")
            if not self.cell_file(w["name"]).is_file():
                out.append(f"workload {w['name']}: no file")
            else:
                spec = load_json(self.cell_file(w["name"]))
                if not (self.bench / "runners"
                        / f"{spec.get('runner')}.py").is_file():
                    out.append(f"workload {w['name']}: no runner file")
        if four > max(1, len(d["workloads"]) // 4):
            out.append(f"{four} four-chip cells of {len(d['workloads'])}")
        used = {w["config"] for w in d["workloads"]}
        for c in self.configs:
            if c not in used:
                out.append(f"config {c}: used by no cell")
        if "setup_s" not in self.end_to_end:
            out.append("no setup_s")
        for table in ("end_to_end", "per_layer"):
            for m in d[table]:
                names.append(m["name"])
                allowed = {"name", "unit", "better", "source", "workloads"}
                allowed |= ({"bound"} if table == "end_to_end"
                            else {"layer", "moves"})
                if not set(m) <= allowed or not allowed - {"workloads"} <= set(m):
                    out.append(f"metric {m['name']}: keys {sorted(m)}")
                if not UNIT.match(m["unit"]):
                    out.append(f"metric {m['name']}: unit {m['unit']!r}")
                if m["better"] not in ("lower", "higher"):
                    out.append(f"metric {m['name']}: better")
                if m["source"] not in SOURCES:
                    out.append(f"metric {m['name']}: source {m['source']!r}")
                for w in m.get("workloads", []):
                    if w not in self.cells:
                        out.append(f"metric {m['name']}: unknown cell {w}")
        for m in d["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                out.append(f"end-to-end {m['name']}: source {m['source']}")
            if not 0 < m["bound"] <= 0.1:
                out.append(f"end-to-end {m['name']}: bound {m['bound']}")
        for m in d["per_layer"]:
            if "workloads" not in m:
                out.append(f"per-layer {m['name']}: no workloads list")
            if m["moves"] not in self.end_to_end:
                out.append(f"per-layer {m['name']}: moves {m['moves']!r}")
                continue
            moved = self.end_to_end[m["moves"]]
            for w in m.get("workloads", []):
                if w not in moved.get("workloads", list(self.cells)):
                    out.append(
                        f"per-layer {m['name']}: cell {w} does not report "
                        f"{m['moves']}")
            if not self.metric_file(m["name"]).is_file():
                out.append(f"per-layer {m['name']}: no reader file")
        for n in names:
            if not NAME.match(n):
                out.append(f"name {n!r}")
        if len(set(names)) != len(names):
            out.append("a name is used twice")
        for cell in self.cells:
            e2e = [m["name"] for m in self.metrics_of(cell, "end_to_end")]
            if "setup_s" not in e2e or len(e2e) < 2:
                out.append(f"cell {cell}: end-to-end metrics {e2e}")
            if not self.metrics_of(cell, "per_layer"):
                out.append(f"cell {cell}: no per-layer metric")
        return out
