"""faultcheck: every FT rule fires on a known-bad fixture and stays
quiet on the clean twin; suppression namespaces are tool-isolated in
every direction (no other analyzer's disable can silence an FT finding
and vice versa); the ``tear-ok`` marker stands the durability rules
down; the shipped repo analyzes clean with every suppression justified
and allowlist-pinned; the CLI keeps the house exit-code and JSON
contracts plus ``--list-sites`` — and the real drift the first strict
run surfaced stays fixed: the GC/prune deletion loops carry seams, the
site registry is fully seamed, and every non-bookkeeping site is
drilled by a chaos preset or test plan."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from pyrecover_tpu.analysis.engine import ModuleInfo
from pyrecover_tpu.analysis.faultcheck import (
    FT_RULES,
    FaultConfig,
    FaultModel,
    analyze_paths,
    analyze_source,
    build_model,
)
from pyrecover_tpu.analysis.report import render_json

REPO = Path(__file__).resolve().parent.parent
GATE_PATHS = [
    str(REPO / "pyrecover_tpu"), str(REPO / "tools"),
    str(REPO / "bench.py"), str(REPO / "chip_smoke.py"),
    str(REPO / "__graft_entry__.py"),
]


def names(result, only_unsuppressed=True):
    fs = result.unsuppressed if only_unsuppressed else result.findings
    return [f.rule for f in fs]


def fc(src):
    """Hermetic analysis: an explicit empty drill corpus so a fixture
    carrying a ``FAULT_SITES`` literal never auto-discovers the real
    ``tests/`` directory."""
    return analyze_source(src, config=FaultConfig(drill_paths=()))


# ---------------------------------------------------------------------------
# rule fixtures: (firing snippet, clean snippet) — each bad snippet
# seeds exactly ONE durability-contract violation and must yield exactly
# one finding carrying exactly its own rule id.
# ---------------------------------------------------------------------------

FT_FIXTURES = {
    # the seam keeps FT02 quiet so the missing fsync is the only hazard
    "publish-before-durability": (
        '''import os
import tempfile

from pyrecover_tpu.resilience import faults


def publish_doc(payload, dest):
    fd, tmp = tempfile.mkstemp(dir=".")
    faults.check("doc_commit", path=tmp)
    with os.fdopen(fd, "wb") as f:
        f.write(payload)
    os.replace(tmp, dest)
''',
        '''import os
import tempfile

from pyrecover_tpu.resilience import faults


def publish_doc(payload, dest):
    fd, tmp = tempfile.mkstemp(dir=".")
    faults.check("doc_commit", path=tmp)
    with os.fdopen(fd, "wb") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, dest)
''',
    ),
    # correctly ordered stage/write/fsync/publish — only the seam is
    # missing, so the chaos harness cannot kill this writer
    "unseamed-durable-effect": (
        '''import os
import tempfile


def publish_doc(payload, dest):
    fd, tmp = tempfile.mkstemp(dir=".")
    with os.fdopen(fd, "wb") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, dest)
''',
        '''import os
import tempfile

from pyrecover_tpu.resilience import faults


def publish_doc(payload, dest):
    fd, tmp = tempfile.mkstemp(dir=".")
    faults.check("doc_commit", path=tmp)
    with os.fdopen(fd, "wb") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, dest)
''',
    ),
    # kind "counter" keeps FT04 exempt, so the phantom seam is the only
    # hazard; the registry literal arms the rule (content detection)
    "seam-drift": (
        '''from pyrecover_tpu.resilience import faults

FAULT_SITES = {
    "alpha": {"kind": "counter"},
}


def seam_alpha():
    faults.check("alpha")


def seam_beta():
    faults.check("beta")
''',
        '''from pyrecover_tpu.resilience import faults

FAULT_SITES = {
    "alpha": {"kind": "counter"},
}


def seam_alpha():
    faults.check("alpha")
''',
    ),
    # both sites registered and seamed; the in-source plan literal arms
    # the drill corpus but only fires beta — alpha is never rehearsed
    "undrilled-seam": (
        '''from pyrecover_tpu.resilience import faults

FAULT_SITES = {
    "alpha": {"kind": "write"},
    "beta": {"kind": "write"},
}

DRILL_PLAN = {"faults": [{"type": "transient_io_error", "site": "beta"}]}


def seam_alpha():
    faults.check("alpha")


def seam_beta():
    faults.check("beta")
''',
        '''from pyrecover_tpu.resilience import faults

FAULT_SITES = {
    "alpha": {"kind": "write"},
    "beta": {"kind": "write"},
}

DRILL_PLAN = {"faults": [
    {"type": "transient_io_error", "site": "alpha"},
    {"type": "transient_io_error", "site": "beta"},
]}


def seam_alpha():
    faults.check("alpha")


def seam_beta():
    faults.check("beta")
''',
    ),
    "leak-on-error": (
        '''from pyrecover_tpu.checkpoint.zerostall import pins


def fetch(exp_dir, manifest):
    lease = pins.pin_manifest(exp_dir, manifest)
    if manifest is None:
        raise RuntimeError("no manifest")
    lease.release()
''',
        '''from pyrecover_tpu.checkpoint.zerostall import pins


def fetch(exp_dir, manifest):
    lease = pins.pin_manifest(exp_dir, manifest)
    try:
        if manifest is None:
            raise RuntimeError("no manifest")
    finally:
        lease.release()
''',
    ),
    "recovery-swallow": (
        '''def restore_latest(path, loader):
    try:
        return loader(path)
    except OSError:
        pass
''',
        '''def restore_latest(path, loader, log_warning):
    try:
        return loader(path)
    except OSError as e:
        log_warning("restore failed: %s", e)
        return None
''',
    ),
}


@pytest.mark.parametrize("rule_name", sorted(FT_FIXTURES))
def test_rule_fires_on_bad_snippet(rule_name):
    bad, _ = FT_FIXTURES[rule_name]
    result = fc(bad)
    got = [(f.rule_id, f.rule) for f in result.findings]
    assert got == [(FT_RULES[rule_name].id, rule_name)], (
        f"{rule_name} must yield exactly one finding with exactly its "
        f"own id; got {got}"
    )


@pytest.mark.parametrize("rule_name", sorted(FT_FIXTURES))
def test_rule_quiet_on_clean_snippet(rule_name):
    _, good = FT_FIXTURES[rule_name]
    result = fc(good)
    assert names(result) == [], (
        f"{rule_name} false-positives on its clean fixture: "
        f"{[f.message for f in result.unsuppressed]}"
    )


@pytest.mark.parametrize("rule_name", sorted(FT_FIXTURES))
def test_rule_suppressible_inline(rule_name):
    """Appending ``# faultcheck: disable=<rule> -- why`` to the firing
    line silences it; the finding is still recorded with its
    justification. Every FT rule anchors on a code line (FT04's anchor
    is the registry dict entry), so all six share the inline channel."""
    bad, _ = FT_FIXTURES[rule_name]
    result = fc(bad)
    target = next(f for f in result.findings if f.rule == rule_name)
    lines = bad.splitlines()
    lines[target.line - 1] += (
        f"  # faultcheck: disable={rule_name} -- fixture-sanctioned"
    )
    suppressed = fc("\n".join(lines))
    assert not any(
        f.rule == rule_name and f.line == target.line
        for f in suppressed.unsuppressed
    )
    rec = next(
        f for f in suppressed.findings
        if f.rule == rule_name and f.line == target.line
    )
    assert rec.suppressed and rec.justification == "fixture-sanctioned"


def test_rule_suppressible_file_wide():
    bad, _ = FT_FIXTURES["unseamed-durable-effect"]
    directive = (
        "# faultcheck: disable-file=unseamed-durable-effect -- "
        "fixture-sanctioned\n"
    )
    result = fc(bad + directive)
    assert names(result) == []
    rec = next(f for f in result.findings)
    assert rec.suppressed and rec.justification == "fixture-sanctioned"


def test_every_catalog_rule_has_a_fixture():
    assert set(FT_FIXTURES) == set(FT_RULES), (
        "each FT rule ships with a true-positive + clean fixture pair"
    )


def test_catalog_ids_unique_and_documented():
    ids = [r.id for r in FT_RULES.values()]
    assert len(set(ids)) == len(ids)
    assert set(ids) == {f"FT{i:02d}" for i in range(1, 7)}
    readme = (REPO / "README.md").read_text(encoding="utf-8")
    for r in FT_RULES.values():
        assert r.id in readme and r.name in readme, (
            f"{r.id} ({r.name}) missing from the README catalog"
        )


# ---------------------------------------------------------------------------
# suppression / marker machinery — cross-tool isolation in every direction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("other_tool", ("jaxlint", "concur", "distcheck",
                                        "obscheck"))
def test_other_namespaces_do_not_suppress_faultcheck(other_tool):
    bad, _ = FT_FIXTURES["unseamed-durable-effect"]
    result = fc(bad)
    target = next(f for f in result.findings)
    lines = bad.splitlines()
    lines[target.line - 1] += (
        f"  # {other_tool}: disable=unseamed-durable-effect -- "
        f"wrong namespace"
    )
    still = fc("\n".join(lines))
    assert "unseamed-durable-effect" in names(still), (
        f"a {other_tool}: directive must never silence a faultcheck "
        f"finding"
    )


def test_faultcheck_namespace_does_not_suppress_jaxlint():
    from pyrecover_tpu.analysis import lint_source

    src = """
import jax

def sample(key):
    a = jax.random.normal(key, (2,))
    b = jax.random.uniform(key, (2,))  # faultcheck: disable=prng-key-reuse -- wrong namespace
    return a, b
"""
    result = lint_source(src)
    assert "prng-key-reuse" in [f.rule for f in result.unsuppressed]


def test_faultcheck_namespace_does_not_suppress_obscheck():
    from pyrecover_tpu.analysis.obscheck import ObsConfig
    from pyrecover_tpu.analysis.obscheck import (
        analyze_source as obs_source,
    )

    src = '''"""Fixture stream.

Core event names across the stack:

    alpha             x
"""

from pyrecover_tpu import telemetry


def publish():
    telemetry.emit("alpha", x=1)
    telemetry.emit("beta", z=3)  # faultcheck: disable=unknown-event -- wrong namespace
'''
    result = obs_source(src, config=ObsConfig(readme_text=""))
    assert "unknown-event" in [f.rule for f in result.unsuppressed]


def test_faultcheck_namespace_does_not_suppress_distcheck():
    from pyrecover_tpu.analysis.distcheck import (
        analyze_source as dist_source,
    )

    src = """
import jax

from pyrecover_tpu.parallel.mesh import sync_global_devices

def save(step):
    if jax.process_index() == 0:
        sync_global_devices("host0_only")  # faultcheck: disable=rank-gated-collective -- wrong namespace
"""
    result = dist_source(src)
    assert "rank-gated-collective" in [f.rule for f in result.unsuppressed]


def test_tear_ok_marker_stands_down_durability_rules():
    """A function marked ``# faultcheck: tear-ok`` declares its artifact
    advisory (caches, rotating logs): FT01 and FT02 stand down. The
    marker is metadata, not a suppression — no finding is recorded."""
    for rule_name in ("publish-before-durability", "unseamed-durable-effect"):
        bad, _ = FT_FIXTURES[rule_name]
        marked = bad.replace(
            "def publish_doc(payload, dest):",
            "def publish_doc(payload, dest):  # faultcheck: tear-ok",
        )
        assert fc(marked).findings == [], rule_name


def test_tear_ok_marker_on_line_above_def():
    bad, _ = FT_FIXTURES["unseamed-durable-effect"]
    marked = bad.replace(
        "def publish_doc(payload, dest):",
        "# advisory artifact  # faultcheck: tear-ok\n"
        "def publish_doc(payload, dest):",
    )
    assert fc(marked).findings == []


# ---------------------------------------------------------------------------
# model extraction
# ---------------------------------------------------------------------------


def _model(src, name="fixture.py"):
    mi = ModuleInfo(name, src, relpath=name, tool="faultcheck")
    return FaultModel([mi], FaultConfig(drill_paths=()))


def test_effect_chain_folds_nested_defs_in_line_order():
    """The vanilla writer's closure idiom: an ``os.fsync`` inside a
    nested def belongs to the OUTERMOST function's chain, ordered by
    source line — which is the crash order a kill -9 sees."""
    model = _model(
        '''import os
import tempfile


def outer(payload, dest):
    fd, tmp = tempfile.mkstemp()

    def _sync(f):
        os.fsync(f.fileno())

    with os.fdopen(fd, "wb") as f:
        f.write(payload)
        _sync(f)
    os.replace(tmp, dest)
'''
    )
    (chain,) = model.chains
    assert chain.label() == "outer"
    assert [e.kind for e in chain.events] == [
        "stage", "fsync", "write", "publish"
    ]


def test_publish_detection_discriminates_replace_flavors():
    """``dataclasses.replace(cfg, ...)`` and ``str.replace(a, b)`` are
    not publishes; ``os.replace`` and a one-arg ``Path.replace`` called
    for effect are."""
    model = _model(
        '''import dataclasses
import os


def not_publishes(cfg, s):
    cfg = dataclasses.replace(cfg, x=1)
    t = s.replace("a", "b")
    return cfg, t


def dotted_publish(tmp, dest):
    os.replace(tmp, dest)


def method_publish(tmp, dest):
    tmp.replace(dest)
'''
    )
    pubs = {
        (c.label(), e.what) for c in model.chains for e in c.publishes
    }
    assert pubs == {("dotted_publish", "os.replace"),
                    ("method_publish", ".replace")}


def test_seam_extraction_literal_and_dynamic():
    model = _model(
        '''from pyrecover_tpu.resilience import faults


def seams(site):
    faults.check("ckpt_write", path="x")
    faults.check(site)
'''
    )
    assert [s.site for s in model.seams] == ["ckpt_write", None]


def test_registry_and_drill_resolution():
    """Registry entries carry kind/owner; plan literals resolve through
    the fault-class declarations — an op maps via ``_OPS``, a typed plan
    with no site covers every declared site, and a literal site stands
    alone."""
    model = _model(
        '''FAULT_SITES = {
    "alpha": {"kind": "write", "module": "m.py"},
    "beta": {"kind": "fsync"},
}


class _Flaky:
    type_name = "flaky"
    sites = ("alpha", "beta")
    _OPS = {"a": "alpha", "b": "beta", "any": None}


PLANS = [
    {"type": "flaky", "op": "a"},
    {"type": "flaky"},
    {"type": "kill9_during_save", "site": "beta"},
]
'''
    )
    assert model.registry_armed
    assert model.registry["alpha"].kind == "write"
    assert model.registry["alpha"].owner == "m.py"
    got = {(r.ftype, tuple(sorted(r.sites))) for r in model.drill_refs}
    assert got == {
        ("flaky", ("alpha",)),
        ("flaky", ("alpha", "beta")),
        ("kill9_during_save", ("beta",)),
    }
    assert model.drilled_sites() == {"alpha", "beta"}


def test_acquire_protection_classification():
    model = _model(
        '''from pyrecover_tpu.checkpoint.zerostall import pins


def with_protected(exp, m, read):
    with pins.pin_manifest(exp, m) as lease:
        read(lease)


class Holder:
    def grab(self, exp, m):
        self.lease = pins.pin_manifest(exp, m)


def handoff(exp, m):
    lease = pins.pin_manifest(exp, m)
    return lease
'''
    )
    whys = {a.why for a in model.acquires}
    assert whys == {
        "with-statement", "stored-on-attribute", "returned (handoff)"
    }
    assert all(a.protected for a in model.acquires)


# ---------------------------------------------------------------------------
# the shipped repo is clean — and the real drifts stay fixed
# ---------------------------------------------------------------------------


def test_repo_analyzes_clean_with_justified_suppressions():
    result = analyze_paths(GATE_PATHS)
    assert result.unsuppressed == [], (
        "faultcheck findings in the shipped repo:\n"
        + "\n".join(
            f"{f.location()}: {f.rule_id} {f.message}"
            for f in result.unsuppressed
        )
    )
    for f in result.suppressed:
        assert f.justification.strip(), (
            f"suppression without justification at {f.location()}"
        )


def test_repo_carries_the_pinned_suppressions():
    """The residual suppressions are a curated allowlist: pin them so a
    new one (or a silent disappearance) is a conscious decision."""
    result = analyze_paths(GATE_PATHS)
    locs = {(Path(f.path).name, f.rule_id) for f in result.suppressed}
    assert ("pins.py", "FT02") in locs, (
        "pin leases are crash-safe by TTL expiry, not injection — a "
        "test-pinned FT02 suppression"
    )
    assert ("autopilot.py", "FT02") in locs, (
        "the failure-history sidecar is controller bookkeeping outside "
        "the checkpoint data plane — a test-pinned FT02 suppression"
    )
    assert ("quarantine.py", "FT02") in locs, (
        "quarantine IS the failure path; seaming it would inject faults "
        "into fault handling — a test-pinned FT02 suppression"
    )
    assert ("train.py", "FT06") in locs, (
        "_resume folds the failure into the broadcast host-0 verdict "
        "and re-raises collectively — a test-pinned FT06 suppression"
    )
    assert len(result.suppressed) <= 8, (
        f"suppression creep: {sorted(locs)} — every addition needs a "
        "justification AND a pin here"
    )


def test_fixed_drift_registry_fully_seamed_and_drilled():
    """THE drift the first strict run surfaced: the GC chunk sweep, the
    pin-lease expiry sweep, and retention's prune loop destroyed durable
    state with no seam — unkillable by the chaos harness. They now call
    ``ckpt_gc_unlink``/``ckpt_prune`` seams, every registry site has a
    live seam, and every non-bookkeeping site is fired by a drill."""
    m = build_model(GATE_PATHS)
    assert m.registry_armed
    assert m.registry_module.relpath.endswith("resilience/faults.py")
    seamed = {s.site for s in m.seams if s.site is not None}
    for site in ("ckpt_gc_unlink", "ckpt_prune"):
        assert site in m.registry, f"{site} missing from FAULT_SITES"
        assert site in seamed, f"{site} registered but never seamed"
    unseamed = set(m.registry) - seamed
    assert unseamed == set(), f"registry sites with no seam: {unseamed}"
    drilled = m.drilled_sites()
    undrilled = {
        site for site, entry in m.registry.items()
        if entry.kind not in {"counter"} and site not in drilled
    }
    assert undrilled == set(), (
        f"registered sites no drill ever fires: {undrilled}"
    )


def test_fixed_drift_runtime_registry_matches_static_view():
    """The static registry the analyzer reads IS the runtime registry
    the engine validates against — same sites, same kinds."""
    from pyrecover_tpu.resilience import faults

    m = build_model([str(REPO / "pyrecover_tpu" / "resilience")])
    assert set(m.registry) == set(faults.FAULT_SITES)
    for site, entry in m.registry.items():
        assert entry.kind == faults.FAULT_SITES[site]["kind"], site


# ---------------------------------------------------------------------------
# CLI / report contracts
# ---------------------------------------------------------------------------


def test_json_report_shape():
    bad, _ = FT_FIXTURES["unseamed-durable-effect"]
    result = fc(bad)
    doc = json.loads(render_json(result, strict=True, tool="faultcheck"))
    assert doc["tool"] == "faultcheck"
    assert doc["strict"] is True
    assert doc["summary"]["unsuppressed"] == 1
    (f,) = doc["findings"]
    assert f["rule_id"] == "FT02" and f["rule"] == "unseamed-durable-effect"


def test_cli_strict_gate(tmp_path):
    from pyrecover_tpu.analysis.faultcheck.cli import main

    bad, _ = FT_FIXTURES["unseamed-durable-effect"]
    target = tmp_path / "bad.py"
    target.write_text(bad)
    report = tmp_path / "report.json"
    rc = main([str(target), "--strict", "--json", str(report)])
    assert rc == 1
    doc = json.loads(report.read_text())
    assert doc["summary"]["unsuppressed"] == 1
    assert main([str(target)]) == 0  # report-only mode stays 0
    assert main([str(tmp_path / "nope.py")]) == 2
    assert main(["--list-rules"]) == 0


def test_cli_select_and_ignore(tmp_path):
    from pyrecover_tpu.analysis.faultcheck.cli import main

    bad, _ = FT_FIXTURES["unseamed-durable-effect"]
    target = tmp_path / "bad.py"
    target.write_text(bad)
    assert main([str(target), "--strict", "--select", "FT01"]) == 0
    assert main([str(target), "--strict",
                 "--ignore", "unseamed-durable-effect"]) == 0
    assert main([str(target), "--strict", "--select", "FT02"]) == 1


def test_cli_list_sites_dumps_model(tmp_path, capsys):
    from pyrecover_tpu.analysis.faultcheck.cli import main

    bad, _ = FT_FIXTURES["undrilled-seam"]
    target = tmp_path / "mod.py"
    target.write_text(bad)
    assert main([str(target), "--list-sites"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {
        "registry", "seams", "effect_chains", "drills", "resources",
        "drill_corpus_files",
    }
    assert sorted(doc["registry"]["sites"]) == ["alpha", "beta"]
    assert doc["registry"]["sites"]["beta"]["drilled"] is True
    assert doc["registry"]["sites"]["alpha"]["drilled"] is False
    assert doc["registry"]["sites"]["alpha"]["seams"], (
        "--list-sites must map each site to its live seams"
    )


def test_cli_strict_clean_on_repo_subprocess(tmp_path):
    """The exact format.sh invocation: exit 0 over the gated set."""
    report = tmp_path / "faultcheck.json"
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "faultcheck.py"),
         *GATE_PATHS, "--strict", "--json", str(report)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(report.read_text())
    assert doc["tool"] == "faultcheck"
    assert doc["summary"]["unsuppressed"] == 0
