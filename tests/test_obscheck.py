"""obscheck: every OB rule fires on a known-bad fixture and stays quiet
on the clean twin; suppression namespaces are tool-isolated in every
direction (a jaxlint/concur/distcheck disable can never silence an OB
finding and vice versa); the ``once`` marker and guardedness steer the
hot-path rule; the shipped repo analyzes clean with every suppression
justified; the CLI keeps the jaxlint exit-code and JSON contracts plus
``--list-events`` — and the real catalog drifts the first strict run
surfaced are regression-pinned: ``ckpt_saved`` is documented in both
catalogs (it was in neither while three consumers keyed on it),
``emergency_peer_exchange`` is in the docstring catalog, and the README
maintenance row spells its full event names instead of the ungreppable
``(+`_retired`)`` shorthand."""

import json
import subprocess
import sys
from pathlib import Path

import pytest
from conftest import obs_model

from pyrecover_tpu.analysis.engine import ModuleInfo
from pyrecover_tpu.analysis.obscheck import (
    OB_RULES,
    ObsConfig,
    ObsModel,
    analyze_paths,
    analyze_source,
)
from pyrecover_tpu.analysis.obscheck.model import (
    parse_docstring_catalog,
    parse_readme_catalog,
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


def obs(src, readme):
    return analyze_source(src, config=ObsConfig(readme_text=readme))


# a README event table that agrees with the fixtures' docstring catalog
README_ALPHA = """\
| event | fields | emitted by |
|---|---|---|
| `alpha` | `x`, `y` | fixture.py |
"""


# ---------------------------------------------------------------------------
# rule fixtures: (firing snippet, clean snippet, readme text) — each bad
# snippet seeds exactly ONE contract violation and must yield exactly one
# finding carrying exactly its own rule id. The docstring sentinel makes
# each fixture its own catalog module (content-based detection), arming
# the cross-surface rules.
# ---------------------------------------------------------------------------

OB_FIXTURES = {
    "unknown-event": (
        '''"""Fixture stream.

Core event names across the stack:

    alpha             x, y
"""

from pyrecover_tpu import telemetry


def publish():
    telemetry.emit("alpha", x=1, y=2)
    telemetry.emit("beta", z=3)
''',
        '''"""Fixture stream.

Core event names across the stack:

    alpha             x, y
"""

from pyrecover_tpu import telemetry


def publish():
    telemetry.emit("alpha", x=1, y=2)
''',
        README_ALPHA,
    ),
    # no README in scope here: a phantom documented on BOTH surfaces
    # would (rightly) fire once per surface; one surface → one finding
    "phantom-catalog-entry": (
        '''"""Fixture stream.

Core event names across the stack:

    alpha             x, y
    gone              a
"""

from pyrecover_tpu import telemetry


def publish():
    telemetry.emit("alpha", x=1, y=2)
''',
        '''"""Fixture stream.

Core event names across the stack:

    alpha             x, y
"""

from pyrecover_tpu import telemetry


def publish():
    telemetry.emit("alpha", x=1, y=2)
''',
        "",
    ),
    "consumer-field-drift": (
        '''"""Fixture stream.

Core event names across the stack:

    alpha             x, y
"""

from pyrecover_tpu import telemetry

EVENT_DEPS = {"alpha": ("x", "zz")}


def publish():
    telemetry.emit("alpha", x=1, y=2)
''',
        '''"""Fixture stream.

Core event names across the stack:

    alpha             x, y
"""

from pyrecover_tpu import telemetry

EVENT_DEPS = {"alpha": ("x", "y")}


def publish():
    telemetry.emit("alpha", x=1, y=2)
''',
        README_ALPHA,
    ),
    "catalog-divergence": (
        '''"""Fixture stream.

Core event names across the stack:

    alpha             x, y
"""

from pyrecover_tpu import telemetry


def publish():
    telemetry.emit("alpha", x=1, y=2)
''',
        '''"""Fixture stream.

Core event names across the stack:

    alpha             x, y
"""

from pyrecover_tpu import telemetry


def publish():
    telemetry.emit("alpha", x=1, y=2)
''',
        # bad run injects the DIVERGENT readme via OB_README_OVERRIDE
        README_ALPHA,
    ),
    "hot-path-emit": (
        '''from pyrecover_tpu import telemetry


def step_loop(n):  # jaxlint: hot-loop
    for i in range(n):
        telemetry.emit("tick", i=i)
''',
        '''from pyrecover_tpu import telemetry


def step_loop(n, should_log):  # jaxlint: hot-loop
    for i in range(n):
        if should_log(i):
            telemetry.emit("tick", i=i)
''',
        "",
    ),
    "metric-name-drift": (
        '''"""Fixture stream.

Core event names across the stack:

    alpha             x, y
"""

from pyrecover_tpu import telemetry
from pyrecover_tpu.telemetry import metrics


def publish():
    telemetry.emit("alpha", x=1, y=2)
    metrics.counter("steps_total").inc()


def consume(hists):
    return hists.get("step_time_s")
''',
        '''"""Fixture stream.

Core event names across the stack:

    alpha             x, y
"""

from pyrecover_tpu import telemetry
from pyrecover_tpu.telemetry import metrics


def publish():
    telemetry.emit("alpha", x=1, y=2)
    metrics.histogram("step_time_s").observe(0.1)


def consume(hists):
    return hists.get("step_time_s")
''',
        README_ALPHA,
    ),
    # LOCAL rule (no catalog sentinel needed): a per-request span
    # (rid= kwarg) with neither trace= nor an enclosing installed(...)
    "untraced-request-span": (
        '''from pyrecover_tpu import telemetry


def finish(rid, t0, t1):
    telemetry.record_span("req_queue", t0, t1, rid=rid)
''',
        '''from pyrecover_tpu import telemetry
from pyrecover_tpu.telemetry import tracing


def finish(rid, t0, t1, ctx):
    with tracing.installed(ctx):
        telemetry.record_span("req_queue", t0, t1, rid=rid)
''',
        "",
    ),
}

# catalog-divergence is the one rule whose hazard lives in the README
# side; its bad run swaps in a field-divergent table (both sides closed)
OB_README_OVERRIDE = {
    "catalog-divergence": """\
| event | fields | emitted by |
|---|---|---|
| `alpha` | `x`, `z` | fixture.py |
""",
}


@pytest.mark.parametrize("rule_name", sorted(OB_FIXTURES))
def test_rule_fires_on_bad_snippet(rule_name):
    bad, _, readme = OB_FIXTURES[rule_name]
    readme = OB_README_OVERRIDE.get(rule_name, readme)
    result = obs(bad, readme)
    got = [(f.rule_id, f.rule) for f in result.findings]
    assert got == [(OB_RULES[rule_name].id, rule_name)], (
        f"{rule_name} must yield exactly one finding with exactly its "
        f"own id; got {got}"
    )


@pytest.mark.parametrize("rule_name", sorted(OB_FIXTURES))
def test_rule_quiet_on_clean_snippet(rule_name):
    _, good, readme = OB_FIXTURES[rule_name]
    result = obs(good, readme)
    assert names(result) == [], (
        f"{rule_name} false-positives on its clean fixture: "
        f"{[f.message for f in result.unsuppressed]}"
    )


# rules whose finding anchors on a CODE line (a tokenize comment can sit
# there); the docstring/README-anchored rules are suppressed file-wide
_INLINE = ("unknown-event", "consumer-field-drift", "hot-path-emit",
           "metric-name-drift", "untraced-request-span")


@pytest.mark.parametrize("rule_name", _INLINE)
def test_rule_suppressible_inline(rule_name):
    """Appending ``# obscheck: disable=<rule> -- why`` to the firing
    line silences it; the finding is still recorded with its
    justification."""
    bad, _, readme = OB_FIXTURES[rule_name]
    readme = OB_README_OVERRIDE.get(rule_name, readme)
    result = obs(bad, readme)
    target = next(f for f in result.findings if f.rule == rule_name)
    lines = bad.splitlines()
    lines[target.line - 1] += (
        f"  # obscheck: disable={rule_name} -- fixture-sanctioned"
    )
    suppressed = obs("\n".join(lines), readme)
    assert not any(
        f.rule == rule_name and f.line == target.line
        for f in suppressed.unsuppressed
    )
    rec = next(
        f for f in suppressed.findings
        if f.rule == rule_name and f.line == target.line
    )
    assert rec.suppressed and rec.justification == "fixture-sanctioned"


@pytest.mark.parametrize(
    "rule_name", ("phantom-catalog-entry", "catalog-divergence")
)
def test_catalog_anchored_rules_suppressible_file_wide(rule_name):
    """OB02/OB04 anchor inside the docstring, where no comment token can
    sit — ``disable-file`` is their suppression channel."""
    bad, _, readme = OB_FIXTURES[rule_name]
    readme = OB_README_OVERRIDE.get(rule_name, readme)
    directive = (
        f"# obscheck: disable-file={rule_name} -- fixture-sanctioned\n"
    )
    result = obs(bad + directive, readme)
    assert names(result) == []
    rec = next(f for f in result.findings if f.rule == rule_name)
    assert rec.suppressed and rec.justification == "fixture-sanctioned"


def test_every_catalog_rule_has_a_fixture():
    assert set(OB_FIXTURES) == set(OB_RULES), (
        "each OB rule ships with a true-positive + clean fixture pair"
    )


def test_catalog_ids_unique_and_documented():
    ids = [r.id for r in OB_RULES.values()]
    assert len(set(ids)) == len(ids)
    assert set(ids) == {f"OB{i:02d}" for i in range(1, 8)}
    readme = (REPO / "README.md").read_text(encoding="utf-8")
    for r in OB_RULES.values():
        assert r.id in readme and r.name in readme, (
            f"{r.id} ({r.name}) missing from the README catalog"
        )


# ---------------------------------------------------------------------------
# suppression / marker machinery — cross-tool isolation in every direction
# ---------------------------------------------------------------------------


def test_jaxlint_namespace_does_not_suppress_obscheck():
    bad, _, readme = OB_FIXTURES["unknown-event"]
    result = obs(bad, readme)
    target = next(f for f in result.findings)
    lines = bad.splitlines()
    lines[target.line - 1] += (
        "  # jaxlint: disable=unknown-event -- wrong namespace"
    )
    still = obs("\n".join(lines), readme)
    assert "unknown-event" in names(still), (
        "a jaxlint: directive must never silence an obscheck finding"
    )


def test_distcheck_namespace_does_not_suppress_obscheck():
    bad, _, readme = OB_FIXTURES["consumer-field-drift"]
    result = obs(bad, readme)
    target = next(f for f in result.findings)
    lines = bad.splitlines()
    lines[target.line - 1] += (
        "  # distcheck: disable=consumer-field-drift -- wrong namespace"
    )
    still = obs("\n".join(lines), readme)
    assert "consumer-field-drift" in names(still)


def test_obscheck_namespace_does_not_suppress_jaxlint():
    from pyrecover_tpu.analysis import lint_source

    src = """
import jax

def sample(key):
    a = jax.random.normal(key, (2,))
    b = jax.random.uniform(key, (2,))  # obscheck: disable=prng-key-reuse -- wrong namespace
    return a, b
"""
    result = lint_source(src)
    assert "prng-key-reuse" in [f.rule for f in result.unsuppressed]


def test_obscheck_namespace_does_not_suppress_distcheck():
    from pyrecover_tpu.analysis.distcheck import (
        analyze_source as dist_source,
    )

    src = """
import jax

from pyrecover_tpu.parallel.mesh import sync_global_devices

def save(step):
    if jax.process_index() == 0:
        sync_global_devices("host0_only")  # obscheck: disable=rank-gated-collective -- wrong namespace
"""
    result = dist_source(src)
    assert "rank-gated-collective" in [f.rule for f in result.unsuppressed]


def test_once_marker_clears_hot_path_emit():
    """A hot function carrying ``# obscheck: once`` declares a warn-once
    discipline the AST cannot see; OB05 stands down. The marker is
    cross-tool metadata, not a suppression: the finding is not even
    recorded."""
    bad, _, _ = OB_FIXTURES["hot-path-emit"]
    marked = bad.replace(
        "def step_loop(n):  # jaxlint: hot-loop",
        "def step_loop(n):  # jaxlint: hot-loop  # obscheck: once",
    )
    result = obs(marked, "")
    assert result.findings == []


# ---------------------------------------------------------------------------
# model extraction
# ---------------------------------------------------------------------------


def _scan(src, name="fixture.py", readme=None):
    mi = ModuleInfo(name, src, relpath=name, tool="obscheck")
    return ObsModel([mi], ObsConfig(readme_text=readme))


def test_docstring_catalog_entry_parsing():
    src = '''"""Stream.

Core event names across the stack:

    alpha             x, y
    multi_a / multi_b  shared
    resume            path; resume_replay: replayed_steps
    elided            a, ... (prose)
"""
'''
    mi = ModuleInfo("m.py", src, relpath="m.py", tool="obscheck")
    cat = parse_docstring_catalog(mi)
    assert cat["alpha"].fields == {"x", "y"} and not cat["alpha"].open
    # /-joined names exist but are never field-compared (forced open)
    assert cat["multi_a"].open and cat["multi_b"].open
    # a ;-chunk declares a sibling event with its own fields
    assert cat["resume_replay"].fields == {"replayed_steps"}
    assert not cat["resume_replay"].open
    # elisions keep the entry out of field comparison
    assert cat["elided"].open and "a" in cat["elided"].fields


def test_readme_catalog_escaped_pipe_stays_one_cell():
    """The slo_alert row regression: ``(`firing`\\|`cleared`)`` is a
    literal pipe inside a cell, not a column divider — naive splitting
    truncated the field set mid-row."""
    text = (
        "| event | fields | emitted by |\n"
        "|---|---|---|\n"
        "| `slo_alert` | `rule`, `kind`, `state` (`firing`\\|`cleared`), "
        "`value` | exporter.py |\n"
    )
    cat = parse_readme_catalog(text)
    e = cat["slo_alert"]
    assert e.fields == {"rule", "kind", "state", "value"} and not e.open


def test_readme_prose_rows_are_open_not_field_compared():
    text = (
        "| event | fields | emitted by |\n"
        "|---|---|---|\n"
        "| `chatty` | `step` plus whatever the caller adds | x.py |\n"
    )
    cat = parse_readme_catalog(text)
    assert cat["chatty"].open and "step" in cat["chatty"].fields


def test_dict_literal_star_spread_folds_keys():
    model = _scan(
        'from pyrecover_tpu import telemetry\n'
        'def f(step):\n'
        '    telemetry.emit("ev", a=1, **{"b": 2, "c": step})\n'
    )
    (site,) = model.emits
    assert site.fields == {"a", "b", "c"} and not site.open


def test_opaque_star_spread_marks_site_open():
    model = _scan(
        'from pyrecover_tpu import telemetry\n'
        'def f(extra):\n'
        '    telemetry.emit("ev", a=1, **extra)\n'
    )
    (site,) = model.emits
    assert site.open
    fields, is_open = model.producer_fields("ev")
    assert is_open  # open sites satisfy any consumer field read


def test_event_keyed_mapping_makes_gets_event_reads():
    """The summarizer idiom: a dict ever subscripted with ``e["event"]``
    turns its ``.get("lit")`` calls into event reads — and a read of an
    event nobody emits is the OB03 hazard."""
    src = '''"""Stream.

Core event names across the stack:

    alpha             x
"""

from pyrecover_tpu import telemetry


def publish():
    telemetry.emit("alpha", x=1)


def summarize(events):
    by = {}
    for e in events:
        by.setdefault(e["event"], []).append(e)
    return by.get("alpha"), by.get("zzz")
'''
    result = obs(src, README_ALPHA.replace(", `y`", ""))
    (f,) = result.unsuppressed
    assert f.rule == "consumer-field-drift" and '"zzz"' in f.message


def test_span_deps_read_without_span_site_is_drift():
    src = '''"""Stream.

Core event names across the stack:

    alpha             x
"""

from pyrecover_tpu import telemetry

SPAN_DEPS = ("no_such_span",)


def publish():
    telemetry.emit("alpha", x=1)
'''
    result = obs(src, README_ALPHA.replace(", `y`", ""))
    (f,) = result.unsuppressed
    assert f.rule == "consumer-field-drift"
    assert 'span "no_such_span"' in f.message


def test_cross_surface_rules_disarm_without_catalog_in_scan():
    """Pointing obscheck at one stray module must not declare its every
    emit unknown — OB01/OB02/OB04/OB06 need the catalog module in the
    scanned set."""
    result = analyze_source(
        'from pyrecover_tpu import telemetry\n'
        'def f():\n'
        '    telemetry.emit("undocumented_here", a=1)\n'
    )
    assert result.findings == []


# ---------------------------------------------------------------------------
# the shipped repo is clean — and the real drifts stay fixed
# ---------------------------------------------------------------------------


def test_repo_analyzes_clean_with_justified_suppressions():
    result = analyze_paths(GATE_PATHS)
    assert result.unsuppressed == [], (
        "obscheck findings in the shipped repo:\n"
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
    assert ("train.py", "OB05") in locs, (
        "the run_start / interval-gated ckpt_saved emits in the hot "
        "train loop are test-pinned OB05 suppressions"
    )
    assert ("aggregate.py", "OB06") in locs, (
        "the fleet drill's subprocess-registered demo series is a "
        "test-pinned OB06 file-level suppression"
    )
    assert len(result.suppressed) <= 10, (
        f"suppression creep: {sorted(locs)} — every addition needs a "
        "justification AND a pin here"
    )


def test_fixed_drift_ckpt_saved_documented_and_produced():
    """THE drift the first strict run surfaced: three consumers (the
    autopilot decision trail, the summarizer's counterfactual, the
    goodput section) key on ``ckpt_saved`` — which no catalog
    documented. Now it's in both, with the field set producers pass."""
    m = obs_model()
    assert "ckpt_saved" in m.sites_by_event
    assert "ckpt_saved" in m.doc_catalog
    assert "ckpt_saved" in m.readme_catalog
    fields, _open = m.producer_fields("ckpt_saved")
    assert {"engine", "path", "step", "blocking_s", "final"} <= fields


def test_fixed_drift_emergency_peer_exchange_in_docstring_catalog():
    m = obs_model()
    assert "emergency_peer_exchange" in m.doc_catalog
    assert "emergency_peer_exchange" in m.readme_catalog
    fields, _open = m.producer_fields("emergency_peer_exchange")
    assert {"engine", "step", "exp_dir", "leaves", "bytes"} <= fields


def test_fixed_drift_maintenance_row_spells_full_event_names():
    """The README maintenance row used ``(+`_retired`/…)`` shorthand —
    ungreppable, and parsed as phantom ``_retired`` events. It now
    spells every name, and each has a real emit site."""
    m = obs_model()
    for name in ("maintenance_event", "maintenance_watcher_retired",
                 "maintenance_degraded", "maintenance_recovered"):
        assert name in m.readme_catalog, f"{name} not a parsed README row"
        assert name in m.sites_by_event, f"{name} has no emit site"


def test_doctor_event_deps_all_satisfied_by_producers():
    """Every (event, field) the doctor declares is producible: the
    declarative table is the contract obscheck checks, so a dead entry
    here means the repo-clean test above would have caught it — pin the
    link explicitly anyway."""
    from pyrecover_tpu.telemetry import doctor

    m = obs_model()
    for event, fields in doctor.EVENT_DEPS.items():
        assert event in m.sites_by_event, f"{event}: no emit site"
        produced, is_open = m.producer_fields(event)
        for field in fields:
            assert is_open or field in produced, (
                f"{event}.{field}: declared by doctor, never passed"
            )
    for span in doctor.SPAN_DEPS:
        assert span in m.span_names


# ---------------------------------------------------------------------------
# CLI / report contracts
# ---------------------------------------------------------------------------


def test_json_report_shape():
    bad, _, readme = OB_FIXTURES["unknown-event"]
    result = obs(bad, readme)
    doc = json.loads(render_json(result, strict=True, tool="obscheck"))
    assert doc["tool"] == "obscheck"
    assert doc["strict"] is True
    assert doc["summary"]["unsuppressed"] == 1
    (f,) = doc["findings"]
    assert f["rule_id"] == "OB01" and f["rule"] == "unknown-event"


def test_cli_strict_gate(tmp_path):
    from pyrecover_tpu.analysis.obscheck.cli import main

    bad, _, _ = OB_FIXTURES["hot-path-emit"]
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


def test_cli_list_events_dumps_model(tmp_path, capsys):
    from pyrecover_tpu.analysis.obscheck.cli import main

    bad, _, _ = OB_FIXTURES["unknown-event"]
    target = tmp_path / "mod.py"
    target.write_text(bad)
    assert main([str(target), "--list-events"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {
        "producers", "spans", "metrics", "catalog", "consumers", "dynamic"
    }
    assert sorted(doc["producers"]) == ["alpha", "beta"]
    assert doc["producers"]["alpha"]["fields"] == ["x", "y"]


def test_cli_strict_clean_on_repo_subprocess(tmp_path):
    """The exact format.sh invocation: exit 0 over the gated set."""
    report = tmp_path / "obscheck.json"
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "obscheck.py"),
         *GATE_PATHS, "--strict", "--json", str(report)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(report.read_text())
    assert doc["tool"] == "obscheck" and doc["summary"]["unsuppressed"] == 0
