"""distcheck: every DC rule fires on a known-bad fixture and stays quiet
on the clean twin; suppression namespaces are tool-isolated in every
direction (a jaxlint/concur disable can never silence a DC finding and
vice versa); the host-local/congruent markers steer the divergence
model; the shipped repo analyzes clean with every suppression justified;
the CLI keeps the jaxlint exit-code and JSON contracts — and the real
divergence fixes are regression-pinned: the emergency peer exchange runs
on a host-0 verdict broadcast (a peer with no env opt-in and no local
record still participates), a mid-restore emergency failure RAISES on a
pod instead of privately rejoining the disk walk, and every raw
multihost wait is bounded by a ``collective_phase`` that turns a silent
forever-hang into a named ``distributed_wait_timeout`` with a flight
bundle."""

import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from pyrecover_tpu import telemetry
from pyrecover_tpu.analysis.distcheck import (
    DC_RULES,
    DistConfig,
    DistModel,
    analyze_paths,
    analyze_source,
)
from pyrecover_tpu.analysis.engine import ModuleInfo
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


# ---------------------------------------------------------------------------
# rule fixtures: (rule name, firing snippet, clean snippet) — each bad
# snippet seeds exactly ONE hazard and must yield exactly one finding
# carrying exactly its own rule id
# ---------------------------------------------------------------------------

DC_FIXTURES = {
    "rank-gated-collective": (
        """
import jax

from pyrecover_tpu.parallel.mesh import sync_global_devices

def save(step):
    if jax.process_index() == 0:
        sync_global_devices("host0_only")
""",
        """
import jax

from pyrecover_tpu.parallel.mesh import sync_global_devices

def save(step, write):
    sync_global_devices("everyone")
    if jax.process_index() == 0:
        write(step)
""",
    ),
    "divergent-collective-order": (
        """
import os

from mylib import process_allgather, sync_global_devices

def exchange(x):
    if os.environ.get("ROLE") == "writer":
        sync_global_devices("pre")
        process_allgather(x)
    else:
        process_allgather(x)
""",
        """
import os

from mylib import process_allgather, sync_global_devices

def exchange(x, log):
    if os.environ.get("ROLE") == "writer":
        log("writer")
        sync_global_devices("pre")
        process_allgather(x)
    else:
        sync_global_devices("pre")
        process_allgather(x)
""",
    ),
    "unbroadcast-verdict": (
        """
import jax

def decide(state, check):
    ok = 0
    if jax.process_index() == 0:
        ok = check(state)
    if ok:
        return 1
    return 0
""",
        """
import jax

from pyrecover_tpu.parallel.mesh import broadcast_host0_scalar

def decide(state, check):
    ok = 0
    if jax.process_index() == 0:
        ok = check(state)
    ok = int(broadcast_host0_scalar(ok))
    if ok:
        return 1
    return 0
""",
    ),
    "collective-under-swallowed-exception": (
        """
from mylib import sync_global_devices

def restore(path, read_blob):
    try:
        data = read_blob(path)
    except OSError:
        data = None
    sync_global_devices("post_restore")
    return data
""",
        """
import jax

from mylib import sync_global_devices

def restore(path, read_blob):
    try:
        data = read_blob(path)
    except OSError:
        if jax.process_count() > 1:
            raise
        data = None
    sync_global_devices("post_restore")
    return data
""",
    ),
    "unbounded-distributed-blocking": (
        """
from jax.experimental import multihost_utils

def barrier(tag):
    multihost_utils.sync_global_devices(tag)
""",
        """
from jax.experimental import multihost_utils

from pyrecover_tpu import telemetry

def barrier(tag):
    with telemetry.collective_phase("barrier"):
        multihost_utils.sync_global_devices(tag)
""",
    ),
    "local-state-collective-count": (
        """
from pathlib import Path

from mylib import process_allgather

def push_all(d, x):
    for p in Path(d).glob("*.ckpt"):
        process_allgather(x)
""",
        """
from pathlib import Path

from mylib import process_allgather
from pyrecover_tpu.parallel.mesh import broadcast_host0_obj

def push_all(d, x):
    work = broadcast_host0_obj(sorted(str(p) for p in Path(d).glob("*.ckpt")))
    for p in work:
        process_allgather(x)
""",
    ),
}


@pytest.mark.parametrize("rule_name", sorted(DC_FIXTURES))
def test_rule_fires_on_bad_snippet(rule_name):
    bad, _ = DC_FIXTURES[rule_name]
    result = analyze_source(bad)
    got = [(f.rule_id, f.rule) for f in result.findings]
    assert got == [(DC_RULES[rule_name].id, rule_name)], (
        f"{rule_name} must yield exactly one finding with exactly its "
        f"own id; got {got}"
    )


@pytest.mark.parametrize("rule_name", sorted(DC_FIXTURES))
def test_rule_quiet_on_clean_snippet(rule_name):
    _, good = DC_FIXTURES[rule_name]
    result = analyze_source(good)
    assert names(result) == [], (
        f"{rule_name} false-positives on its clean fixture: "
        f"{[f.message for f in result.unsuppressed]}"
    )


@pytest.mark.parametrize("rule_name", sorted(DC_FIXTURES))
def test_rule_suppressible_inline(rule_name):
    """Appending ``# distcheck: disable=<rule> -- why`` to the firing
    line silences it; the finding is still recorded with its
    justification."""
    bad, _ = DC_FIXTURES[rule_name]
    result = analyze_source(bad)
    target = next(f for f in result.findings if f.rule == rule_name)
    lines = bad.splitlines()
    lines[target.line - 1] += (
        f"  # distcheck: disable={rule_name} -- fixture-sanctioned"
    )
    suppressed = analyze_source("\n".join(lines))
    assert not any(
        f.rule == rule_name and f.line == target.line
        for f in suppressed.unsuppressed
    )
    rec = next(
        f for f in suppressed.findings
        if f.rule == rule_name and f.line == target.line
    )
    assert rec.suppressed and rec.justification == "fixture-sanctioned"


def test_every_catalog_rule_has_a_fixture():
    assert set(DC_FIXTURES) == set(DC_RULES), (
        "each DC rule ships with a true-positive + clean fixture pair"
    )


def test_catalog_ids_unique_and_documented():
    ids = [r.id for r in DC_RULES.values()]
    assert len(set(ids)) == len(ids)
    assert set(ids) == {f"DC{i:02d}" for i in range(1, 7)}
    readme = (REPO / "README.md").read_text(encoding="utf-8")
    for r in DC_RULES.values():
        assert r.id in readme and r.name in readme, (
            f"{r.id} ({r.name}) missing from the README catalog"
        )


# ---------------------------------------------------------------------------
# suppression / marker machinery — cross-tool isolation in every direction
# ---------------------------------------------------------------------------


def test_jaxlint_namespace_does_not_suppress_distcheck():
    bad, _ = DC_FIXTURES["unbroadcast-verdict"]
    result = analyze_source(bad)
    target = next(f for f in result.findings)
    lines = bad.splitlines()
    lines[target.line - 1] += (
        "  # jaxlint: disable=unbroadcast-verdict -- wrong namespace"
    )
    still = analyze_source("\n".join(lines))
    assert "unbroadcast-verdict" in names(still), (
        "a jaxlint: directive must never silence a distcheck finding"
    )


def test_concur_namespace_does_not_suppress_distcheck():
    bad, _ = DC_FIXTURES["rank-gated-collective"]
    result = analyze_source(bad)
    target = next(f for f in result.findings)
    lines = bad.splitlines()
    lines[target.line - 1] += (
        "  # concur: disable=rank-gated-collective -- wrong namespace"
    )
    still = analyze_source("\n".join(lines))
    assert "rank-gated-collective" in names(still)


def test_distcheck_namespace_does_not_suppress_jaxlint():
    from pyrecover_tpu.analysis import lint_source

    src = """
import jax

def sample(key):
    a = jax.random.normal(key, (2,))
    b = jax.random.uniform(key, (2,))  # distcheck: disable=prng-key-reuse -- wrong namespace
    return a, b
"""
    result = lint_source(src)
    assert "prng-key-reuse" in [f.rule for f in result.unsuppressed]


def test_distcheck_namespace_does_not_suppress_concur():
    from pyrecover_tpu.analysis.concur import analyze_source as concur_source

    src = """
import threading

_pending = []

def _train_impl():
    _pending.append(1)  # distcheck: disable=unguarded-shared-state -- wrong namespace

def _drain():
    while _pending:
        _pending.pop()

t = threading.Thread(target=_drain)
"""
    result = concur_source(src)
    assert "unguarded-shared-state" in [f.rule for f in result.unsuppressed]


def test_host_local_marker_taints_function_returns():
    """A function the linear analysis sees as congruent, declared
    host-local by marker, becomes a divergence source for DC01."""
    src = """
import jax

from mylib import sync_global_devices

_store = {}

def peek(key):  # distcheck: host-local
    return _store.get(key)

def maybe_sync(key):
    if peek(key) is not None:
        sync_global_devices("gated")
"""
    assert names(analyze_source(src)) == ["rank-gated-collective"]
    unmarked = src.replace("  # distcheck: host-local", "")
    assert names(analyze_source(unmarked)) == []


def test_congruent_marker_launders_env_read():
    """An env-reading function declared fleet-uniform stops tainting."""
    src = """
import os

from mylib import sync_global_devices

def device_kind():
    return os.environ.get("DEVICE_KIND", "")

def maybe_sync():
    if device_kind() == "tpu":
        sync_global_devices("tpu_only")
"""
    assert names(analyze_source(src)) == ["rank-gated-collective"]
    marked = src.replace(
        "def device_kind():",
        "def device_kind():  # distcheck: congruent",
    )
    assert names(analyze_source(marked)) == []


# ---------------------------------------------------------------------------
# model extraction
# ---------------------------------------------------------------------------


def _model(src, name="mod.py"):
    return DistModel(
        [ModuleInfo(name, src, relpath=name, tool="distcheck")],
        DistConfig(),
    )


def test_collective_attributed_three_calls_deep():
    """A collective buried three calls under a rank-gated branch is
    still attributed to the branch (the cross-module call-graph
    propagation the tentpole demands)."""
    src = """
import jax

from pyrecover_tpu.parallel.mesh import sync_global_devices

def _c():
    sync_global_devices("deep")

def _b():
    _c()

def _a():
    _b()

def entry():
    if jax.process_index() == 0:
        _a()
"""
    result = analyze_source(src)
    assert names(result) == ["rank-gated-collective"]
    (f,) = result.unsuppressed
    assert "sync_global_devices()" in f.message and "via _c" in f.message


def test_rank_compare_bound_to_name_is_rank_kind():
    """``is_host0 = jax.process_index() == 0`` then ``if is_host0:`` is
    the literal rank gate, not an unbroadcast verdict — and a collective
    under it still fires DC01."""
    src = """
import jax

from mylib import sync_global_devices

def save(write):
    is_host0 = jax.process_index() == 0
    if is_host0:
        write("x")
"""
    assert names(analyze_source(src)) == []
    bad = src.replace('write("x")', 'sync_global_devices("x")')
    assert names(analyze_source(bad)) == ["rank-gated-collective"]


def test_verdict_relaundering_by_reassignment():
    """``verdict = int(broadcast_host0_scalar(verdict))`` clears the
    taint; later control-flow uses are clean (the _resume discipline)."""
    src = """
import jax

from pyrecover_tpu.parallel.mesh import broadcast_host0_scalar

def walk(cands, check):
    for cand in cands:
        verdict = 1
        if jax.process_index() == 0:
            verdict = check(cand)
        verdict = int(broadcast_host0_scalar(verdict))
        if verdict == 0:
            continue
        return cand
    return None
"""
    assert names(analyze_source(src)) == []


def test_conditional_pod_reraise_counts_as_safe_handler():
    """A handler whose re-raise is gated on process_count() > 1 (the
    fixed _resume emergency handler) is not a swallow."""
    _, good = DC_FIXTURES["collective-under-swallowed-exception"]
    model = _model(good)
    fn = next(f for f in model.index.functions if f.name == "restore")
    assert model.reports[fn].swallow_trys == []


def test_raise_arm_is_loud_not_silent_divergence():
    """Per-host validation that RAISES (fail-loud) is sanctioned; the
    same shape with a silent ``return`` is the deadlock."""
    src = """
from pathlib import Path

from mylib import sync_global_devices

def check(d):
    if not Path(d).exists():
        raise NotADirectoryError(d)
    sync_global_devices("ok")
"""
    assert names(analyze_source(src)) == []
    silent = src.replace("raise NotADirectoryError(d)", "return None")
    assert names(analyze_source(silent)) == ["rank-gated-collective"]


def test_broadcast_subtree_is_laundered():
    """Divergent expressions wrapped in a broadcast helper are congruent
    — including the iterable of a collective-bearing loop."""
    _, good = DC_FIXTURES["local-state-collective-count"]
    assert names(analyze_source(good)) == []


def test_rank_gated_region_is_host_local_scope():
    """Inner divergent branches / swallowed exceptions inside a
    rank-gated region don't fire: the region runs on the deciding host
    only and rejoins at the verdict broadcast (the _resume host-0 gate
    shape)."""
    src = """
import os

import jax

from pyrecover_tpu.parallel.mesh import broadcast_host0_scalar

def gate(cand, precheck):
    verdict = 1
    if jax.process_index() == 0:
        try:
            ok = precheck(cand)
            if os.environ.get("STRICT") == "1" and not ok:
                verdict = 0
        except ValueError:
            verdict = 2
    return int(broadcast_host0_scalar(verdict))
"""
    assert names(analyze_source(src)) == []


# ---------------------------------------------------------------------------
# the shipped repo is clean
# ---------------------------------------------------------------------------


def test_repo_analyzes_clean_with_justified_suppressions():
    result = analyze_paths(GATE_PATHS)
    assert result.unsuppressed == [], (
        "distcheck findings in the shipped repo:\n"
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
    assert ("preempt.py", "DC01") in locs, (
        "the should_stop off-schedule early-return suppression is "
        "test-pinned; if the code was restructured, update this pin"
    )
    assert len(result.suppressed) <= 3, (
        f"suppression creep: {sorted(locs)} — every addition needs a "
        "justification AND a pin here"
    )


# ---------------------------------------------------------------------------
# CLI / report contracts
# ---------------------------------------------------------------------------


def test_json_report_shape():
    bad, _ = DC_FIXTURES["rank-gated-collective"]
    result = analyze_source(bad)
    doc = json.loads(render_json(result, strict=True, tool="distcheck"))
    assert doc["tool"] == "distcheck"
    assert doc["strict"] is True
    assert doc["summary"]["unsuppressed"] == 1
    (f,) = doc["findings"]
    assert f["rule_id"] == "DC01" and f["rule"] == "rank-gated-collective"


def test_cli_strict_gate(tmp_path):
    from pyrecover_tpu.analysis.distcheck.cli import main

    bad, _ = DC_FIXTURES["unbounded-distributed-blocking"]
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


def test_cli_strict_clean_on_repo_subprocess(tmp_path):
    """The exact format.sh invocation: exit 0 over the gated set."""
    report = tmp_path / "distcheck.json"
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "distcheck.py"),
         *GATE_PATHS, "--strict", "--json", str(report)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(report.read_text())
    assert doc["tool"] == "distcheck" and doc["summary"]["unsuppressed"] == 0


# ---------------------------------------------------------------------------
# collective_phase: the DC05 bound is real, not just a marker
# ---------------------------------------------------------------------------


@pytest.fixture()
def sink():
    s = telemetry.MemorySink()
    telemetry.add_sink(s)
    yield s
    telemetry.remove_sink(s)


def events(sink, name):
    return [e for e in sink.events if e["event"] == name]


def test_collective_phase_names_the_wait(sink):
    with telemetry.collective_phase("unit_phase", timeout_s=0):
        pass
    (begin,) = events(sink, "span_begin")
    assert begin["name"] == "collective_wait"
    assert begin["phase"] == "unit_phase"
    (end,) = events(sink, "span_end")
    assert end["dur_s"] >= 0
    assert not events(sink, "distributed_wait_timeout")


def test_collective_phase_timeout_fires_once_with_bundle(sink, tmp_path):
    telemetry.flight.install(tmp_path, config={})
    try:
        with telemetry.collective_phase("wedged_exchange", timeout_s=0.05):
            time.sleep(0.2)
        (ev,) = events(sink, "distributed_wait_timeout")
        assert ev["phase"] == "wedged_exchange"
        bundles = telemetry.flight.list_bundles(tmp_path)
        assert any("distributed_wait_timeout" in b.name for b in bundles)
    finally:
        telemetry.flight.uninstall()


def test_collective_phase_bounded_wait_never_fires(sink):
    with telemetry.collective_phase("fast", timeout_s=30.0):
        pass
    time.sleep(0.05)
    assert not events(sink, "distributed_wait_timeout")


def test_collective_phase_env_default(sink, monkeypatch):
    from pyrecover_tpu.telemetry import spans

    monkeypatch.setenv(spans.COLLECTIVE_TIMEOUT_ENV, "0.05")
    with telemetry.collective_phase("env_bounded"):
        time.sleep(0.2)
    assert events(sink, "distributed_wait_timeout")


# ---------------------------------------------------------------------------
# the fixed divergence hazards, regression-pinned (fake 2-host harness)
# ---------------------------------------------------------------------------


def _fake_pod(monkeypatch, *, index, count=2, host0_scalar=None,
              host0_obj=None, leaf_feed=None, calls=None):
    """Impersonate host ``index`` of a ``count``-host pod: rank/count
    patched, broadcast helpers replaced by a host-0 script, and the raw
    leaf exchange fed from ``leaf_feed`` (asserting the placeholder
    shapes peers must supply)."""
    from jax.experimental import multihost_utils

    from pyrecover_tpu.parallel import mesh

    calls = calls if calls is not None else []
    monkeypatch.setattr(jax, "process_count", lambda: count)
    monkeypatch.setattr(jax, "process_index", lambda: index)

    def fake_scalar(value):
        calls.append(("scalar", value))
        return host0_scalar if host0_scalar is not None else value

    def fake_obj(obj):
        calls.append(("obj", obj))
        return host0_obj if host0_obj is not None else obj

    def fake_leaf(src):
        calls.append(("leaf", np.asarray(src).shape))
        assert leaf_feed, "unexpected leaf exchange"
        out = leaf_feed.pop(0)
        src = np.asarray(src)
        assert src.shape == out.shape and src.dtype == out.dtype, (
            "peer placeholder must match the broadcast doc's shape/dtype"
        )
        return out

    monkeypatch.setattr(mesh, "broadcast_host0_scalar", fake_scalar)
    monkeypatch.setattr(mesh, "broadcast_host0_obj", fake_obj)
    monkeypatch.setattr(
        multihost_utils, "broadcast_one_to_all", fake_leaf
    )
    return calls


def _published_record(tmp_path, seed=7):
    """Publish a real zerostall snapshot single-process and hand back
    (exp_dir, the record host 0 would hold)."""
    from pyrecover_tpu.checkpoint import checkpoint_path, save_ckpt_zerostall
    from pyrecover_tpu.checkpoint.zerostall import emergency
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.models import ModelConfig
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.train_state import create_train_state

    optimizer, _ = build_optimizer(TrainConfig(sequence_length=32))
    state = create_train_state(
        jax.random.key(seed), ModelConfig().tiny(max_seq_len=32), optimizer
    )
    path = checkpoint_path(tmp_path, "exp", 3, engine="zerostall")
    save_ckpt_zerostall(
        path, state, {"consumed": 3}, background=False,
        extra_meta={"step": 3},
    )
    exp = path.parent
    step, record = emergency.peek(exp)
    assert step == 3
    return exp, record, state


@pytest.fixture(autouse=True)
def _clean_emergency():
    from pyrecover_tpu.checkpoint.zerostall import emergency

    emergency.drop()
    yield
    emergency.drop()


def test_peer_without_env_or_record_still_joins_exchange(
    tmp_path, monkeypatch
):
    """THE fixed deadlock: host 1 has no $PYRECOVER_EMERGENCY_PEER and
    no local record — the old per-host gate sent it home while host 0
    blocked in the leaf broadcast forever. With the host-0 verdict
    broadcast it participates, supplies doc-derived placeholders, and
    installs a verified, pod-usable record."""
    from pyrecover_tpu.checkpoint.zerostall import emergency
    from pyrecover_tpu.parallel.mesh import state_topology

    exp, record, state = _published_record(tmp_path)
    host0_doc = record["doc"]
    host0_leaves = [np.asarray(a) for a in record["leaves"]]
    emergency.drop()  # host 1 holds nothing
    monkeypatch.delenv(emergency.PEER_EXCHANGE_ENV, raising=False)

    calls = _fake_pod(
        monkeypatch, index=1, host0_scalar=1, host0_obj=host0_doc,
        leaf_feed=list(host0_leaves),
    )
    assert emergency.replicate_to_peers(exp) is True
    # verdict and doc broadcasts happened BEFORE any leaf moved
    kinds = [k for k, _ in calls]
    assert kinds[0] == "scalar" and kinds[1] == "obj"
    assert all(k == "leaf" for k in kinds[2:])
    assert len(kinds) == 2 + len(host0_leaves)

    step, got = emergency.peek(exp)
    assert step == 3 and got["peer_replicated"]
    ok, why = emergency.verify(got)
    assert ok, why  # digests recomputed over the received bytes match
    topo = dict(state_topology(state))
    topo["processes"] = 2
    got["doc"]["topology"]["processes"] = 2
    assert emergency.usable(exp, topo, min_step=3) is got


def test_host0_verdict_broadcast_precedes_payload(tmp_path, monkeypatch):
    """Host-0 side: env set, record held — the decision still goes
    through the broadcast before the payload legs, and the second call
    is a congruent no-op (peer_replicated)."""
    from pyrecover_tpu.checkpoint.zerostall import emergency

    exp, record, _ = _published_record(tmp_path)
    monkeypatch.setenv(emergency.PEER_EXCHANGE_ENV, "1")
    calls = _fake_pod(
        monkeypatch, index=0, host0_scalar=None, host0_obj=None,
        leaf_feed=[np.asarray(a) for a in record["leaves"]],
    )
    assert emergency.replicate_to_peers(exp) is True
    assert calls[0] == ("scalar", 1)
    # replicated record: a second exchange must decline via the SAME
    # congruent verdict broadcast (want=0 on every host)
    calls.clear()
    assert emergency.replicate_to_peers(exp) is False
    assert calls == [("scalar", 0)]


def test_exchange_declined_when_host0_says_no(tmp_path, monkeypatch):
    """No env opt-in on host 0: every host gets want=0 from the verdict
    broadcast and nobody touches the payload legs."""
    from pyrecover_tpu.checkpoint.zerostall import emergency

    exp, _, _ = _published_record(tmp_path)
    monkeypatch.delenv(emergency.PEER_EXCHANGE_ENV, raising=False)
    calls = _fake_pod(monkeypatch, index=0, leaf_feed=[])
    assert emergency.replicate_to_peers(exp) is False
    assert calls == [("scalar", 0)]


def test_resume_emergency_failure_raises_on_pod(tmp_path, monkeypatch):
    """A record that passes the host-0 gate but dies mid-restore must
    RAISE on a pod — the verdict already committed every host to the
    RAM path; privately rejoining the disk walk deadlocks its verdict
    broadcasts. Single-process keeps the loud disk fallback."""
    from pyrecover_tpu.checkpoint.zerostall import emergency
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.data import StatefulSampler
    from pyrecover_tpu.metrics import WallTimeTotals
    from pyrecover_tpu.parallel import mesh
    from pyrecover_tpu.train import _resume

    exp, record, state = _published_record(tmp_path)
    record["doc"]["topology"]["processes"] = 2
    record["peer_replicated"] = True

    config = TrainConfig(
        sequence_length=32, batch_size=8,
        resume_from_checkpoint="latest", checkpoint_engine="zerostall",
    )

    def boom(exp_dir, target_state):
        raise RuntimeError("mid-restore rot")

    monkeypatch.setattr(emergency, "restore", boom)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(mesh, "broadcast_host0_scalar", lambda v: v)
    monkeypatch.setattr(mesh, "broadcast_host0_obj", lambda v: v)
    with pytest.raises(RuntimeError, match="mid-restore rot"):
        _resume(
            config, exp, state, StatefulSampler(64, 8, seed=0), None,
            WallTimeTotals(),
        )


def test_resume_emergency_failure_falls_back_single_process(
    tmp_path, monkeypatch
):
    from pyrecover_tpu.checkpoint.zerostall import emergency
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.data import StatefulSampler
    from pyrecover_tpu.metrics import WallTimeTotals
    from pyrecover_tpu.train import _resume

    exp, record, state = _published_record(tmp_path)

    def boom(exp_dir, target_state):
        raise RuntimeError("mid-restore rot")

    monkeypatch.setattr(emergency, "restore", boom)
    config = TrainConfig(
        sequence_length=32, batch_size=8,
        resume_from_checkpoint="latest", checkpoint_engine="zerostall",
    )
    step, restored = _resume(
        config, exp, state, StatefulSampler(64, 8, seed=0), None,
        WallTimeTotals(),
    )
    assert step == 3  # the disk tier carried the resume


def test_broadcast_host0_obj_identity_single_process():
    from pyrecover_tpu.parallel.mesh import broadcast_host0_obj

    payload = ["ckpt_8.zs.json", "ckpt_4.zs.json"]
    assert broadcast_host0_obj(payload) == payload


def test_broadcast_host0_obj_two_leg_protocol(monkeypatch):
    """Peers learn the byte length first, then supply an exact-size
    placeholder: hosts need not agree on the payload size up front."""
    from jax.experimental import multihost_utils

    from pyrecover_tpu.parallel import mesh

    host0 = json.dumps(["a", "bb", "ccc"]).encode("utf-8")
    legs = []

    def fake_broadcast(arr):
        arr = np.asarray(arr)
        legs.append(arr.shape)
        if arr.ndim == 0:  # the length leg
            return np.asarray(len(host0), dtype=np.int64)
        assert arr.shape == (len(host0),), "placeholder must be exact-size"
        return np.frombuffer(host0, dtype=np.uint8)

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    monkeypatch.setattr(
        multihost_utils, "broadcast_one_to_all", fake_broadcast
    )
    assert mesh.broadcast_host0_obj(["stale", "local"]) == ["a", "bb", "ccc"]
    assert len(legs) == 2 and legs[0] == ()
