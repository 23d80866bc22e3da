"""concur: every CC rule fires on a known-bad fixture and stays quiet on
the clean twin; the guarded-by marker declares lock intent; suppression
namespaces are tool-isolated (a jaxlint disable can never silence a
concur finding); the shipped repo analyzes clean with every suppression
justified; the CLI keeps the jaxlint exit-code and JSON contracts — and
the CC05 fix is proven for real: background save handles join with
bounded timeouts, the vanilla verify thread never leaks on a failed
load, and a train() run with async saves loses no non-daemon checkpoint
work at exit (the ``ckpt_bg_join`` trail)."""

import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from pyrecover_tpu.analysis.concur import (
    CC_RULES,
    ConcurConfig,
    ConcurModel,
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

CC_FIXTURES = {
    "lock-order-inversion": (
        """
import threading

_a = threading.Lock()
_b = threading.Lock()

def _fwd():
    with _a:
        with _b:
            pass

def _rev():
    with _b:
        with _a:
            pass

t1 = threading.Thread(target=_fwd)
t2 = threading.Thread(target=_rev)
""",
        """
import threading

_a = threading.Lock()
_b = threading.Lock()

def _fwd():
    with _a:
        with _b:
            pass

def _rev():
    with _a:
        with _b:
            pass

t1 = threading.Thread(target=_fwd)
t2 = threading.Thread(target=_rev)
""",
    ),
    "blocking-under-lock": (
        """
import threading
import time

_lock = threading.Lock()

def _train_impl(state):
    with _lock:
        state += 1
    return state

def _flush():
    with _lock:
        time.sleep(1.0)

t = threading.Thread(target=_flush)
""",
        """
import threading
import time

_lock = threading.Lock()

def _train_impl(state):
    with _lock:
        state += 1
    return state

def _flush():
    with _lock:
        snapshot = 1
    time.sleep(1.0)
    return snapshot

t = threading.Thread(target=_flush)
""",
    ),
    "unguarded-shared-state": (
        """
import threading

_pending = []

def _train_impl():
    _pending.append(1)

def _drain():
    while _pending:
        _pending.pop()

t = threading.Thread(target=_drain)
""",
        """
import threading

_pending = []
_pending_lock = threading.Lock()

def _train_impl():
    with _pending_lock:
        _pending.append(1)

def _drain():
    while True:
        with _pending_lock:
            _pending.pop()

t = threading.Thread(target=_drain)
""",
    ),
    "signal-unsafe-call": (
        """
import signal

from pyrecover_tpu import telemetry

def handler(signum, frame):
    telemetry.emit("preempted", signum=signum)

signal.signal(signal.SIGTERM, handler)
""",
        """
import signal

_flag = {"seen": False}

def handler(signum, frame):
    _flag["seen"] = True

signal.signal(signal.SIGTERM, handler)
""",
    ),
    "daemon-durable-io": (
        """
import os
import threading

def _writer(path):
    with open(path + ".tmp", "wb") as f:
        f.write(b"x")
        os.fsync(f.fileno())
    os.replace(path + ".tmp", path)

def save(path):
    t = threading.Thread(target=_writer, args=(path,), daemon=True)
    t.start()
""",
        """
import os
import threading

def _writer(path):
    with open(path + ".tmp", "wb") as f:
        f.write(b"x")
        os.fsync(f.fileno())
    os.replace(path + ".tmp", path)

def save(path):
    t = threading.Thread(target=_writer, args=(path,), daemon=True)
    t.start()
    t.join()
""",
    ),
    "unpinned-collective": (
        """
import threading

from pyrecover_tpu.parallel.mesh import sync_global_devices

def _flush():
    sync_global_devices("bg_flush")

t = threading.Thread(target=_flush, daemon=True)
""",
        """
import threading

from pyrecover_tpu.parallel.mesh import sync_global_devices

def _flush():
    pass

def save():
    sync_global_devices("pre_handoff")
    t = threading.Thread(target=_flush, daemon=True)
    t.start()
    t.join()
""",
    ),
}


@pytest.mark.parametrize("rule_name", sorted(CC_FIXTURES))
def test_rule_fires_on_bad_snippet(rule_name):
    bad, _ = CC_FIXTURES[rule_name]
    result = analyze_source(bad)
    got = [(f.rule_id, f.rule) for f in result.findings]
    assert got == [(CC_RULES[rule_name].id, rule_name)], (
        f"{rule_name} must yield exactly one finding with exactly its "
        f"own id; got {got}"
    )


@pytest.mark.parametrize("rule_name", sorted(CC_FIXTURES))
def test_rule_quiet_on_clean_snippet(rule_name):
    _, good = CC_FIXTURES[rule_name]
    result = analyze_source(good)
    assert names(result) == [], (
        f"{rule_name} false-positives on its clean fixture: "
        f"{[f.message for f in result.unsuppressed]}"
    )


@pytest.mark.parametrize("rule_name", sorted(CC_FIXTURES))
def test_rule_suppressible_inline(rule_name):
    """Appending ``# concur: disable=<rule> -- why`` to the firing line
    silences it; the finding is still recorded with its justification."""
    bad, _ = CC_FIXTURES[rule_name]
    result = analyze_source(bad)
    target = next(f for f in result.findings if f.rule == rule_name)
    lines = bad.splitlines()
    lines[target.line - 1] += (
        f"  # concur: disable={rule_name} -- fixture-sanctioned"
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
    assert set(CC_FIXTURES) == set(CC_RULES), (
        "each CC rule ships with a true-positive + clean fixture pair"
    )


def test_catalog_ids_unique_and_documented():
    ids = [r.id for r in CC_RULES.values()]
    assert ids == sorted(ids) or len(set(ids)) == len(ids)
    assert set(ids) == {f"CC{i:02d}" for i in range(1, 7)}
    readme = (REPO / "README.md").read_text(encoding="utf-8")
    for r in CC_RULES.values():
        assert r.id in readme and r.name in readme, (
            f"{r.id} ({r.name}) missing from the README catalog"
        )


# ---------------------------------------------------------------------------
# suppression / marker machinery
# ---------------------------------------------------------------------------


def test_guarded_by_marker_declares_common_lock():
    """Both mutation sites declare the same (caller-held) lock: the CC03
    common-guard test accepts the declared intent."""
    bad, _ = CC_FIXTURES["unguarded-shared-state"]
    marked = bad.replace(
        "    _pending.append(1)",
        "    _pending.append(1)  # concur: guarded-by=_registry_lock",
    ).replace(
        "        _pending.pop()",
        "        _pending.pop()  # concur: guarded-by=_registry_lock",
    )
    assert names(analyze_source(marked)) == []


def test_guarded_by_on_def_line_covers_every_site():
    src = """
import threading

_seen = {}

def _train_impl(k):  # concur: guarded-by=_table_lock
    _seen[k] = 1

def _drain(k):  # concur: guarded-by=_table_lock
    _seen[k] = 0

t = threading.Thread(target=_drain)
"""
    assert names(analyze_source(src)) == []


def test_guarded_by_resolves_real_lock_by_suffix():
    """The marker value matches a discovered lock id by suffix; a
    declared lock that IS held at one site and marker-declared at the
    other counts as common."""
    src = """
import threading

_table_lock = threading.Lock()
_seen = {}

def _train_impl(k):
    with _table_lock:
        _seen[k] = 1

def _drain(k):
    _seen[k] = 0  # concur: guarded-by=_table_lock

t = threading.Thread(target=_drain)
"""
    assert names(analyze_source(src)) == []


def test_jaxlint_namespace_does_not_suppress_concur():
    bad, _ = CC_FIXTURES["unguarded-shared-state"]
    result = analyze_source(bad)
    target = next(f for f in result.findings)
    lines = bad.splitlines()
    lines[target.line - 1] += (
        "  # jaxlint: disable=unguarded-shared-state -- wrong namespace"
    )
    still = analyze_source("\n".join(lines))
    assert "unguarded-shared-state" in names(still), (
        "a jaxlint: directive must never silence a concur finding"
    )


def test_concur_namespace_does_not_suppress_jaxlint():
    from pyrecover_tpu.analysis import lint_source

    src = """
import jax

def sample(key):
    a = jax.random.normal(key, (2,))
    b = jax.random.uniform(key, (2,))  # concur: disable=prng-key-reuse -- wrong namespace
    return a, b
"""
    result = lint_source(src)
    assert "prng-key-reuse" in [f.rule for f in result.unsuppressed]


# ---------------------------------------------------------------------------
# model extraction
# ---------------------------------------------------------------------------


def _model(src, name="mod.py"):
    return ConcurModel(
        [ModuleInfo(name, src, relpath=name, tool="concur")], ConcurConfig()
    )


def test_thread_root_discovery_all_kinds():
    src = """
import atexit
import signal
import sys
import threading

def _worker():
    pass

def _handler(signum, frame):
    pass

def _hook(t, v, tb):
    pass

def _cleanup():
    pass

def main():
    t = threading.Thread(target=_worker, daemon=True)
    t.start()
    signal.signal(signal.SIGTERM, _handler)
    sys.excepthook = _hook
    atexit.register(_cleanup)
"""
    model = _model(src)
    by_kind = {r.kind: r for r in model.roots}
    assert set(by_kind) == {"main", "thread", "signal", "hook", "atexit"}
    assert by_kind["thread"].daemon
    assert by_kind["thread"].entries[0].name == "_worker"
    assert by_kind["signal"].entries[0].name == "_handler"
    assert by_kind["hook"].entries[0].name == "_hook"
    assert by_kind["atexit"].entries[0].name == "_cleanup"
    # the main root reaches the spawning function but NOT the thread
    # target (it belongs to its own root)
    main_names = {fn.name for fn in by_kind["main"].reach}
    assert "main" in main_names and "_worker" not in main_names


def test_lock_model_module_and_instance_level():
    src = """
import threading

_mod_lock = threading.RLock()

class Engine:
    def __init__(self):
        self._lock = threading.Lock()
"""
    model = _model(src)
    assert "mod._mod_lock" in model.locks
    assert "Engine._lock" in model.locks


def test_join_matching_is_class_scoped_for_self_attrs():
    """A ``self._thread`` binding demands a join in the SAME class — a
    different class joining its own ``_thread`` must not launder the
    leak (the maintenance-watcher-vs-loader shape)."""
    src = """
import os
import threading

class Leaky:
    def start(self):
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        os.replace("a.staged", "a")

class Clean:
    def start(self):
        self._thread = threading.Thread(target=self._run2, daemon=True)
        self._thread.start()

    def stop(self):
        self._thread.join(timeout=5)

    def _run2(self):
        os.replace("b.staged", "b")
"""
    result = analyze_source(src)
    cc05 = [f for f in result.findings if f.rule_id == "CC05"]
    assert len(cc05) == 1
    assert "Leaky._run" in cc05[0].message


def test_hot_loop_marker_seeds_main_root():
    src = """
import threading
import time

_lock = threading.Lock()

def poll(readings):  # jaxlint: hot-loop
    with _lock:
        return list(readings)

def _flush():
    with _lock:
        time.sleep(1.0)

t = threading.Thread(target=_flush)
"""
    assert names(analyze_source(src)) == ["blocking-under-lock"]


def test_acquire_release_pairs_bound_the_region():
    """A linear .acquire()/.release() pair closes the held region: the
    blocking call AFTER release() is clean."""
    src = """
import threading
import time

_lock = threading.Lock()

def _train_impl():
    _lock.acquire()
    x = 1
    _lock.release()
    return x

def _flush():
    _lock.acquire()
    x = 1
    _lock.release()
    time.sleep(1.0)
    return x

t = threading.Thread(target=_flush)
"""
    assert names(analyze_source(src)) == []


# ---------------------------------------------------------------------------
# the shipped repo is the ultimate fixture
# ---------------------------------------------------------------------------


def test_repo_analyzes_clean_with_justified_suppressions():
    """The exact surface format.sh gates: zero unsuppressed findings over
    the whole repo, and every suppression carries a justification."""
    result = analyze_paths(GATE_PATHS)
    offenders = [
        f"{f.location()} {f.rule}: {f.message}" for f in result.unsuppressed
    ]
    assert offenders == [], "\n".join(offenders)
    assert result.suppressed, (
        "the threaded stack carries deliberate, documented exceptions — "
        "an empty suppression set means the analyzer stopped seeing them"
    )
    for f in result.suppressed:
        assert f.justification, (
            f"{f.location()}: suppression without a justification"
        )


# ---------------------------------------------------------------------------
# reporters + CLI (the format.sh / CI surface)
# ---------------------------------------------------------------------------


def test_json_report_shape():
    bad = CC_FIXTURES["blocking-under-lock"][0]
    result = analyze_source(bad)
    doc = json.loads(render_json(result, strict=True, tool="concur"))
    assert doc["tool"] == "concur" and doc["strict"] is True
    assert doc["summary"]["unsuppressed"] == 1
    assert doc["summary"]["by_rule"]["blocking-under-lock"]["unsuppressed"] == 1
    f = doc["findings"][0]
    assert {"rule", "rule_id", "severity", "path", "line", "col",
            "message", "suppressed", "justification"} <= set(f)


def test_cli_strict_gate(tmp_path):
    from pyrecover_tpu.analysis.concur.cli import main

    bad = tmp_path / "bad.py"
    bad.write_text(CC_FIXTURES["daemon-durable-io"][0])
    json_out = tmp_path / "report.json"
    assert main([str(bad), "--strict", "--json", str(json_out)]) == 1
    doc = json.loads(json_out.read_text())
    assert doc["tool"] == "concur"
    assert doc["summary"]["unsuppressed"] >= 1
    assert main([str(bad)]) == 0  # report-only mode never gates
    assert main([str(bad), "--strict", "--ignore", "CC05"]) == 0
    assert main([str(tmp_path / "missing.py"), "--strict"]) == 2
    assert main(["--list-rules"]) == 0


def test_cli_strict_clean_on_repo_subprocess():
    """The exact invocation format.sh and the acceptance criteria run."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "concur.py"),
         *GATE_PATHS, "--strict"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


# ---------------------------------------------------------------------------
# the CC05 fix, for real: bounded joins + no lost non-daemon work at exit
# ---------------------------------------------------------------------------


def test_vanilla_handle_wait_timeout_is_bounded():
    from pyrecover_tpu.checkpoint.vanilla import VanillaSaveHandle

    release = threading.Event()
    t = threading.Thread(target=release.wait, args=(10,), daemon=True)
    t.start()
    handle = VanillaSaveHandle(t)
    with pytest.raises(TimeoutError):
        handle.wait(timeout=0.05)
    assert not handle.done
    release.set()
    handle.wait(timeout=5)  # completes once the writer finishes
    assert handle.done


def test_zerostall_handle_wait_timeout_is_bounded():
    from pyrecover_tpu.checkpoint.zerostall.snapshot import ZerostallSaveHandle

    release = threading.Event()
    t = threading.Thread(target=release.wait, args=(10,), daemon=True)
    t.start()
    handle = ZerostallSaveHandle()
    handle._thread = t
    with pytest.raises(TimeoutError):
        handle.wait(timeout=0.05)
    release.set()
    handle.wait(timeout=5)
    assert handle.done
    handle.error = RuntimeError("writer died")
    with pytest.raises(RuntimeError):
        handle.wait()


def test_load_vanilla_joins_verify_thread_on_decode_failure(tmp_path):
    """A truncated checkpoint makes the decode raise while the background
    verify thread is still checksumming — the thread must be joined on
    that path, not leaked once per rejected fallback candidate."""
    import jax

    from pyrecover_tpu.checkpoint import save_ckpt_vanilla, load_ckpt_vanilla
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.models import ModelConfig
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.train_state import create_train_state

    cfg = TrainConfig(sequence_length=32)
    model_cfg = ModelConfig().tiny(max_seq_len=32)
    optimizer, _ = build_optimizer(cfg)
    state = create_train_state(jax.random.key(0), model_cfg, optimizer)
    path = tmp_path / "ckpt_1.ckpt"
    save_ckpt_vanilla(path, state, verify=True)
    data = path.read_bytes()
    path.write_bytes(data[: len(data) // 2])  # torn mid-write

    before = {t.ident for t in threading.enumerate()}
    with pytest.raises(Exception):
        load_ckpt_vanilla(path, state, verify=True)
    # the verify thread was joined inside the failing load; give the
    # scheduler a beat, then require no surviving new thread
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        leaked = [
            t for t in threading.enumerate()
            if t.ident not in before and t.is_alive()
        ]
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, f"verify thread leaked: {leaked}"


def test_train_async_saves_join_with_bg_join_trail(tmp_path):
    """End-to-end regression for the CC05 satellite: a run with async
    background saves must join every writer before exit (``ckpt_bg_join``
    with completed/ok for each), and every checkpoint on disk — the final
    one included — must decode whole: no non-daemon work lost at exit."""
    from pyrecover_tpu import telemetry
    from pyrecover_tpu.checkpoint.registry import VANILLA_SUFFIX
    from pyrecover_tpu.checkpoint.vanilla import read_ckpt_raw
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.models import ModelConfig
    from pyrecover_tpu.train import train

    sink = telemetry.add_sink(telemetry.MemorySink())
    try:
        c = TrainConfig(
            sequence_length=32, batch_size=8, training_samples=64,
            training_steps=5, learning_rate=1e-3, seed=3,
            checkpoint_dir=str(tmp_path), checkpoint_frequency=2,
            experiment_name="exp", logging_frequency=2,
            async_checkpoint=True,
        )
        c.model = ModelConfig().tiny(max_seq_len=32, vocab_size=128)
        c.__post_init__()
        train(c)
    finally:
        telemetry.remove_sink(sink)

    joins = [e for e in sink.events if e["event"] == "ckpt_bg_join"]
    # both async periodic saves (steps 2 and 4) were joined before the
    # next save serialized behind them; the happy-path final save drains
    # the queue synchronously, so the bounded unwind join has nothing
    # left to do (its TimeoutError path is unit-tested on the handles)
    assert len(joins) >= 2, joins
    assert all(e["completed"] and e["ok"] for e in joins), joins

    ckpts = sorted((tmp_path / "exp").glob(f"ckpt_*{VANILLA_SUFFIX}"))
    assert ckpts, "periodic + final checkpoints must exist"
    for p in ckpts:
        meta, _, leaves = read_ckpt_raw(p)  # raises on a torn file
        assert len(leaves) == meta["num_leaves"]
