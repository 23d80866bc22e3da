"""Fault injection + hardened recovery tests (pyrecover_tpu/resilience).

Fast tier: the fault engine's plan parsing and per-fault semantics, the
transient-I/O retry path (``ckpt_io_retry`` telemetry against a REAL
vanilla save), corruption → precheck failure → quarantine, the loader
stall watchdog, retention's quarantine blindness, and signal escalation.

Slow tier: the full kill/corrupt/resume soak — ``tools/chaos.py --preset
smoke --seed 0`` must complete its kill/resume cycles with bit-exact
stitched-loss continuity against the uninterrupted golden run, the
injected ``corrupt_ckpt_bytes`` checkpoint quarantined, and resume falling
back to the previous good checkpoint.
"""

import errno
import json
import os
import signal

import numpy as np
import pytest

from pyrecover_tpu import telemetry
from pyrecover_tpu.resilience import faults
from pyrecover_tpu.resilience.quarantine import (
    QUARANTINE_DIRNAME,
    list_quarantined,
    quarantine_checkpoint,
)
from pyrecover_tpu.resilience.retry import io_retry


@pytest.fixture()
def mem_sink():
    sink = telemetry.add_sink(telemetry.MemorySink())
    yield sink
    telemetry.remove_sink(sink)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def events(sink, name):
    return [e for e in sink.events if e["event"] == name]


def tiny_state():
    return {"a": np.arange(64, dtype=np.float32),
            "b": np.ones((4, 4), np.float32)}


# ---- fault plan parsing -----------------------------------------------------

def test_plan_from_env_inline_and_file(tmp_path, monkeypatch):
    plan = {"seed": 7, "faults": [{"type": "loader_stall", "seconds": 1}]}
    monkeypatch.setenv(faults.PLAN_ENV, json.dumps(plan))
    assert faults.load_env_plan() == plan
    f = tmp_path / "plan.json"
    f.write_text(json.dumps(plan))
    monkeypatch.setenv(faults.PLAN_ENV, str(f))
    assert faults.load_env_plan() == plan
    monkeypatch.delenv(faults.PLAN_ENV)
    assert faults.load_env_plan() is None


def test_unknown_fault_type_fails_loudly():
    with pytest.raises(faults.FaultPlanError, match="unknown fault type"):
        faults.install({"faults": [{"type": "meteor_strike"}]})


def test_malformed_env_plan_raises(monkeypatch):
    monkeypatch.setenv(faults.PLAN_ENV, "{not json")
    with pytest.raises(faults.FaultPlanError):
        faults.load_env_plan()


def test_seams_are_noops_without_plan():
    faults.clear()
    assert faults.active() is None
    faults.check("ckpt_write", path="x", written=0)  # must not raise
    faults.check("train_step", step=1)


def test_install_and_clear_rebind_check(mem_sink):
    engine = faults.install(
        {"faults": [{"type": "transient_io_error", "fail_count": 1}]}
    )
    assert faults.active() is engine
    with pytest.raises(OSError) as ei:
        faults.check("ckpt_write", path="x", written=0)
    assert ei.value.errno == errno.EIO
    faults.clear()
    faults.check("ckpt_write", path="x", written=0)  # healed by clear


# ---- transient_io_error + retry path ---------------------------------------

def test_io_retry_backoff_and_telemetry(mem_sink):
    delays = []
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 4:
            raise OSError(errno.EIO, "blip")
        return "done"

    out = io_retry(flaky, op="write", path="p", attempts=5,
                   base_delay_s=0.1, max_delay_s=0.3, sleep=delays.append)
    assert out == "done" and len(calls) == 4
    retries = events(mem_sink, "ckpt_io_retry")
    assert [e["attempt"] for e in retries] == [1, 2, 3]
    # capped exponential backoff, jittered by a factor in [0.5, 1.5)
    for delay, nominal in zip(delays, (0.1, 0.2, 0.3)):
        assert 0.5 * nominal <= delay < 1.5 * nominal


def test_io_retry_gives_up_after_attempts():
    def always_eio():
        raise OSError(errno.EIO, "x")

    with pytest.raises(OSError):
        io_retry(always_eio, op="write", attempts=2, sleep=lambda s: None)


def test_io_retry_permanent_errors_propagate_immediately(mem_sink):
    calls = []

    def nospace():
        calls.append(1)
        raise OSError(errno.ENOSPC, "disk full")

    with pytest.raises(OSError):
        io_retry(nospace, op="write", attempts=5, sleep=lambda s: None)
    assert len(calls) == 1  # no retry can conjure disk space
    assert not events(mem_sink, "ckpt_io_retry")


def test_transient_io_error_absorbed_by_real_save(tmp_path, mem_sink):
    """The acceptance path: injected transient_io_error faults are absorbed
    by the retry/backoff around a REAL vanilla checkpoint write, with
    ckpt_io_retry telemetry emitted and the checkpoint intact."""
    from pyrecover_tpu.checkpoint.vanilla import (
        load_ckpt_vanilla,
        precheck_ckpt_vanilla,
        save_ckpt_vanilla,
    )

    faults.install({"seed": 0, "faults": [
        {"type": "transient_io_error", "op": "write", "fail_count": 2},
        {"type": "transient_io_error", "op": "rename", "fail_count": 1},
    ]})
    path = tmp_path / "ckpt_1.ckpt"
    state = tiny_state()
    save_ckpt_vanilla(path, state, verify=True)
    retries = events(mem_sink, "ckpt_io_retry")
    assert {e["op"] for e in retries} == {"write", "rename"}
    assert len([e for e in retries if e["op"] == "write"]) == 2
    ok, reason = precheck_ckpt_vanilla(path, verify=True)
    assert ok, reason
    restored, _, _ = load_ckpt_vanilla(path, state, verify=True)
    np.testing.assert_array_equal(restored["a"], state["a"])


# ---- corrupt_ckpt_bytes + quarantine ---------------------------------------

def test_corrupt_ckpt_bytes_then_quarantine(tmp_path, mem_sink):
    from pyrecover_tpu.checkpoint.registry import list_checkpoints
    from pyrecover_tpu.checkpoint.vanilla import (
        precheck_ckpt_vanilla,
        save_ckpt_vanilla,
    )

    faults.install({"faults": [
        {"type": "corrupt_ckpt_bytes", "count": 32},
    ]})
    path = tmp_path / "ckpt_2.ckpt"
    save_ckpt_vanilla(path, tiny_state(), verify=True)
    ok, reason = precheck_ckpt_vanilla(path, verify=True)
    assert not ok and "checksum" in reason

    dest = quarantine_checkpoint(path, reason=reason)
    assert dest is not None and dest.parent.name == QUARANTINE_DIRNAME
    assert not path.exists()
    # the checksum sidecar travels with the corpse
    assert (dest.parent / (dest.name + ".sha256")).exists()
    q = events(mem_sink, "ckpt_quarantined")
    assert len(q) == 1 and q[0]["reason"] == reason
    assert list_quarantined(tmp_path) == [dest]
    # quarantined entries are invisible to checkpoint discovery
    assert list_checkpoints(tmp_path) == []


def test_quarantine_name_collisions_never_overwrite(tmp_path):
    for _ in range(3):
        p = tmp_path / "ckpt_5.ckpt"
        p.write_bytes(b"corpse")
        assert quarantine_checkpoint(p) is not None
    assert len(list_quarantined(tmp_path)) == 3


def test_quarantine_missing_path_is_noop(tmp_path):
    assert quarantine_checkpoint(tmp_path / "ckpt_9.ckpt") is None


def test_prune_never_counts_or_deletes_quarantined(tmp_path, mem_sink):
    from pyrecover_tpu.checkpoint.registry import prune_checkpoints

    for step in (1, 2, 3, 4):
        (tmp_path / f"ckpt_{step}.ckpt").write_bytes(b"x")
    quarantine_checkpoint(tmp_path / "ckpt_1.ckpt")
    # 3 live entries + 1 quarantined: max_keep=2 must delete exactly the
    # oldest LIVE one and leave the quarantine dir untouched
    doomed = prune_checkpoints(tmp_path, 2, engine="vanilla")
    assert [p.name for p in doomed] == ["ckpt_2.ckpt"]
    assert len(list_quarantined(tmp_path)) == 1
    pruned = events(mem_sink, "ckpt_pruned")
    assert len(pruned) == 1
    assert pruned[0]["path"] == "ckpt_2.ckpt" and pruned[0]["step"] == 2


# ---- loader stall watchdog --------------------------------------------------

def test_loader_stall_watchdog_raises_typed_error(mem_sink):
    from pyrecover_tpu.data import DataLoader, LoaderStallError, StatefulSampler
    from pyrecover_tpu.data.synthetic import SyntheticTextDataset

    faults.install({"faults": [
        {"type": "loader_stall", "seconds": 30.0, "batch": 1},
    ]})
    ds = SyntheticTextDataset(num_samples=8, seq_len=8, vocab_size=32, seed=0)
    sampler = StatefulSampler(dataset_len=8, global_batch_size=4, seed=0)
    loader = DataLoader(ds, sampler, pad_token_id=0, mesh=None,
                        prefetch=2, num_workers=1, stall_timeout=0.3)
    try:
        with pytest.raises(LoaderStallError, match="no batch"):
            next(loader)
    finally:
        faults.clear()  # unwedge the producer before stopping it
        loader.stop()
    stalls = events(mem_sink, "loader_stall_timeout")
    assert len(stalls) == 1 and stalls[0]["timeout_s"] == 0.3


def test_loader_without_watchdog_still_blocks_and_serves():
    from pyrecover_tpu.data import DataLoader, StatefulSampler
    from pyrecover_tpu.data.synthetic import SyntheticTextDataset

    ds = SyntheticTextDataset(num_samples=8, seq_len=8, vocab_size=32, seed=0)
    sampler = StatefulSampler(dataset_len=8, global_batch_size=4, seed=0)
    loader = DataLoader(ds, sampler, pad_token_id=0, mesh=None,
                        prefetch=2, num_workers=1)
    try:
        _, batch = next(loader)
        assert batch["inputs"].shape[0] == 4
    finally:
        loader.stop()


# ---- signal escalation ------------------------------------------------------

def test_second_signal_during_save_escalates(tmp_path, mem_sink):
    from pyrecover_tpu.preempt import REQUEUE_MARKER, PreemptionWatcher
    from pyrecover_tpu.telemetry import flight

    flight.install(tmp_path, enable_faulthandler=False)
    w = PreemptionWatcher(enabled=True, job_end_time=None)
    w.install_signal_handler()
    exits = []
    w._exit_fn = exits.append
    try:
        os.kill(os.getpid(), signal.SIGUSR1)
        assert w.signal_count == 1 and not exits  # first: deferred exit
        w.arm_escalation(tmp_path, step=42)
        os.kill(os.getpid(), signal.SIGUSR1)
        assert exits == [75]  # second, mid-save: immediate requeue + exit
        marker = json.loads((tmp_path / REQUEUE_MARKER).read_text())
        assert marker["step"] == 42 and marker["done"] is False
        esc = events(mem_sink, "preempt_signal_escalation")
        assert len(esc) == 1 and esc[0]["count"] == 2
        # the escalation's last act is a black-box bundle: os._exit skips
        # every other teardown, so this is the postmortem's only record
        bundles = flight.list_bundles(tmp_path)
        assert len(bundles) == 1
        manifest = json.loads((bundles[0] / "MANIFEST.json").read_text())
        assert manifest["reason"] == "preempt_escalation"
        assert manifest["escalation_step"] == 42
    finally:
        flight.uninstall()
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        signal.signal(signal.SIGUSR1, signal.SIG_DFL)


def test_second_signal_outside_save_does_not_escalate():
    from pyrecover_tpu.preempt import PreemptionWatcher

    w = PreemptionWatcher(enabled=True, job_end_time=None)
    w.install_signal_handler()
    exits = []
    w._exit_fn = exits.append
    try:
        os.kill(os.getpid(), signal.SIGUSR1)
        os.kill(os.getpid(), signal.SIGUSR1)
        assert w.signal_count == 2 and not exits  # not armed: no escalation
        w.arm_escalation("/tmp", 1)
        w.disarm_escalation()
        os.kill(os.getpid(), signal.SIGUSR1)
        assert not exits  # disarmed again
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        signal.signal(signal.SIGUSR1, signal.SIG_DFL)


def test_install_signal_handler_is_idempotent():
    from pyrecover_tpu.preempt import PreemptionWatcher

    w = PreemptionWatcher(enabled=True, job_end_time=None)
    try:
        w.install_signal_handler().install_signal_handler()
        os.kill(os.getpid(), signal.SIGUSR1)
        assert w.signal_count == 1  # one handler, one count
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        signal.signal(signal.SIGUSR1, signal.SIG_DFL)


# ---- save-index bookkeeping -------------------------------------------------

def test_save_index_counts_both_engines(tmp_path):
    engine = faults.install({"faults": []})
    from pyrecover_tpu.checkpoint.vanilla import save_ckpt_vanilla

    save_ckpt_vanilla(tmp_path / "ckpt_1.ckpt", tiny_state())
    save_ckpt_vanilla(tmp_path / "ckpt_2.ckpt", tiny_state())
    assert engine.save_index == 2


def test_kill9_waits_for_its_save_index(tmp_path):
    """A kill9 aimed at save #3 must not fire during saves 1-2 (firing is
    SIGKILL, so reaching this assert at all IS the test)."""
    from pyrecover_tpu.checkpoint.vanilla import save_ckpt_vanilla

    engine = faults.install({"faults": [
        {"type": "kill9_during_save", "save_index": 3},
    ]})
    save_ckpt_vanilla(tmp_path / "ckpt_1.ckpt", tiny_state())
    save_ckpt_vanilla(tmp_path / "ckpt_2.ckpt", tiny_state())
    assert engine.save_index == 2 and engine.faults[0].fired == 0


# ---- the soak proof (slow tier) --------------------------------------------

@pytest.mark.slow
def test_chaos_smoke_soak_bitexact(tmp_path):
    """ISSUE 4 acceptance: `tools/chaos.py --preset smoke --seed 0`
    completes its kill/resume cycles with bit-exact stitched-loss
    continuity vs the uninterrupted golden run; the injected
    corrupt_ckpt_bytes checkpoint is quarantined while resume falls back
    to the previous good checkpoint; transient_io_error faults are
    absorbed with ckpt_io_retry telemetry."""
    from pyrecover_tpu.resilience.chaos import run_soak

    report = run_soak(
        "smoke", seed=0, workdir=tmp_path / "chaos",
        json_out=tmp_path / "report.json",
    )
    assert report["ok"], report["violations"]
    assert report["kill_resume_cycles"] >= 2
    assert report["continuity_ok"] and report["first_divergence"] is None
    s2 = report["schedule"]["sigterm_step_2"]
    assert len(report["quarantined"]) == 1
    assert report["quarantined"][0].startswith(f"ckpt_{s2}_final")
    counts = report["telemetry_counts"]
    assert counts["ckpt_io_retry"] >= 2
    assert counts["ckpt_quarantined"] == 1
    assert counts["fault_injected"] >= 4
    # the recovery run fell back: precheck failure recorded, then a resume
    assert counts["ckpt_precheck_failed"] >= 1 and counts["resume"] >= 2
    # ISSUE 6 hang drill: the watchdog fired under the seeded loader
    # stall, a postmortem bundle landed, and doctor read the artifacts as
    # a hang wedged in the loader_wait phase
    assert report["hang"]["hang_detected"] >= 1
    assert report["hang"]["bundles"]
    assert report["hang"]["doctor_classification"] == "hang"
    assert report["hang"]["doctor_phase"] == "loader_wait"
    # ISSUE 7 elastic_shrink drill: kill at 4 devices → resume at 2 → grow
    # back to 4, loss-continuity gated (bit-exact before the shrink,
    # tolerance-aware after) with the elastic_resume telemetry present
    el = report["elastic"]
    assert (4, 2) in el["transitions"] and (2, 4) in el["transitions"]
    assert el["bitexact_rows"] >= 1
    assert el["max_rel_diff"] <= el["rtol"]
    assert el["doctor_classification"] == "healthy"
    # ISSUE 10 zero1 flag-flip drill: a zero1 run killed mid-training
    # resumes with --optimizer-sharding none and the stitched CSV stays
    # BIT-EXACT vs the zero1 golden (the convergence-parity contract),
    # with the spec-drifted checkpoint restored — never quarantined
    z1 = report["zero1"]
    assert z1["continuity_ok"] and z1["bitexact"]
    assert z1["resumes"] >= 1
    assert z1["quarantined"] == []
    # ISSUE 11 bucket flag-flip drills: a bucketed-int8 run killed
    # mid-training resumes with buckets off (bit-exact to the flip,
    # tolerance after — re-blocked quantization groups), and a bucketed
    # fp32 run resumes with a DIFFERENT bucket cap BIT-EXACTLY
    # (per-bucket psums are exact sums); neither flip quarantines
    bk = report["bucket"]
    assert bk["int8"]["bitexact_rows"] >= 1
    assert bk["int8"]["max_rel_diff"] <= bk["int8"]["rtol"]
    assert bk["int8"]["quarantined"] == []
    assert bk["int8"]["grad_bucket_events"] >= 1
    assert bk["fp32_layout_flip"]["bitexact"]
    assert bk["fp32_layout_flip"]["continuity_ok"]
    assert bk["fp32_layout_flip"]["quarantined"] == []
    # ISSUE 14 autopilot drill: seeded hazard-rate kills with a mid-run
    # rate shift under --checkpoint-frequency auto — the adapted interval
    # lands within 2x of the analytic Young-Daly optimum on both sides of
    # the shift, the ckpt_policy trail survives every kill/resume via the
    # failure-history sidecar (which counts exactly the observed kills),
    # and the zero-failure golden run holds the bounded prior
    ap = report["autopilot"]
    assert ap["kills"] >= 2
    assert ap["sidecar_interruptions"] == ["hard_kill"] * ap["kills"]
    assert ap["segments_with_decisions"] >= ap["kills"] + 1
    for side in ("pre_shift", "post_shift"):
        assert ap[side] is not None
        assert 0.5 <= ap[side]["ratio"] <= 2.0
    assert ap["quarantined"] == []
    from pyrecover_tpu.resilience.chaos import AP_CEILING

    assert ap["golden_intervals"] == [AP_CEILING]
    assert (tmp_path / "report.json").exists()


# ---- site registry validation + the retry-path seams ------------------------

def test_fault_sites_registry_shape():
    """Every declarative FAULT_SITES entry documents its owner, kind,
    and the drill that fires it — the contract faultcheck FT03/FT04
    cross-check statically."""
    assert faults.FAULT_SITES
    for site, meta in faults.FAULT_SITES.items():
        assert {"module", "kind", "drill"} <= set(meta), site


def test_plan_spec_unknown_site_fails_naming_known_sites():
    with pytest.raises(faults.FaultPlanError, match="unknown site") as ei:
        faults.install({"faults": [
            {"type": "transient_io_error", "site": "ckpt_nope"},
        ]})
    # the error teaches the registry instead of silently never firing
    assert "ckpt_write" in str(ei.value)


def test_live_seam_unknown_site_fails_loudly():
    """A seam naming an unregistered site could never match any plan —
    with an engine active it must fail the run, not silently skip
    injection."""
    faults.install({"faults": [{"type": "loader_stall", "seconds": 1}]})
    with pytest.raises(faults.FaultPlanError, match="unknown site"):
        faults.check("definitely_not_a_site")
    faults.check("train_step", step=1)  # registered sites still flow


def test_transient_fsync_and_read_heal_via_retry(tmp_path, mem_sink):
    """The two retry-path seams the site registry documents but no test
    drilled: an EIO at ckpt_fsync during a real vanilla save and at
    ckpt_read during the load-back are both absorbed by io_retry."""
    from pyrecover_tpu.checkpoint.vanilla import (
        load_ckpt_vanilla,
        save_ckpt_vanilla,
    )

    faults.install({"faults": [
        {"type": "transient_io_error", "op": "fsync", "fail_count": 1},
        {"type": "transient_io_error", "op": "read", "fail_count": 1},
    ]})
    path = tmp_path / "ckpt_1.ckpt"
    state = tiny_state()
    save_ckpt_vanilla(path, state, verify=True)
    restored, _, _ = load_ckpt_vanilla(path, state, verify=True)
    np.testing.assert_array_equal(restored["a"], state["a"])
    retries = events(mem_sink, "ckpt_io_retry")
    assert {e["op"] for e in retries} >= {"fsync", "read"}
