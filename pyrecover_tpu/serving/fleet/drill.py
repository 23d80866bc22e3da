"""Fleet proof harness: replica-loss chaos drill + canary-rollback drill.

Two gates, both wired into ``format.sh`` through
``tools/bench_decode.py --fleet-smoke``:

  * :func:`fleet_chaos_drill` — ≥2 replica subprocesses behind the
    front door under seeded open-loop load. One replica is SIGKILLed
    mid-flight through the ``replica_kill`` fault seam (rc −9,
    announce-then-kill trail in its telemetry shard) while the parent
    injects a transient I/O error into the router's ``router_redrive``
    seam. Verdicts: the multi-target workload split reassembles into
    the single-stream Poisson process exactly; accounting is exact
    (``submitted == done + shed``, zero silent losses) with ≥1 request
    explicitly redriven and every result bit-identical to the no-kill
    baseline run; the kill-window fleet p99 stays within
    ``P99_FACTOR · baseline_p99 + P99_SLACK_S`` of the no-kill
    baseline; the supervisor respawns the killed replica and the
    respawn serves the cold-restore probe tokens; admission under
    zeroed capacity sheds loudly (``fleet_shed`` per request, counted,
    never silent); a crash-looping replica (no checkpoint → rc 2) is
    quarantined after exactly ``quarantine_after`` spawns instead of
    being restarted forever. The tracing plane is gated here too:
    every completed request must assemble (via
    :mod:`pyrecover_tpu.telemetry.traceassembly`) into exactly one
    rooted skew-corrected trace with zero orphan spans, the redriven
    request's trace must link BOTH attempts under one root with the
    kill hole attributed to ``redrive_gap``, and the critical-path
    buckets must sum to e2e inside the named residual tolerance.
    Per-replica telemetry shards are merged
    (tagged by replica) with the parent's fleet events into one
    ``fleet_telemetry.jsonl`` for the summarizer, and the per-replica
    metrics exporters are scraped into one FleetAggregator snapshot.
  * :func:`canary_rollout_drill` — three manifests: old (serving),
    healthy (the true next release), divergent (wrong weights claiming
    the same release). Rolling the divergent manifest canaries it on
    one replica, fails the token-equality gate, auto-rolls-back, and
    leaves EVERY replica pinned on the old manifest serving
    bit-identical probe tokens to a cold restore of it — with the pin
    lease still live and the non-canary replica never having left the
    old step. Rolling the healthy manifest passes the canary gate and
    waves to all replicas with zero swap rejections.

The replica subprocess entry lives in :mod:`replica`
(``python -m pyrecover_tpu.serving.fleet.replica``).
"""

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

from pyrecover_tpu import telemetry
from pyrecover_tpu.resilience import faults
from pyrecover_tpu.telemetry import traceassembly, tracing
from pyrecover_tpu.serving.fleet.router import FleetRouter
from pyrecover_tpu.serving.fleet.supervisor import (
    QUARANTINED,
    READY,
    ReplicaSupervisor,
)
from pyrecover_tpu.serving.fleet.rollout import _p99, canary_rollout
from pyrecover_tpu.serving.hotswap.drill import (
    P99_FACTOR,
    P99_SLACK_S,
    _drill_model_config,
    _probe_workload,
    _run_probe,
    _save_zs,
    _scan_status,
    _serving_config,
    _train_state,
)
from pyrecover_tpu.serving.loadgen import open_loop_workload, request_id

_READY_TIMEOUT_S = 180.0


# ---- replica process plumbing ----------------------------------------------


def _replica_cmd(exp, status, telem, *, replica_id, probe_seed,
                 manifest=None):
    cmd = [
        sys.executable, "-m", "pyrecover_tpu.serving.fleet.replica",
        "--exp", str(exp), "--status", str(status),
        "--telemetry", str(telem), "--replica-id", str(replica_id),
        "--probe-seed", str(probe_seed),
    ]
    if manifest is not None:
        cmd += ["--manifest", str(manifest)]
    return cmd


def _spawn_replica(exp, status, telem, *, fault_plan=None, **kw):  # jaxlint: host-only
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if fault_plan is not None:
        env["PYRECOVER_FAULT_PLAN"] = json.dumps(fault_plan)
    else:
        env.pop("PYRECOVER_FAULT_PLAN", None)
    return subprocess.Popen(
        _replica_cmd(exp, status, telem, **kw), env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
    )


class _Fleet:
    """Drill-side wiring: a supervisor spawning real replica
    subprocesses, readiness via each incarnation's status JSONL, and a
    router that attaches each replica as it reports ready."""

    def __init__(self, exp, workdir, n_replicas, *, seed=0,
                 fault_plans=None, manifest=None, backoff_base_s=0.1,
                 backoff_max_s=1.0, quarantine_after=3, max_inflight=8,
                 max_queue=256, trace_epoch=""):
        self.exp = Path(exp)
        self.workdir = Path(workdir)
        self.workdir.mkdir(parents=True, exist_ok=True)
        self.n_replicas = n_replicas
        self.seed = seed
        self.manifest = manifest
        self.fault_plans = dict(fault_plans or {})
        self.shards = {
            slot: self.workdir / f"replica_{slot}_telemetry.jsonl"
            for slot in range(n_replicas)
        }
        # guards procs/status/ready_info (monitor thread + drill main)
        self._plock = threading.Lock()
        self.procs = {}       # (slot, incarnation) -> Popen
        self.status = {}      # (slot, incarnation) -> status path
        self.ready_info = {}  # slot -> latest ready record
        self.router = FleetRouter(
            max_inflight=max_inflight, max_queue=max_queue,
            trace_epoch=trace_epoch)
        self.sup = ReplicaSupervisor(
            n_replicas, self._spawn, self._ready_check,
            on_ready=self._on_ready, backoff_base_s=backoff_base_s,
            backoff_max_s=backoff_max_s, quarantine_after=quarantine_after,
        )

    def _spawn(self, slot, incarnation):  # jaxlint: host-only
        status = self.workdir / f"replica_{slot}_{incarnation}.status.jsonl"
        plan = self.fault_plans.get((slot, incarnation))
        proc = _spawn_replica(
            self.exp, status, self.shards[slot], replica_id=slot,
            probe_seed=self.seed, manifest=self.manifest, fault_plan=plan,
        )
        with self._plock:
            self.procs[(slot, incarnation)] = proc
            self.status[(slot, incarnation)] = status
        return proc

    def _ready_check(self, slot, incarnation, proc):  # jaxlint: host-only
        with self._plock:
            status = self.status[(slot, incarnation)]
        return _scan_status(status, "ready")

    def _on_ready(self, slot, info):  # jaxlint: host-only
        with self._plock:
            self.ready_info[slot] = dict(info)
        self.router.connect(slot, "127.0.0.1", info["port"])

    def proc(self, slot, incarnation):
        with self._plock:
            return self.procs[(slot, incarnation)]

    def metrics_targets(self):
        with self._plock:
            return [
                f"127.0.0.1:{info['metrics_port']}"
                for _, info in sorted(self.ready_info.items())
            ]

    def start(self, *, timeout_s=_READY_TIMEOUT_S):  # jaxlint: host-only
        self.sup.start()
        self.wait_ready(timeout_s=timeout_s)

    def wait_ready(self, slots=None, *, timeout_s=_READY_TIMEOUT_S):  # jaxlint: host-only
        slots = list(range(self.n_replicas)) if slots is None else slots
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            states = self.sup.states()
            if all(states[s] == READY for s in slots):
                return
            time.sleep(0.05)
        raise TimeoutError(
            f"fleet drill: replicas not ready within {timeout_s}s "
            f"(states {self.sup.states()})"
        )

    def probe(self, slot, *, timeout_s=120.0):  # jaxlint: host-only
        return self.router.request(
            slot, {"type": "probe", "seed": self.seed}, "probe_result",
            timeout_s=timeout_s,
        )

    def status_of(self, slot, *, timeout_s=60.0):  # jaxlint: host-only
        return self.router.request(
            slot, {"type": "status"}, "status_result", timeout_s=timeout_s,
        )

    def stop(self):  # jaxlint: host-only
        self.router.close()
        self.sup.stop()


def _run_open_loop(router, workload, *, timeout_s=120.0):  # jaxlint: host-only
    """Drive the seeded arrival process through the front door and
    drain. Returns the router's accounting after drain."""
    t0 = time.monotonic()
    for req in workload:
        delay = req["arrival_s"] - (time.monotonic() - t0)
        if delay > 0:
            time.sleep(delay)
        router.submit({
            "rid": req["rid"], "prompt": req["prompt"],
            "max_new_tokens": req["max_new_tokens"],
        })
    router.drain(timeout_s)
    return router.accounting()


def _cold_probe(manifest, seed):  # jaxlint: host-only
    """Ground truth: restore the manifest cold in-parent and serve the
    probe through a fresh engine."""
    from pyrecover_tpu.serving.engine import ServingEngine
    from pyrecover_tpu.serving.restore import load_serving_params

    cfg = _drill_model_config()
    params, _ = load_serving_params(Path(manifest), cfg)
    engine = ServingEngine(params, cfg, _serving_config())
    return _run_probe(engine, _probe_workload(seed))


def _merge_shards(out_path, parent_jsonl, shards):  # jaxlint: host-only
    """Merge the parent's fleet events with every replica's telemetry
    shard (tagged ``replica=<slot>``) into one JSONL for the
    summarizer."""
    lines = []
    if Path(parent_jsonl).exists():
        for e in telemetry.read_events(parent_jsonl):
            lines.append(json.dumps(e))
    for slot, shard in sorted(shards.items()):
        if not Path(shard).exists():
            continue
        for e in telemetry.read_events(shard):
            e.setdefault("replica", slot)
            lines.append(json.dumps(e))
    # jaxlint: disable-next=torn-write -- post-hoc report artifact for the
    # summarizer, rebuilt from the per-replica shards on every drill run
    Path(out_path).write_text("\n".join(lines) + "\n")
    return len(lines)


# ---- replica-loss chaos drill ----------------------------------------------


def fleet_chaos_drill(workdir, *, n_replicas=2, seed=0, duration_s=2.0,  # jaxlint: host-only
                      arrival_rate=25.0, kill_after=3, timeout_s=240.0):
    """SIGKILL a replica under open-loop load; prove zero silent loss.
    See the module docstring for the verdict list. Returns the report
    dict; raises AssertionError on any violated invariant."""
    workdir = Path(workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    parent_jsonl = workdir / "fleet_parent_telemetry.jsonl"
    sink = telemetry.JsonlSink(parent_jsonl)
    telemetry.add_sink(sink)
    mem = telemetry.MemorySink()
    telemetry.add_sink(mem)
    try:
        report = _chaos_body(
            workdir, mem, n_replicas=n_replicas, seed=seed,
            duration_s=duration_s, arrival_rate=arrival_rate,
            kill_after=kill_after, timeout_s=timeout_s,
        )
    finally:
        telemetry.remove_sink(mem)
        telemetry.remove_sink(sink)
        sink.close()
    shards = {
        slot: workdir / f"fleet_b/replica_{slot}_telemetry.jsonl"
        for slot in range(n_replicas)
    }
    shards[n_replicas] = workdir / "fleet_c/replica_0_telemetry.jsonl"
    report["telemetry_records"] = _merge_shards(
        workdir / "fleet_telemetry.jsonl", parent_jsonl, shards)
    return report


def _chaos_body(workdir, mem, *, n_replicas, seed, duration_s,  # jaxlint: host-only
                arrival_rate, kill_after, timeout_s):
    assert n_replicas >= 2, "the chaos drill needs a fleet, not a replica"
    cfg = _drill_model_config()
    exp = workdir / "exp"
    exp.mkdir(parents=True, exist_ok=True)
    manifest = _save_zs(exp, 1, _train_state(seed))
    probe_tokens = _cold_probe(manifest, seed)

    # ---- the multi-target split must BE the single-stream process ----
    single = open_loop_workload(
        duration_s, vocab_size=cfg.vocab_size,
        max_model_len=cfg.max_seq_len, seed=seed,
        arrival_rate=arrival_rate,
    )
    streams = open_loop_workload(
        duration_s, vocab_size=cfg.vocab_size,
        max_model_len=cfg.max_seq_len, seed=seed,
        arrival_rate=arrival_rate, targets=n_replicas,
    )
    merged = sorted(
        (req for stream in streams for req in stream),
        key=lambda r: r["arrival_s"],
    )
    if merged != single:
        raise AssertionError(
            "fleet drill: multi-target split does not reassemble into "
            "the global Poisson process"
        )

    # ---- phase A: no-kill baseline fleet -----------------------------
    fleet_a = _Fleet(exp, workdir / "fleet_a", n_replicas, seed=seed,
                     trace_epoch="a")
    fleet_a.start()
    acc_a = _run_open_loop(fleet_a.router, single, timeout_s=timeout_s)
    if acc_a["done"] != acc_a["submitted"] or acc_a["shed"]:
        raise AssertionError(f"fleet drill: baseline accounting {acc_a}")
    baseline = fleet_a.router.results
    baseline_p99 = _p99(fleet_a.router.latencies())
    for slot in range(n_replicas):
        if fleet_a.probe(slot)["tokens"] != probe_tokens:
            raise AssertionError(
                f"fleet drill: baseline replica {slot} probe diverged "
                f"from the cold restore"
            )

    # one merged fleet view over every replica's live metrics exporter
    from pyrecover_tpu.telemetry.aggregate import FleetAggregator

    agg = FleetAggregator(fleet_a.metrics_targets())
    snap = agg.poll()
    if len(snap["targets"]) != n_replicas or snap["stale"]:
        raise AssertionError(
            f"fleet drill: aggregator saw {len(snap['targets'])} targets "
            f"(stale {snap['stale']}), wanted {n_replicas} live"
        )

    # admission under zero capacity sheds loudly, never silently
    fleet_a.router.max_inflight = 0
    fleet_a.router.max_queue = 0
    shed_rids = [request_id(seed + 777, i) for i in range(3)]
    for rid in shed_rids:
        verdict = fleet_a.router.submit(
            {"rid": rid, "prompt": [1, 2, 3], "max_new_tokens": 2})
        if verdict != "shed":
            raise AssertionError(
                f"fleet drill: zero-capacity submit was {verdict!r}")
    shed_events = {
        e["rid"] for e in mem.events if e["event"] == "fleet_shed"}
    if not set(shed_rids) <= shed_events:
        raise AssertionError("fleet drill: shed requests missing events")
    acc_a = fleet_a.router.accounting()
    if acc_a["submitted"] != acc_a["done"] + acc_a["shed"]:
        raise AssertionError(
            f"fleet drill: shed accounting leaks requests {acc_a}")
    fleet_a.stop()

    # ---- phase B: SIGKILL one replica mid-flight ---------------------
    # replica 1's first incarnation carries the kill plan: announce
    # fault_injected to its shard, then SIGKILL itself after
    # ``kill_after`` completed requests. Respawns carry no plan.
    kill_plan = {
        "seed": seed,
        "faults": [{
            "type": "kill9_during_save", "site": "replica_kill",
            "save_index": 0, "after_bytes": kill_after,
        }],
    }
    fleet_b = _Fleet(
        exp, workdir / "fleet_b", n_replicas, seed=seed,
        fault_plans={(1, 0): kill_plan}, trace_epoch="b",
    )
    # the parent's redrive seam: the first redrive hits a transient I/O
    # error and must retry through io_retry, never drop the request
    faults.install({
        "seed": seed,
        "faults": [{
            "type": "transient_io_error", "op": "redrive", "fail_count": 1,
        }],
    })
    try:
        fleet_b.start()
        acc_b = _run_open_loop(fleet_b.router, single, timeout_s=timeout_s)
    finally:
        faults.clear()
    kill_p99 = _p99(fleet_b.router.latencies())
    p99_gate = P99_FACTOR * baseline_p99 + P99_SLACK_S

    proc_killed = fleet_b.proc(1, 0)
    proc_killed.wait(timeout=30)
    if proc_killed.returncode != -9:
        raise AssertionError(
            f"fleet drill: killed replica exited rc "
            f"{proc_killed.returncode}, wanted -9 (SIGKILL)"
        )
    if acc_b["submitted"] != acc_b["done"] + acc_b["shed"] or acc_b["shed"]:
        raise AssertionError(
            f"fleet drill: kill-run accounting leaks requests {acc_b}")
    if acc_b["redriven"] < 1:
        raise AssertionError(
            "fleet drill: replica died but nothing was redriven")
    results_b = fleet_b.router.results
    for rid, tokens in baseline.items():
        if results_b.get(rid) != tokens:
            raise AssertionError(
                f"fleet drill: request {rid} diverged after redrive")
    if kill_p99 > p99_gate:
        raise AssertionError(
            f"fleet drill: kill-window p99 {kill_p99:.3f}s exceeds "
            f"{P99_FACTOR}x baseline {baseline_p99:.3f}s + "
            f"{P99_SLACK_S}s"
        )

    # announce-then-kill trail in the murdered replica's shard
    shard = telemetry.read_events(fleet_b.shards[1])
    kills = [
        e for e in shard
        if e["event"] == "fault_injected" and e.get("site") == "replica_kill"
    ]
    if not kills:
        raise AssertionError(
            "fleet drill: no fault_injected trail in the killed "
            "replica's shard — the kill was silent"
        )
    # the parent's redrive trail: event, injected transient, and retry
    redriven = [e for e in mem.events if e["event"] == "request_redriven"]
    seam = [
        e for e in mem.events
        if e["event"] == "fault_injected"
        and e.get("site") == "router_redrive"
    ]
    retries = [
        e for e in mem.events
        if e["event"] == "ckpt_io_retry" and e.get("op") == "redrive"
    ]
    if not redriven or not seam or not retries:
        raise AssertionError(
            f"fleet drill: torn redrive trail — redriven="
            f"{len(redriven)} seam={len(seam)} retries={len(retries)}"
        )

    # the supervisor must have respawned the dead slot, and the respawn
    # must serve the same weights
    fleet_b.wait_ready([1], timeout_s=_READY_TIMEOUT_S)
    spawned = [
        e for e in mem.events
        if e["event"] == "replica_spawned" and e.get("replica") == 1
    ]
    if len(spawned) < 2:
        raise AssertionError(
            f"fleet drill: killed replica was not respawned "
            f"({len(spawned)} spawns)"
        )
    if fleet_b.probe(1)["tokens"] != probe_tokens:
        raise AssertionError(
            "fleet drill: respawned replica probe diverged")
    dead = [
        e for e in mem.events
        if e["event"] == "replica_dead" and e.get("replica") == 1
    ]
    if not dead:
        raise AssertionError("fleet drill: replica death went unobserved")
    fleet_b.stop()

    # ---- trace completeness gate -------------------------------------
    # Every completed request must assemble into exactly ONE rooted,
    # skew-corrected trace with zero orphan spans; the redriven request
    # must link BOTH attempts under one root with the kill hole
    # attributed to redrive-gap; and the critical-path buckets must sum
    # to e2e inside the named residual tolerance. Replica shards are
    # durable here (both fleets stopped → sinks closed, per-event
    # flush), so assembly sees the complete per-process evidence.
    domains = [traceassembly.Domain("parent", list(mem.events))]
    for fleet, tag in ((fleet_a, "fleet_a"), (fleet_b, "fleet_b")):
        for slot in range(n_replicas):
            events = telemetry.read_events(fleet.shards[slot])
            if events:
                domains.append(traceassembly.Domain(
                    f"{tag}/replica_{slot}", events))
    trace_report = traceassembly.assemble(domains)
    per_trace = trace_report["per_trace"]
    if trace_report["traces"]["orphan_spans"]:
        raise AssertionError(
            f"fleet drill: {trace_report['traces']['orphan_spans']} orphan "
            f"span(s) detached from their request roots "
            f"(e.g. {trace_report['orphans'][:3]})"
        )
    untraced = [
        (epoch, rid)
        for epoch, results in (("a", baseline), ("b", results_b))
        for rid in results
        if "e2e_s" not in per_trace.get(tracing.trace_id(rid, epoch), {})
    ]
    if untraced:
        raise AssertionError(
            f"fleet drill: {len(untraced)} completed request(s) have no "
            f"completed trace (e.g. {untraced[:3]})"
        )
    redriven_rids = sorted({e["rid"] for e in redriven})
    redrive_gap_s = 0.0
    for rid in redriven_rids:
        entry = per_trace[tracing.trace_id(rid, "b")]
        gap = entry["buckets"]["redrive_gap"]
        if entry["attempts"] < 2 or gap <= 0.0:
            raise AssertionError(
                f"fleet drill: redriven request {rid} trace does not link "
                f"both attempts under one root with the kill hole in "
                f"redrive-gap ({entry})"
            )
        redrive_gap_s = max(redrive_gap_s, gap)
    residual_bad = [
        e for e in per_trace.values()
        if e.get("complete") and not e["residual_ok"]
    ]
    if residual_bad:
        raise AssertionError(
            f"fleet drill: critical-path buckets do not sum to e2e within "
            f"the named residual tolerance for {len(residual_bad)} "
            f"trace(s) (e.g. {residual_bad[:2]})"
        )

    # ---- phase C: crash-looper is quarantined, not restarted forever -
    empty = workdir / "empty_exp"
    empty.mkdir(parents=True, exist_ok=True)
    fleet_c = _Fleet(
        empty, workdir / "fleet_c", 1, seed=seed, backoff_base_s=0.05,
        backoff_max_s=0.2, quarantine_after=3, trace_epoch="c",
    )
    fleet_c.sup.start()
    deadline = time.monotonic() + _READY_TIMEOUT_S
    while (fleet_c.sup.state(0) != QUARANTINED
           and time.monotonic() < deadline):
        time.sleep(0.05)
    state = fleet_c.sup.state(0)
    spawns = fleet_c.sup.spawns(0)
    fleet_c.sup.stop()
    if state != QUARANTINED:
        raise AssertionError(
            f"fleet drill: crash-looper state {state!r}, not quarantined")
    if spawns != 3:
        raise AssertionError(
            f"fleet drill: crash-looper spawned {spawns} times, "
            f"wanted exactly 3 (quarantine_after)"
        )
    quarantined = [
        e for e in mem.events if e["event"] == "replica_quarantined"]
    if not quarantined:
        raise AssertionError("fleet drill: quarantine was silent")

    return {
        "replicas": n_replicas,
        "requests": len(single),
        "baseline_p99_s": round(baseline_p99, 4),
        "kill_p99_s": round(kill_p99, 4),
        "p99_gate_s": round(p99_gate, 4),
        "killed_rc": proc_killed.returncode,
        "redriven": acc_b["redriven"],
        "shed": len(shed_rids),
        "respawns": len(spawned) - 1,
        "quarantine_spawns": spawns,
        "aggregator_targets": len(snap["targets"]),
        "trace_assembled": trace_report["traces"]["assembled"],
        "trace_completed": trace_report["traces"]["completed"],
        "trace_orphans": trace_report["traces"]["orphan_spans"],
        "trace_redriven_linked": len(redriven_rids),
        "trace_redrive_gap_s": round(redrive_gap_s, 4),
        "trace_residual_violations": len(residual_bad),
        "trace_dominant_tail_bucket": trace_report["dominant_tail_bucket"],
    }


# ---- canary-rollback drill --------------------------------------------------


def canary_rollout_drill(workdir, *, seed=0, timeout_s=240.0):  # jaxlint: host-only
    """Divergent manifest fails the canary gate and auto-rolls-back to
    the pinned old manifest; a healthy manifest waves to every replica.
    Returns the report dict; raises AssertionError on any violation."""
    from pyrecover_tpu.checkpoint.zerostall import pins

    workdir = Path(workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    sink = telemetry.JsonlSink(workdir / "canary_telemetry.jsonl")
    telemetry.add_sink(sink)
    mem = telemetry.MemorySink()
    telemetry.add_sink(mem)
    fleet = None
    try:
        exp = workdir / "exp"
        exp.mkdir(parents=True, exist_ok=True)
        # three releases with independently-initialized weights: the
        # canary gate needs probe tokens that actually DIFFER between
        # releases (the hotswap drill's tiny lm-head perturbation shifts
        # every logit uniformly — argmax-invariant, useless here)
        m_old = _save_zs(exp, 1, _train_state(seed))
        m_healthy = _save_zs(exp, 2, _train_state(seed + 1))
        m_divergent = _save_zs(exp, 3, _train_state(seed + 2))
        probe_old = _cold_probe(m_old, seed)
        probe_new = _cold_probe(m_healthy, seed)
        if probe_old == probe_new:
            raise AssertionError(
                "canary drill: releases serve identical probe tokens")

        fleet = _Fleet(
            exp, workdir / "fleet", 2, seed=seed, manifest=m_old,
            trace_epoch="canary")
        fleet.start()
        pre = fleet.probe(0)
        if pre["tokens"] != probe_old:
            raise AssertionError(
                "canary drill: fleet does not serve the old manifest")
        baseline_p99 = _p99(pre["e2e_s"])

        # the divergent artifact claims to be the next release: it
        # swaps fine (valid checkpoint) and the TOKEN gate catches it
        bad = canary_rollout(
            fleet.router, [0, 1], manifest=m_divergent,
            old_manifest=m_old, exp_dir=exp, expected_tokens=probe_new,
            baseline_p99_s=baseline_p99, probe_seed=seed,
            timeout_s=timeout_s,
        )
        if bad["verdict"] != "fail" or bad["reason"] != "token_mismatch":
            raise AssertionError(
                f"canary drill: divergent rollout verdict {bad['verdict']} "
                f"({bad['reason']}), wanted token_mismatch fail"
            )
        if bad["waved"]:
            raise AssertionError(
                "canary drill: divergent manifest leaked past the canary")
        live = [p.name for p in pins.live_pins(exp)]
        if not any(Path(m_old).name in name for name in live):
            raise AssertionError(
                f"canary drill: old manifest not pinned after rollback "
                f"(live pins {live})"
            )
        for slot in (0, 1):
            status = fleet.status_of(slot)
            if status["loaded_step"] != 1:
                raise AssertionError(
                    f"canary drill: replica {slot} on step "
                    f"{status['loaded_step']} after rollback, wanted 1"
                )
            if fleet.probe(slot)["tokens"] != probe_old:
                raise AssertionError(
                    f"canary drill: replica {slot} probe diverged from "
                    f"the cold restore after rollback"
                )
        bad["lease"].release()  # operator acks the failed rollout

        # the healthy release canaries, passes, and waves everywhere
        good = canary_rollout(
            fleet.router, [0, 1], manifest=m_healthy,
            old_manifest=m_old, exp_dir=exp, expected_tokens=probe_new,
            baseline_p99_s=baseline_p99, probe_seed=seed,
            timeout_s=timeout_s,
        )
        if good["verdict"] != "pass":
            raise AssertionError(
                f"canary drill: healthy rollout failed ({good['reason']})")
        for slot in (0, 1):
            status = fleet.status_of(slot)
            if status["loaded_step"] != 2 or status["rejected"]:
                raise AssertionError(
                    f"canary drill: replica {slot} step "
                    f"{status['loaded_step']} rejected "
                    f"{status['rejected']} after the healthy wave"
                )
            if fleet.probe(slot)["tokens"] != probe_new:
                raise AssertionError(
                    f"canary drill: replica {slot} probe diverged after "
                    f"the healthy wave"
                )
        verdicts = [
            (e["verdict"], e["reason"]) for e in mem.events
            if e["event"] == "canary_verdict"
        ]
        if verdicts != [("fail", "token_mismatch"), ("pass", "")]:
            raise AssertionError(
                f"canary drill: verdict trail {verdicts}")
        fleet.stop()
        fleet = None
        return {
            "divergent_verdict": bad["verdict"],
            "divergent_reason": bad["reason"],
            "healthy_verdict": good["verdict"],
            "healthy_waved": len(good["waved"]),
            "baseline_p99_s": round(baseline_p99, 4),
            "p99_gate_s": good["p99_gate_s"],
        }
    finally:
        if fleet is not None:
            fleet.stop()
        telemetry.remove_sink(mem)
        telemetry.remove_sink(sink)
        sink.close()


def fleet_smoke(workdir, *, seed=0):  # jaxlint: host-only
    """The format.sh gate body: both drills, one merged report."""
    workdir = Path(workdir)
    chaos = fleet_chaos_drill(workdir / "chaos", seed=seed)
    canary = canary_rollout_drill(workdir / "canary", seed=seed)
    return {"chaos": chaos, "canary": canary}
