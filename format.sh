#!/usr/bin/env bash
# Lint/format harness (parity with reference format.sh).
#
# Usage:
#   ./format.sh           rewrite files in place
#   ./format.sh --check   report-only mode (CI): exit 1 on violations,
#                         rewrite nothing
#
# Formatters that are not installed are skipped with a note (the container
# may not ship them); a missing tool is never a failure.
set -u

TARGETS="pyrecover_tpu tests tools bench.py chip_smoke.py __graft_entry__.py"
ISORT_ARGS=""
BLACK_ARGS=""
if [ "${1:-}" = "--check" ]; then
  ISORT_ARGS="--check-only --diff"
  BLACK_ARGS="--check --diff"
fi

rc=0
if python -c "import isort" 2>/dev/null; then
  python -m isort $ISORT_ARGS $TARGETS || rc=1
else
  echo "isort not installed; skipped"
fi
if python -c "import black" 2>/dev/null; then
  python -m black $BLACK_ARGS $TARGETS || rc=1
else
  echo "black not installed; skipped"
fi
if python -c "import flake8" 2>/dev/null; then
  python -m flake8 --max-line-length 100 pyrecover_tpu || rc=1
else
  echo "flake8 not installed; skipped"
fi

# jaxlint: JAX-aware static analysis (pyrecover_tpu/analysis — pure stdlib,
# always available). --strict fails on any unsuppressed finding: this is the
# CI gate that keeps host syncs / PRNG reuse / donation bugs out of the hot
# path. The JSON report (path overridable via JAXLINT_JSON) gives CI tooling
# the same machine-readable surface as tools/summarize_telemetry.py.
python tools/jaxlint.py pyrecover_tpu tools bench.py chip_smoke.py __graft_entry__.py \
  --strict --json "${JAXLINT_JSON:-/tmp/jaxlint_report.json}" || rc=1

# concur: static concurrency-safety analysis (pyrecover_tpu/analysis/concur
# — pure stdlib, same engine/suppression machinery as jaxlint under the
# `concur:` namespace). Machine-checks the threading invariants the async
# checkpoint stack documents in prose: no blocking I/O under hot-path
# locks (CC02), no lock-order inversions across thread roots (CC01), no
# unguarded cross-root shared state (CC03), signal handlers stay
# lock/emit-free (CC04), daemon writers that own durable commits are
# joined (CC05), collectives stay pinned to the calling thread (CC06).
# JSON report beside the jaxlint one (CONCUR_JSON).
python tools/concur.py pyrecover_tpu tools bench.py chip_smoke.py __graft_entry__.py \
  --strict --json "${CONCUR_JSON:-/tmp/concur_report.json}" || rc=1

# distcheck: static multi-host collective-congruence analysis
# (pyrecover_tpu/analysis/distcheck — pure stdlib, same engine/suppression
# machinery under the `distcheck:` namespace). Machine-checks the SPMD
# protocol discipline the resilience stack documents in prose: no
# collective gated on a single host's state (DC01), congruent collective
# sequences across branch arms (DC02), host-0 verdicts broadcast before
# they steer control flow (DC03), no collectives in reach of swallowed
# exceptions (DC04), every raw multihost wait bounded by a
# collective_phase (DC05), collective trip counts never driven by
# host-local state (DC06). JSON report beside the others (DISTCHECK_JSON).
python tools/distcheck.py pyrecover_tpu tools bench.py chip_smoke.py __graft_entry__.py \
  --strict --json "${DISTCHECK_JSON:-/tmp/distcheck_report.json}" || rc=1

# obscheck: static observability-contract analysis
# (pyrecover_tpu/analysis/obscheck — pure stdlib, same engine/suppression
# machinery under the `obscheck:` namespace). Machine-checks the
# event/metric plane's three-way contract: every literal emit documented
# in both catalogs (OB01), no phantom catalog rows (OB02), every
# consumer-read event/field/span actually produced (OB03) — including
# the declarative doctor.EVENT_DEPS/SPAN_DEPS and exporter.DEFAULT_SERIES
# tables — catalogs in agreement with each other (OB04), no unconditional
# emits on the training hot path (OB05), and every consumed metric series
# registered (OB06). JSON report beside the others (OBSCHECK_JSON).
python tools/obscheck.py pyrecover_tpu tools bench.py chip_smoke.py __graft_entry__.py \
  --strict --json "${OBSCHECK_JSON:-/tmp/obscheck_report.json}" || rc=1

# faultcheck: static crash-consistency & fault-coverage analysis
# (pyrecover_tpu/analysis/faultcheck — pure stdlib, same engine/suppression
# machinery under the `faultcheck:` namespace). Machine-checks the
# durability plane's triangle: every rename publish fsync-ordered (FT01),
# every durable-effect chain behind a faults.check seam the chaos harness
# can kill (FT02), live seams and the FAULT_SITES registry in agreement
# both ways (FT03), every registered site fired by some drill (FT04), no
# error-path resource leaks on pool blocks / pin leases / subprocesses
# (FT05), no recovery-path exception swallows (FT06). JSON report beside
# the others (FAULTCHECK_JSON).
python tools/faultcheck.py pyrecover_tpu tools bench.py chip_smoke.py __graft_entry__.py \
  --strict --json "${FAULTCHECK_JSON:-/tmp/faultcheck_report.json}" || rc=1

# shardcheck: abstract SPMD preflight (pyrecover_tpu/analysis/shardcheck).
# Every shipped preset must validate clean — partition-spec divisibility,
# axis use, replication, collective census — on 1/2/4/8-device virtual
# meshes, entirely on CPU (the tool forces JAX_PLATFORMS=cpu + virtual
# devices itself). JSON report published next to the jaxlint one.
if SHARDCHECK_OUT=$(JAX_PLATFORMS=cpu python tools/shardcheck.py \
    --all-presets --strict \
    --json "${SHARDCHECK_JSON:-/tmp/shardcheck_report.json}" 2>&1); then
  echo "$SHARDCHECK_OUT" | tail -1   # clean: one summary line
else
  echo "$SHARDCHECK_OUT"             # findings: full report
  rc=1
fi

# shardcheck bandwidth-lean gate: the BUCKETED zero1 + int8 update path
# must stay wired end to end — the same 1/2/4/8-device mesh matrix with
# --optimizer-sharding zero1 --grad-allreduce int8 --grad-bucket-mb 64
# re-resolves the state specs per mesh (data-sharded moments, the int8
# error-feedback residual), traces the census (SC12 fires if the
# quantized sync collective ever drops out of the step, or if zero1
# stops sharding anything; SC13 fires if the per-bucket collectives
# ever collapse back into one tail-of-backward blob), and prices the
# wire traffic — per bucket, with the modelled exposed-vs-hidden split
# — against the fp32/none baseline in the JSON report.
if SHARDCHECK_Z1_OUT=$(JAX_PLATFORMS=cpu python tools/shardcheck.py \
    --preset llama-150m --strict \
    --optimizer-sharding zero1 --grad-allreduce int8 --grad-bucket-mb 64 \
    --json "${SHARDCHECK_Z1_JSON:-/tmp/shardcheck_zero1_report.json}" 2>&1); then
  echo "$SHARDCHECK_Z1_OUT" | tail -3   # clean: wire + overlap + count line
else
  echo "$SHARDCHECK_Z1_OUT"
  rc=1
fi

# chaos smoke: the recovery stack's soak gate (pyrecover_tpu/resilience).
# Runs the real tiny-model trainer on CPU under a seeded fault plan —
# SIGTERM drill, SIGKILL mid-save, transient EIO under the writer, flipped
# bytes in a committed checkpoint — across kill/resume cycles, and fails
# on ANY continuity or quarantine violation: the stitched loss CSV must be
# bit-exact against an uninterrupted golden run, exactly the injected
# corruption quarantined, and the ckpt_io_retry/ckpt_quarantined telemetry
# trail present. Also gates the elastic_shrink drill (kill at 4 virtual
# devices -> resume on 2 -> grow back to 4, loss continuity + the
# elastic_resume telemetry trail) and the hang-watchdog drill. JSON report
# at CHAOS_JSON, beside the other gate reports.
# The workdir is kept (and pre-cleaned) so the traceview smoke below can
# merge the telemetry shards the soak just produced.
CHAOS_WORK="${CHAOS_WORK:-/tmp/pyrecover_chaos_smoke}"
rm -rf "$CHAOS_WORK"
if CHAOS_OUT=$(JAX_PLATFORMS=cpu python tools/chaos.py \
    --preset smoke --seed 0 --workdir "$CHAOS_WORK" \
    --json "${CHAOS_JSON:-/tmp/chaos_report.json}" 2>&1); then
  echo "$CHAOS_OUT" | tail -1        # clean: one OK line
else
  echo "$CHAOS_OUT"                  # violations: full cycle report
  rc=1
fi

# goodput-autopilot summarizer gate: the chaos soak's autopilot drill
# (cycles 25+ — seeded hazard-rate kills with a mid-run rate shift,
# --checkpoint-frequency auto) just produced a ckpt_policy decision trail;
# summarize_telemetry must render the "checkpoint policy (autopilot)"
# section AND the goodput-vs-static counterfactual ("static policy ...
# would have lost X s") from that same stream — the convergence/sidecar/
# no-quarantine verdicts themselves are gated inside the chaos report.
if AP_SUM=$(JAX_PLATFORMS=cpu python tools/summarize_telemetry.py \
    "$CHAOS_WORK"/ap/ap_telemetry.jsonl 2>&1); then
  if echo "$AP_SUM" | grep -q "checkpoint policy (autopilot)" \
      && echo "$AP_SUM" | grep -q "static policy"; then
    echo "$AP_SUM" | grep -A 5 "checkpoint policy (autopilot)" | head -6
  else
    echo "summarize_telemetry: autopilot decision-trail or goodput-vs-static section missing"
    rc=1
  fi
else
  echo "$AP_SUM"
  rc=1
fi

# traceview smoke: the tracing stack's gate (pyrecover_tpu/telemetry).
# Merges the chaos soak's telemetry shards (the interrupted run + the
# golden run — rotation-split JSONL included), exports Chrome-trace-event
# JSON, and fails unless the trace is valid (loads as JSON, has span
# slices) and the analysis report is non-empty. Trace at TRACEVIEW_TRACE
# (open in https://ui.perfetto.dev), report JSON beside the other gates.
TRACEVIEW_TRACE="${TRACEVIEW_TRACE:-/tmp/traceview_trace.json}"
if TV_OUT=$(JAX_PLATFORMS=cpu python tools/traceview.py \
    "$CHAOS_WORK"/chaos/chaos_telemetry.jsonl \
    "$CHAOS_WORK"/golden/golden_telemetry.jsonl \
    --out "$TRACEVIEW_TRACE" \
    --report-json "${TRACEVIEW_JSON:-/tmp/traceview_report.json}" 2>&1); then
  if [ -z "$TV_OUT" ]; then
    echo "traceview: empty analysis report"; rc=1
  else
    echo "$TV_OUT" | head -3
  fi
  python - "$TRACEVIEW_TRACE" <<'PYEOF' || rc=1
import json, sys
trace = json.load(open(sys.argv[1]))
spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
assert spans, "trace exported no span slices"
assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans)
print(f"traceview: OK — {len(trace['traceEvents'])} trace events, "
      f"{len(spans)} span slices")
PYEOF
else
  echo "$TV_OUT"
  rc=1
fi

# checkpoint-phase regression gate (ROADMAP item 1's gate, now wired into
# the build): traceview diffs the chaos soak's checkpoint-phase p50s —
# the zerostall drill's ckpt_blocking/ckpt_snapshot/... spans and the
# main drill's vanilla ckpt_save — against the baseline COMMITTED in the
# repo (baselines/ckpt_phase_baseline.json, which also pins the >=5x
# zerostall-blocking-vs-vanilla-save ratio asserted in tests). A
# blocking-save-time regression beyond 2.5x the stored p50 fails the
# build; the generous tolerance absorbs CI-machine noise while still
# catching the failure mode that matters (the snapshot window silently
# becoming a full synchronous save is a 10-100x move).
if TVB_OUT=$(JAX_PLATFORMS=cpu python tools/traceview.py \
    "$CHAOS_WORK"/zs/zs_telemetry.jsonl \
    "$CHAOS_WORK"/zs_golden/zs_golden_telemetry.jsonl \
    "$CHAOS_WORK"/chaos/chaos_telemetry.jsonl \
    --baseline baselines/ckpt_phase_baseline.json \
    --regression-tolerance 1.5 2>&1); then
  echo "ckpt-phase baseline: OK (no regression vs baselines/ckpt_phase_baseline.json)"
else
  echo "$TVB_OUT" | grep -E "REGRESSION|error" || echo "$TVB_OUT" | tail -5
  rc=1
fi

# doctor smoke: the crash-forensics gate (pyrecover_tpu/telemetry/doctor).
# Classifies the chaos workdir's artifacts (postmortem bundles + telemetry
# shards the soak just produced): the recovered main experiment must read
# HEALTHY (its kill/resume history notwithstanding), and the hang drill
# must read as a HANG wedged in the loader_wait phase. --expect makes a
# misclassification exit 3; the JSON reports are then re-validated so an
# unreadable/invalid report also fails the gate.
DOCTOR_JSON="${DOCTOR_JSON:-/tmp/doctor_report.json}"
DOCTOR_HANG_JSON="${DOCTOR_JSON%.json}_hang.json"
if DR_OUT=$(JAX_PLATFORMS=cpu python tools/doctor.py "$CHAOS_WORK"/chaos \
    --expect healthy --json "$DOCTOR_JSON" 2>&1); then
  echo "$DR_OUT" | head -1
else
  echo "$DR_OUT"; rc=1
fi
if DR_OUT=$(JAX_PLATFORMS=cpu python tools/doctor.py "$CHAOS_WORK"/hang \
    --expect hang --json "$DOCTOR_HANG_JSON" 2>&1); then
  echo "$DR_OUT" | head -1
else
  echo "$DR_OUT"; rc=1
fi
python - "$DOCTOR_JSON" "$DOCTOR_HANG_JSON" <<'PYEOF' || rc=1
import json, sys
healthy = json.load(open(sys.argv[1]))
hang = json.load(open(sys.argv[2]))
assert healthy["classification"] == "healthy", healthy["classification"]
assert hang["classification"] == "hang", hang["classification"]
assert hang["phase"] == "loader_wait", hang["phase"]
assert hang["evidence"]["n_bundles"] >= 1, "hang drill left no bundle"
print("doctor: OK — chaos exp healthy; hang drill classified as hang in "
      f"phase {hang['phase']} ({hang['evidence']['n_bundles']} bundle(s))")
PYEOF

# serving smoke: the continuous-batching engine's gate (pyrecover_tpu/
# serving). Saves a tiny checkpoint on virtual devices, restores it
# through the serving restore path (elastic preflight included), serves a
# seeded Poisson workload under the load generator, and fails unless (a)
# every request's greedy output is token-for-token equal to lockstep
# generate_tokens, (b) every KV block is back on the free list at drain
# (zero leaks — asserted inside the smoke), and (c) the latency report is
# non-empty. The smoke also serves its metrics registry over HTTP and
# scrapes itself MID-RUN (>= half the requests finished, engine still
# serving): the live scrape must render the key series non-zero —
# serving tokens/sec, request p99, KV peak occupancy (README "Live
# metrics"). The smoke's telemetry shard is then fed to
# summarize_telemetry, which must render the request-latency percentiles
# — and the live scrape's e2e p99 (a bucket-midpoint estimate) must
# agree with the summarizer's exact request_done-derived p99 within one
# histogram bucket width (grid base 2^0.25 ~ 19% relative, plus midpoint
# slop: factor 1.25).
SERVING_WORK="${SERVING_WORK:-/tmp/pyrecover_serving_smoke}"
rm -rf "$SERVING_WORK"
if SRV_OUT=$(JAX_PLATFORMS=cpu python tools/bench_decode.py \
    --smoke "$SERVING_WORK" 2>&1); then
  SRV_LINE=$(echo "$SRV_OUT" | grep '"metric": "serving_smoke"' | tail -1) \
    || SRV_LINE=""
  SRV_LINE="$SRV_LINE" python - <<'PYEOF' || rc=1
import json, os
rep = json.loads(os.environ["SRV_LINE"])
assert rep["ok"] and rep["metric"] == "serving_smoke", rep
assert rep["greedy_matches"] == rep["requests"], \
    "serving output diverged from lockstep decode"
assert rep["tokens_per_sec"] and rep["ttft_s"]["p50"] is not None, \
    f"empty latency report: {rep}"
mid = rep["live_scrape"]["mid"]
for key in ("tokens_per_sec", "ttft_p50", "e2e_p99",
            "kv_peak_occupancy_pct"):
    assert mid.get(key), f"live mid-run scrape missing {key}: {mid}"
assert mid["e2e_count"] >= rep["requests"] // 2, \
    f"mid-run scrape saw too few finished requests: {mid}"
print(f"serving smoke: OK — {rep['requests']} requests greedy-equal to "
      f"lockstep at {rep['tokens_per_sec']} tok/s, zero leaked KV blocks; "
      f"live scrape mid-run at {mid['e2e_count']}/{rep['requests']} done: "
      f"{mid['tokens_per_sec']} tok/s, e2e p99 {mid['e2e_p99']}s, KV peak "
      f"{mid['kv_peak_occupancy_pct']}%")
PYEOF
else
  echo "$SRV_OUT"
  rc=1
fi
if SRV_SUM=$(JAX_PLATFORMS=cpu python tools/summarize_telemetry.py \
    "$SERVING_WORK/serving_telemetry.jsonl" \
    --json "$SERVING_WORK/serving_summary.json" 2>&1); then
  if echo "$SRV_SUM" | grep -q "serving (request latency)" \
      && echo "$SRV_SUM" | grep -q "ttft"; then
    echo "$SRV_SUM" | grep -A 4 "serving (request latency)" | head -5
  else
    echo "summarize_telemetry: serving request-latency section missing"
    rc=1
  fi
  SRV_LINE="$SRV_LINE" python - "$SERVING_WORK/serving_summary.json" \
      <<'PYEOF' || rc=1
import json, os, sys
rep = json.loads(os.environ["SRV_LINE"])
blob = json.load(open(sys.argv[1]))
exact = blob["extra"]["serving"]["e2e_s"]["p99"]
live = rep["live_scrape"]["final"]["e2e_p99"]
assert exact and live, (exact, live)
ratio = max(live / exact, exact / live)
assert ratio <= 1.25, (
    f"live scrape p99 {live}s drifted {ratio:.3f}x from the post-hoc "
    f"summarizer's exact p99 {exact}s (> one bucket width)")
print(f"live-vs-posthoc: OK — scraped e2e p99 {live}s vs exact {exact}s "
      f"({ratio:.3f}x, gate 1.25x = one bucket width + midpoint slop)")
PYEOF
else
  echo "$SRV_SUM"
  rc=1
fi

# hot-swap smoke + chaos drill: the train→serve distribution plane's gate
# (pyrecover_tpu/serving/hotswap). One process trains (zerostall saves of
# a partially-perturbed state) while the load generator drives the engine
# open-loop and the registry watcher swaps weights live; then a serving
# replica subprocess is SIGKILLed mid-fetch. Fails unless (a) >=1 swap
# completed with token-level equality vs a COLD restore of the final
# manifest, (b) the incremental fetch moved strictly less than the full
# params bytes (reused bytes reported), (c) p99 latency across the swap
# window stays within the gate vs the same workload on a no-swap engine,
# and (d) the chaos drill proves zero torn state: restart serves the old
# manifest digest-verified, the pin lease shields in-fetch chunks from
# GC, zero quarantines, zero leaked chunks after lease expiry. The
# smoke's telemetry shard is then fed to summarize_telemetry, which must
# render the hot-swap section (count, bytes fetched vs reused, p99
# across swaps).
HOTSWAP_WORK="${HOTSWAP_WORK:-/tmp/pyrecover_hotswap_smoke}"
rm -rf "$HOTSWAP_WORK"
if HS_OUT=$(JAX_PLATFORMS=cpu python tools/bench_decode.py \
    --hotswap-smoke "$HOTSWAP_WORK" 2>&1); then
  HS_LINE=$(echo "$HS_OUT" | grep '"metric": "hotswap_smoke"' | tail -1) \
    || HS_LINE=""
  HS_LINE="$HS_LINE" python - <<'PYEOF' || rc=1
import json, os
rep = json.loads(os.environ["HS_LINE"])
assert rep["ok"] and rep["metric"] == "hotswap_smoke", rep
assert rep["swaps"] >= 1 and rep["rejected"] == 0, rep
assert rep["token_equal"], "post-swap serving diverged from cold restore"
assert rep["reused_bytes"] > 0, "incremental fetch reused nothing"
assert rep["fetched_bytes"] < rep["swaps"] * rep["params_bytes"], \
    "fetch moved the whole params set — nothing incremental"
assert rep["p99_e2e_s"] <= rep["p99_gate_s"], \
    f"p99 across the swap window broke the gate: {rep['p99_e2e_s']}"
ch = rep["chaos"]
assert ch["kill_rc"] == -9 and ch["old_manifest_probe_equal"], ch
assert not ch["quarantined"] and ch["chunks_leaked"] == 0, ch
# the train-and-serve live scrape: all four key series, mid-run, from
# one registry — trainer step time, serving throughput + tail, KV peak
mid = rep["live_scrape"]["mid"]
for key in ("tokens_per_sec", "step_iter_p50", "e2e_p99",
            "kv_peak_occupancy_pct"):
    assert mid.get(key), f"live mid-run scrape missing {key}: {mid}"
print(f"hotswap smoke: OK — {rep['swaps']} live swaps token-equal to "
      f"cold restore ({rep['fetched_bytes']} B fetched / "
      f"{rep['reused_bytes']} B reused), p99 {rep['p99_e2e_s']}s <= gate "
      f"{rep['p99_gate_s']}s; chaos: kill mid-swap -> old manifest "
      f"served, 0 quarantined, 0 leaked; live scrape mid-run: step p50 "
      f"{mid['step_iter_p50']}s, {mid['tokens_per_sec']} tok/s, e2e p99 "
      f"{mid['e2e_p99']}s, KV peak {mid['kv_peak_occupancy_pct']}%")
PYEOF
else
  echo "$HS_OUT"
  rc=1
fi
if HS_SUM=$(JAX_PLATFORMS=cpu python tools/summarize_telemetry.py \
    "$HOTSWAP_WORK/hotswap_telemetry.jsonl" \
    --json "$HOTSWAP_WORK/hotswap_summary.json" 2>&1); then
  if echo "$HS_SUM" | grep -q "hot-swap" \
      && echo "$HS_SUM" | grep -q "bytes fetched" \
      && echo "$HS_SUM" | grep -q "p99 across swaps"; then
    echo "$HS_SUM" | grep -A 4 "hot-swap (train" | head -5
  else
    echo "summarize_telemetry: hot-swap section missing"
    rc=1
  fi
  # live-vs-posthoc on the train-and-serve run: the final scrape's e2e
  # p99 (bucket midpoint, swap-window registry) vs the summarizer's
  # exact request_done-derived p99. The shard also carries the no-swap
  # baseline window (identical workload, p99 within the drill's own
  # gate), so the tolerance is one bucket width + midpoint slop + the
  # two-window composition drift. Under load on a single-core box the
  # baseline window drifts further from the swap window (observed up to
  # ~1.5x with an untouched tree), so the factor is 1.65 — still an
  # order of magnitude below any real wrong-series/wrong-unit bug.
  HS_LINE="$HS_LINE" python - "$HOTSWAP_WORK/hotswap_summary.json" \
      <<'PYEOF' || rc=1
import json, os, sys
rep = json.loads(os.environ["HS_LINE"])
blob = json.load(open(sys.argv[1]))
exact = blob["extra"]["serving"]["e2e_s"]["p99"]
live = rep["live_scrape"]["final"]["e2e_p99"]
assert exact and live, (exact, live)
ratio = max(live / exact, exact / live)
assert ratio <= 1.65, (
    f"live scrape p99 {live}s drifted {ratio:.3f}x from the post-hoc "
    f"summarizer's exact p99 {exact}s")
print(f"live-vs-posthoc: OK — scraped e2e p99 {live}s vs exact {exact}s "
      f"({ratio:.3f}x, gate 1.65x)")
PYEOF
else
  echo "$HS_SUM"
  rc=1
fi

# live-metrics fleet drill: the aggregator's gate (pyrecover_tpu/
# telemetry/aggregate). Spawns TWO genuinely separate exporter
# subprocesses, scrapes both over real TCP, and fails (inside the drill)
# unless the merged counters equal the exact sum of the parts, the
# histogram merge is bucket-wise identical to one process observing all
# samples, fleet p99 matches the single-process reference, and a
# SIGKILLed target is reported STALE while its last-known totals keep
# contributing to the fleet sums (flagged, never silently dropped).
FLEET_WORK="${FLEET_WORK:-/tmp/pyrecover_fleet_drill}"
rm -rf "$FLEET_WORK"
if FLEET_OUT=$(JAX_PLATFORMS=cpu python -m pyrecover_tpu.telemetry.aggregate \
    --drill "$FLEET_WORK" 2>&1); then
  FLEET_LINE=$(echo "$FLEET_OUT" | tail -1)
  FLEET_LINE="$FLEET_LINE" python - <<'PYEOF' || rc=1
import json, os
rep = json.loads(os.environ["FLEET_LINE"])
assert rep["targets"] == 2 and rep["merged_requests_total"] == 12, rep
assert rep["stale_after_kill"] == [rep["killed"]], rep
print(f"fleet drill: OK — 2 subprocess endpoints merged over TCP "
      f"(requests_total {rep['merged_requests_total']} = 7 + 5 exactly, "
      f"lat p99 {rep['lat_p99']}s bucket-wise-exact); SIGKILLed "
      f"{rep['killed']} reported stale, totals retained")
PYEOF
else
  echo "$FLEET_OUT"
  rc=1
fi

# serving-fleet smoke: the front door's gate (pyrecover_tpu/serving/
# fleet). Two real drills behind tools/bench_decode.py --fleet-smoke:
# (a) replica-loss chaos — >=2 replica subprocesses under seeded
# open-loop load, one SIGKILLed mid-flight through the replica_kill
# seam (rc -9, announce-then-kill trail in its telemetry shard) while
# the router's redrive seam eats an injected transient I/O error;
# fails unless accounting is exact (submitted == done + shed, zero
# silent losses), >=1 request was explicitly redriven with results
# bit-identical to the no-kill baseline, the kill-window fleet p99
# stays inside the gate, zero-capacity admission sheds LOUDLY (3/3
# fleet_shed), the supervisor respawns the dead replica (probe equal
# to a cold restore) and quarantines a crash-looper after exactly 3
# spawns. (b) canary rollback — a divergent manifest fails the canary
# token gate, auto-rolls-back to the pin-leased old manifest on every
# replica (probe equal to a cold restore), and a healthy manifest
# waves with zero rejections. The chaos drill also gates the
# distributed-tracing contract: every completed request (baseline AND
# kill phase) assembles into exactly ONE rooted trace with zero orphan
# spans across the parent + replica shards, the SIGKILL-redriven
# request's trace links BOTH attempts under one root with the kill
# hole attributed to redrive_gap, and every complete trace's bucket
# sum stays inside the named residual tolerance. The merged
# per-replica telemetry is then fed to summarize_telemetry (fleet +
# request-tracing sections must render) and to tools/tracepath.py
# --expect-complete (the CI trace-assembly gate).
FLEETSMOKE_WORK="${FLEETSMOKE_WORK:-/tmp/pyrecover_fleet_smoke}"
rm -rf "$FLEETSMOKE_WORK"
if FS_OUT=$(JAX_PLATFORMS=cpu python tools/bench_decode.py \
    --fleet-smoke "$FLEETSMOKE_WORK" 2>&1); then
  FS_LINE=$(echo "$FS_OUT" | grep '"metric": "fleet_smoke"' | tail -1) \
    || FS_LINE=""
  FS_LINE="$FS_LINE" python - <<'PYEOF' || rc=1
import json, os
rep = json.loads(os.environ["FS_LINE"])
assert rep["ok"] and rep["metric"] == "fleet_smoke", rep
ch = rep["chaos"]
assert ch["killed_rc"] == -9, f"replica not SIGKILLed: {ch}"
assert ch["redriven"] >= 1, f"death produced no redrive: {ch}"
assert ch["kill_p99_s"] <= ch["p99_gate_s"], \
    f"kill-window p99 {ch['kill_p99_s']}s broke the gate {ch['p99_gate_s']}s"
assert ch["shed"] == 3, f"zero-capacity admission did not shed 3/3: {ch}"
assert ch["respawns"] >= 1, f"dead replica never respawned: {ch}"
assert ch["quarantine_spawns"] == 3, \
    f"crash-looper not quarantined after exactly 3 spawns: {ch}"
assert ch["aggregator_targets"] == ch["replicas"], ch
ca = rep["canary"]
assert ca["divergent_verdict"] == "fail" \
    and ca["divergent_reason"] == "token_mismatch", \
    f"divergent manifest leaked past the canary gate: {ca}"
assert ca["healthy_verdict"] == "pass" and ca["healthy_waved"] >= 1, \
    f"healthy rollout did not wave: {ca}"
assert ch["trace_assembled"] > 0, f"no request traces assembled: {ch}"
assert ch["trace_orphans"] == 0, \
    f"trace assembly left orphan spans: {ch}"
assert ch["trace_redriven_linked"] >= 1 and ch["trace_redrive_gap_s"] > 0, \
    f"redriven request's attempts not linked under one root: {ch}"
assert ch["trace_residual_violations"] == 0, \
    f"critical-path buckets do not sum to e2e within tolerance: {ch}"
print(f"fleet smoke: OK — chaos: {ch['replicas']} replicas, "
      f"{ch['requests']} requests, kill rc {ch['killed_rc']}, "
      f"{ch['redriven']} redriven, p99 {ch['kill_p99_s']}s <= gate "
      f"{ch['p99_gate_s']}s, {ch['shed']}/3 shed loudly, "
      f"{ch['respawns']} respawn(s), crash-looper parked after "
      f"{ch['quarantine_spawns']} spawns; canary: divergent "
      f"{ca['divergent_verdict']} ({ca['divergent_reason']}) -> rolled "
      f"back, healthy {ca['healthy_verdict']} waved "
      f"{ca['healthy_waved']} replica(s); tracing: "
      f"{ch['trace_assembled']} trace(s) assembled "
      f"({ch['trace_completed']} completed, {ch['trace_orphans']} "
      f"orphans), redrive gap {ch['trace_redrive_gap_s']}s, tail "
      f"dominated by {ch['trace_dominant_tail_bucket']}")
PYEOF
else
  echo "$FS_OUT"
  rc=1
fi
if FS_SUM=$(JAX_PLATFORMS=cpu python tools/summarize_telemetry.py \
    "$FLEETSMOKE_WORK/chaos/fleet_telemetry.jsonl" \
    --json "$FLEETSMOKE_WORK/fleet_summary.json" 2>&1); then
  if echo "$FS_SUM" | grep -q "serving fleet (front door)" \
      && echo "$FS_SUM" | grep -q "redrives"; then
    echo "$FS_SUM" | grep -A 6 "serving fleet (front door)" | head -7
  else
    echo "summarize_telemetry: serving-fleet section missing"
    rc=1
  fi
  # the request-tracing section must render with nonzero assembled
  # traces and zero orphan spans over the merged drill shard
  if echo "$FS_SUM" | grep -q "request tracing (cross-process)" \
      && echo "$FS_SUM" | grep -Eq "(^| )0 orphan span" \
      && echo "$FS_SUM" | grep -Eq "[1-9][0-9]* assembled"; then
    echo "$FS_SUM" | grep -A 4 "request tracing (cross-process)" | head -5
  else
    echo "summarize_telemetry: request-tracing section missing/empty"
    rc=1
  fi
else
  echo "$FS_SUM"
  rc=1
fi
# tracepath CLI over the same merged shard: the trace-assembly CI gate
# (exit 1 on any orphan span, zero assembled traces, or a complete
# trace outside the residual tolerance)
if TP_OUT=$(JAX_PLATFORMS=cpu python tools/tracepath.py \
    "$FLEETSMOKE_WORK/chaos/fleet_telemetry.jsonl" \
    --json "$FLEETSMOKE_WORK/tracepath.json" --expect-complete 2>&1); then
  echo "$TP_OUT" | head -6
else
  echo "$TP_OUT"
  echo "tracepath: trace-assembly gate failed"
  rc=1
fi

exit $rc
