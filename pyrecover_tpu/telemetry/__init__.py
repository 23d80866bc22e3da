"""pyrecover_tpu.telemetry — structured event bus with pluggable sinks.

The machine-readable observability substrate: every subsystem emits
structured events (``emit("ckpt_commit", path=..., write_s=...)``) through
one process-wide bus into pluggable sinks — a host-0 JSONL file for real
runs, an in-memory list for tests, the text log for eyeballs. Costs
nothing when no sink is registered and never forces a device sync.

Event envelope (every record):
    ts      unix seconds (float)
    event   event name (str)
    host    jax process index of the emitting host

Core event names across the stack (fields beyond the envelope):
    run_start         devices, device_kind, processes, mesh, params_m,
                      loop_steps, layer_passes, mamba_layers, attn_layers,
                      ssm_state_elems, scan_chunk (a hybrid stack's: 0
                      Mamba layers and no state in a plain decoder),
                      import_s (seconds from the process's start to the
                      end of ``pyrecover_tpu.train``'s import; None where
                      /proc is unreadable), ckpt_modules (Orbax, Flax and
                      the engine modules loaded by then: an engine's come
                      in at ``open_engine``), ...
    step_time         step, data_wait_s, dispatch_s
    train_sync        step, loss, steps, interval_s, iter_s, sync_s
    throughput        step, tokens_per_sec, mfu_pct, tflops, ...
    eval              step, loss, seconds
    ckpt_save_start   engine, path, background/async_
    ckpt_commit       engine, path, bytes, write_s, checksum
                      (zerostall adds reused_bytes, chunks_written,
                      chunks_reused — the chunk-dedup ledger)
    ckpt_save_blocking engine, path, step, blocking_s, final
    ckpt_save_shadow  engine, path, shadow_s, ok (background save work
                      that OVERLAPPED training — recovered goodput, split
                      from the blocking stall in WallTimeTotals)
    ckpt_save_durable engine, wait_s
    ckpt_saved        engine, path, step, blocking_s, final (one fully
                      committed save; the goodput-autopilot decision
                      trail and the summarizer's static-policy
                      counterfactual both key on it)
    ckpt_backpressure engine, path, wait_s (a save arrived while the
                      previous zerostall save was still in flight; the
                      depth-1 queue made it wait, loudly)
    ckpt_bg_join      engine, waited_s, completed, ok, bounded (a pending
                      background save handle was joined — mid-run before
                      the next save, and with a bounded timeout on
                      train()'s unwind, so no non-daemon checkpoint work
                      is ever abandoned at exit)
    ckpt_gc           engine, removed, removed_bytes, kept, seconds
                      (refcounted chunk GC collected orphans; a chunk any
                      live manifest references is never collected)
    emergency_publish engine, step, exp_dir, leaves, bytes (a committed
                      zerostall snapshot entered the in-RAM tier)
    emergency_restore engine, step, seconds (_resume restored from RAM,
                      disk tier bypassed)
    emergency_restore_rejected  reason[, step] (the strict freshness/
                      digest gate refused the RAM record; disk wins)
    emergency_peer_exchange  engine, step, exp_dir, leaves, bytes (the
                      host-0-verdict-broadcast RAM exchange landed the
                      committed snapshot in every host's RAM)
    distributed_wait_timeout  phase, timeout_s (a collective_phase-bounded
                      cross-host wait — barrier / verdict broadcast /
                      peer RAM exchange — outlived its bound: some host
                      never reached the collective; a flight bundle is
                      dumped and doctor reads the open collective_wait
                      span as collective_hang evidence)
    ckpt_restore_start/ckpt_restore_done  engine, path, seconds
    ckpt_precheck_failed / ckpt_restore_fallback  path, reason
    ckpt_io_retry     op, path, attempt, errno, delay_s (transient-IO retry)
    ckpt_quarantined  path, dest, reason (moved into .corrupt/, never pruned)
    ckpt_prune        engine, count, removed
    ckpt_pruned       engine, path, step (one per retention removal)
    resume            path, step, seconds; resume_replay: replayed_steps
    elastic_resume    path, step, saved_topology, target_topology,
                      resharded_leaves, plan_bytes_moved (a checkpoint was
                      restored onto a DIFFERENT topology; the restore ran
                      inside a `reshard` span)
    elastic_preflight_failed  path, reason (shardcheck rejected the
                      reshard plan — SC11/SC05 — before any restore I/O;
                      resume falls back to an older fitting checkpoint)
    topology_mismatch path, reason (--elastic-resume off and the saved
                      topology differs: TopologyMismatchError follows)
    sampler_rescaled  saved_replicas, target_replicas, consumed (the data
                      pipeline re-derived its per-replica split; global
                      sample order preserved exactly)
    grad_quantize     mode, optimizer_sharding, block, data_replicas,
                      error_feedback, grad_bytes_fp32, wire_bytes_per_leg
                      (once per run when the bandwidth-lean update path is
                      on: the wire format the step was BUILT to move, with
                      the modelled per-leg bytes — shardcheck's traffic
                      model carries the full before/after ledger)
    grad_bucket       bucket_mb, mode, buckets, degenerate,
                      bucket_bytes_f32, min/max_bucket_bytes (once per
                      run when --grad-bucket-mb is set: the resolved
                      overlap bucket layout the jitted step issues —
                      reverse-autodiff order, one data-axis collective
                      per bucket; degenerate=True means the cap admitted
                      everything into one bucket and the step kept the
                      unbucketed single-collective form)
    remat_autosize    rung, saved_names, fits, device_kind,
                      limit_bytes, margin_bytes, modelled_bytes (per
                      rung), fell_back, compiled_peak_bytes,
                      batch_size, batch_per_chip, suggested_batch_size,
                      suggested_batch_per_chip, suggested_total_bytes
                      (once per run with --remat, when the step has
                      compiled: the rung of utils/remat.py's ladder the
                      layer scan runs and the checkpoint names it keeps,
                      the SC05 model's bytes per rung against the
                      compiler's limit for the device kind, how many
                      rungs the compiler refused, and its own peak)
    step_scopes       path, module, instructions, by_phase{}, by_scope{},
                      unscoped, build_s (once per run, when the train
                      step has compiled — before its first call, with
                      --remat or without — and only while a sink is
                      registered: stepscopes.py read the compiled
                      module's metadata and wrote <exp_dir>/
                      step_scopes.json, a table from instruction name to
                      [phase, scopes, root opcode, product]; the event
                      carries the file's path and COUNTS of instructions
                      by phase (fwd / remat / bwd / update / none) and by
                      sublayer, how many have neither, and the seconds
                      the table took — never the table itself. A step
                      wrapped in something that is no jitted function
                      has no table and no event. The executable's
                      metadata is that of the compile that MADE it: a
                      persistent compile cache keyed without metadata
                      can hand back an executable compiled before a
                      scope was renamed — `unscoped` then reads high;
                      clear the cache)
    flash_plan        steps_visited, steps_interior, steps_edge,
                      steps_above, block_q, block_kv, seq_q, seq_kv,
                      causal, batch, heads, kv_heads, head_dim, segments
                      (once a traced shape, at TRACE time and never from
                      the step loop: the (q block, kv block) pairs one
                      (batch row, head) of the flash kernels walks —
                      interior steps build no positional mask, edge
                      steps (the diagonal, a ragged tail) run the masked
                      body, pairs above the causal diagonal are neither
                      fetched nor stepped; ops/flash_attention.py
                      `flash_plan`)
    rope_plan         form, heads, head_dim, seq, batch_dims (once a
                      traced shape, at TRACE time and never from the step
                      loop: which form rotated q or k — since PR 39
                      `pair_swap_product`, the partner lane from one exact
                      product with a constant permutation; ops/rope.py)
    request_admitted  rid, prompt_tokens, max_new_tokens, blocks, slot,
                      queue_s (the serving scheduler admitted a request:
                      a decode slot plus its WHOLE KV-block footprint
                      were reserved — mid-flight allocation can never
                      fail after this)
    request_done      rid, prompt_tokens, new_tokens, blocks_released,
                      ttft_s, tpot_s, e2e_s (a request finished; its KV
                      blocks went back to the free list mid-flight and
                      its latencies fed the ttft_s/tpot_s/e2e_s
                      histograms — the serving SLO surface)
    kv_backpressure   rid, needed_blocks, free_blocks, free_slots,
                      queued (the KV pool or slot table cannot admit the
                      head-of-queue request; it waits loudly — the
                      ckpt_backpressure precedent — instead of OOMing;
                      emitted once per stall episode)
    weights_loaded    engine, path, step, leaves, bytes,
                      resharded_leaves, plan_bytes_moved, seconds,
                      target_topology (the serving engine restored the
                      .params subtree read-only from a checkpoint,
                      preflighted and placed for the serving mesh)
    weights_swap_begin  path, engine, from_step, to_step (the hot-swap
                      watcher found a newer committed checkpoint and
                      started fetching; serving continues on the old
                      weights throughout)
    weights_swap_done  step, swap_s, in_flight, path, engine, from_step,
                      fetched_bytes, reused_bytes (the serving engine
                      flipped its params reference at a step boundary —
                      swap_s covers fetch+verify+place+flip, in_flight
                      the requests that rode through untouched)
    weights_swap_rejected  path, engine, from_step, to_step, reason (a
                      fetch/digest/shape-stability failure: the manifest
                      is remembered as rejected — no retry loop — and
                      the replica keeps serving the old weights)
    swap_fetch_bytes  path, incremental, fetched_bytes, reused_bytes,
                      chunks_fetched, chunks_reused, changed_leaves,
                      leaves (the swap's transfer ledger: an incremental
                      zerostall fetch moves only changed-digest chunks;
                      vanilla/sharded fall back to a full read with
                      reused_bytes 0)
    replica_spawned   replica, incarnation, pid, backoff_s (the fleet
                      supervisor (re)spawned a serving-replica
                      subprocess; incarnation 0 is the initial spawn,
                      backoff_s the capped-exponential delay served
                      before a respawn)
    replica_dead      replica, rc, incarnation, was_ready (the
                      supervisor observed a replica process exit; the
                      router redrives its orphaned requests and the
                      slot heads to backoff or quarantine)
    replica_quarantined  replica, strikes, rc (a slot died before
                      becoming ready `quarantine_after` consecutive
                      times — it is parked, never respawned, so a
                      crash-looper burns bounded capacity)
    request_redriven  rid, from_replica, attempt (a replica died owning
                      this accepted request; the router re-queued it at
                      the head of the line through the router_redrive
                      seam under io_retry — redriven, never lost)
    fleet_shed        rid, queued, inflight, replicas (SLO-aware
                      admission refused a request: every replica at
                      max_inflight AND the router queue full — the
                      shed is loud and counted, submitted == done +
                      shed stays exact)
    trace_root        rid, trace, span, verdict, mono (the router minted
                      a distributed trace at admission: trace is the
                      deterministic 16-hex id from the content-derived
                      rid (+ optional deployment epoch), span the
                      ``<trace>:r`` root id — every cross-process span
                      of this request hangs under it)
    fleet_send        rid, kind, attempt, trace, mono (a traced frame
                      left a process at the socket edge: kind "submit"
                      on the router, kind "done" on the replica — one
                      half of the skew-anchor pair traceassembly aligns
                      process clocks with)
    fleet_recv        rid, kind, attempt, trace, mono (the matching
                      arrival edge: kind "submit" on the replica, kind
                      "done" on the router — the other anchor half; a
                      killed attempt honestly leaves its done legs
                      unpaired)
    trace_exemplar    rid, trace, reason, e2e_s (tail-based retention
                      mark after a successful drain: reason is
                      redriven|shed|p99_tail — traceassembly keeps the
                      FULL trace tree only for marked requests,
                      counts-only for the rest)
    canary_verdict    verdict, manifest, reason, canary, waved,
                      probe_p99_s, p99_gate_s (one canary rollout's
                      outcome: "pass" waved the manifest fleet-wide,
                      "fail" rolled every touched replica back to the
                      pin-leased old manifest — reason is
                      swap_rejected/token_mismatch/p99_regression)
    ckpt_policy       step, source, engine, interval_steps,
                      prev_interval_steps, optimum_steps, optimum_s,
                      cost_s, mtti_s, step_iter_s, failures_observed,
                      failures_window, reason, floor, ceiling,
                      static_interval, engine_recommendation (one goodput-
                      autopilot decision under --checkpoint-frequency
                      auto: the live failure model's inputs, the analytic
                      Young-Daly optimum, and the chosen bounded interval;
                      the trail survives kill/resume via the
                      failure_history.json sidecar and summarize_telemetry
                      renders it plus the goodput-vs-static
                      counterfactual)
    ckpt_policy_sidecar_error  error (the failure-history sidecar could
                      not be persisted — the policy degrades to stale
                      estimates on the next resume, the run continues)
    preempt_check     step, time_left_s, threshold_s
    preempt_notice / preempt_stop / preempt_estimate
    preempt_signal_escalation  signal, count, step (2nd signal mid-save)
    maintenance_event / maintenance_watcher_retired / maintenance_degraded
    maintenance_recovered / maintenance_watcher_hang  (flap + wedge drill)
    data_stall        wait_s, depth, batch
    loader_stall_timeout  wait_s, timeout_s, batch (stall watchdog tripped)
    fault_injected    type, site, ... (resilience.faults fired an injection)
    mfu_peak_unknown  device_kind (not in the peak table: mfu_pct is null)
    hang_detected     silent_s, window_s, sources{} (run-health watchdog:
                      no heartbeat progress for a full window)
    flight_dump       reason, path, last_step (a postmortem bundle was
                      written under <exp_dir>/.postmortem/)
    recompile         fn, count, changed (train-step signature drift — a
                      genuine retrace; recompile_total counter rides along)
    implicit_transfer fn, step, error (jax.transfer_guard tripped inside
                      the dispatch under --transfer-guard disallow)
    platform_fallback reason, resolved, expected (jax resolved CPU when
                      $PYRECOVER_EXPECT_ACCELERATOR declared an accelerator
                      — the trainer raises right after emitting it)
    spec_axis_dropped axis, mesh_axes (a sharding spec named a missing axis)
    ckpt_manifest_dtype_drift  path, detail (resume will cast the leaf)
    run_summary       status, step, + WallTimeTotals.as_dict() (goodput)

Serving spans + histograms (``serving/engine.py``; README "Serving"):
retroactive ``req_queue`` / ``req_prefill`` / ``req_decode`` spans per
finished request, a ``serving_restore`` span around the weight restore,
and the ``ttft_s`` / ``tpot_s`` / ``e2e_s`` request-latency histograms
(p50/p95/p99 rendered by ``tools/summarize_telemetry.py``).

Checkpoint spans (``checkpoint/sharded.py``; README "Tracing & trace
analysis"): inside the trainer's ``ckpt_save`` the sharded engine opens,
one after the other, ``ckpt_wait_previous`` (async only: the previous
save's background write; engine, step, waited — whether one was in
flight), ``ckpt_serialize`` (the device→host copy of the whole state and
the dispatch: first the one snapshot every later reader reads, then Orbax's
save call, which finds the copies made; engine, path, step, async_, bytes,
and, noted when the snapshot is in, snapshot_s, snapshot_bytes — the bytes
it copied — and fallback_leaves — the leaves left as they are: on the host
already, or replicated and cut up by Orbax on their devices) and
``ckpt_prune`` (retention on this thread; engine, step, removed). Inside
``ckpt_serialize``, between the snapshot and Orbax's call, lies
``ckpt_digest``: what the BLAKE2b tamper gate still costs the loop, picking
the snapshot's ``.params`` leaves and, on a sync save, hashing them; an
async save hands the hash on (fields engine, step, leaves, bytes, deferred
— the leaves whose hash was handed on). The three in a row cover every
blocking second of the save but the manifest and the fault seams. An async
save's hash is a commit future of the same Orbax save and records a
retroactive ``ckpt_digest_background`` (engine, step, leaves, bytes) on its
own thread; Orbax's commit thread, which waits for it and for the writes,
records a retroactive
``ckpt_write_background`` (engine) when it ends: its start to the commit.
Each feeds a ``ckpt_sharded_<phase>_s`` histogram.

Device-side scopes (``stepscopes.py``; README "Tracing & trace
analysis"): the jitted step opens ``jax.named_scope``s from ONE vocabulary
— sublayers ``embed``, ``attn``, ``ffn``, ``moe_ffn``, ``mamba_mixer``,
``loss_head`` / ``exit_head_loss``, ``optimizer``; kernels
``flash_attention``, ``ssm_scan``; groups ``layers``, ``loop_pass`` — which
reach a profile only through the compiled module's metadata; the
``step_scopes`` table is what joins a profile's operations to them
(``tools/step_scopes.py``; the benchmark's ``step_*_ms`` metrics).

Tracing + metrics events (``spans.py`` / ``metrics.py``; see README
"Tracing & trace analysis" for the span catalog):
    span_begin        name, span, parent, tid, thread, mono, ...
    span_end          name, span, parent, tid, mono, dur_s [, ok, error]
    span              retroactive span: name, span, parent, mono, dur_s
    metrics_snapshot  reason, counters{}, gauges{}, hists{name: {count,
                      sum, min, max, p50, p95, p99}}

Live metrics plane (``exporter.py`` / ``aggregate.py``; README "Live
metrics"): a per-process HTTP exposition endpoint over the metrics
registry, a fleet aggregator that scrapes N endpoints over TCP, and
SLO burn-rate alert rules evaluated on the exporter's serve thread:
    exporter_started  host, port, url, rules[] (exposition endpoint up)
    exporter_stopped  host, port, scrapes, uptime_s (bounded-join stop)
    metrics_scrape    poll, targets, ok, stale, seconds (one aggregator
                      sweep over its scrape targets)
    slo_alert         rule, kind, state (firing|cleared), value,
                      threshold, window_s, series (a burn-rate rule
                      transitioned; ``slo_alerts_total`` counter rides
                      along — summarizer "SLO alerts" section + doctor
                      evidence both read this trail)

``tools/summarize_telemetry.py`` turns a run's JSONL into a goodput
report; ``tools/traceview.py`` merges multi-host shards into a
Perfetto-loadable Chrome trace + straggler/spike/regression analysis;
``tools/tracepath.py`` (over ``traceassembly.py`` + ``tracing.py``)
reassembles cross-process request traces from per-process shards —
skew-corrected against the ``fleet_send``/``fleet_recv`` wire markers
— and attributes each request's end-to-end latency to critical-path
buckets; ``sinks.read_events`` is the tolerant (rotation-aware)
read-back all three build on.

Failure-time half (``flight.py`` / ``watchdog.py`` / ``detectors.py`` /
``doctor.py``; README "Crash forensics & run health"): an always-on
in-memory ring of recent events + open spans, black-box postmortem
bundles under ``<exp_dir>/.postmortem/`` (unhandled exceptions, fatal
signals, SIGTERM escalation, watchdog hangs, explicit ``flight.dump``),
silent-failure detectors (recompile / implicit transfer / platform
fallback / HBM gauges), and the ``doctor`` CLI that classifies a dead
run from those artifacts.
"""

from pyrecover_tpu.telemetry import flight, metrics, spans, tracing, watchdog
from pyrecover_tpu.telemetry.bus import (
    add_sink,
    close,
    emit,
    enabled,
    remove_sink,
)
from pyrecover_tpu.telemetry.sinks import (
    JsonlSink,
    LogSink,
    MemorySink,
    last_recorded_step,
    read_events,
    rotated_paths,
)
from pyrecover_tpu.telemetry.spans import collective_phase, record_span, span

__all__ = [
    "collective_phase",
    "emit",
    "enabled",
    "add_sink",
    "remove_sink",
    "close",
    "JsonlSink",
    "MemorySink",
    "LogSink",
    "read_events",
    "rotated_paths",
    "last_recorded_step",
    "span",
    "record_span",
    "spans",
    "tracing",
    "metrics",
    "flight",
    "watchdog",
]
