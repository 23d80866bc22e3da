"""Hot-swap proof harness: train-and-serve smoke + kill-mid-swap drill.

Two gates, both wired into ``format.sh`` through
``tools/bench_decode.py --hotswap-smoke``:

  * :func:`hotswap_smoke` — ONE process trains and serves concurrently:
    a trainer thread perturbs a subset of the params and commits
    zerostall checkpoints while the load generator drives the engine
    open-loop for a fixed window and the watcher swaps weights live.
    Gated on ≥1 completed swap, token-level equality of a post-swap
    probe against a COLD restore of the final manifest, the incremental
    fetch moving only changed-leaf bytes (reused bytes reported), and
    p99 latency across the swap window staying within a (generous,
    CPU-noise-tolerant) bound of the same workload against a no-swap
    engine.
  * :func:`hotswap_chaos_drill` — a serving replica subprocess is
    SIGKILLed mid-fetch (the ``swap_fetch`` fault seam) while swapping
    toward a new manifest. The drill proves zero torn state: the pin
    lease survives the kill and shields the in-fetch manifest's chunks
    from GC, a restart serving the OLD manifest reproduces the pre-kill
    probe tokens bit-for-bit (every chunk digest-verified on read), a
    restarted watcher completes the interrupted swap cleanly, nothing
    is quarantined, and after the stale lease expires the chunk store
    holds exactly the live manifests' chunks (zero leaked).

The module doubles as the drill's server entry::

    python -m pyrecover_tpu.serving.hotswap.drill --serve EXP_DIR \
        --status STATUS.jsonl [--manifest PATH] [--watch] [...]
"""

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np

from pyrecover_tpu import telemetry
from pyrecover_tpu.telemetry import metrics

# p99 gate across the swap window vs the no-swap baseline: generous —
# CI CPU timing is noisy at millisecond decode steps — but real: a swap
# that stalls the serve loop (a synchronous fetch, a retrace storm)
# moves p99 by whole seconds and fails it.
P99_FACTOR = 5.0
P99_SLACK_S = 0.5


def _drill_model_config():
    """The tiny serving-smoke model — parent and server subprocesses
    must build the IDENTICAL config or probe equality means nothing."""
    from pyrecover_tpu.models import ModelConfig

    return ModelConfig().tiny(
        max_seq_len=96, vocab_size=64, compute_dtype="float32",
        param_dtype="float32",
    )


def _serving_config():
    from pyrecover_tpu.serving.engine import ServingConfig

    return ServingConfig(
        block_size=8, max_seqs=4, prefill_chunk=16,
        prefill_token_budget=32,
    )


def _train_state(seed):
    import jax

    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.train_state import create_train_state

    optimizer, _ = build_optimizer(TrainConfig())
    return create_train_state(
        jax.random.key(seed), _drill_model_config(), optimizer
    )


def _perturb(state, i):
    """Deterministic 'training step': move ONLY the lm head and final
    norm, leaving the layer stack and embeddings byte-identical — the
    unchanged leaves are what make the incremental fetch measurable."""
    import jax
    import jax.numpy as jnp

    def bump(x):
        return (x + jnp.asarray(1e-3 * i, x.dtype)).astype(x.dtype)

    params = dict(state.params)
    for key in ("output", "final_norm"):
        if key in params:
            params[key] = jax.tree_util.tree_map(bump, params[key])
    return dataclasses.replace(state, params=params)


def _save_zs(exp_dir, step, state):
    from pyrecover_tpu.checkpoint.zerostall import save_ckpt_zerostall

    path = Path(exp_dir) / f"ckpt_{step}.zs.json"
    save_ckpt_zerostall(
        path, state, {}, background=False, emergency_tier=False,
        extra_meta={"step": int(step)},
    )
    return path


def _probe_workload(seed, n=6):
    """Fixed post-swap probe: a handful of seeded prompts whose greedy
    outputs fingerprint the serving weights."""
    rng = np.random.default_rng(1000 + seed)
    cfg = _drill_model_config()
    return [
        {
            "prompt": rng.integers(
                0, cfg.vocab_size, (int(rng.integers(4, 13)),)
            ).tolist(),
            "max_new_tokens": int(rng.integers(4, 9)),
        }
        for _ in range(n)
    ]


def _run_probe(engine, probe):
    """Serve the probe through the engine (works with the background
    loop running or via the manual pump) and return the token lists in
    submission order."""
    if engine._loop_owner() is None:
        engine.reopen()  # a stopped engine refuses submit() (typed)
    rids = [
        engine.submit(req["prompt"], req["max_new_tokens"]) for req in probe
    ]
    if engine._loop_owner() is None:
        engine.run_until_drained()
    else:
        deadline = time.monotonic() + 120.0
        while any(engine.result(r) is None for r in rids):
            if time.monotonic() > deadline:
                raise TimeoutError("probe requests did not drain")
            time.sleep(0.005)
    return [engine.result(r) for r in rids]


# ---- train-and-serve smoke --------------------------------------------------


def hotswap_smoke(workdir, *, duration_s=3.0, n_saves=3, seed=0,  # jaxlint: host-only
                  arrival_rate=120.0):
    """The format.sh train-and-serve gate body. Returns the report dict;
    raises AssertionError on any violated invariant."""
    workdir = Path(workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    sink = telemetry.JsonlSink(workdir / "hotswap_telemetry.jsonl")
    telemetry.add_sink(sink)
    mem = telemetry.MemorySink()
    telemetry.add_sink(mem)
    metrics.reset()
    try:
        return _hotswap_smoke_body(
            workdir, mem, duration_s=duration_s, n_saves=n_saves,
            seed=seed, arrival_rate=arrival_rate,
        )
    finally:
        metrics.flush(reason="hotswap_smoke")
        telemetry.remove_sink(mem)
        telemetry.remove_sink(sink)
        sink.close()


def _hotswap_smoke_body(workdir, mem, *, duration_s, n_saves, seed,
                        arrival_rate):
    from pyrecover_tpu.serving.engine import ServingEngine
    from pyrecover_tpu.serving.hotswap.swap import HotSwapper
    from pyrecover_tpu.serving.loadgen import open_loop_workload, run_loadgen
    from pyrecover_tpu.serving.restore import load_serving_params

    cfg = _drill_model_config()
    exp = workdir / "exp"
    exp.mkdir(parents=True, exist_ok=True)
    state = _train_state(seed)
    first = _save_zs(exp, 1, state)
    params, _ = load_serving_params(first, cfg)
    engine = ServingEngine(params, cfg, _serving_config())
    # warm both compiles outside the measured window (identically for
    # the no-swap baseline below, so the p99 comparison is honest)
    engine.submit([1, 2, 3], 2)
    engine.run_until_drained()

    swapper = HotSwapper(
        engine, exp, cfg, loaded_path=first, poll_interval_s=0.03,
    )
    workload = open_loop_workload(
        duration_s, vocab_size=cfg.vocab_size,
        max_model_len=engine.max_model_len, seed=seed,
        prompt_lens=(3, 20), new_tokens=(1, 10),
        arrival_rate=arrival_rate,
    )
    final_step = n_saves + 1

    def _trainer():
        st = state
        gap = duration_s / (n_saves + 1)
        for i in range(2, final_step + 1):
            time.sleep(gap)
            t_iter = time.monotonic()
            st = _perturb(st, i)
            _save_zs(exp, i, st)
            # the trainer half's step cadence, into the same series the
            # real train loop feeds — the live scrape's step-time p50
            metrics.histogram("step_iter_s").observe(
                time.monotonic() - t_iter
            )
            metrics.gauge("train_step").set(i)

    # live telemetry plane over the WHOLE train-and-serve window: the
    # exporter serves this process's registry over real TCP; one scrape
    # lands mid-run (>= half the requests finished, trainer + swapper
    # still live) and one post-drain — the format.sh gate checks both
    # against the post-hoc summarizer
    from pyrecover_tpu.serving.loadgen import live_scrape_digest
    from pyrecover_tpu.telemetry.aggregate import scrape
    from pyrecover_tpu.telemetry.exporter import MetricsExporter

    exporter = MetricsExporter(port=0).start()
    scrapes = {}

    trainer = threading.Thread(target=_trainer, name="hotswap-trainer")
    swapper.start()
    trainer.start()
    try:
        metrics.reset()
        _, swap_report = run_loadgen(
            engine, workload,
            mid_hook=lambda: scrapes.__setitem__(
                "mid",
                scrape(f"127.0.0.1:{exporter.port}", timeout_s=30.0),
            ),
        )
        scrapes["final"] = scrape(
            f"127.0.0.1:{exporter.port}", timeout_s=30.0
        )
    finally:
        exporter.stop()
        trainer.join(timeout=60.0)
        deadline = time.monotonic() + 30.0
        while (swapper.loaded_step < final_step
               and time.monotonic() < deadline):
            time.sleep(0.02)
        swapper.stop()
    if trainer.is_alive():
        raise AssertionError("hotswap smoke: trainer thread wedged")
    if swapper.loaded_step < final_step:
        raise AssertionError(
            f"hotswap smoke: watcher never reached the final manifest "
            f"(loaded step {swapper.loaded_step} < {final_step}; "
            f"rejected: {swapper.rejected})"
        )

    # probe AFTER the final swap (the manual pump applies any staged
    # flip), then prove token-level equality vs a COLD restore
    probe = _probe_workload(seed)
    live_tokens = _run_probe(engine, probe)
    engine.pool.check_drained()
    final_path = exp / f"ckpt_{final_step}.zs.json"
    cold_params, _ = load_serving_params(final_path, cfg)
    cold = ServingEngine(cold_params, cfg, _serving_config())
    cold_tokens = _run_probe(cold, probe)
    mismatched = [
        i for i, (a, b) in enumerate(zip(live_tokens, cold_tokens))
        if a != b
    ]
    if mismatched:
        raise AssertionError(
            f"hotswap smoke: post-swap serving diverged from a cold "
            f"restore of {final_path.name} on probes {mismatched}"
        )

    # swap accounting from the telemetry trail: ≥1 live swap, and the
    # incremental fetch moved strictly less than the full params bytes
    events = mem.events
    done = [e for e in events if e["event"] == "weights_swap_done"]
    rejected = [e for e in events if e["event"] == "weights_swap_rejected"]
    fetches = [
        e for e in events
        if e["event"] == "swap_fetch_bytes" and e.get("incremental")
    ]
    if not done:
        raise AssertionError("hotswap smoke: no weights_swap_done event")
    if rejected:
        raise AssertionError(
            f"hotswap smoke: unexpected swap rejections: {rejected}"
        )
    from pyrecover_tpu.checkpoint.zerostall.chunkstore import read_manifest

    params_bytes = sum(
        int(e["nbytes"]) for e in read_manifest(final_path)["leaves"]
        if e["path"].startswith(".params")
    )
    fetched = sum(int(e["fetched_bytes"]) for e in fetches)
    reused = sum(int(e["reused_bytes"]) for e in fetches)
    if not fetches or reused <= 0:
        raise AssertionError(
            f"hotswap smoke: incremental fetch reused no bytes ({fetches})"
        )
    if fetched >= len(fetches) * params_bytes:
        raise AssertionError(
            f"hotswap smoke: fetch moved {fetched} bytes over "
            f"{len(fetches)} swap(s) of a {params_bytes}-byte params set "
            "— nothing was incremental"
        )

    # p99 across the swap window vs the SAME workload on a no-swap
    # engine (already compiled above — both runs are warm)
    cold.submit([1, 2, 3], 2)
    cold.run_until_drained()
    metrics.reset()
    _, base_report = run_loadgen(cold, workload)
    p99 = swap_report["e2e_s"]["p99"]
    base_p99 = base_report["e2e_s"]["p99"]
    gate = P99_FACTOR * (base_p99 or 0.0) + P99_SLACK_S
    if p99 is None or base_p99 is None:
        raise AssertionError("hotswap smoke: empty latency report")
    if p99 > gate:
        raise AssertionError(
            f"hotswap smoke: p99 across the swap window {p99:.4f}s "
            f"exceeds the gate {gate:.4f}s ({P99_FACTOR}x no-swap "
            f"{base_p99:.4f}s + {P99_SLACK_S}s)"
        )
    return {
        "requests": swap_report["requests"],
        "tokens_per_sec": swap_report["tokens_per_sec"],
        "swaps": len(done),
        "rejected": len(rejected),
        "final_step": final_step,
        "token_equal": True,
        "probe_requests": len(probe),
        "params_bytes": params_bytes,
        "fetched_bytes": fetched,
        "reused_bytes": reused,
        "p99_e2e_s": round(p99, 6),
        "noswap_p99_e2e_s": round(base_p99, 6),
        "p99_gate_s": round(gate, 6),
        "duration_s": duration_s,
        "live_scrape": {
            "url": f"http://127.0.0.1:{exporter.port}",
            "mid": live_scrape_digest(scrapes["mid"]),
            "final": live_scrape_digest(scrapes["final"]),
        },
    }


# ---- kill-mid-swap chaos drill ----------------------------------------------


def _server_cmd(exp, status, *, manifest=None, watch=False,
                exit_after_swap=False, poll=0.05, probe_seed=0):
    cmd = [
        sys.executable, "-m", "pyrecover_tpu.serving.hotswap.drill",
        "--serve", str(exp), "--status", str(status),
        "--poll", str(poll), "--probe-seed", str(probe_seed),
    ]
    if manifest is not None:
        cmd += ["--manifest", str(manifest)]
    if watch:
        cmd.append("--watch")
    if exit_after_swap:
        cmd.append("--exit-after-swap")
    return cmd


def _spawn_server(exp, status, *, fault_plan=None, **kw):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if fault_plan is not None:
        env["PYRECOVER_FAULT_PLAN"] = json.dumps(fault_plan)
    else:
        env.pop("PYRECOVER_FAULT_PLAN", None)
    return subprocess.Popen(
        _server_cmd(exp, status, **kw), env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
    )


def _scan_status(status_path, event):
    status_path = Path(status_path)
    if not status_path.exists():
        return None
    for line in status_path.read_text().splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue  # torn tail of an append mid-write
        if rec.get("event") == event:
            return rec
    return None


def _wait_status(status_path, event, proc, *, timeout_s=120.0):
    """Tail the server's status JSONL for the first ``event`` record.
    Raises if the server dies without writing it, or on timeout."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        rec = _scan_status(status_path, event)
        if rec is not None:
            return rec
        if proc.poll() is not None:
            # one last read: the record may have landed just before exit
            rec = _scan_status(status_path, event)
            if rec is not None:
                return rec
            raise AssertionError(
                f"hotswap drill: server died (rc {proc.returncode}) "
                f"before reporting {event!r}"
            )
        time.sleep(0.05)
    raise TimeoutError(
        f"hotswap drill: no {event!r} status within {timeout_s}s"
    )


def hotswap_chaos_drill(workdir, *, seed=0, timeout_s=180.0):  # jaxlint: host-only
    """SIGKILL a serving replica mid-swap; prove zero torn state. See
    the module docstring for the verdict list. Returns the report dict;
    raises AssertionError on any violation."""
    from pyrecover_tpu.checkpoint.zerostall import pins
    from pyrecover_tpu.checkpoint.zerostall.chunkstore import (
        chunks_root,
        collect_garbage,
        referenced_digests,
    )
    from pyrecover_tpu.resilience.quarantine import list_quarantined
    from pyrecover_tpu.serving.engine import ServingEngine
    from pyrecover_tpu.serving.restore import load_serving_params

    workdir = Path(workdir)
    exp = workdir / "chaos_exp"
    exp.mkdir(parents=True, exist_ok=True)
    cfg = _drill_model_config()
    state_a = _train_state(seed)
    path1 = _save_zs(exp, 1, state_a)
    state_b = _perturb(state_a, 2)
    probe = _probe_workload(seed)

    # parent-side ground truth for both manifests (cold restores)
    params_a, _ = load_serving_params(path1, cfg)
    probe_a = _run_probe(ServingEngine(params_a, cfg, _serving_config()),
                         probe)

    # 1) server serves manifest 1, watcher armed, killed mid-fetch: the
    # swap_fetch seam fires on the FIRST chunk read of the swap toward
    # manifest 2 (save_index 0 — a serving replica never saves)
    status1 = workdir / "status_kill.jsonl"
    plan = {"seed": seed, "faults": [{
        "type": "kill9_during_save", "save_index": 0, "site": "swap_fetch",
    }]}
    proc = _spawn_server(exp, status1, watch=True, fault_plan=plan,
                         probe_seed=seed)
    try:
        ready = _wait_status(status1, "ready", proc, timeout_s=timeout_s)
        if ready["step"] != 1 or ready["probe"] != probe_a:
            raise AssertionError(
                f"hotswap drill: pre-kill server served {ready['step']} "
                "with drifted probe tokens"
            )
        path2 = _save_zs(exp, 2, state_b)
        rc = proc.wait(timeout=timeout_s)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    if rc != -9:
        raise AssertionError(
            f"hotswap drill: expected the swap_fetch SIGKILL (rc -9), "
            f"got rc {rc}"
        )

    # 2) torn-state forensics: the pin lease survived the kill, GC with
    # the pin held collects nothing premature, nothing was quarantined,
    # and the killed segment's trail shows begin-without-done
    pinned = [p.name for p in pins.live_pins(exp)]
    if not any(path2.name in name for name in pinned):
        raise AssertionError(
            f"hotswap drill: no pin lease for {path2.name} after the "
            f"mid-fetch kill (pins: {pinned})"
        )
    collect_garbage(exp)
    refs = referenced_digests(exp)
    on_disk = {
        p.name for p in chunks_root(exp).rglob("*") if p.is_file()
    }
    missing = sorted(refs - on_disk)
    if missing:
        raise AssertionError(
            f"hotswap drill: {len(missing)} referenced chunk(s) gone "
            f"after GC with a pin held (e.g. {missing[:3]})"
        )
    quarantined = [p.name for p in list_quarantined(exp)]
    if quarantined:
        raise AssertionError(
            f"hotswap drill: kill mid-swap quarantined {quarantined}"
        )
    server_events = telemetry.read_events(exp / "server_telemetry.jsonl")
    begins = [e for e in server_events
              if e["event"] == "weights_swap_begin" and e.get("to_step") == 2]
    dones = [e for e in server_events
             if e["event"] == "weights_swap_done" and e.get("step") == 2]
    kills = [e for e in server_events
             if e["event"] == "fault_injected" and e.get("site") == "swap_fetch"]
    if not begins or dones or not kills:
        raise AssertionError(
            f"hotswap drill: torn telemetry trail — begins={len(begins)} "
            f"dones={len(dones)} kills={len(kills)}"
        )

    # 3) restart serving the OLD manifest: bit-identical probe tokens,
    # every chunk digest-verified on read — zero torn state
    status2 = workdir / "status_old.jsonl"
    proc2 = _spawn_server(exp, status2, manifest=path1, watch=False,
                          probe_seed=seed)
    try:
        ready2 = _wait_status(status2, "ready", proc2, timeout_s=timeout_s)
    finally:
        if proc2.poll() is None:
            proc2.terminate()
        proc2.wait(timeout=60)
    if ready2["step"] != 1 or ready2["probe"] != probe_a:
        raise AssertionError(
            "hotswap drill: restart on the old manifest did not "
            "reproduce the pre-kill serving output"
        )

    # 4) a restarted watcher completes the interrupted swap cleanly
    probe_b = _run_probe(
        ServingEngine(load_serving_params(path2, cfg)[0], cfg,
                      _serving_config()),
        probe,
    )
    status3 = workdir / "status_resume.jsonl"
    proc3 = _spawn_server(exp, status3, manifest=path1, watch=True,
                          exit_after_swap=True, probe_seed=seed)
    try:
        swapped = _wait_status(status3, "swapped", proc3,
                               timeout_s=timeout_s)
        rc3 = proc3.wait(timeout=timeout_s)
    finally:
        if proc3.poll() is None:
            proc3.kill()
            proc3.wait(timeout=30)
    if swapped["step"] != 2 or swapped["probe"] != probe_b:
        raise AssertionError(
            "hotswap drill: the restarted watcher's completed swap does "
            "not match a cold restore of the target manifest"
        )
    if rc3 != 0:
        raise AssertionError(
            f"hotswap drill: resume server exited rc {rc3}"
        )

    # 5) the dead fetcher's stale lease expires (TTL forced to zero) and
    # a final GC leaves the store holding exactly the live manifests'
    # chunks — the kill leaked nothing
    pins.expire_stale_pins(exp, ttl_s=0.0)
    collect_garbage(exp)
    refs = referenced_digests(exp)
    on_disk = {
        p.name for p in chunks_root(exp).rglob("*") if p.is_file()
    }
    leaked = sorted(on_disk - refs)
    missing = sorted(refs - on_disk)
    if leaked or missing:
        raise AssertionError(
            f"hotswap drill: chunk ledger broken after lease expiry "
            f"(leaked {leaked[:3]}, missing {missing[:3]})"
        )
    return {
        "kill_rc": rc,
        "pin_after_kill": pinned,
        "old_manifest_probe_equal": True,
        "resumed_swap_step": int(swapped["step"]),
        "quarantined": quarantined,
        "chunks_on_disk": len(on_disk),
        "chunks_referenced": len(refs),
        "chunks_leaked": len(leaked),
        "swap_begins_before_kill": len(begins),
        "swap_fetch_kills": len(kills),
    }


# ---- the drill's server process ---------------------------------------------


def _append_status(path, record):
    # jaxlint: disable-next=torn-write -- append-only drill status stream;
    # the parent's reader skips a torn tail line and re-polls
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")
        f.flush()


def _serve_main(args):  # jaxlint: host-only
    """The drill's serving replica: load a manifest, report a probe
    fingerprint, optionally watch-and-swap. Status protocol (JSONL):
    ``{"event": "ready", "step", "probe"}`` once serving, then one
    ``{"event": "swapped", "step", "probe"}`` per completed swap."""
    from pyrecover_tpu.checkpoint.registry import (
        get_latest_checkpoint,
        parse_step,
    )
    from pyrecover_tpu.serving.engine import ServingEngine
    from pyrecover_tpu.serving.hotswap.swap import HotSwapper
    from pyrecover_tpu.serving.restore import load_serving_params

    exp = Path(args.serve)
    sink = telemetry.JsonlSink(exp / "server_telemetry.jsonl")
    telemetry.add_sink(sink)
    path = Path(args.manifest) if args.manifest else get_latest_checkpoint(exp)
    if path is None:
        print(f"no checkpoint in {exp}", file=sys.stderr)
        return 2
    cfg = _drill_model_config()
    params, _ = load_serving_params(path, cfg)
    engine = ServingEngine(params, cfg, _serving_config())
    probe = _probe_workload(args.probe_seed)
    tokens = _run_probe(engine, probe)
    _append_status(args.status, {
        "event": "ready", "step": parse_step(path), "probe": tokens,
    })
    if not args.watch:
        telemetry.remove_sink(sink)
        sink.close()
        return 0
    swapper = HotSwapper(
        engine, exp, cfg, loaded_path=path, poll_interval_s=args.poll,
    )
    engine.start()
    swapper.start()
    try:
        reported = swapper.loaded_step
        deadline = time.monotonic() + args.serve_s
        while time.monotonic() < deadline:
            time.sleep(args.poll)
            step = swapper.loaded_step
            if step > reported:
                # probe through the live engine: the staged swap applies
                # at the next pump, and results reflect the new weights
                tokens = _run_probe(engine, probe)
                _append_status(args.status, {
                    "event": "swapped", "step": step, "probe": tokens,
                })
                reported = step
                if args.exit_after_swap:
                    return 0
    finally:
        swapper.stop()
        engine.stop()
        telemetry.remove_sink(sink)
        sink.close()
    return 0


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--serve", required=True,
                    help="experiment dir to serve from (server mode)")
    ap.add_argument("--status", required=True,
                    help="status JSONL the parent drill tails")
    ap.add_argument("--manifest", default=None,
                    help="serve this checkpoint (default: registry latest)")
    ap.add_argument("--watch", action="store_true",
                    help="run the hot-swap watcher after ready")
    ap.add_argument("--exit-after-swap", action="store_true",
                    help="exit 0 after reporting the first completed swap")
    ap.add_argument("--poll", type=float, default=0.05)
    ap.add_argument("--probe-seed", type=int, default=0)
    ap.add_argument("--serve-s", type=float, default=300.0,
                    help="watch-mode serving window before a clean exit")
    args = ap.parse_args(argv)
    return _serve_main(args)


if __name__ == "__main__":
    sys.exit(main())
