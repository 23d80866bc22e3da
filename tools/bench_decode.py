"""Decode/serving throughput bench (BENCH JSON contract).

Five modes, all printing exactly ONE JSON line on stdout. The two TIMED
modes (default, ``--serving``) measure the accelerator and exit non-zero
without one; the three ``--*-smoke`` drills are CPU by construction
(tiny models on virtual/CPU devices — correctness gates, not timings):

  * default — the lockstep steady-state decode number (unchanged
    contract: two timed generations with identical prefill, their
    difference is pure decode steps).
  * ``--serving`` — the continuous-batching engine under the seeded
    Poisson load generator (``pyrecover_tpu/serving/loadgen.py``):
    mixed prompt/output lengths on concurrent streams vs the
    serial-lockstep baseline, with ttft/tpot/e2e p50/p95/p99 and the
    fp32-vs-int8 resident-sequence capacity ledger in
    ``extra.serving`` — the serving numbers land in the same
    trajectory files as training MFU.
  * ``--smoke DIR`` — the format.sh serving gate: tiny checkpoint →
    serving restore → load generator on virtual devices, asserting
    greedy equality vs lockstep, zero leaked KV blocks at drain, and a
    non-empty latency report. Exit 1 on any violation.
  * ``--hotswap-smoke DIR`` — the format.sh hot-swap gate
    (``pyrecover_tpu/serving/hotswap/drill.py``): the one-process
    train-and-serve smoke (≥1 live swap, token equality vs a cold
    restore of the final manifest, incremental fetch accounting, p99
    across the swap window) followed by the SIGKILL-mid-swap chaos
    drill (restart serves the old manifest, pin-guarded GC, zero torn
    state). Exit 1 on any violation.
  * ``--fleet-smoke DIR`` — the format.sh serving-fleet gate
    (``pyrecover_tpu/serving/fleet/drill.py``): the replica-loss chaos
    drill (two subprocess replicas under open-loop load, SIGKILL one
    mid-flight, assert redrive with zero silent losses, bounded p99,
    supervisor respawn, crash-loop quarantine) followed by the
    canary-rollback drill (divergent manifest fails the token gate and
    rolls back pinned; healthy manifest waves). Exit 1 on any
    violation.

Run (on the chip): python tools/bench_decode.py [--serving] [--batch 8] ...
"""

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import require_accelerator  # noqa: E402


def _lockstep_bench(args, cfg, params, platform):
    """The original steady-state lockstep number (prefill cancelled)."""
    import numpy as np

    from pyrecover_tpu.models.decode import generate_tokens

    rng = np.random.default_rng(0)
    prompts = rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)
    ).tolist()

    # warmup: compiles the prefill (chunk=prompt_len) and the chunk=1 step
    generate_tokens(params, cfg, prompts, 4, max_len=args.max_len)

    # two timed runs with IDENTICAL prefill: their difference is N-1 pure
    # decode steps, so the prefill cost cancels out of the headline
    t0 = time.perf_counter()
    generate_tokens(params, cfg, prompts, 1, max_len=args.max_len)
    t_one = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = generate_tokens(params, cfg, prompts, args.new,
                          max_len=args.max_len)
    t_full = time.perf_counter() - t0
    assert len(out) == args.batch and all(
        len(seq) == args.prompt_len + args.new for seq in out
    )
    decode_s = max(t_full - t_one, 1e-9)
    steps = args.new - 1
    return {
        "metric": "decode_tok_per_sec",
        "value": round(args.batch * steps / decode_s, 1),
        "unit": "tok/s",
        "extra": {
            "model": args.model,
            "batch": args.batch,
            "prompt_len": args.prompt_len,
            "new_tokens": args.new,
            "cache_len": args.max_len,
            "per_seq_tok_s": round(steps / decode_s, 1),
            "ms_per_decode_step": round(decode_s / steps * 1e3, 2),
            "e2e_s_incl_prefill": round(t_full, 3),
            "platform": platform,
        },
    }


def _serving_bench(args, cfg, params, platform):
    """Continuous batching vs the serial-lockstep baseline on the SAME
    seeded workload; extra.serving is the BENCH trajectory record."""
    from pyrecover_tpu.serving.engine import ServingConfig, ServingEngine
    from pyrecover_tpu.serving.kvpool import resident_sequences
    from pyrecover_tpu.serving.loadgen import (
        lockstep_baseline,
        run_loadgen,
        sample_workload,
    )
    from pyrecover_tpu.telemetry import metrics

    max_model_len = args.max_len
    workload = sample_workload(
        args.requests, vocab_size=cfg.vocab_size,
        max_model_len=max_model_len, seed=args.seed,
        prompt_lens=(args.prompt_len // 4, args.prompt_len),
        new_tokens=(args.new // 4, args.new),
        arrival_rate=args.arrival_rate,
    )
    _, base = lockstep_baseline(params, cfg, workload, max_len=max_model_len)

    scfg = ServingConfig(
        block_size=args.block_size, max_seqs=args.max_seqs,
        prefill_chunk=args.prefill_chunk,
        prefill_token_budget=2 * args.prefill_chunk,
        kv_mode=args.kv_mode, max_model_len=max_model_len,
    )
    engine = ServingEngine(params, cfg, scfg)
    # warm both compiles outside the timed window (arrival offsets start
    # the clock at t0; a 30 s first-compile would poison every ttft)
    warm = engine.submit([1] * min(4, max_model_len - 1), 1)
    engine.run_until_drained()
    assert engine.result(warm) is not None
    metrics.reset()
    results, rep = run_loadgen(engine, workload)
    engine.pool.check_drained()
    assert all(r is not None for r in results)

    pool_bytes = engine.pool.pool_bytes()
    capacity = {
        mode: resident_sequences(
            pool_bytes, cfg, args.block_size, mode, max_model_len,
            dtype="float32" if mode == "native" else None,
        )
        for mode in ("native", "int8")
    }
    pct = lambda d: {k: (round(v, 6) if v is not None else None)  # noqa: E731
                     for k, v in d.items()}
    serving = {
        "requests": rep["requests"],
        "tokens_per_sec": rep["tokens_per_sec"],
        "baseline_tokens_per_sec": base["tokens_per_sec"],
        "speedup_vs_lockstep": round(
            rep["tokens_per_sec"] / max(base["tokens_per_sec"], 1e-9), 2
        ),
        "ttft_s": pct(rep["ttft_s"]),
        "tpot_s": pct(rep["tpot_s"]),
        "e2e_s": pct(rep["e2e_s"]),
        "backpressure_events": rep["backpressure_events"],
        "kv_mode": args.kv_mode,
        "block_size": args.block_size,
        "max_seqs": args.max_seqs,
        "pool_bytes": pool_bytes,
        "capacity_fp32": capacity["native"],
        "capacity_int8": capacity["int8"],
        "capacity_ratio": round(
            capacity["int8"] / max(capacity["native"], 1), 2
        ),
    }
    print(
        f"serving: {rep['tokens_per_sec']} tok/s vs lockstep "
        f"{base['tokens_per_sec']} ({serving['speedup_vs_lockstep']}x), "
        f"ttft p50 {serving['ttft_s']['p50']}s, int8 capacity "
        f"{capacity['int8']} vs fp32 {capacity['native']} seqs",
        file=sys.stderr,
    )
    return {
        "metric": "serving_tok_per_sec",
        "value": rep["tokens_per_sec"],
        "unit": "tok/s",
        "extra": {
            "model": args.model,
            "platform": platform,
            "serving": serving,
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama-1b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--new", type=int, default=128)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--serving", action="store_true",
                    help="continuous-batching loadgen bench")
    ap.add_argument("--smoke", metavar="DIR", default=None,
                    help="format.sh serving gate (tiny model, asserts)")
    ap.add_argument("--hotswap-smoke", metavar="DIR", default=None,
                    help="format.sh hot-swap gate: train-and-serve smoke "
                    "+ SIGKILL-mid-swap chaos drill")
    ap.add_argument("--fleet-smoke", metavar="DIR", default=None,
                    help="format.sh serving-fleet gate: replica-loss "
                    "chaos drill + canary-rollback drill")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arrival-rate", type=float, default=100.0)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-seqs", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--kv-mode", default="native",
                    choices=("native", "int8"))
    args = ap.parse_args()

    if args.smoke is not None:
        from pyrecover_tpu.serving.loadgen import serving_smoke

        report = serving_smoke(args.smoke, seed=args.seed)
        print(json.dumps({"metric": "serving_smoke", "ok": True,
                          **report}, default=str))
        return

    if args.hotswap_smoke is not None:
        from pyrecover_tpu.serving.hotswap import (
            hotswap_chaos_drill,
            hotswap_smoke,
        )

        work = Path(args.hotswap_smoke)
        report = hotswap_smoke(work, seed=args.seed)
        report["chaos"] = hotswap_chaos_drill(work, seed=args.seed)
        print(json.dumps({"metric": "hotswap_smoke", "ok": True,
                          **report}, default=str))
        return

    if args.fleet_smoke is not None:
        from pyrecover_tpu.serving.fleet.drill import fleet_smoke

        report = fleet_smoke(Path(args.fleet_smoke), seed=args.seed)
        print(json.dumps({"metric": "fleet_smoke", "ok": True,
                          **report}, default=str))
        return

    # first: the package places the persistent compile cache on import
    import pyrecover_tpu  # noqa: F401

    import jax

    from pyrecover_tpu.models import presets
    from pyrecover_tpu.models.llama import init_params

    platform = require_accelerator("bench_decode").platform

    cfg = dataclasses.replace(
        presets.PRESETS[args.model](max_seq_len=args.max_len),
        param_dtype="bfloat16", compute_dtype="bfloat16", remat=False,
    )
    params = init_params(jax.random.key(0), cfg)

    if args.serving:
        print(json.dumps(_serving_bench(args, cfg, params, platform)))
    else:
        print(json.dumps(_lockstep_bench(args, cfg, params, platform)))


if __name__ == "__main__":
    main()
