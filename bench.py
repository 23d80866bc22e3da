"""Benchmark harness: training throughput + checkpoint save/restore at ~1B.

Prints ONE JSON line:
  {"metric": "tokens_per_sec_per_chip", "value": N, "unit": "tok/s/chip",
   "vs_baseline": R, "extra": {...}}

The reference publishes no benchmark numbers (BASELINE.json "published": {};
its README defines procedures only — README.md:209-235), so ``vs_baseline``
is hardware-normalized: our measured MFU divided by 0.35, a typical
DDP+flash-attention MFU for ~1B models on the reference's H100-class target
hardware (whose 989e12 peak the reference hard-codes at train.py:287).
R > 1 means we extract more of our silicon than the reference stack
typically extracts of its own.

Extras report the BASELINE.md checkpoint target: save+restore seconds at
~1B params (target: save < 30 s).
"""

import argparse
import dataclasses
import json
import os
import shutil
import tempfile
import time
from pathlib import Path

import jax
import numpy as np


def build(model_scale, seq_len, batch_size, remat=True):
    from pyrecover_tpu.models import presets
    from pyrecover_tpu.models.llama import init_params

    preset = presets.PRESETS[model_scale]
    cfg = dataclasses.replace(
        preset(max_seq_len=seq_len),
        param_dtype="bfloat16",  # the reference's all-bf16 policy (train.py:100-101)
        compute_dtype="bfloat16",
        remat=remat,
        # Pallas flash attention: the seq×seq score matrix never
        # materializes (the SDPA path OOMs a 16G v5e at this config)
        attention_impl="flash",
    )
    return cfg


def require_accelerator(script):
    """A timed script measures the chip or nothing: with no accelerator it
    exits non-zero — no CPU fallback, no shrink, no number under a device
    metric's name. Returns the first device."""
    import sys

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print(
            f"{script}: resolved platform is cpu — this script times the "
            "accelerator and refuses to time anything else (CPU checks "
            "live in tests/ and `chip_smoke.py --rehearse-cpu`)",
            file=sys.stderr,
        )
        sys.exit(3)
    return dev


def main():
    # first: the package places the persistent compile cache on import
    import pyrecover_tpu  # noqa: F401

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama-1b")
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--skip-ckpt", action="store_true")
    ap.add_argument("--ckpt-model", default="llama-150m",
                    help="model preset whose state the checkpoint timing "
                         "uses (llama-1b = the full 7.6 GB train state)")
    ap.add_argument("--learning-rate", type=float, default=3e-4)
    ap.add_argument("--loss-chunk-size", type=int, default=512)
    ap.add_argument("--grad-accumulation-steps", "--grad-accum",
                    dest="grad_accum", type=int, default=1,
                    help="micro-steps per optimizer update (scanned inside "
                         "the jitted step); batch-size is the GLOBAL batch")
    ap.add_argument("--no-remat", action="store_true",
                    help="disable block rematerialization (more HBM, fewer FLOPs)")
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "save-attn", "auto"],
                    help="what the layer scan keeps under remat: nothing "
                         "(full), the flash call's residuals (save-attn: "
                         "the forward kernel runs once), or auto — the "
                         "richest save-set of utils/remat.py's ladder "
                         "that fits the live device kind, with a per-chip "
                         "batch suggestion (--no-remat stays no remat)")
    ap.add_argument("--flash-block-q", type=int, default=0,
                    help="flash-attention q tile; 0 = the per-device-kind "
                         "default (ops/flash_attention.py DEFAULT_BLOCKS, "
                         "fed by tools/bench_flash_blocks.py sweeps)")
    ap.add_argument("--flash-block-kv", type=int, default=0)
    ap.add_argument("--moe-dispatch", default="auto",
                    choices=["auto", "grouped", "einsum", "scatter"],
                    help="MoE dispatch backend (A/B the grouped ragged-GEMM "
                         "path against the r3 einsum/scatter backends)")
    ap.add_argument("--optimizer-sharding", default="none",
                    choices=["none", "zero1"],
                    help="run the timed loop with ZeRO-1 cross-replica "
                         "optimizer sharding (the bandwidth_lean extra "
                         "records the modelled wire/HBM deltas either way)")
    ap.add_argument("--grad-allreduce", default="fp32",
                    choices=["fp32", "bf16", "int8"],
                    help="gradient-sync wire format for the timed loop "
                         "(int8 = block-scaled quantized collectives with "
                         "error feedback)")
    ap.add_argument("--grad-bucket-mb", type=float, default=0,
                    help="latency-hidden gradients for the timed loop: "
                         "bucket the gradient sync at this MiB cap "
                         "(reverse-autodiff order, one collective per "
                         "bucket) so XLA overlaps wire time with the "
                         "remaining backward; extra.overlap records the "
                         "layout + modelled exposed-vs-hidden comm")
    ap.add_argument("--write-ckpt-baseline", default=None,
                    help="write a traceview-format checkpoint-phase "
                         "baseline JSON ({phase_key: p50_s}) from this "
                         "run's measured save timings — the artifact "
                         "committed at baselines/ckpt_phase_bench_"
                         "baseline.json that pins the zerostall blocking-"
                         "vs-vanilla-full-save ratio on the bench state")
    args = ap.parse_args()

    device = require_accelerator("bench")
    n_devices = jax.device_count()

    from pyrecover_tpu.checkpoint import load_ckpt_vanilla, save_ckpt_vanilla
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.data import DataLoader, StatefulSampler, SyntheticTextDataset
    from pyrecover_tpu.models.llama import init_params
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.parallel.mesh import MeshConfig, create_mesh
    from pyrecover_tpu.train import init_sharded_state
    from pyrecover_tpu.train_state import make_train_step
    from pyrecover_tpu.utils.perf import (
        get_num_flop_per_token,
        get_num_params,
        tpu_peak_flops,
    )

    model_cfg = build(args.model, args.seq_len, args.batch_size,
                      remat=not args.no_remat)
    model_cfg = dataclasses.replace(
        model_cfg, flash_block_q=args.flash_block_q,
        flash_block_kv=args.flash_block_kv, remat_policy=args.remat_policy,
        moe_dispatch=args.moe_dispatch,
    )
    # --remat-policy auto: pick the save-set (and a per-chip batch
    # suggestion) against the SC05 HBM model BEFORE anything builds the
    # model — the ROADMAP "spend the zero1 headroom" lever, measured
    remat_decision = None
    if args.remat_policy == "auto" and not args.no_remat:
        from pyrecover_tpu.utils.remat import resolve_remat_policy

        remat_decision = resolve_remat_policy(
            model_cfg, {"data": n_devices},
            batch_size=args.batch_size, seq_len=args.seq_len,
            loss_chunk_size=args.loss_chunk_size,
            optimizer_sharding=args.optimizer_sharding,
            grad_allreduce=args.grad_allreduce,
            device_kind=device.device_kind,
        )
        model_cfg = remat_decision.apply(model_cfg)
    train_cfg = TrainConfig(
        sequence_length=args.seq_len,
        batch_size=args.batch_size,
        learning_rate=args.learning_rate,
        lr_warmup_steps=10,
        optimizer_sharding=args.optimizer_sharding,
        grad_allreduce=args.grad_allreduce,
        # all-bf16 like the reference (train.py:100-101); TrainConfig's
        # fp32-master default would double params AND Adam moments — at the
        # 1B point that alone (14.2G of state) overflows a 16G v5e chip
        model_dtype="bf16",
        param_dtype="bf16",
    )
    train_cfg.model = model_cfg
    train_cfg.__post_init__()
    model_cfg = train_cfg.model

    mesh = create_mesh(MeshConfig())  # all devices on the data axis
    optimizer, _ = build_optimizer(train_cfg)
    state = init_sharded_state(
        jax.random.key(0), model_cfg, optimizer, mesh,
        optimizer_sharding=args.optimizer_sharding,
        grad_allreduce=args.grad_allreduce,
    )
    n_params = get_num_params(state.params)

    ds = SyntheticTextDataset(
        num_samples=1024, seq_len=args.seq_len, vocab_size=model_cfg.vocab_size
    )
    sampler = StatefulSampler(dataset_len=1024, global_batch_size=args.batch_size)
    loader = DataLoader(ds, sampler, pad_token_id=0, mesh=mesh, prefetch=2).start()
    step_fn = make_train_step(
        model_cfg, optimizer, loss_chunk_size=args.loss_chunk_size,
        grad_accumulation_steps=args.grad_accum,
        optimizer_sharding=args.optimizer_sharding,
        grad_allreduce=args.grad_allreduce,
        grad_bucket_mb=args.grad_bucket_mb,
    )

    def sync(state):
        # the timed region ends when the device has finished the last step
        jax.block_until_ready(state)

    from pyrecover_tpu import telemetry

    with jax.sharding.set_mesh(mesh):
        # warmup (compile)
        for _ in range(args.warmup):
            _, batch = next(loader)
            state, metrics = step_fn(state, batch)
        sync(state)

        # per-step wall times feed the telemetry metrics histogram so the
        # BENCH JSON carries the same metrics_snapshot-derived p50/p95/p99
        # a real run's telemetry stream reports (under async dispatch these
        # are enqueue+backpressure times; the final sync bounds the total)
        bench_sink = telemetry.add_sink(telemetry.MemorySink())
        step_hist = telemetry.metrics.histogram("bench_step_time_s")
        t0 = time.monotonic()
        t_prev = t0
        for _ in range(args.steps):
            _, batch = next(loader)
            state, metrics = step_fn(state, batch)
            t_now = time.monotonic()
            # jaxlint: disable-next=untimed-device-work -- per-step enqueue
            # time is the point here; the distribution's tail shows queue
            # backpressure, and the synced total below bounds the truth
            step_hist.observe(t_now - t_prev)
            t_prev = t_now
        sync(state)
        dt = time.monotonic() - t0
    loader.stop()

    telemetry.metrics.flush(reason="bench")
    snap = next(
        (e for e in reversed(bench_sink.events)
         if e["event"] == "metrics_snapshot"), {},
    )
    step_pct = (snap.get("hists") or {}).get("bench_step_time_s") or {}
    telemetry.remove_sink(bench_sink)

    tokens = args.steps * args.batch_size * args.seq_len
    tok_per_sec = tokens / dt
    tok_per_sec_chip = tok_per_sec / n_devices
    from pyrecover_tpu.models.presets import analytic_active_param_count

    # MoE: FLOPs/token counts only the top-k active experts.
    # exclude_embedding: the reference's 6N convention drops the token
    # embedding table (train.py:126-127); the untied output proj stays.
    n_params_active = analytic_active_param_count(
        model_cfg, exclude_embedding=True
    )
    flop_per_token = get_num_flop_per_token(
        n_params_active, model_cfg.n_layers, model_cfg.n_heads,
        model_cfg.head_dim, args.seq_len,
    )
    peak = tpu_peak_flops()
    mfu = flop_per_token * tok_per_sec / (peak * n_devices)

    # live HBM after the hot loop (params + opt state + cached buffers)
    mem = getattr(jax.devices()[0], "memory_stats", lambda: None)() or {}
    hbm_gb = round(mem.get("bytes_in_use", 0) / 1e9, 2) or None

    extra = {
        "model": args.model,
        "n_params": n_params,
        "platform": device.platform,
        "device_kind": device.device_kind,
        "n_devices": n_devices,
        "hbm_in_use_gb": hbm_gb,
        "seq_len": args.seq_len,
        "batch_size": args.batch_size,
        "step_time_s": round(dt / args.steps, 4),
        # metrics_snapshot-derived distribution (telemetry/metrics.py
        # log-bucketed histogram; dispatch-side times, see note above)
        "step_time_p50_s": step_pct.get("p50"),
        "step_time_p95_s": step_pct.get("p95"),
        "step_time_p99_s": step_pct.get("p99"),
        "mfu_pct": round(mfu * 100, 2),
        "mfu_convention": "6N excludes token embedding (ref train.py:126-127)",
        "tflops_per_chip": round(flop_per_token * tok_per_sec_chip / 1e12, 2),
    }

    # ---- bandwidth-lean update path: traffic + optimizer-HBM deltas --------
    # The shardcheck analytic traffic model priced at THIS bench point's
    # state and mesh: bytes-on-wire per step for the fp32/none baseline vs
    # the zero1/int8 lean path (and the mode actually timed above), plus
    # the per-chip optimizer HBM the zero1 layout frees — the recorded
    # proof of the modelled reduction the acceptance gate reads.
    from pyrecover_tpu.analysis.shardcheck.checks import (
        leaf_nbytes,
        spec_shard_factor,
    )
    from pyrecover_tpu.analysis.shardcheck.collectives import traffic_model
    from pyrecover_tpu.analysis.shardcheck.runner import abstract_state_leaves

    mesh_shape = {str(k): int(v) for k, v in dict(mesh.shape).items()}

    leaves_n, _ = abstract_state_leaves(model_cfg)
    param_leaves = [l for l in leaves_n if l[0].startswith(".params")]
    lean = traffic_model(
        param_leaves, mesh_shape,
        grad_allreduce="int8", optimizer_sharding="zero1",
    )
    configured = traffic_model(
        param_leaves, mesh_shape,
        grad_allreduce=args.grad_allreduce,
        optimizer_sharding=args.optimizer_sharding,
    )
    # reference-scale projection at 8 data replicas: a single-chip bench
    # host has no wire to model (every live number above is honestly 0),
    # but the state is real — this records the modelled reduction the
    # same state sees on a pod, so every BENCH round carries the delta
    ref_shape = {"data": 8}
    lean8 = traffic_model(
        param_leaves, ref_shape,
        grad_allreduce="int8", optimizer_sharding="zero1",
    )
    int8_only8 = traffic_model(param_leaves, ref_shape, grad_allreduce="int8")

    def opt_hbm_at(optimizer_sharding, shape):
        leaves, specs = abstract_state_leaves(
            model_cfg, optimizer_sharding=optimizer_sharding,
            mesh_shape=shape,
        )
        return sum(
            leaf_nbytes(sh, dt) // spec_shard_factor(spec, shape)
            for (path, sh, dt), spec in zip(leaves, specs)
            if path.startswith(".opt_state")
        )

    extra["bandwidth_lean"] = {
        "projected_dp8": {
            "wire_bytes_per_step_fp32_none":
                lean8["baseline"]["bytes_on_wire_per_step"],
            "wire_bytes_per_step_int8_none":
                int8_only8["configured"]["bytes_on_wire_per_step"],
            "wire_bytes_per_step_zero1_int8":
                lean8["configured"]["bytes_on_wire_per_step"],
            "wire_reduction_pct_zero1_int8": lean8["reduction_pct"],
            "wire_reduction_pct_int8": int8_only8["reduction_pct"],
            "optimizer_hbm_bytes_per_chip_none": opt_hbm_at("none", ref_shape),
            "optimizer_hbm_bytes_per_chip_zero1":
                opt_hbm_at("zero1", ref_shape),
        },
        "timed_mode": f"{args.grad_allreduce}/{args.optimizer_sharding}",
        "data_replicas": mesh_shape.get("data", 1),
        "wire_bytes_per_step_fp32_none":
            lean["baseline"]["bytes_on_wire_per_step"],
        "wire_bytes_per_step_zero1_int8":
            lean["configured"]["bytes_on_wire_per_step"],
        "wire_reduction_pct_zero1_int8": lean["reduction_pct"],
        "wire_bytes_per_step_timed_mode":
            configured["configured"]["bytes_on_wire_per_step"],
        "optimizer_hbm_bytes_per_chip_none": opt_hbm_at("none", mesh_shape),
        "optimizer_hbm_bytes_per_chip_zero1": opt_hbm_at("zero1", mesh_shape),
        "modelled": True,
    }
    hbm_none = extra["bandwidth_lean"]["optimizer_hbm_bytes_per_chip_none"]
    hbm_zero1 = extra["bandwidth_lean"]["optimizer_hbm_bytes_per_chip_zero1"]
    extra["bandwidth_lean"]["optimizer_hbm_reduction_pct"] = round(
        100.0 * (1 - hbm_zero1 / hbm_none), 2
    ) if hbm_none else 0.0

    # ---- overlap: bucket layout + modelled exposed-vs-hidden comm ----------
    # The layout the timed step actually ran with (live mesh), plus the
    # dp8 projection every round carries so single-chip rounds still
    # record the overlap delta a pod would see at this state size.
    from pyrecover_tpu.analysis.shardcheck.collectives import overlap_model

    overlap_live = overlap_model(
        param_leaves, mesh_shape, grad_allreduce=args.grad_allreduce,
        grad_bucket_mb=args.grad_bucket_mb,
    )
    overlap_dp8 = overlap_model(
        param_leaves, ref_shape, grad_allreduce=args.grad_allreduce,
        grad_bucket_mb=args.grad_bucket_mb,
    )
    extra["overlap"] = {
        "bucket_mb": float(args.grad_bucket_mb),
        "buckets": overlap_dp8["buckets"],
        "per_bucket_wire_bytes_dp8": overlap_dp8["per_bucket_wire_bytes"],
        "modelled_exposed_wire_bytes_dp8": overlap_dp8["exposed_wire_bytes"],
        "modelled_hidden_wire_bytes_dp8": overlap_dp8["hidden_wire_bytes"],
        "hidden_pct_dp8": overlap_dp8["hidden_pct"],
        "live": overlap_live,
        "modelled": True,
    }
    if remat_decision is not None:
        extra["remat_auto"] = remat_decision.as_event()

    # one-line overlap/remat summary (PR 10's wire-summary precedent):
    # the run's effective bucket layout + remat sizing, visible without
    # reading the jaxpr; stderr keeps the stdout contract at ONE JSON line
    import sys as _sys

    if overlap_dp8["buckets"]:
        per = overlap_dp8["per_bucket_wire_bytes"]
        ov_part = (
            f"{overlap_dp8['buckets']} buckets @ "
            f"{args.grad_bucket_mb:g} MiB "
            f"(dp8 wire {min(per)/2**20:.1f}..{max(per)/2**20:.1f} MiB "
            f"each, modelled hidden {overlap_dp8['hidden_pct']:.1f}%)"
        )
    elif args.grad_bucket_mb:
        ov_part = (
            f"bucket cap {args.grad_bucket_mb:g} MiB degenerate "
            "(one bucket) — unbucketed"
        )
    else:
        ov_part = "buckets off (single tail collective)"
    if remat_decision is not None:
        rm_part = (
            f"remat auto -> {remat_decision.rung} "
            f"(modelled {remat_decision.table[remat_decision.rung]/2**30:.2f}"
            f" GiB/chip vs the compiler's limit "
            + (f"{remat_decision.limit_bytes/2**30:.2f} GiB"
               if remat_decision.limit_bytes else "unknown")
            + f", suggested per-chip batch "
              f"{remat_decision.suggested_batch_per_chip})"
        )
    else:
        rm_part = (
            f"remat {args.remat_policy}"
            if not args.no_remat else "remat off"
        )
    print(f"bench: overlap — {ov_part}; {rm_part}", file=_sys.stderr)

    if not args.skip_ckpt:
        # Checkpoint engine timing, component-split so device<->host
        # transfer and the I/O engine are reported separately (the BASELINE
        # target is "sharded, preemption-triggered save < 30 s at 1B"):
        #   d2h / h2d    — device<->host transfer
        #   write / read — the host-side engine (native C++ parallel pwrite
        #                  or msgpack+disk; orbax/tensorstore for sharded)
        #   sharded blocking vs durable — async save: seconds the training
        #                  loop stalls vs seconds to durability
        # Default state is ~0.9GB (llama-150m); --ckpt-model llama-1b
        # measures the full-size state.
        from pyrecover_tpu.checkpoint.sharded import ShardedCheckpointer
        from pyrecover_tpu.checkpoint.vanilla import _leaf_to_numpy, read_ckpt_raw

        ckpt_model = build(args.ckpt_model, 512, 1)
        ckpt_state = (
            state if args.ckpt_model == args.model
            else init_sharded_state(
                jax.random.key(1), ckpt_model, optimizer, mesh
            )
        )
        state_bytes = sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(ckpt_state)
        )
        tmp = Path(tempfile.mkdtemp(prefix="bench_ckpt_"))
        try:
            ck = {"model": args.ckpt_model,
                  "state_gb": round(state_bytes / 1e9, 3)}

            # -- sharded async (Orbax): blocking vs durable vs restore -----
            with ShardedCheckpointer(use_async=True) as ckptr:
                blocking_s = ckptr.save(
                    tmp / "ckpt_1_sharded", ckpt_state, {"consumed": 1}
                )
                t0 = time.monotonic()
                ckptr.wait()
                durable_s = blocking_s + (time.monotonic() - t0)
                t0 = time.monotonic()
                restored, _, _ = ckptr.restore(
                    tmp / "ckpt_1_sharded", ckpt_state
                )
                jax.block_until_ready(restored.params)
                ck["sharded_blocking_s"] = round(blocking_s, 2)
                ck["sharded_durable_s"] = round(durable_s, 2)
                ck["sharded_restore_s"] = round(time.monotonic() - t0, 2)
            del restored  # full device copy; free HBM before the vanilla leg

            # -- vanilla, split: d2h | serialize+write | read | h2d --------
            t0 = time.monotonic()
            # _leaf_to_numpy allgathers non-addressable leaves on pods
            host_leaves = [
                _leaf_to_numpy(x) for x in jax.tree_util.tree_leaves(ckpt_state)
            ]
            d2h_s = time.monotonic() - t0
            host_state = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(ckpt_state), host_leaves
            )
            path = tmp / "ckpt_1.ckpt"
            t0 = time.monotonic()
            save_ckpt_vanilla(path, host_state, verify=False)  # host → disk
            write_s = time.monotonic() - t0
            del host_leaves, host_state
            t0 = time.monotonic()
            _meta, _paths, raw_leaves = read_ckpt_raw(path)  # disk → host
            read_s = time.monotonic() - t0
            del raw_leaves
            t0 = time.monotonic()
            restored, _, _ = load_ckpt_vanilla(path, ckpt_state, verify=False)
            jax.block_until_ready(restored.params)
            restore_s = time.monotonic() - t0  # read + h2d + reshard
            del restored
            nbytes = path.stat().st_size
            ck.update({
                "vanilla_d2h_s": round(d2h_s, 2),
                "vanilla_write_s": round(write_s, 2),
                "vanilla_read_s": round(read_s, 2),
                "vanilla_restore_s": round(restore_s, 2),
                "bytes": nbytes,
                "d2h_gbps": round(state_bytes / max(d2h_s, 1e-9) / 1e9, 3),
                "disk_write_gbps": round(nbytes / max(write_s, 1e-9) / 1e9, 3),
                # the file was just written: this read is page-cache-warm
                "read_gbps_cachewarm": round(
                    nbytes / max(read_s, 1e-9) / 1e9, 3
                ),
            })
            # -- zerostall: blocking window + chunk dedup ------------------
            # save twice: the first save pays the full chunk-store write,
            # the second (unchanged state) dedups to ~zero bytes — and the
            # blocking window stays snapshot-sized both times. The
            # emergency tier is off here (it would pin a full state copy
            # in the bench host's RAM for no measurement value).
            from pyrecover_tpu.checkpoint.zerostall import (
                chunkstore as zs_chunkstore,
                save_ckpt_zerostall,
            )

            zs_exp = tmp / "zs"
            b1, h1 = save_ckpt_zerostall(
                zs_exp / "ckpt_1.zs.json", ckpt_state, {"consumed": 1},
                extra_meta={"step": 1}, background=True,
                emergency_tier=False,
            )
            h1.wait()
            b2, h2 = save_ckpt_zerostall(
                zs_exp / "ckpt_2.zs.json", ckpt_state, {"consumed": 2},
                extra_meta={"step": 2}, background=True,
                emergency_tier=False,
            )
            h2.wait()
            reuse = zs_chunkstore.read_manifest(
                zs_exp / "ckpt_2.zs.json"
            )["reuse"]
            ck.update({
                "zerostall_blocking_s": round(b1, 4),
                "zerostall_blocking2_s": round(b2, 4),
                "zerostall_shadow_s": round(h1.shadow_s, 2),
                "zerostall_dedup_bytes_written": reuse["bytes_written"],
                "zerostall_dedup_bytes_reused": reuse["bytes_reused"],
            })

            # ckpt_blocking_s distribution across the engines measured
            # above — the same histogram the train loop feeds, so the
            # BENCH JSON's p50/total and a real run's telemetry agree on
            # what "blocking save time" means (the perf trajectory's
            # stall-shrinking signal across rounds)
            blocking_sink = telemetry.add_sink(telemetry.MemorySink())
            blocking_hist = telemetry.metrics.histogram("ckpt_blocking_s")
            for v in (blocking_s, write_s, b1, b2):
                blocking_hist.observe(v)
            telemetry.metrics.flush(reason="bench_ckpt")
            bsnap = next(
                (e for e in reversed(blocking_sink.events)
                 if e["event"] == "metrics_snapshot"), {},
            )
            bh = (bsnap.get("hists") or {}).get("ckpt_blocking_s") or {}
            telemetry.remove_sink(blocking_sink)
            ck["ckpt_blocking_p50_s"] = bh.get("p50")
            ck["ckpt_blocking_total_s"] = round(
                blocking_s + write_s + b1 + b2, 4
            )
            # the operational dial: Young-Daly optimal save cadence for
            # the measured per-save blocking cost of each engine across
            # an MTTI ladder (the goodput autopilot computes the same
            # quantity online from the live failure model — this is the
            # static planning table for operators reading BENCH JSON)
            from pyrecover_tpu.resilience.autopilot import (
                young_daly_interval_s,
            )

            ck["young_daly_interval_s"] = {
                engine_name: {
                    f"mtti_{mtti_s}s": round(
                        young_daly_interval_s(cost, mtti_s), 1
                    )
                    for mtti_s in (1800, 7200, 28800)
                }
                for engine_name, cost in (
                    ("vanilla", d2h_s + write_s),
                    ("zerostall", min(b1, b2)),
                )
            }
            if args.write_ckpt_baseline:
                # traceview-format {phase_key: p50_s}: the vanilla full
                # save vs the zerostall blocking window, ON THE SAME
                # STATE — the committed proof of the stall reduction
                baseline = {
                    "vanilla:ckpt_save": round(write_s + d2h_s, 6),
                    "zerostall:ckpt_blocking": round(min(b1, b2), 6),
                    "zerostall:ckpt_shadow": round(h1.shadow_s, 6),
                }
                Path(args.write_ckpt_baseline).parent.mkdir(
                    parents=True, exist_ok=True
                )
                # jaxlint: disable-next=torn-write -- committed baseline
                # artifact: written by an operator run, read by the CI gate;
                # a tear is caught by json.loads and rewritten
                Path(args.write_ckpt_baseline).write_text(
                    json.dumps(baseline, indent=2)
                )

            ck["host_cpu_cores"] = os.cpu_count()
            extra["ckpt"] = ck
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    reference_mfu = 0.35  # see module docstring
    extra["vs_baseline_assumption"] = (
        "ASSUMED reference MFU 0.35 (typical DDP+flash ~1B on H100-class; "
        "the reference publishes no numbers — BASELINE.json's published "
        "section is empty)"
    )
    print(json.dumps({
        "metric": "tokens_per_sec_per_chip",
        "value": round(tok_per_sec_chip, 1),
        "unit": "tok/s/chip",
        "vs_baseline": round(mfu / reference_mfu, 3),
        "extra": extra,
    }))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
