"""On-chip block-size sweep for the Pallas flash-attention kernel.

The kernel's (block_q, block_kv) tiling fixes its VMEM working set and its
grid parallelism; the right point depends on head_dim, sequence length and
the chip generation, and nothing but a measurement decides it (the round-3
default 1024x1024 was picked on first principles, never swept). This sweeps
the fwd+bwd attention op alone at the flagship bench point's shapes and
prints per-config times plus the argmin. The winner feeds the
PER-DEVICE-KIND defaults table (``ops/flash_attention.py::DEFAULT_BLOCKS``,
consumed whenever ``ModelConfig.flash_block_q/kv`` is 0 = auto and pinned
by ``tests/test_flash_attention.py::test_default_blocks_table``):
re-run the sweep on new hardware, update that row, update the pin.
``bench.py --flash-block-q/--flash-block-kv`` validates a candidate
end-to-end before it becomes the row.

Prints ONE JSON line:
  {"metric": "flash_block_sweep", "value": <best ms>, "unit": "ms fwd+bwd",
   "extra": {"best": [bq, bk], "results_ms": {...}, "kernel_ms": {...},
             "causal_to_full": {...}, "plan": {...}, "platform": ...}}
and writes it to ``chiprun_out/bench_flash_blocks[.<preset>].json``.
``results_ms`` is the host's clock round the whole fwd+bwd (XLA's transposes
and the loss's sum in it); ``kernel_ms`` is the device time of the events
named ``flash_fwd`` / ``flash_dq`` / ``flash_dkv`` alone, from a trace of one
call, causal and not (what ``flash_roofline`` divides by); ``causal_to_full``
is their ratio a kernel, to be read beside ``plan``: ``flash_plan``'s
``steps_visited`` of all the pairs is what a causal call would cost if a
pair above the diagonal cost nothing and a mask nothing.

Run (on the chip; exits non-zero without one):
  python tools/bench_flash_blocks.py [--seq-len 2048] ...
  python tools/bench_flash_blocks.py --preset ouro-2.6b --blocks 1024x1024
"""

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import require_accelerator  # noqa: E402

# the benchmark cells' attention shapes (batch rows a chip holds, q heads,
# kv heads; all at sequence 4096 and head size 128): BENCHMARK.json's
# configurations under benchmark/configs/
PRESETS = {
    "mistral-7b": (4, 32, 8),
    "ouro-2.6b": (4, 16, 16),
    "jamba2-3b": (2, 20, 1),
}
KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS),
                    help="a benchmark cell's batch and heads at sequence "
                         "4096, head size 128 (overrides those flags)")
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--blocks", default="",
                    help="comma list of <bq>x<bk> to time instead of the "
                         "eight candidates")
    ap.add_argument("--causal", action=argparse.BooleanOptionalAction,
                    default=True)
    args = ap.parse_args()
    if args.preset:
        args.batch_size, args.heads, args.kv_heads = PRESETS[args.preset]
        args.seq_len, args.head_dim = 4096, 128

    import jax
    import jax.numpy as jnp

    from benchmark.lib import xplane
    from pyrecover_tpu.ops.flash_attention import flash_attention, flash_plan

    device = require_accelerator("bench_flash_blocks")

    b, s = args.batch_size, args.seq_len
    hq, hkv, d = args.heads, args.kv_heads, args.head_dim
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, hq, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, hkv, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, hkv, d), jnp.bfloat16)

    # Eight candidates: compiles dominate the sweep's wall time
    candidates = [
        (256, 512), (512, 256), (512, 512), (512, 1024),
        (1024, 512), (1024, 1024), (1024, 2048), (2048, 1024),
    ]
    if args.blocks:
        candidates = [tuple(int(x) for x in c.split("x"))
                      for c in args.blocks.split(",")]
    candidates = [(bq, bk) for bq, bk in candidates if bq <= s and bk <= s]

    def build(bq, bk, causal):
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=causal,
                                block_q=bq, block_kv=bk)
            return jnp.sum(o.astype(jnp.float32))

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    def traced(step):
        """Device ms of each named kernel in one (compiled) fwd+bwd call."""
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                jax.block_until_ready(step(q, k, v))
            path = next(Path(tmp).glob("plugins/profile/*/*.xplane.pb"))
            planes = xplane.device_ops(xplane.load(path))
        events = next(iter(planes.values()), [])  # no device plane: no kernels
        out = {}
        for name in KERNELS:
            secs, calls = xplane.kernel_time(events, name)
            out[name] = round(secs / calls * 1e3, 4) if calls else None
        return out

    results, kernels, ratios, plans = {}, {}, {}, {}
    for bq, bk in candidates:
        step = build(bq, bk, args.causal)
        try:
            out = step(q, k, v)  # compile + warmup
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = step(q, k, v)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / args.iters * 1e3
            name = f"{bq}x{bk}"
            kernels[name] = {"causal" if args.causal else "full": traced(step)}
            if args.causal:  # the same blocks with every pair visited
                full = build(bq, bk, False)
                jax.block_until_ready(full(q, k, v))
                kernels[name]["full"] = traced(full)
                ratios[name] = {
                    kn: round(kernels[name]["causal"][kn] / t, 4)
                    for kn, t in kernels[name]["full"].items()
                    if t and kernels[name]["causal"][kn]
                }
        except Exception as e:  # noqa: BLE001 — a config may exceed VMEM
            print(f"block ({bq},{bk}) failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", file=sys.stderr)
            continue
        results[name] = round(ms, 3)
        plans[name] = flash_plan(s, s, bq, bk, args.causal).counts()
        print(f"block ({bq:4d},{bk:4d}): {ms:8.3f} ms  {kernels[name]}",
              file=sys.stderr)

    # A sweep that lost most of its candidates (tiles Mosaic refused) must
    # NOT look like a completed measurement: a truncated argmin is not the
    # answer, so it is an error, not a result line.
    if not results or len(results) < (len(candidates) + 1) // 2:
        print(f"bench_flash_blocks: only {len(results)}/{len(candidates)} "
              f"configs succeeded ({results}); not trustworthy",
              file=sys.stderr)
        sys.exit(1)
    best_key = min(results, key=results.get)
    bq, bk = (int(x) for x in best_key.split("x"))
    line = {
        "metric": "flash_block_sweep",
        "value": results[best_key],
        "unit": "ms fwd+bwd",
        "extra": {
            "best": [bq, bk],
            "results_ms": results,
            "kernel_ms": kernels,
            "causal_to_full": ratios,
            "plan": plans,
            "shape": {"batch": b, "seq": s, "q_heads": hq,
                      "kv_heads": hkv, "head_dim": d},
            "preset": args.preset,
            "iters": args.iters,
            "platform": device.platform,
            "device_kind": device.device_kind,
        },
    }
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    stem = "bench_flash_blocks" + (f".{args.preset}" if args.preset else "")
    # jaxlint: disable-next=torn-write -- a report, regenerated by a rerun
    (out_dir / f"{stem}.json").write_text(json.dumps(line) + "\n")
    print(json.dumps(line))


if __name__ == "__main__":
    main()
