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
   "extra": {"best": [bq, bk], "results_ms": {...}, "platform": ...}}

Run (on the chip; exits non-zero without one):
  python tools/bench_flash_blocks.py [--seq-len 2048] ...
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import require_accelerator  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--causal", action=argparse.BooleanOptionalAction,
                    default=True)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from pyrecover_tpu.ops.flash_attention import flash_attention

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
    candidates = [(bq, bk) for bq, bk in candidates if bq <= s and bk <= s]

    results = {}
    for bq, bk in candidates:
        def loss(q, k, v, _bq=bq, _bk=bk):
            o = flash_attention(q, k, v, causal=args.causal,
                                block_q=_bq, block_kv=_bk)
            return jnp.sum(o.astype(jnp.float32))

        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        try:
            out = step(q, k, v)  # compile + warmup
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = step(q, k, v)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / args.iters * 1e3
        except Exception as e:  # noqa: BLE001 — a config may exceed VMEM
            print(f"block ({bq},{bk}) failed: {type(e).__name__}: "
                  f"{str(e)[:200]}", file=sys.stderr)
            continue
        results[f"{bq}x{bk}"] = round(ms, 3)
        print(f"block ({bq:4d},{bk:4d}): {ms:8.3f} ms", file=sys.stderr)

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
    print(json.dumps({
        "metric": "flash_block_sweep",
        "value": results[best_key],
        "unit": "ms fwd+bwd",
        "extra": {
            "best": [bq, bk],
            "results_ms": results,
            "shape": {"batch": b, "seq": s, "q_heads": hq,
                      "kv_heads": hkv, "head_dim": d},
            "iters": args.iters,
            "platform": device.platform,
            "device_kind": device.device_kind,
        },
    }))


if __name__ == "__main__":
    main()
