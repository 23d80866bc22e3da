"""On-chip timing of the selective scan alone (ops/selective_scan.py): the
Pallas kernel pair against the XLA formulation, forward and forward +
backward, at a hybrid stack's own widths (Jamba2-3B: 2 x 4096 tokens,
d_inner 5120, d_state 16), over the kernels' channel block and the chunk.

Both formulations sit under one ``custom_vjp`` and count the same work; this
is where "which one ships" is decided and where the numbers in PERF.md
(section 6) come from. Also checks, on the chip, that the two agree.

Prints ONE JSON line:
  {"metric": "selective_scan_sweep", "value": <best fwd+bwd ms>, "unit": "ms",
   "extra": {"results_ms": {variant: [fwd, fwd_bwd]}, "least_ms": ...,
             "max_gap": ..., "platform": ...}}
and writes it to ``chiprun_out/bench_selective_scan.json``.

Run (on the chip; exits non-zero without one):
  python tools/bench_selective_scan.py [--variants pallas:512:256,xla:256:8]
A variant is ``pallas:<block_d>:<chunk>`` or ``xla:<chunk>:<unroll>``.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import require_accelerator  # noqa: E402

DEFAULT_VARIANTS = (
    "pallas:256:256,pallas:512:256,pallas:1024:256,pallas:512:128,"
    "xla:256:8,xla:128:1"
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--d-inner", type=int, default=5120)
    ap.add_argument("--d-state", type=int, default=16)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--variants", default=DEFAULT_VARIANTS)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import pyrecover_tpu.ops.selective_scan as ss

    device = require_accelerator("bench_selective_scan")
    b, s, d, n = args.batch_size, args.seq_len, args.d_inner, args.d_state
    k = jax.random.split(jax.random.key(0), 7)
    f32 = jnp.float32
    u = jax.random.normal(k[0], (b, s, d), f32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, d), f32) - 4.0)
    a = -jnp.exp(jax.random.normal(k[2], (d, n), f32))
    bm = jax.random.normal(k[3], (b, s, n), f32)
    cm = jax.random.normal(k[4], (b, s, n), f32)
    skip = jnp.ones((d,), f32)
    w = jax.random.normal(k[5], (b, s, d), f32)
    operands = (u, dt, a, bm, cm, skip)

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))  # compile + warm up
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1e3, out

    results, outputs, failed = {}, {}, {}
    for variant in args.variants.split(","):
        impl, p1, p2 = variant.split(":")
        if impl == "pallas":
            ss.DEFAULT_BLOCK_D, chunk = int(p1), int(p2)
        else:
            chunk, ss.XLA_UNROLL = int(p1), int(p2)
        fwd = jax.jit(lambda *xs, c=chunk, i=impl: ss.selective_scan(
            *xs, chunk=c, impl=i))
        grad = jax.jit(jax.grad(
            lambda *xs, c=chunk, i=impl: jnp.sum(ss.selective_scan(
                *xs, chunk=c, impl=i) * w), argnums=range(6)))
        try:
            t_f, y = timed(fwd, *operands)
            t_g, g = timed(grad, *operands)
        except Exception as err:  # a variant the compiler refuses is a result
            failed[variant] = f"{type(err).__name__}: {str(err)[:300]}"
            continue
        results[variant] = [round(t_f, 3), round(t_g, 3)]
        outputs[variant] = (y, g)
        print(f"{variant}: fwd {t_f:.2f} ms, fwd+bwd {t_g:.2f} ms",
              file=sys.stderr)

    # agreement of every variant with the first, on the chip
    gaps = {}
    names = list(outputs)
    for name in names[1:]:
        y0, g0 = outputs[names[0]]
        y1, g1 = outputs[name]
        rel = [float(jnp.max(jnp.abs(p - q)) / jnp.max(jnp.abs(p)))
               for p, q in zip((y0, *g0), (y1, *g1))]
        gaps[name] = max(rel)
    # the least the chip could take: u, dt, B, C in and y out, float32 here
    # (forward); with their cotangents both ways for forward + backward
    peaks = json.loads((ROOT / "benchmark/lib/peaks.json").read_text())
    peak = peaks.get(device.device_kind, {}).get("hbm_bytes_per_s")
    tok = b * s
    fwd_bytes = tok * 4 * (3 * d + 2 * n)
    least = {"fwd": fwd_bytes / peak * 1e3,
             "fwd_bwd": 3 * fwd_bytes / peak * 1e3} if peak else None
    best = min(results.values(), key=lambda r: r[1], default=[None, None])
    line = {
        "metric": "selective_scan_sweep", "value": best[1], "unit": "ms",
        "extra": {
            "results_ms": results, "failed": failed, "max_gap": gaps,
            "least_ms": least, "shape": [b, s, d, n],
            "platform": device.platform, "device_kind": device.device_kind,
        },
    }
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    # jaxlint: disable-next=torn-write -- a report, regenerated by a rerun
    (out / "bench_selective_scan.json").write_text(json.dumps(line) + "\n")
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
