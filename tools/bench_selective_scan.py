"""On-chip timing of the selective scan alone (ops/selective_scan.py): the
Pallas kernel pair against the XLA formulation, the forward and the backward
sweep apart, at a hybrid stack's own widths (Jamba2-3B: 2 x 4096 tokens,
d_inner 5120, d_state 16), over the kernels' channel block and the chunk.

Both formulations sit under one ``custom_vjp`` and count the same work; this
is where "which one ships" is decided and where the numbers in PERF.md
(section 6) come from. Also checks, on the chip, that the two agree.

Prints ONE JSON line:
  {"metric": "selective_scan_sweep", "value": <best fwd + bwd ms>, "unit": "ms",
   "extra": {"results_ms": {variant: [fwd, bwd]}, "kernel_ms": {variant: [fwd, bwd]},
             "least_ms": {"fwd", "bwd"}, "moved_mb": {variant: [fwd, bwd]},
             "max_gap": ..., "platform": ...}}
and writes it to ``chiprun_out/bench_selective_scan.json``. ``least_ms`` is
the bytes bound of what the benchmark counts (u, B, C at 2 B, dt at 4 B in, y
out; backward those and dy in, their cotangents out); ``moved_mb`` is what a
variant's kernels move through HBM a call, so both walls can be read: a time
near ``moved_mb`` / 819 GB/s is bytes, one far above it is the token loop.
``results_ms`` is the host's clock round the whole sweep (the skip, the casts
and the relabelling XLA does round a kernel included); ``kernel_ms`` is the
device time of the events named ``ssm_scan_fwd`` / ``ssm_scan_bwd`` alone, from
a trace of one call each (what ``ssm_scan_roofline`` divides by).

Run (on the chip; exits non-zero without one):
  python tools/bench_selective_scan.py [--variants pallas:1024:256,xla:256:8]
A variant is ``pallas:<block_d>:<chunk>`` or ``xla:<chunk>:<unroll>``; a
fourth field ``bf16`` gives it u at 2 bytes, as the model holds it.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import require_accelerator  # noqa: E402

# the shipped kernels (block 1024, chunk 256) and a shorter chunk beside
# them, then the XLA formulation they are held to; the same pair again with u
# as the model holds it (Jamba's 5120 channels divide by no larger block)
DEFAULT_VARIANTS = (
    "pallas:1024:256,pallas:1024:128,"
    "xla:256:8,pallas:1024:256:bf16,xla:256:8:bf16"
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

    def traced(fwd, bwd, xs, pull):
        """Device ms of the named kernels in one forward and one backward."""
        import tempfile

        from benchmark.lib import xplane

        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                jax.block_until_ready(fwd(*xs))
                jax.block_until_ready(bwd(pull, w))
            path = next(Path(tmp).glob("plugins/profile/*/*.xplane.pb"))
            planes = xplane.device_ops(xplane.load(path))
        events = next(iter(planes.values()), [])  # no device plane: no kernels
        return [round(xplane.kernel_time(events, name)[0] * 1e3, 3)
                for name in ("ssm_scan_fwd", "ssm_scan_bwd")]

    tok = b * s
    results, kernels, moved, outputs, failed = {}, {}, {}, {}, {}
    for variant in args.variants.split(","):
        impl, p1, p2, *narrow = variant.split(":")
        xs = (u.astype(jnp.bfloat16), *operands[1:]) if narrow else operands
        if impl == "pallas":
            ss.DEFAULT_BLOCK_D, chunk = int(p1), int(p2)
            ub = 2 if narrow else 4
            nd = d // ss._block_d(d)
            bounds = tok // chunk * n * d * 4
            # u, dt, B, C in, y and a state a chunk out; backward those, dy
            # and the states in, du, ddt and a block's dB, dC rows out
            moved[variant] = [round(x / 1e6, 1) for x in (
                tok * (d * (ub + 8) + 8 * n) + bounds,
                tok * (d * (ub + 16) + 8 * n + nd * 512) + bounds)]
        else:
            chunk, ss.XLA_UNROLL = int(p1), int(p2)

        def scan(*ops, c=chunk, i=impl):
            return ss.selective_scan(*ops, chunk=c, impl=i)

        fwd = jax.jit(scan)
        # the backward sweep alone: the residuals come in as the pytree
        # `jax.vjp` returns, the cotangent is float32 as the gate gives it
        residuals = jax.jit(lambda *ops: jax.vjp(scan, *ops)[1])
        bwd = jax.jit(lambda pull, cot: pull(cot))
        try:
            pull = residuals(*xs)
            t_f, y = timed(fwd, *xs)
            t_b, g = timed(bwd, pull, w)
            if impl == "pallas":
                kernels[variant] = traced(fwd, bwd, xs, pull)
        except Exception as err:  # a variant the compiler refuses is a result
            failed[variant] = f"{type(err).__name__}: {str(err)[:300]}"
            continue
        results[variant] = [round(t_f, 3), round(t_b, 3)]
        outputs[variant] = (y, g)
        print(f"{variant}: fwd {t_f:.2f} ms, bwd {t_b:.2f} ms",
              file=sys.stderr)

    # agreement of every variant with the LAST that was given the same u
    # (the XLA formulation, as the defaults list them), on the chip: y and
    # the float32 cotangents (a bfloat16 u's is rounded on both sides)
    gaps = {}
    for name in outputs:
        same_u = [v for v in outputs if v.endswith(":bf16") == name.endswith(":bf16")]
        if name == same_u[-1]:
            continue
        y0, g0 = outputs[same_u[-1]]
        y1, g1 = outputs[name]
        rel = [float(jnp.max(jnp.abs(p - q)) / jnp.max(jnp.abs(p)))
               for p, q in zip((y0, *g0), (y1, *g1)) if p.dtype == f32]
        gaps[name] = max(rel)
    # the least the chip could take, by the benchmark's count of bytes
    # (benchmark/lib/counts_hybrid.py): u, B, C at 2 B and dt at 4 B in, y out;
    # backward those and dy in, their cotangents out
    peaks = json.loads((ROOT / "benchmark/lib/peaks.json").read_text())
    peak = peaks.get(device.device_kind, {}).get("hbm_bytes_per_s")
    least = {"fwd": tok * (8 * d + 4 * n) / peak * 1e3,
             "bwd": tok * (14 * d + 8 * n) / peak * 1e3} if peak else None
    best = min(results.values(), key=sum, default=[None, None])
    line = {
        "metric": "selective_scan_sweep",
        "value": None if best[0] is None else round(sum(best), 3),
        "unit": "ms",
        "extra": {
            "results_ms": results, "kernel_ms": kernels, "moved_mb": moved,
            "failed": failed,
            "max_gap": gaps, "least_ms": least, "shape": [b, s, d, n],
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
