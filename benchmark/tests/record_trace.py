"""Record the small trace the reduction's test reads (run once, on the chip).

    python3 benchmark/tests/record_trace.py <out.xplane.pb> <out.json>

A few jitted products with a pause between them, the harness's anchor
annotation, and the host's own clock readings written beside the trace, so the
test can check busy union, idle gaps and their names against known times."""

import glob
import json
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out_pb, out_json):
    n = jax.device_count()
    mesh = jax.make_mesh((n,), ("x",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = jax.device_put(jnp.ones((n * 512, 1024), jnp.bfloat16),
                       NamedSharding(mesh, P("x")))

    @jax.jit
    def work(a):
        b = a
        for _ in range(4):
            b = jnp.tanh(b @ jnp.ones((1024, 1024), jnp.bfloat16))
        return b + jnp.sum(b, axis=0, keepdims=True)  # all-reduce over x

    work(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_anchor"):
        anchor = time.monotonic_ns()
    t0 = time.monotonic()
    spans = []
    for i in range(3):
        a = time.monotonic()
        work(x).block_until_ready()
        b = time.monotonic()
        time.sleep(0.02)
        c = time.monotonic()
        spans += [["work", a, b], ["pause", b, c]]
    t1 = time.monotonic()
    jax.profiler.stop_trace()
    pb = glob.glob(tmp + "/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(pb, out_pb)
    json.dump({"anchor_ns": anchor, "t0": t0, "t1": t1, "spans": spans,
               "devices": n, "kind": jax.devices()[0].device_kind},
              open(out_json, "w"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(*sys.argv[1:3])
