#!/usr/bin/env python3
"""Device→host probe for the sharded save (ISSUE 28): how fast each way of
getting the save cell's state (params + both Adam moments of the
`mistral-7b` benchmark configuration, ~6.8 GB of bf16) off the chip is.

    chiprun --timeout 1500 -- python3 tools/ckpt_d2h_probe.py

Variants, each on state arrays no host copy has been cached for (a donated
jitted `+ 0` between variants hands out fresh ones):

  a_all        `copy_to_host_async()` on every leaf, then `np.asarray`
  a_params     the same for the `.params` leaves alone (what PR 26's
               `ckpt_digest` span did)
  a_groups_*   `a_all` in groups of at most 256 MB .. 2 GB, each group
               materialised before the next starts
  a_window_*   at most that many bytes in flight, the oldest transfer
               materialised before the next starts (no barrier)
  a_threads_N  `np.asarray` of each leaf from N threads, nothing started
               ahead
  h_first_touch  writing one byte a page of as many fresh bytes: what the
               fresh host buffers cost without any transfer
  w_*          windows of 2, 3, 4 GB and all, threads 6, 8, 12, twice each
  s_save_N     (`--parts s`) six saves through `ShardedCheckpointer` itself:
               the spans of each, and the resident memory after it
  e_shards_*   the copy made through each leaf's `addressable_shards[i]
               .data` (the array objects Orbax's serialization asks for),
               then Orbax's call on the same state: does it find the host
               copies the runtime cached on them?
  b_pinned_N   `jax.device_put(state, <own sharding>.with_memory_kind(
               "pinned_host"))` + `block_until_ready`, calls 1..3
  b_unpinned   the same into `unpinned_host`
  c_h2h        `np.asarray` of every leaf of a pinned snapshot
  d_orbax_*    the blocking seconds of the engine's own Orbax save call
               (`AsyncCheckpointer.save`, chunk and file sizes as
               `sharded.py` sets them) fed the device state (today), a
               pinned snapshot, and the device state under
               `PyTreeCheckpointHandler(enable_pinned_host_transfer=True)`;
               `*_bg_s` is the wait for the background write after it

Timings are wall seconds on the host clock around work that ends in
`block_until_ready` / a materialised numpy array. Refuses to run off a
TPU: a CPU's numbers are not device numbers. Results: stdout (a table and
one JSON line) and `chiprun_out/ckpt_d2h_probe.<parts>.json`.
"""

import argparse
import json
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

GROUP_BYTES = 2 * 1024**3


def build_state(seed, config_name):  # jaxlint: host-only
    import jax

    from benchmark.runners.train_window import model_config
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.parallel.mesh import MeshConfig, create_mesh
    from pyrecover_tpu.train import init_sharded_state

    cfg = json.loads(
        (ROOT / "benchmark" / "configs" / f"{config_name}.json").read_text()
    )
    config = TrainConfig(
        model=model_config(cfg), mesh=MeshConfig(), param_dtype="bf16",
        model_dtype="bf16",
    )
    optimizer, _ = build_optimizer(config)
    mesh = create_mesh(config.mesh)
    return init_sharded_state(
        jax.random.key(seed), config.model, optimizer, mesh
    )


def main(argv=None):  # jaxlint: host-only
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=28)
    ap.add_argument("--config", default="mistral-7b")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="walk the control flow on the CPU at a toy size; "
                         "prints no rate")
    ap.add_argument("--parts", default="a,b,c,d,e",
                    help="which variant families to run (comma-separated)")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    import orbax.checkpoint as ocp

    from pyrecover_tpu.checkpoint.sharded import CHUNK_BYTES, DATA_FILE_BYTES

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse_cpu:
        print(f"ckpt_d2h_probe: resolved platform is {platform}; it measures "
              "a TPU or nothing", file=sys.stderr)
        return 3

    if args.rehearse_cpu:
        # a toy state of the same structure, so the script can be rehearsed
        import jax.numpy as jnp

        from pyrecover_tpu.train_state import TrainState

        k, k_w = jax.random.split(jax.random.key(args.seed))
        params = {
            "w": jax.random.normal(k_w, (4, 256, 512), jnp.bfloat16),
            "norm": jnp.ones((4, 256), jnp.bfloat16),
        }
        state = TrainState(
            params=params,
            opt_state={"mu": jax.tree.map(jnp.zeros_like, params),
                       "nu": jax.tree.map(jnp.zeros_like, params),
                       "count": jnp.zeros((), jnp.int32)},
            step=jnp.zeros((), jnp.int32), epoch=jnp.zeros((), jnp.int32),
            rng=jax.random.key_data(k),
        )
    else:
        state = build_state(args.seed, args.config)
    jax.block_until_ready(state)
    parts = set(args.parts.split(","))

    refresh = jax.jit(
        lambda s: jax.tree.map(lambda x: x + 0, s), donate_argnums=0
    )

    def fresh(s):
        """New arrays with the same values and no cached host copy."""
        s = refresh(s)
        jax.block_until_ready(s)
        return s

    leaves = jax.tree_util.tree_leaves(state)
    total = sum(x.nbytes for x in leaves)
    out = {
        "device": {"platform": platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": jax.device_count()},
        "leaves": len(leaves), "bytes": total,
        "memories": [m.kind for m in jax.devices()[0].addressable_memories()],
        "variants": {},
    }

    def rss_gb():
        for line in Path("/proc/self/status").read_text().splitlines():
            if line.startswith("VmRSS:"):
                return round(int(line.split()[1]) / 1e6, 2)
        return None

    def record(name, secs, nbytes=total, **extra):
        rec = {"s": round(secs, 4), "bytes": int(nbytes),
               "rss_gb": rss_gb(), **extra}
        if platform == "tpu":
            rec["GB_per_s"] = round(nbytes / secs / 1e9, 3) if secs else None
        out["variants"][name] = rec
        print(f"{name:28s} {secs:8.3f} s  {nbytes / 1e9:6.2f} GB  "
              + (f"{rec.get('GB_per_s')} GB/s" if platform == "tpu" else "")
              + f"  rss {rec['rss_gb']} GB"
              + (f"  {extra}" if extra else ""), flush=True)
        if (rec["rss_gb"] or 0) > 30:
            raise MemoryError(f"resident memory {rec['rss_gb']} GB after {name}")

    def copy_async(group):
        for x in group:
            x.copy_to_host_async()
        return [np.asarray(x) for x in group]

    def groups_of(xs, cap):
        group, size = [], 0
        for x in xs:
            if group and size + x.nbytes > cap:
                yield group
                group, size = [], 0
            group.append(x)
            size += x.nbytes
        if group:
            yield group

    MB = 1024**2

    def part_a(state):
        """copy_to_host_async + np.asarray, by how much is in flight."""
        state = fresh(state)
        t0 = time.monotonic()
        host = copy_async(jax.tree_util.tree_leaves(state))
        record("a_all", time.monotonic() - t0)
        del host

        for cap in (256 * MB, 512 * MB, 1024 * MB, GROUP_BYTES):
            state = fresh(state)
            t0 = time.monotonic()
            n_groups = 0
            host = []
            for group in groups_of(jax.tree_util.tree_leaves(state), cap):
                host.extend(copy_async(group))
                n_groups += 1
            record(f"a_groups_{cap // MB}MB", time.monotonic() - t0,
                   groups=n_groups)
            del host

        for cap in (512 * MB, 1024 * MB, GROUP_BYTES):
            state = fresh(state)
            t0 = time.monotonic()
            host = windowed(jax.tree_util.tree_leaves(state), cap)
            record(f"a_window_{cap // MB}MB", time.monotonic() - t0)
            del host

        for n in (2, 4, 8):
            state = fresh(state)
            t0 = time.monotonic()
            with ThreadPoolExecutor(n) as pool:
                host = list(pool.map(
                    np.asarray, jax.tree_util.tree_leaves(state)))
            record(f"a_threads_{n}", time.monotonic() - t0)
            del host

        t0 = time.monotonic()
        buf = np.empty(total, np.uint8)
        buf[::4096] = 1
        record("h_first_touch", time.monotonic() - t0)
        del buf

        state = fresh(state)
        p_leaves = jax.tree_util.tree_leaves(state.params)
        t0 = time.monotonic()
        host = copy_async(p_leaves)
        record("a_params", time.monotonic() - t0,
               sum(x.nbytes for x in p_leaves), leaves=len(p_leaves))
        return state

    def windowed(xs, cap):
        """At most ``cap`` bytes in flight (one array at least)."""
        out, flying, size = [], [], 0
        for x in xs:
            while flying and size + x.nbytes > cap:
                head = flying.pop(0)
                out.append(np.asarray(head))
                size -= head.nbytes
            x.copy_to_host_async()
            flying.append(x)
            size += x.nbytes
        out.extend(np.asarray(x) for x in flying)
        return out

    def to_kind(s, kind):
        shardings = jax.tree.map(
            lambda x: x.sharding.with_memory_kind(kind), s
        )
        snap = jax.device_put(s, shardings)
        jax.block_until_ready(snap)
        return snap

    tmp = Path(tempfile.mkdtemp(prefix="d2h_probe_"))

    def orbax_save(name, tree, **handler_kw):
        ckptr = ocp.AsyncCheckpointer(
            ocp.PyTreeCheckpointHandler(**handler_kw)
        )
        path = tmp / name
        t0 = time.monotonic()
        ckptr.save(
            path,
            args=ocp.args.PyTreeSave(
                tree,
                save_args=jax.tree.map(
                    lambda _: ocp.SaveArgs(chunk_byte_size=CHUNK_BYTES), tree
                ),
                ocdbt_target_data_file_size=DATA_FILE_BYTES,
            ),
            force=True,
        )
        call_s = time.monotonic() - t0
        ckptr.wait_until_finished()
        bg_s = time.monotonic() - t0 - call_s
        ckptr.close()
        files = [p for p in path.rglob("*") if p.is_file()]
        record(name, call_s, bg_s=round(bg_s, 3), files=len(files),
               disk_bytes=sum(p.stat().st_size for p in files))
        shutil.rmtree(path, ignore_errors=True)

    def part_bcd(state):
        """Host memory kinds: device_put, numpy out of it, Orbax fed it."""
        snap = None
        for i in (1, 2, 3):
            state = fresh(state)
            del snap
            t0 = time.monotonic()
            snap = to_kind(state, "pinned_host")
            record(f"b_pinned_{i}", time.monotonic() - t0)

        t0 = time.monotonic()
        host = [np.asarray(x) for x in jax.tree_util.tree_leaves(snap)]
        record("c_h2h_np_asarray", time.monotonic() - t0)
        out["pinned_equals_device"] = all(
            np.array_equal(a, np.asarray(b))
            for a, b in zip(host[:3], jax.tree_util.tree_leaves(state)[:3])
        )
        del host
        # a second read of the same snapshot (is the host copy cached?)
        t0 = time.monotonic()
        host = [np.asarray(x) for x in jax.tree_util.tree_leaves(snap)]
        record("c_h2h_second_read", time.monotonic() - t0)
        del host

        if "unpinned_host" in out["memories"]:
            state = fresh(state)
            t0 = time.monotonic()
            usnap = to_kind(state, "unpinned_host")
            record("b_unpinned", time.monotonic() - t0)
            del usnap

        orbax_save("d_orbax_pinned_snapshot", snap)
        del snap
        state = fresh(state)
        orbax_save("d_orbax_device_today", state)
        state = fresh(state)
        orbax_save("d_orbax_device_pinned_flag", state,
                   enable_pinned_host_transfer=True)
        # pinned again after Orbax has run: the steady cost of a later save
        state = fresh(state)
        t0 = time.monotonic()
        snap = to_kind(state, "pinned_host")
        record("b_pinned_after_orbax", time.monotonic() - t0)
        orbax_save("d_orbax_pinned_snapshot_2", snap)
        return state

    def part_e(state):
        """The copy made on the shards' own array objects, which Orbax's
        serialization asks for again: its call should find them done."""
        def shard_arrays(s):
            return [
                shard.data
                for x in jax.tree_util.tree_leaves(s)
                for shard in x.addressable_shards if shard.replica_id == 0
            ]

        for cap in (512 * MB, 1024 * MB):
            state = fresh(state)
            t0 = time.monotonic()
            host = windowed(shard_arrays(state), cap)
            record(f"e_shards_window_{cap // MB}MB", time.monotonic() - t0)
            t0 = time.monotonic()
            again = [np.asarray(a) for a in shard_arrays(state)]
            record(f"e_shards_reread_{cap // MB}MB", time.monotonic() - t0,
                   same_buffers=all(
                       np.shares_memory(a, b) for a, b in zip(host, again)))
            del host, again
            orbax_save(f"e_orbax_after_shards_{cap // MB}MB", state)
        state = fresh(state)
        orbax_save("e_orbax_device_today", state)
        return state

    def part_w(state):
        """Windows and thread counts over again, interleaved, to tell a
        difference from the noise between two runs of one variant."""
        for rep in (1, 2):
            for cap in (2 * GROUP_BYTES // 2, 3 * GROUP_BYTES // 2,
                        2 * GROUP_BYTES, total):
                state = fresh(state)
                t0 = time.monotonic()
                host = windowed(jax.tree_util.tree_leaves(state), cap)
                record(f"w_window_{cap // MB}MB_{rep}", time.monotonic() - t0)
                del host
            for n in (6, 8, 12):
                state = fresh(state)
                t0 = time.monotonic()
                with ThreadPoolExecutor(n) as pool:
                    host = list(pool.map(
                        np.asarray, jax.tree_util.tree_leaves(state)))
                record(f"w_threads_{n}_{rep}", time.monotonic() - t0)
                del host
        return state

    def part_s(state):
        """Saves through the engine itself, as the trainer makes them
        (async, two kept, a new state each time, the steps between two
        saves slept away): the spans of each and the process's resident
        memory after it, under several bounds on the bytes in flight."""
        from pyrecover_tpu import telemetry
        from pyrecover_tpu.checkpoint import sharded

        sink = telemetry.add_sink(telemetry.MemorySink())
        saves = []
        with sharded.ShardedCheckpointer(use_async=True) as ckptr:
            for step, cap in enumerate(
                (2, 2, 3, 3, 4, 4) if platform == "tpu" else (2, 3), start=1
            ):
                sharded.IN_FLIGHT_BYTES = cap * 1024**3
                state = fresh(state)
                n0 = len(sink.events)
                blocking = ckptr.save(
                    tmp / f"ckpt_{step}", state, max_keep=2,
                    extra_meta={"step": step},
                )
                spans = {
                    e["name"]: e for e in sink.events[n0:]
                    if e["event"] == "span_end"
                }
                rec = {
                    "in_flight_gib": cap, "blocking_s": round(blocking, 3),
                    "rss_gb": rss_gb(),
                    **{k: spans["ckpt_serialize"].get(k) for k in (
                        "snapshot_s", "snapshot_bytes", "fallback_leaves")},
                    **{n + "_s": round(e["dur_s"], 4)
                       for n, e in spans.items()},
                }
                saves.append(rec)
                print(f"s_save_{step}", rec, flush=True)
                time.sleep(7.0 if platform == "tpu" else 0.1)
        out["engine_saves"] = saves
        out["engine_background"] = [
            {"name": e["name"], "dur_s": round(e["dur_s"], 3)}
            for e in sink.events if e["event"] == "span"
        ]
        print("background", out["engine_background"], "rss_gb", rss_gb())
        return state

    try:
        if "s" in parts:
            state = part_s(state)
        if "w" in parts:
            state = part_w(state)
        if "a" in parts:
            state = part_a(state)
        if parts & {"b", "c", "d"}:
            state = part_bcd(state)
        if "e" in parts:
            state = part_e(state)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    name = "ckpt_d2h_probe." + "".join(sorted(parts)) + ".json"
    # jaxlint: disable-next=torn-write -- a report, regenerated by a rerun
    (out_dir / name).write_text(json.dumps(out, indent=1))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
