"""Worker for the multi-process distributed test (launched by
test_multiprocess.py, one instance per simulated host). Exercises the real
multi-host paths: jax.distributed.initialize rendezvous, per-process batch
slicing assembled into global arrays, host-0 broadcast, barriers, and
checkpointing from a multi-process mesh."""

import json
import sys

import pyrecover_tpu  # noqa: F401  (re-asserts JAX_PLATFORMS before jax init)
import jax

import os


def _run_train(workdir, model_overrides=None, **overrides):
    """One `train()` call with the tiny 2-proc config (the REAL driver —
    resume, preemption, checkpoint strategy dispatch all included)."""
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.models import ModelConfig
    from pyrecover_tpu.train import train

    base = dict(
        sequence_length=32, batch_size=8, training_samples=64,
        training_steps=8, learning_rate=1e-3, lr_warmup_steps=2, seed=13,
        checkpoint_dir=workdir, checkpoint_frequency=4,
        experiment_name="mp", logging_frequency=100,
        verify_checkpoints=True,
    )
    base.update(overrides)
    cfg = TrainConfig(**base)
    cfg.model = ModelConfig(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=128,
        multiple_of=32, max_seq_len=32, **(model_overrides or {}),
    )
    cfg.__post_init__()
    return train(cfg)


def _capture_host0_log():
    """Collect the pyrecover log lines (host 0 emits; other hosts see
    nothing — which is itself part of what the scenarios assert)."""
    import logging

    msgs = []

    class _H(logging.Handler):
        def emit(self, record):
            msgs.append(record.getMessage())

    from pyrecover_tpu.utils.logging import init_logger

    init_logger().addHandler(_H())
    return msgs


def mode_preempt(proc_id, workdir):
    """A preemption notice visible ONLY to host 0, landing mid-interval
    (present from step 1; check interval 4): host 1 must learn the stop
    through the check-step broadcast, and both hosts must exit together
    with the final checkpoint — the deadlock mode the coordinated
    protocol exists to prevent (reference train.py:342-346's rank-0 +
    broadcast shape)."""
    from pathlib import Path

    from pyrecover_tpu.preempt import PREEMPT_NOTICE_ENV

    notice = Path(workdir) / f"notice_{proc_id}"
    os.environ[PREEMPT_NOTICE_ENV] = str(notice)  # per-proc: host-0-only
    if proc_id == 0:
        notice.write_text("preempt")
    msgs = _capture_host0_log()
    _, end_step, stopped = _run_train(
        workdir, training_steps=100, timeaware_checkpointing=True,
        preempt_check_interval=4, checkpoint_frequency=50,
    )
    exp = Path(workdir) / "mp"
    return {
        "end_step": end_step,
        "stopped": stopped,
        "requeue": (exp / "REQUEUE").exists(),
        "finals": sorted(p.name for p in exp.glob("ckpt_*_final*")),
        "midinterval_logged": any(
            "mid-interval" in m for m in msgs
        ),
    }


def mode_resume(proc_id, workdir, sharded):
    """Corrupt-newest resume, coordinated: train 8 steps, host 0 tears the
    newest checkpoint, then BOTH hosts resume from 'latest' — the host-0
    integrity verdict broadcast must walk every host back to the same
    intact candidate (ckpt_4) without desynchronizing the collective
    load."""
    from pathlib import Path

    from pyrecover_tpu.parallel.mesh import sync_global_devices

    _run_train(
        workdir, checkpoint_engine="sharded" if sharded else "vanilla"
    )
    sync_global_devices("pre_corrupt")
    exp = Path(workdir) / "mp"
    if proc_id == 0:
        if sharded:
            (exp / "ckpt_8_final" / "_CHECKPOINT_METADATA").unlink()
        else:
            newest = exp / "ckpt_8_final.ckpt"
            data = newest.read_bytes()
            newest.write_bytes(data[: len(data) // 2])
    sync_global_devices("post_corrupt")
    msgs = _capture_host0_log()
    _, end_step, stopped = _run_train(
        workdir, checkpoint_engine="sharded" if sharded else "vanilla",
        resume_from_checkpoint="latest",
    )
    return {
        "end_step": end_step,
        "stopped": stopped,
        "fallback_logged": any(
            "failed integrity pre-check" in m and "ckpt_8" in m for m in msgs
        ),
        "resumed_from_4": any(
            "Resumed from" in m and "ckpt_4" in m for m in msgs
        ),
    }


def mode_moe_ep(proc_id, workdir):
    """Grouped ragged-GEMM MoE dispatch (the explicitly-SPMD shard_map
    path, psum over (expert, tensor)) training through the REAL
    multi-process driver: EP×TP shard within each simulated host (the
    ICI-friendly layout create_mesh picks) with cross-process data
    parallelism composed on top, plus Orbax multihost sharded
    checkpointing of the expert-sharded params. Both hosts must finish
    every step and agree exactly on the trained parameters."""
    from pyrecover_tpu.parallel.mesh import MeshConfig

    state, end_step, stopped = _run_train(
        workdir,
        model_overrides=dict(
            n_experts=4, moe_top_k=2, moe_dispatch="grouped"
        ),
        mesh=MeshConfig(data=2, tensor=2, expert=2),
        checkpoint_engine="sharded",  # Orbax multihost writes of EP-sharded leaves
    )
    # one number per HOST, computed from purely local data: params are
    # sharded over (expert, tensor) — both axes inside one host on this
    # mesh — and replicated across the cross-host data axis, so each
    # host's addressable shards are exactly one full copy. A collective
    # sum here would be replicated by construction and the cross-host
    # equality assertion vacuous; summing local shards makes divergent
    # replicas actually comparable.
    import numpy as np

    fp = []
    for leaf in jax.tree_util.tree_leaves(state.params):
        fp.append(sum(
            float(np.sum(np.asarray(shard.data, dtype=np.float32) ** 2))
            for shard in leaf.addressable_shards
        ))
    # per-leaf, full float precision (json round-trips doubles exactly):
    # a single rounded total would hide sub-1e-6 divergence and
    # compensating per-leaf differences
    return {
        "end_step": end_step,
        "stopped": stopped,
        "param_l2sq": fp,
    }


def mode_emergency_peer(proc_id, workdir):
    """The fixed DC01/DC05 finding, on a REAL 2-process group: the
    emergency peer RAM exchange with ``$PYRECOVER_EMERGENCY_PEER=1`` set
    on HOST 0 ONLY. Before the fix, the per-host env/record gate sent
    host 1 home while host 0 sat in ``broadcast_one_to_all`` forever —
    the canonical rank-gated-collective deadlock, which this harness
    bounds with its subprocess timeout (the hang watchdog). After the
    fix the participation verdict is host-0-decided and broadcast, so
    BOTH hosts run the exchange, and host 1's RAM ends up holding a
    record whose chunk digests verify against the committed manifest —
    byte-equality with host 0's published snapshot, by construction."""
    import hashlib
    from pathlib import Path

    import numpy as np

    from pyrecover_tpu.checkpoint import checkpoint_path, save_ckpt_zerostall
    from pyrecover_tpu.checkpoint.zerostall import emergency
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.models import ModelConfig
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.parallel.mesh import (
        MeshConfig,
        create_mesh,
        state_topology,
        sync_global_devices,
    )
    from pyrecover_tpu.train import init_sharded_state

    # the smoke mode's mesh shape: tensor=2 keeps a sharded axis inside
    # each host (pure cross-process replication is unsupported on the
    # virtual CPU backend), data spans the two processes — so the saved
    # leaves exercise the non-addressable allgather path too
    mesh = create_mesh(MeshConfig(data=jax.device_count() // 2, tensor=2))
    model_cfg = ModelConfig(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=128,
        multiple_of=32, max_seq_len=32,
    )
    cfg = TrainConfig(sequence_length=32, batch_size=8, training_samples=64,
                      learning_rate=1e-3)
    cfg.model = model_cfg
    cfg.__post_init__()
    optimizer, _ = build_optimizer(cfg)
    state = init_sharded_state(jax.random.key(3), cfg.model, optimizer, mesh)

    exp = Path(workdir) / "ep"
    path = checkpoint_path(str(exp.parent), "ep", 3, engine="zerostall")
    save_ckpt_zerostall(
        path, state, {"consumed": 3}, background=False,
        extra_meta={"step": 3},
    )
    sync_global_devices("post_save")

    # the deadlock seed: only host 0 opts in; only host 0 holds a record
    if proc_id == 0:
        os.environ[emergency.PEER_EXCHANGE_ENV] = "1"
    did = emergency.replicate_to_peers(str(exp))

    got = emergency.peek(str(exp))
    verified, why = (
        emergency.verify(got[1]) if got is not None else (False, "no record")
    )
    usable = emergency.usable(
        str(exp), state_topology(state), min_step=0
    ) is not None
    digests = []
    if got is not None:
        for leaf in got[1]["leaves"]:
            digests.append(hashlib.blake2b(
                np.ascontiguousarray(leaf).tobytes(), digest_size=8
            ).hexdigest())
    # a second call must be a congruent no-op on every host (the record
    # is already peer_replicated)
    again = emergency.replicate_to_peers(str(exp))
    sync_global_devices("post_exchange")
    return {
        "did": bool(did),
        "again": bool(again),
        "has_record": got is not None,
        "verified": bool(verified),
        "verify_reason": why,
        "usable": bool(usable),
        "step": int(got[0]) if got is not None else -1,
        "digests": digests,
    }


def main():
    proc_id = int(sys.argv[1])
    num_procs = int(sys.argv[2])
    port = sys.argv[3]
    workdir = sys.argv[4]
    mode = sys.argv[5] if len(sys.argv) > 5 else "smoke"

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=num_procs,
        process_id=proc_id,
    )
    assert jax.process_count() == num_procs

    if mode != "smoke":
        if mode == "preempt":
            result = mode_preempt(proc_id, workdir)
        elif mode == "resume_vanilla":
            result = mode_resume(proc_id, workdir, sharded=False)
        elif mode == "resume_sharded":
            result = mode_resume(proc_id, workdir, sharded=True)
        elif mode == "moe_ep":
            result = mode_moe_ep(proc_id, workdir)
        elif mode == "emergency_peer":
            result = mode_emergency_peer(proc_id, workdir)
        else:
            raise SystemExit(f"unknown mode {mode}")
        result["proc"] = proc_id
        print("WORKER_RESULT " + json.dumps(result))
        jax.distributed.shutdown()
        return

    import numpy as np

    from pyrecover_tpu.checkpoint import (
        checkpoint_path,
        load_ckpt_vanilla,
        save_ckpt_vanilla,
        load_ckpt_sharded,
        save_ckpt_sharded,
    )
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.data import DataLoader, StatefulSampler, SyntheticTextDataset
    from pyrecover_tpu.models import ModelConfig
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.parallel.mesh import (
        MeshConfig,
        broadcast_host0_scalar,
        create_mesh,
        sync_global_devices,
    )
    from pyrecover_tpu.train import init_sharded_state
    from pyrecover_tpu.train_state import make_train_step

    n_global = jax.device_count()
    mesh = create_mesh(MeshConfig(data=n_global // 2, tensor=2))

    model_cfg = ModelConfig(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=128,
        multiple_of=32, max_seq_len=32,
    )
    cfg = TrainConfig(sequence_length=32, batch_size=8, training_samples=64,
                      learning_rate=1e-3)
    cfg.model = model_cfg
    cfg.__post_init__()
    model_cfg = cfg.model

    optimizer, _ = build_optimizer(cfg)
    state = init_sharded_state(jax.random.key(0), model_cfg, optimizer, mesh)

    ds = SyntheticTextDataset(num_samples=64, seq_len=32, vocab_size=128, seed=7)
    sampler = StatefulSampler(dataset_len=64, global_batch_size=8, seed=7)
    loader = DataLoader(ds, sampler, pad_token_id=0, mesh=mesh, prefetch=0)
    step_fn = make_train_step(model_cfg, optimizer, donate=False)

    losses = []
    with jax.sharding.set_mesh(mesh):
        for _ in range(3):
            _, batch = next(loader)
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))

    # host-0 decision broadcast (the stop-flag pattern)
    flag = broadcast_host0_scalar(proc_id == 0 and 42 or 0)
    assert flag == 42, f"broadcast gave {flag}"
    sync_global_devices("worker_mid")

    # vanilla checkpoint from a multi-process mesh (allgather of sharded
    # leaves to host 0) then restore onto the mesh
    vpath = checkpoint_path(workdir, "dist", 3)
    save_ckpt_vanilla(vpath, state, {"consumed": 3}, verify=True)
    state_v, sampler_meta, _ = load_ckpt_vanilla(vpath, state, verify=True)
    assert sampler_meta["consumed"] == 3

    # sharded checkpoint: every process writes its own shards
    spath = checkpoint_path(workdir, "dist", 4, engine="sharded")
    save_ckpt_sharded(spath, state, {"consumed": 4}, extra_meta={"step": 4})
    state_s, _, meta = load_ckpt_sharded(spath, state)
    assert meta["step"] == 4

    for a, b in zip(jax.tree_util.tree_leaves(state_v),
                    jax.tree_util.tree_leaves(state_s)):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))
        )

    print("WORKER_RESULT " + json.dumps({
        "proc": proc_id,
        "devices": n_global,
        "losses": losses,
    }))
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()
