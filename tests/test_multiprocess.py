"""True multi-process distributed backend test: two OS processes, each with
4 virtual CPU devices, rendezvous via jax.distributed into one 8-device
mesh — the closest a single host gets to a real TPU pod (one process per
host). Covers what the single-process suite cannot: cross-process
collectives, per-process data slicing into global arrays, multihost
barriers/broadcast, vanilla-save allgather, and Orbax multihost writes.

(The reference's multi-node path was only ever testable on a live SLURM
cluster — SURVEY §4.)"""

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # driver/cluster-scale suite; fast tier skips it

REPO = Path(__file__).resolve().parent.parent
WORKER = Path(__file__).resolve().parent / "_dist_worker.py"


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_MP_PROBE = """
import sys, jax
jax.distributed.initialize(coordinator_address="127.0.0.1:" + sys.argv[2],
                           num_processes=2, process_id=int(sys.argv[1]))
import numpy as np
from jax.experimental import multihost_utils
multihost_utils.broadcast_one_to_all(np.zeros(1))
jax.distributed.shutdown()
"""

_mp_supported = None


def _multiprocess_supported():
    """Capability probe (the ring-attention precedent): some jaxlib CPU
    builds rendezvous fine but refuse cross-process XLA computations
    ("Multiprocess computations aren't implemented on the CPU backend").
    Nothing in this module can run there — skip with the reason instead
    of failing every scenario on an environment limitation."""
    global _mp_supported
    if _mp_supported is not None:
        return _mp_supported
    port = str(free_port())
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("_PYRECOVER_TPU_TEST_ENV", None)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _MP_PROBE, str(i), port], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        for i in range(2)
    ]
    ok = True
    for p in procs:
        try:
            ok = (p.wait(timeout=120) == 0) and ok
        except subprocess.TimeoutExpired:
            p.kill()
            ok = False
    _mp_supported = ok
    return ok


@pytest.fixture(autouse=True)
def _require_multiprocess():
    if not _multiprocess_supported():
        pytest.skip(
            "cross-process XLA computations unsupported on this backend "
            "(CPU jaxlib without multiprocess support)"
        )


def run_workers(tmp_path, mode=None, timeout=420):
    port = str(free_port())
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYRECOVER_LOAD_STAGGER_S"] = "0.2"  # exercise the stagger, fast
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("_PYRECOVER_TPU_TEST_ENV", None)

    args = [] if mode is None else [mode]
    procs = [
        subprocess.Popen(
            [sys.executable, str(WORKER), str(i), "2", port, str(tmp_path),
             *args],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=REPO,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=timeout)
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out[-3000:]}"

    results = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("WORKER_RESULT "):
                r = json.loads(line[len("WORKER_RESULT "):])
                results[r["proc"]] = r
    assert set(results) == {0, 1}
    return results


def test_two_process_mesh(tmp_path):
    results = run_workers(tmp_path)
    assert results[0]["devices"] == 8
    # both processes computed the same global losses (SPMD consistency)
    np.testing.assert_allclose(results[0]["losses"], results[1]["losses"])
    # and training actually progressed
    assert results[0]["losses"][0] != results[0]["losses"][-1]


def test_two_process_preemption_coordinated_stop(tmp_path):
    """A preemption notice only host 0 can see (per-proc notice file),
    present from step 1 with check interval 4: host 0 logs the
    mid-interval observation, both hosts take the coordinated stop at
    step 4 via the check-step broadcast, write ONE final checkpoint, and
    exit with the REQUEUE marker. This is the deadlock mode the
    coordinated protocol exists against — round-4 verdict weak #5 (the
    protocol was only ever exercised single-process)."""
    results = run_workers(tmp_path, mode="preempt")
    for proc, r in results.items():
        assert r["stopped"], f"proc {proc} did not stop early"
        assert r["end_step"] == 4, f"proc {proc} stopped at {r['end_step']}"
        assert r["requeue"]
        assert [f for f in r["finals"] if f.endswith(".ckpt")] == [
            "ckpt_4_final.ckpt"
        ], r["finals"]
    assert results[0]["midinterval_logged"]  # host 0 saw it off-schedule


@pytest.mark.parametrize("mode", ["resume_vanilla", "resume_sharded"])
def test_two_process_corrupt_newest_fallback(tmp_path, mode):
    """Corrupt-newest resume across two processes: host 0's integrity
    verdict is broadcast BEFORE any collective, so both hosts walk back to
    the same intact candidate (ckpt_4) and finish the run — on both
    checkpoint engines."""
    results = run_workers(tmp_path, mode=mode)
    for proc, r in results.items():
        assert r["end_step"] == 8, f"proc {proc} ended at {r['end_step']}"
        assert not r["stopped"]
    assert results[0]["fallback_logged"]
    assert results[0]["resumed_from_4"]
    # host 1 emits nothing (log_host0) — its agreement is proven by a
    # clean, non-hanging exit at the same step
    assert not results[1]["fallback_logged"]


def test_two_process_emergency_peer_exchange(tmp_path):
    """The fixed rank-gated-collective deadlock (distcheck DC01/DC05),
    regressed on a REAL 2-process group: $PYRECOVER_EMERGENCY_PEER=1 on
    host 0 ONLY. The pre-fix gate read the env var and probed the local
    record store per host, so host 1 returned early while host 0 blocked
    in broadcast_one_to_all forever — this test would then die on the
    subprocess timeout (the harness's hang watchdog). With the host-0
    verdict broadcast, both hosts complete the exchange, host 1's RAM
    record digest-verifies against the committed manifest, the pod
    ``usable()`` gate passes (peer_replicated), and both hosts hold
    byte-identical leaves."""
    results = run_workers(tmp_path, mode="emergency_peer", timeout=300)
    for proc, r in results.items():
        assert r["did"], f"proc {proc} did not run the exchange"
        assert not r["again"], f"proc {proc} re-ran a replicated exchange"
        assert r["has_record"], f"proc {proc} holds no RAM record"
        assert r["verified"], (
            f"proc {proc} record failed the digest gate: "
            f"{r['verify_reason']}"
        )
        assert r["usable"], f"proc {proc} usable() gate failed"
        assert r["step"] == 3
        assert r["digests"], f"proc {proc} reported no leaf digests"
    assert results[0]["digests"] == results[1]["digests"]


def test_two_process_grouped_moe_expert_parallel(tmp_path):
    """The MXU MoE path (grouped ragged-GEMM dispatch inside its
    explicitly-SPMD shard_map, one psum over (expert, tensor)) training
    through the real driver on a REAL 2-process mesh: EP×TP within each
    simulated host, data parallelism across them, expert-sharded params
    checkpointed multihost. Both hosts must agree bit-for-bit on the
    trained parameters — the vma/psum AD hazards this path documents
    (models/moe.py) would show up here as cross-host divergence."""
    results = run_workers(tmp_path, mode="moe_ep")
    for proc, r in results.items():
        assert r["end_step"] == 8, f"proc {proc} ended at {r['end_step']}"
        assert not r["stopped"]
    assert results[0]["param_l2sq"] == results[1]["param_l2sq"]
