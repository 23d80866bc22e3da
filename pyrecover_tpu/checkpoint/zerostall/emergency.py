"""In-RAM emergency checkpoint tier: restore without touching disk.

Disk restores scale with checkpoint size; a fleet that restarts often
pays that tax on every churn event. This tier keeps the latest COMMITTED
zerostall snapshot in host RAM so ``train._resume`` can restore in
milliseconds when the disk tier is behind (a save was mid-write when the
process died and its manifest never published) or gone entirely.

Semantics:

  * **Publish** happens from the zerostall writer thread AFTER the
    manifest commit — the tier only ever holds states that were durable
    at least once, so preferring it can never resurrect an uncommitted
    step.
  * **Single host degenerates to a local shadow copy**: the writer
    already holds the host-side numpy leaves; publishing is a pointer
    hand-off, not a copy. Costs one state-sized slab of host RAM
    (disable with ``$PYRECOVER_EMERGENCY=0``).
  * **Multi-host**: host 0 (the writer) always holds the shadow copy.
    With ``$PYRECOVER_EMERGENCY_PEER=1`` (read on HOST 0 — participation
    is a host-0 verdict broadcast, never a per-host probe) every host
    joins a process-group exchange (``multihost_utils.broadcast_one_to_all``
    over the manifest doc then every committed leaf, pinned to the
    CALLING thread like every other collective — it runs inside the next
    save's blocking window, not the shadow) so each host's RAM holds the
    full state and a restart can restore from a *peer's* RAM even when
    the local disk is cold. The exchange rides the ICI broadcast because
    JAX exposes no host-to-host point-to-point primitive; it is opt-in
    precisely because it moves state-sized bytes.
  * **Strict freshness/digest gate before the tier is ever preferred**:
    the record's step must be at least the newest disk manifest's, the
    saved topology must match the live mesh exactly (elastic restores
    belong to the disk path), and every leaf's chunk digests are
    RECOMPUTED over the in-RAM bytes and compared against the manifest
    — a bit-flipped or torn RAM record is rejected, never restored.

The store is process-local by construction (RAM dies with the process);
it exists across ``train()`` calls in one process — the resilient-
launcher / notebook / test scenario — and for peers, in their processes.
"""

import os
import threading
import time
from pathlib import Path

import jax
import numpy as np

from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint.zerostall import chunkstore
from pyrecover_tpu.utils.logging import log_host0

EMERGENCY_ENV = "PYRECOVER_EMERGENCY"
PEER_EXCHANGE_ENV = "PYRECOVER_EMERGENCY_PEER"

_store = {}
_lock = threading.Lock()


def enabled():  # jaxlint: host-only
    return os.environ.get(EMERGENCY_ENV, "1") != "0"


def _key(exp_dir):
    return str(Path(exp_dir).absolute())


def publish(exp_dir, doc, np_leaves):  # jaxlint: host-only
    """Install a just-committed snapshot as the experiment's emergency
    record (writer thread, host 0). Pointer hand-off — the caller must
    not mutate ``np_leaves`` afterwards."""
    if not enabled():
        return None
    record = {
        "doc": doc,
        "leaves": np_leaves,
        "step": int(doc.get("step", 0)),
        "published_ts": time.time(),
        "peer_replicated": False,
    }
    with _lock:
        _store[_key(exp_dir)] = record
    telemetry.emit(
        "emergency_publish", engine="zerostall", step=record["step"],
        exp_dir=str(exp_dir), leaves=len(np_leaves),
        bytes=int(sum(a.nbytes for a in np_leaves)),
    )
    return record


def replicate_to_peers(exp_dir):  # jaxlint: host-only sync-point
    """Opt-in process-group exchange (``$PYRECOVER_EMERGENCY_PEER=1``
    read on HOST 0): broadcast the latest published record — manifest
    doc first, then every leaf — so EVERY host's RAM holds the full,
    verifiable state. Collective — must run on the main thread (the
    zerostall engine calls it inside the next save's blocking window).
    No-op on a single host (the local shadow copy already is the tier).

    Congruence protocol (the deadlock this function used to carry):
    whether the exchange happens is a HOST-0 verdict, broadcast before
    any payload moves. The old gate read the env var and probed the
    local record store per host — but only host 0 ever holds a record
    (``publish`` runs in its writer), so every peer returned early while
    host 0 sat in ``broadcast_one_to_all`` waiting for participants that
    had already left: the canonical rank-gated-collective deadlock
    (distcheck DC01/DC06). Peers now learn the leaf shapes from the
    broadcast doc, supply placeholder buffers, and install the received
    record with ``peer_replicated=True`` — which is also what makes
    ``usable()``'s pod gate passable at all. The whole exchange runs in
    one bounded ``collective_phase`` (DC05): a host that never arrives
    becomes a named ``distributed_wait_timeout``, not a silent hang."""
    if jax.process_count() <= 1:
        return False
    from jax.experimental import multihost_utils

    from pyrecover_tpu.checkpoint.vanilla import _dtype_from_str
    from pyrecover_tpu.parallel.mesh import (
        broadcast_host0_obj,
        broadcast_host0_scalar,
    )

    want = 0
    record = None
    if jax.process_index() == 0:
        if os.environ.get(PEER_EXCHANGE_ENV) == "1":
            with _lock:
                record = _store.get(_key(exp_dir))
            if record is not None and not record.get("peer_replicated"):
                want = 1
    if int(broadcast_host0_scalar(want)) != 1:
        return False
    # the manifest doc first: peers need the leaf shapes/dtypes to build
    # their placeholder buffers — and the doc itself, to digest-verify
    # and restore from the record later
    doc = broadcast_host0_obj(record["doc"] if record is not None else None)
    local_leaves = record["leaves"] if record is not None else None
    replicated = []
    with telemetry.collective_phase(
        "emergency_peer_exchange", leaves=len(doc.get("leaves", ())),
    ):
        for i, entry in enumerate(doc["leaves"]):
            # host 0 supplies the payload (want==1 implies it holds the
            # record); peers supply placeholder buffers whose shape/dtype
            # come from the broadcast doc, so every host participates in
            # the SAME leaf sequence regardless of local record state
            if jax.process_index() == 0:
                src = local_leaves[i]
            else:
                src = np.zeros(
                    tuple(int(s) for s in entry["shape"]),
                    dtype=_dtype_from_str(entry["dtype"]),
                )
            replicated.append(
                np.asarray(multihost_utils.broadcast_one_to_all(src))
            )
    new_record = {
        "doc": doc,
        "leaves": replicated,
        "step": int(doc.get("step", 0)),
        "published_ts": (
            record["published_ts"] if record is not None else time.time()
        ),
        "peer_replicated": True,
    }
    with _lock:
        _store[_key(exp_dir)] = new_record
    telemetry.emit(
        "emergency_peer_exchange", engine="zerostall",
        step=new_record["step"], exp_dir=str(exp_dir),
        leaves=len(replicated),
        bytes=int(sum(a.nbytes for a in replicated)),
    )
    return True


def peek(exp_dir):
    """(step, record) of the experiment's emergency record, else None."""
    with _lock:
        record = _store.get(_key(exp_dir))
    if record is None:
        return None
    return record["step"], record


def usable(exp_dir, target_topology, *, min_step=0):
    """Host-local gate: is there a record fresh enough and on the SAME
    topology? (Elastic cross-topology restores go through the disk path,
    which has the preflight machinery.) Returns the record or None."""
    from pyrecover_tpu.checkpoint.elastic import topologies_differ

    got = peek(exp_dir)
    if got is None:
        return None
    step, record = got
    if step < min_step:
        return None
    if topologies_differ(record["doc"].get("topology"), target_topology):
        return None
    if jax.process_count() > 1 and not record.get("peer_replicated"):
        # without peer replication only host 0 holds the bytes; a pod
        # restore needs them everywhere — fall back to disk
        return None
    return record


def verify(record):  # jaxlint: host-only
    """Strict digest check: recompute every leaf's chunk digests over the
    in-RAM bytes and compare against the committed manifest. Returns
    ``(ok, reason)`` — the gate ``train._resume`` runs on host 0 before
    the tier is ever preferred over disk."""
    doc, np_leaves = record["doc"], record["leaves"]
    if len(np_leaves) != len(doc.get("leaves", [])):
        return False, (
            f"record holds {len(np_leaves)} leaves, manifest lists "
            f"{len(doc.get('leaves', []))}"
        )
    for entry, arr in zip(doc["leaves"], np_leaves):
        digests = chunkstore.leaf_chunk_digests(
            arr, int(entry["chunk_bytes"])
        )
        if digests != entry["chunks"]:
            return False, (
                f"{entry['path']}: in-RAM bytes no longer match the "
                "committed manifest digests"
            )
    return True, ""


def restore(exp_dir, target_state):  # jaxlint: host-only
    """Restore ``target_state`` from the in-RAM record, verifying every
    leaf's chunk digests against the manifest first (strict: a digest
    mismatch raises and the caller falls back to disk). Returns
    ``(state, sampler_state, doc)``."""
    got = peek(exp_dir)
    if got is None:
        raise LookupError(f"no emergency record for {exp_dir}")
    _, record = got
    doc, np_leaves = record["doc"], record["leaves"]
    t0 = time.monotonic()
    leaves, treedef = jax.tree_util.tree_flatten(target_state)
    if len(np_leaves) != len(leaves):
        raise ValueError(
            f"emergency record has {len(np_leaves)} leaves, target "
            f"expects {len(leaves)}"
        )
    with telemetry.span(
        "ckpt_emergency_verify", engine="zerostall",
        metric="ckpt_zerostall_emergency_verify_s",
    ):
        ok, reason = verify(record)
        if not ok:
            raise ValueError(f"emergency record rejected: {reason}")
    with telemetry.span(
        "ckpt_emergency_restore", engine="zerostall",
        metric="ckpt_zerostall_emergency_restore_s",
    ):
        restored = []
        for tgt, src in zip(leaves, np_leaves):
            if tuple(tgt.shape) != tuple(src.shape):
                raise ValueError(
                    f"emergency record shape {src.shape} vs target "
                    f"{tgt.shape}"
                )
            src = np.asarray(src).astype(tgt.dtype)
            if isinstance(tgt, jax.Array) and hasattr(tgt, "sharding"):
                restored.append(jax.device_put(src, tgt.sharding))
            else:
                restored.append(jax.numpy.asarray(src))
        state = jax.tree_util.tree_unflatten(treedef, restored)
    # jaxlint: disable-next=untimed-device-work -- the milliseconds
    # claimed here are digest verification + device_put enqueue; the
    # first post-restore train step syncs the transfers
    seconds = time.monotonic() - t0
    log_host0(
        "Restored step %d from the in-RAM emergency tier in %.3fs "
        "(disk tier bypassed)", int(doc.get("step", 0)), seconds,
    )
    telemetry.emit(
        "emergency_restore", engine="zerostall",
        step=int(doc.get("step", 0)), seconds=round(seconds, 4),
    )
    return state, doc.get("sampler", {}), doc


class RamTier:
    """One experiment's record, as the resume walk asks for it
    (``CheckpointEngine.ram_tier``). The functions are looked up when
    called, so a test that patches one on this module is obeyed."""

    def __init__(self, exp_dir):
        self.exp_dir = exp_dir

    def peek(self):
        return peek(self.exp_dir)

    def usable(self, target_topology, *, min_step=0):
        return usable(self.exp_dir, target_topology, min_step=min_step)

    def verify(self, record):
        return verify(record)

    def restore(self, target_state):
        return restore(self.exp_dir, target_state)


def drop(exp_dir=None):
    """Forget records (all of them with no argument) — test hygiene and
    the explicit opt-out for memory-tight callers."""
    with _lock:
        if exp_dir is None:
            _store.clear()
        else:
            _store.pop(_key(exp_dir), None)
