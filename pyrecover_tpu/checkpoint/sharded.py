"""Sharded distributed checkpointing (Orbax/tensorstore) with async saves.

Capability parity with reference `save_ckpt_distributed` /
`load_ckpt_distributed` (checkpoint.py:218-368), which wrap
`torch.distributed.checkpoint` + FileSystemWriter/Reader. The TPU-native
engine is Orbax: every host writes exactly its own shards (OCDBT/tensorstore
under the hood), restore reshards onto whatever mesh the target state
carries, and — beyond the reference — saves are ASYNC: the device→host
copy happens at the save call, the filesystem write overlaps subsequent
training steps, which is what makes the <30 s preemption-save target
feasible (BASELINE.md).

A checkpoint directory holds two items: ``state`` (the sharded pytree) and
``meta`` (JSON: sampler data-order state + counters) — the analogue of the
reference's `metadata={epoch,step}` planner state (checkpoint.py:254-258).

``meta`` also carries ``leaf_digests``: the BLAKE2b-128 digest of every
fully-addressable ``.params`` leaf, the serving restore's tamper gate.

The state crosses the host link ONCE a save. The save call takes one
snapshot of it into host memory (``_snapshot``, under ``ckpt_serialize``):
``copy_to_host_async()`` on the array of every shard this process writes,
a bounded number of bytes in flight, then waits for them. The runtime keeps
each host copy on the shard's own array object, and both later readers ask
those objects: the digests (``_host_leaf``) and Orbax's serialization
(``addressable_shards[i].data`` of the replica it writes), whose save call
therefore finds every transfer done and only dispatches the write. After
the snapshot nothing reads the device, so the caller may donate or delete
the state the moment ``save`` returns. A leaf that is no ``jax.Array`` is on
the host already; a leaf with several replicas is cut up on its devices by
Orbax (replica-parallel: every replica writes a part) and goes that way as
before, one replica of it snapshotted only where it needs a digest.

An async save hashes the snapshot's ``.params`` leaves in a commit future
of the same Orbax save (``_MetaHandler``), off the loop's thread (the
retroactive ``ckpt_digest_background`` span), and Orbax's atomic rename
waits for it: no checkpoint commits without its digests, and a failed hash
fails the save like a failed write. A sync save has no later commit and
hashes inline, inside ``ckpt_digest``.
"""

import collections
import dataclasses
import json
import threading
import time
from pathlib import Path

import jax
import numpy as np
import orbax.checkpoint as ocp

from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint.engine import (
    PARAMS_PREFIX,
    CheckpointEngine,
    CheckpointIntegrityError,
    CheckpointStructureError,
    nest_params,
)
from pyrecover_tpu.checkpoint.registry import prune_checkpoints
from pyrecover_tpu.resilience import faults
from pyrecover_tpu.utils.logging import log_host0

# No file of a checkpoint grows with the model: every array is cut into
# chunks of at most CHUNK_BYTES and OCDBT starts a new data file once one
# passes DATA_FILE_BYTES, so the largest file is about their sum (92 MiB
# among the 107 files of the 7.6 GB llama-1b state). Orbax's defaults are
# one chunk per array shard (587 MB for a stacked llama-1b FFN leaf) in
# data files of 2 GiB, and a host with a file-size ceiling fails such a
# write with EFBIG — as the vanilla engine's single 7.6 GB file did on the
# machine that checks chip_smoke.py.
DATA_FILE_BYTES = 64 * 1024 * 1024
CHUNK_BYTES = 32 * 1024 * 1024


# Orbax's commit thread reports its own life (start to commit) under this
# name when it ends; the listener below turns it into a span on that thread
_BACKGROUND_WRITE_EVENT = "/jax/checkpoint/write/async/thread_duration_sec"
_listener_lock = threading.Lock()
_listener_registered = False


def _on_duration_event(event, secs, **_):  # jaxlint: host-only
    if event != _BACKGROUND_WRITE_EVENT:
        return
    now = time.monotonic()
    telemetry.record_span(
        "ckpt_write_background", now - secs, now, engine="sharded",
        metric="ckpt_sharded_background_write_s",
    )


def _register_background_write_listener():  # jaxlint: host-only
    """Once a process: ``jax.monitoring`` keeps listeners for its life."""
    global _listener_registered
    with _listener_lock:
        if not _listener_registered:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration_event
            )
            _listener_registered = True


def _digestable_params(state):  # jaxlint: host-only
    """``[(manifest path, leaf)]`` of the fully-addressable ``.params``
    leaves, whose BLAKE2b-128 digests are the serving restore's tamper
    gate (non-addressable pod shards are skipped: no gathers in the save
    path)."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        key = jax.tree_util.keystr(path)
        if not key.startswith(".params"):
            continue
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            continue
        out.append((key, leaf))
    return out


def _nbytes(leaves):  # jaxlint: host-only
    return int(sum(getattr(leaf, "nbytes", 0) for leaf in leaves))


# What the copy waits for is the host's first touch of the fresh buffers it
# lands in, and the runtime touches the more of them at once the more
# transfers are under way: on a TPU v5e host the 6.8 GB Mistral state takes
# 7.2-9.4 s with 0.5-1 GiB in flight, 4.3-5.2 s with 2-4 GiB, and 6.4-10.3 s
# with all of it (PERF.md §6, PR 28). So the snapshot starts a transfer only
# while fewer bytes than this are under way
IN_FLIGHT_BYTES = 3 * 1024**3


def _start_host_copy(arr):  # jaxlint: host-only
    """The one place a save starts a device→host transfer (the tests count
    the calls); the runtime keeps the copy on ``arr``."""
    arr.copy_to_host_async()


def _await_host_copy(arr):  # jaxlint: host-only
    """Wait for ``arr``'s transfer; its host copy, which ``arr`` keeps."""
    return np.asarray(arr)


def _written_shards(leaf):  # jaxlint: host-only
    """The shard arrays of ``leaf`` that this process's save reads: those
    of replica 0, the very objects Orbax's serialization asks for."""
    return [s for s in leaf.addressable_shards if s.replica_id == 0]


def _has_replicas(leaf):  # jaxlint: host-only
    """More devices than distinct shards: Orbax cuts such a leaf up on its
    devices and every replica sends a part (its replica-parallel rule)."""
    index_of = leaf.sharding.devices_indices_map(leaf.shape)
    return len(index_of) > len(set(map(str, index_of.values())))


def _snapshot(leaves, digestable):  # jaxlint: host-only
    """Copy to the host, once, every shard array the save's readers will
    ask for (Orbax's serialization, and the hash of the ``digestable``
    leaves), with at most IN_FLIGHT_BYTES under way, and wait for them.
    Returns ``(bytes copied, leaves left as they are)``. Nothing reads the
    device after this returns."""
    hashed = {id(leaf) for _, leaf in digestable}
    flying, in_flight, copied, left = collections.deque(), 0, 0, 0
    for leaf in leaves:
        if not isinstance(leaf, jax.Array) or (
            id(leaf) not in hashed and _has_replicas(leaf)
        ):
            left += 1
            continue
        for shard in _written_shards(leaf):
            arr = shard.data
            while flying and in_flight + arr.nbytes > IN_FLIGHT_BYTES:
                head = flying.popleft()
                _await_host_copy(head)
                in_flight -= head.nbytes
            _start_host_copy(arr)
            flying.append(arr)
            in_flight += arr.nbytes
            copied += arr.nbytes
    for arr in flying:
        _await_host_copy(arr)
    return copied, left


def _host_leaf(leaf):  # jaxlint: host-only
    """The whole of a fully-addressable leaf as one host array, put
    together from the snapshot's copies of its shards."""
    if not isinstance(leaf, jax.Array):
        return np.asarray(leaf)
    shards = _written_shards(leaf)
    if len(shards) == 1:
        return _await_host_copy(shards[0].data)
    out = np.empty(leaf.shape, leaf.dtype)
    for shard in shards:
        out[shard.index] = _await_host_copy(shard.data)
    return out


def _leaf_digests(host_leaves):  # jaxlint: host-only
    from pyrecover_tpu.checkpoint.zerostall.chunkstore import leaf_digest

    return {key: leaf_digest(arr) for key, arr in host_leaves}


class _MetaHandler(ocp.JsonCheckpointHandler):
    """The ``meta`` item: Orbax's JSON item (``meta/metadata``), whose
    commit future first hashes the host copies handed to it and files the
    result under ``leaf_digests``. The future runs on a thread of its own
    and Orbax's commit waits for it like for any write."""

    @classmethod
    def typestr(cls):
        # what the commit marker records for the item: it is Orbax's JSON
        # item on disk, and any Orbax reader may restore it as such
        return ocp.JsonCheckpointHandler.typestr()

    async def async_save(self, directory, item=None, args=None):
        meta, deferred, step = dict(args.item), args.deferred, args.step

        async def fill_and_write():
            if deferred:
                t0 = time.monotonic()
                meta["leaf_digests"] = _leaf_digests(deferred)
                telemetry.record_span(
                    "ckpt_digest_background", t0, time.monotonic(),
                    engine="sharded", step=step, leaves=len(deferred),
                    bytes=_nbytes(arr for _, arr in deferred),
                    metric="ckpt_sharded_digest_background_s",
                )
            await self._save_fn(meta, directory)  # Orbax's own JSON write

        return [ocp.future.CommitFutureAwaitingContractedSignals(
            fill_and_write(), name="meta_digest_save"
        )]


@dataclasses.dataclass
class _MetaSave(ocp.args.CheckpointArgs):
    """``item``: the JSON mapping; ``deferred``: ``[(key, host array)]``
    still to be hashed into ``item["leaf_digests"]`` by the commit. Known
    to ``_composite_handler``'s registry only, not to Orbax's global one."""

    item: dict
    deferred: list
    step: int | None = None


def _composite_handler():
    """``meta`` is written by ``_MetaHandler`` and read as plain JSON."""
    registry = ocp.handlers.DefaultCheckpointHandlerRegistry()
    meta = _MetaHandler()
    registry.add("meta", _MetaSave, meta)
    registry.add("meta", ocp.args.JsonRestore, meta)
    return ocp.CompositeCheckpointHandler(handler_registry=registry)


class ShardedCheckpointer(CheckpointEngine):
    """The sharded engine. Long-lived; owns the async machinery. Use as a
    context manager or call close(). ``verify`` is taken for the engines'
    common constructor: the commit marker and the digests are not
    optional."""

    name = "sharded"

    def __init__(self, use_async=True, verify=False, max_keep=None):
        self.use_async = use_async
        self.max_keep = max_keep
        self._in_flight = False  # a save was dispatched and nobody waited
        handler = _composite_handler()
        if use_async:
            self._ckptr = ocp.AsyncCheckpointer(handler)
            _register_background_write_listener()
        else:
            self._ckptr = ocp.Checkpointer(handler)

    def save(self, path, state, sampler_state=None, *, max_keep=None,
             extra_meta=None, final=False):
        """Start (async) or perform (sync) a sharded save. Returns wall
        seconds spent blocking the training loop; a ``final`` save then
        waits until it is durable. ``max_keep`` overrides the engine's.

        The blocking seconds lie under three spans in a row:
        ``ckpt_wait_previous`` (async only), ``ckpt_serialize``,
        ``ckpt_prune``; the manifest, the topology and the fault seams read
        metadata only and stay outside them. ``ckpt_serialize`` is the
        device→host copy of the whole state and the dispatch: first the
        snapshot (noted on it: ``snapshot_s``; ``snapshot_bytes``, the
        bytes the snapshot copied, against ``bytes`` the share of the state
        it engaged on; ``fallback_leaves``, the leaves left as they are),
        then ``ckpt_digest`` inside it, then Orbax's save call, which finds
        the copies made. ``ckpt_digest`` is what the digests still cost the
        loop: picking the snapshot's ``.params`` leaves (``leaves``,
        ``bytes``) and, on a sync save, their hash; an async save hands the
        hash of all of them (``deferred``) to its commit, where it is the
        retroactive ``ckpt_digest_background``. The state is read by the
        snapshot and never after it. The write that goes on after the
        return is ``ckpt_write_background``, recorded by Orbax's commit
        thread when it ends, the hash included."""
        t0 = time.monotonic()
        path = Path(path).absolute()
        step = (extra_meta or {}).get("step")
        if max_keep is None:
            max_keep = self.max_keep
        telemetry.emit(
            "ckpt_save_start", engine="sharded", path=str(path),
            async_=self.use_async,
        )
        faults.check("ckpt_save_begin", engine="sharded", path=str(path))
        # same schema manifest the vanilla engine embeds (one schema,
        # two producers): preflight/resume diff it without tensor reads
        from pyrecover_tpu.analysis.shardcheck.manifest import state_manifest
        from pyrecover_tpu.parallel.mesh import state_topology

        meta = {
            "sampler": sampler_state or {},
            "manifest": state_manifest(state),
            # saved topology: the elastic-resume gate (checkpoint/elastic.py)
            # diffs this against the live mesh before any tensor read
            "topology": state_topology(state),
            # filled in below (sync) or by the commit (async)
            "leaf_digests": {},
        }
        # per-params-leaf content digests: Orbax's raw (target-free)
        # read verifies nothing, so the serving restore needs its own
        # tamper gate. Fully-addressable leaves only — digesting a
        # pod-sharded leaf would force the allgather this engine
        # exists to avoid; a leaf without a digest is simply not
        # verifiable on that path (single-process covers them all).
        digestable = _digestable_params(state)
        leaves = jax.tree_util.tree_leaves(state)
        if extra_meta:
            meta.update(extra_meta)
        if self.use_async:
            # Orbax's save waits for the previous save's background write
            # before it copies anything; waiting here first, where its own
            # wait would come, gives that wait a span of its own, leaves
            # Orbax's nothing to wait for, and lets the previous write drop
            # its snapshot before this one is taken
            with telemetry.span(
                "ckpt_wait_previous", engine="sharded", step=step,
                waited=self._write_in_flight(),
                metric="ckpt_sharded_wait_previous_s",
            ):
                self._ckptr.wait_until_finished()
        # the part the training loop pays for: the copy cannot wait, the
        # next step donates these buffers. The write-to-durable tail is
        # ckpt_write_background on the commit thread, and shows up as the
        # ckpt_wait_durable span when someone waits
        with telemetry.span(
            "ckpt_serialize", engine="sharded", path=str(path), step=step,
            async_=self.use_async, bytes=_nbytes(leaves),
            metric="ckpt_sharded_serialize_s",
        ) as serialize_span:
            t_snap = time.monotonic()
            copied, left = _snapshot(leaves, digestable)
            serialize_span.note(
                snapshot_s=round(time.monotonic() - t_snap, 6),
                snapshot_bytes=copied, fallback_leaves=left,
            )
            with telemetry.span(
                "ckpt_digest", engine="sharded", step=step,
                leaves=len(digestable),
                bytes=_nbytes(leaf for _, leaf in digestable),
                deferred=len(digestable) if self.use_async else 0,
                metric="ckpt_sharded_digest_s",
            ):
                # the hash can wait, as long as the commit waits for it
                deferred = [(key, _host_leaf(leaf)) for key, leaf in digestable]
                if not self.use_async:
                    meta["leaf_digests"] = _leaf_digests(deferred)
                    deferred = []
            self._ckptr.save(
                path,
                args=ocp.args.Composite(
                    state=ocp.args.PyTreeSave(
                        state,
                        save_args=jax.tree_util.tree_map(
                            lambda _: ocp.SaveArgs(chunk_byte_size=CHUNK_BYTES),
                            state,
                        ),
                        ocdbt_target_data_file_size=DATA_FILE_BYTES,
                    ),
                    meta=_MetaSave(meta, deferred, step),
                ),
                force=True,
            )
            self._in_flight = self.use_async
        # async saves: dispatch accepted (durability is wait()'s business);
        # sync saves: the directory is committed at this point
        telemetry.watchdog.beat("ckpt_writer")
        faults.check("ckpt_commit", engine="sharded", path=str(path))
        if max_keep:
            # prune only already-finalized checkpoints; the in-flight save's
            # tmp dir is invisible to the registry until orbax renames it.
            if jax.process_index() == 0:
                with telemetry.span(
                    "ckpt_prune", engine="sharded", step=step,
                    metric="ckpt_sharded_prune_s",
                ) as prune_span:
                    removed = prune_checkpoints(
                        path.parent, max_keep, engine="sharded"
                    )
                    prune_span.note(removed=len(removed))
        blocking_s = time.monotonic() - t0
        telemetry.emit(
            "ckpt_save_blocking", engine="sharded", path=str(path),
            blocking_s=round(blocking_s, 4), async_=self.use_async,
        )
        if final:
            self.wait()
        return blocking_s

    def _write_in_flight(self):
        """Is the previous async save's commit thread still at work? Read
        off Orbax's own handle on it (no public accessor); None where this
        Orbax keeps it elsewhere."""
        manager = getattr(self._ckptr, "_async_manager", None)
        if not hasattr(manager, "_thread"):
            return None
        thread = manager._thread
        return thread is not None and thread.is_alive()

    def wait(self):
        """Block until any in-flight async save is durable."""
        self._in_flight = False
        if hasattr(self._ckptr, "wait_until_finished"):
            t0 = time.monotonic()
            with telemetry.span(
                "ckpt_wait_durable", engine="sharded",
                metric="ckpt_sharded_durable_wait_s",
            ):
                self._ckptr.wait_until_finished()
            telemetry.watchdog.beat("ckpt_writer")
            # background seconds the training loop did NOT pay for: the gap
            # between dispatch (blocking_s) and durability shows up here
            # only when someone waits — final saves and shutdown
            telemetry.emit(
                "ckpt_save_durable", engine="sharded",
                wait_s=round(time.monotonic() - t0, 4),
            )

    def join(self, timeout_s=None):
        """``wait`` under the interface's name. Orbax's wait takes no
        bound, and nothing of its write is counted as shadow seconds."""
        self.wait()
        return 0.0

    def precheck(self, path, target_state):
        return precheck_ckpt_sharded(path, target_state)

    def load(self, path, target_state, *, prechecked=False):
        return self.restore(path, target_state)

    def restore(self, path, target_state):
        """Restore onto the shardings carried by ``target_state``'s leaves
        (the TARGET shardings, not the saved ones: Orbax range-reads each
        leaf straight into its target shards — this engine's reshard)."""
        path = Path(path).absolute()
        t0 = time.monotonic()
        telemetry.emit("ckpt_restore_start", engine="sharded", path=str(path))
        restore_args = ocp.checkpoint_utils.construct_restore_args(target_state)
        with telemetry.span(
            "ckpt_restore", engine="sharded", path=str(path),
            metric="ckpt_sharded_restore_s",
        ):
            result = self._ckptr.restore(
                path,
                args=ocp.args.Composite(
                    state=ocp.args.PyTreeRestore(
                        item=target_state, restore_args=restore_args
                    ),
                    meta=ocp.args.JsonRestore(),
                ),
            )
        meta = result.meta or {}
        telemetry.emit(
            "ckpt_restore_done", engine="sharded", path=str(path),
            seconds=round(time.monotonic() - t0, 4),
            step=int(meta.get("step", 0)),
        )
        return result.state, meta.get("sampler", {}), meta

    def read_params(self, path):
        """Raw (target-free) Orbax read of the ``state`` item; returns the
        ``params`` subtree as host arrays. Verifies each leaf against the
        content digests the save recorded in the ``meta`` item (Orbax's
        raw read detects NO tensor corruption of its own — measured: a
        flipped tensorstore byte loads silently) — a mismatch raises
        before any placement."""
        from pyrecover_tpu.checkpoint.zerostall.chunkstore import leaf_digest

        path = Path(path)
        with ocp.Checkpointer(ocp.PyTreeCheckpointHandler()) as ckptr:
            tree = ckptr.restore(path / "state")
        params = tree["params"] if isinstance(tree, dict) else tree.params
        meta_file = path / "meta" / "metadata"
        digests = {}
        if meta_file.exists():
            try:
                digests = json.loads(meta_file.read_text()).get(
                    "leaf_digests"
                ) or {}
            except ValueError:
                digests = {}
        flat = []
        for p, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            key = PARAMS_PREFIX + jax.tree_util.keystr(p)
            arr = np.asarray(leaf)
            expected = digests.get(key)
            if expected is not None and leaf_digest(arr) != expected:
                raise CheckpointIntegrityError(
                    f"checkpoint {path.name}: leaf {key} fails its "
                    "recorded content digest — tensorstore file tampered "
                    "or bit-flipped after save"
                )
            flat.append((key, arr))
        return nest_params(flat)

    def close(self):
        if self._in_flight:
            self.wait()
        self._ckptr.close()


def precheck_ckpt_sharded(path, target_state=None):
    """Host-LOCAL integrity pre-check of an Orbax checkpoint directory (no
    collectives, no tensor reads) — the sharded engine's analogue of
    ``precheck_ckpt_vanilla``, so the latest-resume fallback can walk past
    a preemption-torn newest checkpoint on THIS engine too (a preemption
    mid-async-save is precisely the sharded engine's use case; reference
    recovery intent: checkpoint.py:371-404's latest discovery).

    Checks, cheapest first:
      * the directory exists and carries Orbax's commit marker
        ``_CHECKPOINT_METADATA`` (written at finalize — a torn save that
        never reached its atomic rename has no marker) and it parses;
      * the ``meta`` item (sampler state / counters JSON) parses;
      * the ``state`` item has its OCDBT manifest and pytree ``_METADATA``;
      * the pytree metadata probe (structure + per-leaf shapes/dtypes, no
        tensor data) succeeds.

    Returns ``(ok, reason)``. When ``target_state`` is given and the
    checkpoint's leaf count or shape multiset doesn't fit it, raises
    ``CheckpointStructureError`` instead of returning False: a wrong model
    config fails on EVERY candidate, and silently walking back would
    restart the run from an old step (or step 0) with the wrong model.

    Tensor DATA corruption inside ``state/d/`` is out of scope (that would
    be a full read, not a pre-check); it surfaces as a restore exception,
    which the single-process fallback path also survives.
    """
    path = Path(path)
    try:
        if not path.is_dir():
            return False, "not a directory"
        commit = path / "_CHECKPOINT_METADATA"
        if not commit.exists():
            return False, "missing commit marker _CHECKPOINT_METADATA (torn save?)"
        json.loads(commit.read_text())
        meta_file = path / "meta" / "metadata"
        if not meta_file.exists():
            return False, "missing meta item"
        meta = json.loads(meta_file.read_text())
        state_dir = path / "state"
        manifest = state_dir / "manifest.ocdbt"
        if not manifest.exists() or manifest.stat().st_size == 0:
            return False, "missing/empty OCDBT manifest"
        tree_meta = state_dir / "_METADATA"
        if not tree_meta.exists():
            return False, "missing pytree _METADATA"
        # the metadata probe below parses _METADATA itself; malformed JSON
        # surfaces there (.tree on newer orbax, the raw dict on older)
        md = ocp.PyTreeCheckpointHandler().metadata(state_dir)
        md = md.tree if hasattr(md, "tree") else md
        ck_shapes = sorted(
            tuple(x.shape)
            for x in jax.tree_util.tree_leaves(
                md, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype")
            )
        )
    except Exception as e:
        return False, f"{type(e).__name__}: {e}"
    if target_state is not None:
        # schema manifest (saved by this engine since v0.5): exact per-
        # path diff with real leaf names — and dtype-drift visibility the
        # shape multiset below cannot give
        if isinstance(meta, dict) and "manifest" in meta:
            from pyrecover_tpu.analysis.shardcheck.manifest import (
                diff_manifests,
                state_manifest,
            )

            findings = diff_manifests(
                meta["manifest"], state_manifest(target_state),
                locus=path.name, check_specs=False,
            )
            structural = [
                f for f in findings if f.rule_id in ("SC07", "SC08")
            ]
            if structural:
                raise CheckpointStructureError(
                    f"checkpoint {path.name} does not fit the configured "
                    "model: "
                    + "; ".join(f.message for f in structural[:3])
                )
            for f in findings:
                if f.rule_id == "SC09":
                    log_host0(
                        "resume manifest: %s (restore will cast)",
                        f.message, level=30,  # WARNING
                    )
                    telemetry.emit(
                        "ckpt_manifest_dtype_drift", path=str(path),
                        detail=f.message,
                    )
            return True, ""
        tgt_shapes = sorted(
            tuple(x.shape) for x in jax.tree_util.tree_leaves(target_state)
        )
        if ck_shapes != tgt_shapes:
            from collections import Counter

            ck_c, tgt_c = Counter(ck_shapes), Counter(tgt_shapes)
            only_ck = list((ck_c - tgt_c).elements())[:4]
            only_tgt = list((tgt_c - ck_c).elements())[:4]
            raise CheckpointStructureError(
                f"checkpoint {path.name} does not fit the configured model: "
                f"{len(ck_shapes)} leaves vs {len(tgt_shapes)}; shapes only "
                f"in checkpoint {only_ck}, only in model {only_tgt} — wrong "
                "model config, not corruption"
            )
    return True, ""


def save_ckpt_sharded(path, state, sampler_state=None, *, max_keep=None,
                      extra_meta=None):
    """One-shot synchronous sharded save (tests / final preemption save)."""
    with ShardedCheckpointer(use_async=False) as ckptr:
        secs = ckptr.save(
            path, state, sampler_state, max_keep=max_keep, extra_meta=extra_meta
        )
    log_host0("Sharded checkpoint saved to %s", path)
    return secs


def load_ckpt_sharded(path, target_state):
    with ShardedCheckpointer(use_async=False) as ckptr:
        return ckptr.restore(path, target_state)
