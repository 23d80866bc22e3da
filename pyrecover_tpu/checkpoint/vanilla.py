"""Vanilla checkpointing: host-0 single-file save with checksum verification.

Capability parity with reference `save_ckpt_vanilla` / `load_ckpt_vanilla`
(checkpoint.py:25-215): one file holds the FULL training state, a checksum
sidecar guards integrity (verification overlaps the load in a background
thread, the reference's trick at checkpoint.py:151-178), retention pruning
keeps the newest N, and `latest` is discoverable. TPU-native differences:

  * The payload is the whole functional state pytree (params, optimizer
    state, step/epoch, RNG key data) + the sampler's data-order state — so a
    resume is bit-exact by construction. The reference loses sampler state
    silently (SURVEY §2.3 defect 3) and never saves RNG.
  * Serialization STREAMS leaf-by-leaf (format v2: a JSON header with
    per-leaf dtype/shape followed by length-prefixed raw buffers) with the
    checksum folded into the same write pass, so host-0 RAM is bounded by
    O(largest leaf) on a synchronous save — leaves are gathered, written,
    and freed one at a time — instead of the v1 msgpack path's whole-state
    payload copy on top of the gathered leaves (≈4× state bytes at the 8B
    flagship; the reference's `torch.save` streams, checkpoint.py:74).
    Background saves must gather on the calling thread (collectives can't
    run concurrently with training), so they hold the gathered state once
    and decay it leaf-by-leaf as the writer drains. Writes are atomic
    (tmp file + rename) so a preemption mid-write can never corrupt
    `latest` — the reference writes in place. v1 checkpoints remain
    readable.
  * Multi-host: non-addressable (sharded) leaves are allgathered to host 0;
    on load every host reads the file and `device_put`s onto its target
    shardings. SHA-256/xxh64-tree replaces MD5.
"""

import hashlib
import json
import os
import tempfile
import threading
import time
from pathlib import Path

import jax
import numpy as np
from flax.serialization import msgpack_restore

from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint.engine import (
    CheckpointIntegrityError,
    CheckpointStructureError,  # noqa: F401  (re-exported)
    HandleEngine,
    nest_params,
)
from pyrecover_tpu.checkpoint.registry import prune_checkpoints
from pyrecover_tpu.parallel.mesh import state_topology, sync_global_devices
from pyrecover_tpu.resilience import faults
from pyrecover_tpu.resilience.retry import io_retry
from pyrecover_tpu.utils.logging import log_host0

FORMAT_VERSION = 2
SUPPORTED_FORMATS = (1, 2)  # v1 (msgpack) stays readable
MAGIC = b"PYRCKPT2"


def _leaf_to_numpy(leaf):
    if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
        from jax.experimental import multihost_utils

        from pyrecover_tpu import telemetry

        # pod path: every host must reach this allgather; the bounded
        # phase makes a host that never arrives a named hang, and the
        # addressability test is a global array property (congruent)
        with telemetry.collective_phase("ckpt_leaf_allgather"):
            return np.asarray(
                multihost_utils.process_allgather(leaf, tiled=True)
            )
    return np.asarray(leaf)


def _dtype_from_str(s):
    """np dtype from its str() name, including the ml_dtypes family
    (bfloat16 etc.) that np.dtype() alone doesn't resolve."""
    try:
        return np.dtype(s)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, s))


_HASH_CHUNK = 16 * 1024 * 1024


def compute_checksum(path):
    """Self-describing checksum string. Prefers the native multithreaded
    xxh64-tree engine (native/pyrecover_io.cpp); falls back to sha256."""
    from pyrecover_tpu.checkpoint import native_io

    if native_io.available():
        digest = native_io.hash_file(path, chunk=_HASH_CHUNK)
        return f"xxh64tree:{_HASH_CHUNK}:{digest:016x}"
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            chunk = f.read(_HASH_CHUNK)
            if not chunk:
                break
            h.update(chunk)
    return f"sha256::{h.hexdigest()}"


def verify_checksum(path, expected):
    """Verify ``path`` against a checksum string from ``compute_checksum``.
    Either implementation (native C++ / pure Python) can verify either
    scheme, so checkpoints move freely between hosts."""
    algo, param, digest = expected.strip().split(":", 2)
    if algo == "xxh64tree":
        from pyrecover_tpu.checkpoint import native_io
        from pyrecover_tpu.utils import xxh

        chunk = int(param)
        if native_io.available():
            actual = f"{native_io.hash_file(path, chunk=chunk):016x}"
        else:
            actual = f"{xxh.tree_hash_file(path, chunk):016x}"
        return actual == digest
    if algo == "sha256":
        h = hashlib.sha256()
        with open(path, "rb") as f:
            while True:
                c = f.read(_HASH_CHUNK)
                if not c:
                    break
                h.update(c)
        return h.hexdigest() == digest
    raise ValueError(f"Unknown checksum algorithm {algo!r}")


def _sidecar(path):
    p = Path(path)
    return p.with_suffix(p.suffix + ".sha256")


class _IncrementalChecksum:
    """Folds the sidecar checksum into the streaming write pass (no
    re-read of the file): the native xxh64-tree scheme when the C++
    engine is available — per-_HASH_CHUNK digests over the byte stream,
    combined at the end, byte-identical to ``hash_file`` — else streaming
    sha256. Both produce strings ``verify_checksum`` accepts."""

    def __init__(self, chunk=_HASH_CHUNK):
        from pyrecover_tpu.checkpoint import native_io

        self.chunk = chunk
        self.native = native_io.available()
        if self.native:
            self._xxh = native_io.xxh64
            self._buf = bytearray()
            self._digests = []
        else:
            self._h = hashlib.sha256()

    def update(self, data):
        if not self.native:
            self._h.update(data)
            return
        self._buf += data
        while len(self._buf) >= self.chunk:
            self._digests.append(
                self._xxh(bytes(self._buf[: self.chunk])).to_bytes(8, "little")
            )
            del self._buf[: self.chunk]

    def result(self):
        if not self.native:
            return f"sha256::{self._h.hexdigest()}"
        if self._buf or not self._digests:
            self._digests.append(self._xxh(bytes(self._buf)).to_bytes(8, "little"))
            self._buf = bytearray()
        digest = self._xxh(b"".join(self._digests))
        return f"xxh64tree:{self.chunk}:{digest:016x}"


class VanillaSaveHandle:
    """Handle for a background vanilla save. ``wait()`` re-raises any write
    error. Only the serialize/write half runs in the thread; everything
    touching devices or collectives happened before the handle existed."""

    def __init__(self, thread=None):
        self._thread = thread
        self.error = None
        # background wall seconds the train loop did NOT pay for — the
        # goodput ledger's ckpt_shadow_s feed (0 for synchronous saves)
        self.shadow_s = 0.0

    def wait(self, timeout=None):
        """Join the writer (bounded when ``timeout`` is given — the
        train() unwind must not hang forever behind a wedged disk) and
        re-raise any writer error. A timeout raises ``TimeoutError``
        with the thread still running: the caller decides whether that
        fails the run or just gets logged on an already-failing unwind."""
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise TimeoutError(
                    f"background checkpoint writer still running after "
                    f"{timeout:.0f}s"
                )
            self._thread = None
        if self.error is not None:
            raise self.error

    @property
    def done(self):
        return self._thread is None or not self._thread.is_alive()


def save_ckpt_vanilla(path, state, sampler_state=None, *, verify=False,
                      max_keep=None, extra_meta=None, background=False):
    """Write the full training state to a single file (host 0 only).

    Returns wall seconds spent blocking the caller (host 0; other hosts
    return barrier time) — the save-timing signal the reference logs
    (train.py:332-340). With ``background=True`` returns
    ``(blocking_seconds, VanillaSaveHandle)``: the device→host gather and
    cross-host barrier stay on the calling thread (collectives must never
    run concurrently), while the streaming write, checksum, and retention
    pruning — pure host-0-local work — overlap subsequent training steps.
    The reference's vanilla save stalls every rank for the full write
    (checkpoint.py:55-103); this one stalls only for the gather.

    Host-0 RAM: synchronous saves INTERLEAVE gather and write, holding one
    leaf at a time — O(largest leaf). Background saves must finish every
    gather before returning, so they hold the gathered state once and free
    each leaf as the writer drains it.
    """
    t0 = time.monotonic()
    path = Path(path)
    telemetry.emit(
        "ckpt_save_start", engine="vanilla", path=str(path),
        background=bool(background),
    )
    faults.check("ckpt_save_begin", engine="vanilla", path=str(path))
    sync_global_devices("vanilla_save_enter")

    # schema manifest (paths/shapes/dtypes/pspecs): the single cross-
    # engine schema record — shardcheck diffs it at preflight/resume and
    # tools/inspect_checkpoint.py --manifest prints it
    from pyrecover_tpu.analysis.shardcheck.manifest import state_manifest

    manifest = state_manifest(state)
    path_leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
    keystrs = [jax.tree_util.keystr(p) for p, _ in path_leaves]
    meta = {
        "format": FORMAT_VERSION,
        "num_leaves": len(path_leaves),
        "treedef": str(treedef),
        # leaf key-paths, for the equality CLI and cross-format comparison
        "paths": keystrs,
        "sampler": sampler_state or {},
        # per-leaf dtype/shape: the v2 frame decoder's index
        "leaves": [
            {"dtype": str(np.dtype(x.dtype)), "shape": list(x.shape)}
            for _, x in path_leaves
        ],
        "manifest": manifest,
        # the topology this state spans — the elastic-resume gate diffs it
        # against the live mesh from the header alone (checkpoint/elastic.py)
        "topology": state_topology(state),
    }
    if extra_meta:
        meta.update(extra_meta)
    is_host0 = jax.process_index() == 0

    if background:
        # gather NOW (collectives stay on the calling thread); only host 0
        # keeps the numpy copies, and the writer frees each one as written
        np_leaves = []
        with telemetry.span(
            "ckpt_gather", engine="vanilla", metric="ckpt_vanilla_gather_s"
        ):
            for _, x in path_leaves:
                arr = _leaf_to_numpy(x)
                np_leaves.append(arr if is_host0 else None)
                del arr
        handle = VanillaSaveHandle()
        if is_host0:

            def drain():
                for i in range(len(np_leaves)):
                    arr = np_leaves[i]
                    np_leaves[i] = None  # decay RAM as the write advances
                    yield arr

            def _bg():
                t_bg = time.monotonic()
                try:
                    _write_stream(path, drain(), meta, verify, max_keep)
                except BaseException as e:  # surfaced at wait()
                    handle.error = e
                finally:
                    handle.shadow_s = time.monotonic() - t_bg
                    telemetry.emit(
                        "ckpt_save_shadow", engine="vanilla",
                        path=str(path),
                        shadow_s=round(handle.shadow_s, 4),
                        ok=handle.error is None,
                    )

            t = threading.Thread(target=_bg, daemon=True)
            handle._thread = t
            t.start()
        # no exit barrier in background mode: the remaining work is
        # host-0-local, so other hosts have nothing to wait for
        blocking_s = time.monotonic() - t0
        telemetry.emit(
            "ckpt_save_blocking", engine="vanilla", path=str(path),
            blocking_s=round(blocking_s, 4), background=True,
        )
        return blocking_s, handle

    # synchronous: interleave gather → write → free, one leaf live at a
    # time. Every host walks the SAME leaf order so the allgather
    # collectives line up; non-zero hosts drop each leaf immediately.
    if is_host0:
        _write_stream(
            path, (_leaf_to_numpy(x) for _, x in path_leaves), meta,
            verify, max_keep,
        )
    else:
        for _, x in path_leaves:
            arr = _leaf_to_numpy(x)
            del arr

    sync_global_devices("vanilla_save_exit")
    blocking_s = time.monotonic() - t0
    telemetry.emit(
        "ckpt_save_blocking", engine="vanilla", path=str(path),
        blocking_s=round(blocking_s, 4), background=False,
    )
    return blocking_s


def _write_stream(path, leaves_iter, meta, verify, max_keep):
    """Stream the v2 container: MAGIC, u64 meta length, meta JSON, then per
    leaf a u64 byte length + the raw little-endian C-order buffer. The
    sidecar checksum is computed over the same byte stream in-pass (no
    re-read). Leaves are written through a zero-copy uint8 view (numpy's
    buffer protocol rejects ml_dtypes like bfloat16, so the view is taken
    after reinterpreting the buffer as uint8), so peak extra RAM is the
    checksum's chunk buffer — plus a one-leaf copy only if a leaf arrives
    non-contiguous."""
    t0 = time.monotonic()
    written = 0
    path_s = str(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    meta_b = json.dumps(meta).encode()
    checksum = _IncrementalChecksum() if verify else None
    fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=path.name, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb", buffering=4 * 1024 * 1024) as f:

            def _write_once(b):
                # the injection seam raises BEFORE the real write, so a
                # retried chunk is never half-applied by the fault itself;
                # a real transient EIO leaves the buffered writer's state
                # to the retry — the best available recovery either way
                faults.check("ckpt_write", path=path_s, written=written)
                f.write(b)

            def w(b):
                nonlocal written
                io_retry(lambda: _write_once(b), op="write", path=path_s)
                written += len(b)
                # every landed chunk is checkpoint-writer progress for the
                # run-health watchdog (no-op when none is active): a save
                # that is WRITING is slow, not hung
                telemetry.watchdog.beat("ckpt_writer")
                if checksum is not None:
                    checksum.update(b)

            def _fsync_once():
                faults.check("ckpt_fsync", path=path_s)
                os.fsync(f.fileno())

            with telemetry.span(
                "ckpt_write", engine="vanilla", path=path_s,
                metric="ckpt_vanilla_write_s",
            ):
                w(MAGIC)
                w(len(meta_b).to_bytes(8, "little"))
                w(meta_b)
                for arr in leaves_iter:
                    data = memoryview(
                        np.ascontiguousarray(arr).view(np.uint8)
                    ).cast("B")
                    del arr
                    w(len(data).to_bytes(8, "little"))
                    for off in range(0, len(data), _HASH_CHUNK):
                        w(data[off : off + _HASH_CHUNK])
                    del data
            # durability BEFORE the atomic publish: a power cut after the
            # rename must not leave `latest` pointing at unsynced pages
            with telemetry.span(
                "ckpt_fsync", engine="vanilla", metric="ckpt_vanilla_fsync_s"
            ):
                f.flush()
                io_retry(_fsync_once, op="fsync", path=path_s)

        def _rename_once():
            faults.check("ckpt_rename", path=path_s)
            os.replace(tmp, path)  # atomic publish

        with telemetry.span(
            "ckpt_rename", engine="vanilla", metric="ckpt_vanilla_commit_s"
        ):
            io_retry(_rename_once, op="rename", path=path_s)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    if verify:
        with telemetry.span(
            "ckpt_sidecar", engine="vanilla", metric="ckpt_vanilla_sidecar_s"
        ):
            # jaxlint: disable-next=torn-write -- the sidecar is advisory
            # integrity metadata: a torn sidecar FAILS verification and the
            # resume falls back/quarantines — it can never be half-trusted
            io_retry(
                lambda: _sidecar(path).write_text(checksum.result()),
                op="sidecar", path=path_s,
            )
    faults.check("ckpt_commit", engine="vanilla", path=path_s)
    telemetry.emit(
        "ckpt_commit", engine="vanilla", path=str(path), bytes=written,
        write_s=round(time.monotonic() - t0, 4), checksum=bool(verify),
    )
    if max_keep:
        prune_checkpoints(path.parent, max_keep, engine="vanilla")


def read_ckpt_raw(path, *, check_version=True):
    """Read a vanilla checkpoint without a target state: returns
    ``(meta, paths, leaves)`` where ``paths`` are leaf key-path strings and
    ``leaves`` are numpy arrays in tree-flatten order. The single decoder of
    the on-disk layout — the equality CLI and the inspector build on it.
    Decodes both the v2 framed container (zero-copy views into the read
    buffer) and legacy v1 msgpack files.

    ``check_version=False`` lets diagnostic tools display/compare
    checkpoints from other format versions on a best-effort basis instead
    of refusing them; the restore path must keep the check."""
    from pyrecover_tpu.checkpoint import native_io

    path = Path(path)

    def _read_once():
        faults.check("ckpt_read", path=str(path))
        if native_io.available():
            return native_io.read_file(path)[0]  # parallel pread
        return path.read_bytes()

    data = io_retry(_read_once, op="read", path=str(path))
    return _decode_ckpt_bytes(data, check_version=check_version)


def _leaf_nbytes(lm):
    """Byte count a leaf's frame must have, from its meta entry."""
    count = int(np.prod(lm["shape"], dtype=np.int64)) if lm["shape"] else 1
    return count * _dtype_from_str(lm["dtype"]).itemsize


def _check_leaf_frame(i, lm, n, end, size):
    """Validate one v2 leaf frame — the single source of truth shared by
    the decoder and the structural walk. A corrupted length prefix (with
    enough trailing bytes) would otherwise silently desynchronize every
    subsequent leaf into garbage, so every load path fails loudly here.
    ``end`` is the frame's end offset, ``size`` the total byte count."""
    expect = _leaf_nbytes(lm)
    if n != expect:
        raise ValueError(
            f"leaf {i}: length prefix {n} != {expect} expected from meta "
            f"(dtype {lm['dtype']}, shape {lm['shape']}) — corrupt frame"
        )
    if end > size:
        raise ValueError(
            f"leaf {i}: frame extends past end of file ({end} > {size}) "
            "— truncated checkpoint"
        )


def diagnose_ckpt_bytes(data):
    """Best-effort forensic walk of a (possibly corrupt) checkpoint buffer
    — kept NEXT TO the real decoder so the format knowledge lives in one
    module. Never raises. Returns a dict:
    ``{"magic_ok", "meta" (dict or None), "meta_error", "intact_leaves",
    "break_offset"}``."""
    out = {"magic_ok": data[: len(MAGIC)] == MAGIC, "meta": None,
           "meta_error": None, "intact_leaves": 0, "break_offset": None}
    if not out["magic_ok"]:
        return out
    off = len(MAGIC)
    try:
        mlen = int.from_bytes(data[off : off + 8], "little")
        out["meta"] = json.loads(data[off + 8 : off + 8 + mlen].decode())
        off = off + 8 + mlen
    except Exception as e:
        out["meta_error"] = f"{type(e).__name__}: {e}"
        return out
    for lm in out["meta"].get("leaves", []):
        try:
            if off + 8 > len(data):
                break
            n = int.from_bytes(data[off : off + 8], "little")
            if n != _leaf_nbytes(lm) or off + 8 + n > len(data):
                break
            out["intact_leaves"] += 1
            off += 8 + n
        except Exception:
            break  # garbled leaf metadata: stop the walk here
    out["break_offset"] = off
    return out


def _decode_ckpt_bytes(data, *, check_version=True):
    """Decode an in-memory checkpoint buffer (both formats); see
    ``read_ckpt_raw``."""
    if data[: len(MAGIC)] == MAGIC:
        off = len(MAGIC)
        mlen = int.from_bytes(data[off : off + 8], "little")
        off += 8
        meta = json.loads(data[off : off + mlen].decode())
        off += mlen
        if check_version and meta["format"] not in SUPPORTED_FORMATS:
            raise ValueError(f"Unsupported checkpoint format {meta['format']}")
        leaves = []
        for i, lm in enumerate(meta["leaves"]):
            n = int.from_bytes(data[off : off + 8], "little")
            off += 8
            _check_leaf_frame(i, lm, n, off + n, len(data))
            dt = _dtype_from_str(lm["dtype"])
            count = int(np.prod(lm["shape"], dtype=np.int64)) if lm["shape"] else 1
            arr = np.frombuffer(data, dtype=dt, count=count, offset=off)
            leaves.append(arr.reshape(lm["shape"]))
            off += n
        paths = meta.get("paths") or [f"leaf{i}" for i in range(len(leaves))]
        return meta, paths, leaves
    # legacy v1: flat msgpack of {"meta": json, "leaves": {i: array}}
    raw = msgpack_restore(data)
    meta = json.loads(raw["meta"])
    if check_version and meta["format"] not in SUPPORTED_FORMATS:
        raise ValueError(f"Unsupported checkpoint format {meta['format']}")
    leaves = [raw["leaves"][str(i)] for i in range(meta["num_leaves"])]
    paths = meta.get("paths") or [f"leaf{i}" for i in range(len(leaves))]
    return meta, paths, leaves


def read_ckpt_meta(path, *, check_version=True):
    """Header-only read of a vanilla checkpoint's meta JSON: MAGIC + one
    length prefix + the meta blob — O(meta) bytes, no tensor data. The
    millisecond path behind manifest diffs at resume. Legacy v1 files
    have no framed header, so they fall back to a full decode."""
    path = Path(path)
    with open(path, "rb") as f:
        if f.read(len(MAGIC)) != MAGIC:
            return read_ckpt_raw(path, check_version=check_version)[0]
        mlen = int.from_bytes(f.read(8), "little")
        meta = json.loads(f.read(mlen).decode())
    if check_version and meta["format"] not in SUPPORTED_FORMATS:
        raise ValueError(f"Unsupported checkpoint format {meta['format']}")
    return meta


def _walk_ckpt_frames(path):
    """Seek-based structural walk of a v2 container: reads only the magic,
    the meta header, and each leaf's 8-byte length prefix — O(meta) bytes
    and O(1) RAM, no whole-file buffer. Raises on any structural
    inconsistency (bad magic handled by the v1 fallback, bad length
    prefix, truncation). Legacy v1 files have no frame structure to walk
    without a full msgpack decode, so they fall back to a full read."""
    size = os.path.getsize(path)
    with open(path, "rb") as f:
        if f.read(len(MAGIC)) != MAGIC:
            f.seek(0)
            _decode_ckpt_bytes(f.read())  # legacy v1: full decode
            return
        mlen = int.from_bytes(f.read(8), "little")
        meta = json.loads(f.read(mlen).decode())
        if meta["format"] not in SUPPORTED_FORMATS:
            raise ValueError(f"Unsupported checkpoint format {meta['format']}")
        off = len(MAGIC) + 8 + mlen
        for i, lm in enumerate(meta["leaves"]):
            prefix = f.read(8)
            if len(prefix) < 8:
                raise ValueError(f"leaf {i}: truncated length prefix")
            n = int.from_bytes(prefix, "little")
            off += 8 + n
            _check_leaf_frame(i, lm, n, off, size)
            f.seek(off)


def precheck_ckpt_vanilla(path, *, verify=False, target_state=None):
    """Host-LOCAL integrity check (no collectives): the sidecar checksum is
    verified with a CHUNKED streaming read (O(chunk) host RAM — at the 8B
    flagship a whole-file buffer here would undo the streaming-save RAM
    work on the restore side), and the v2 container's frame structure is
    walked with header-only seeks. Returns (ok, reason). Used by the
    latest-resume fallback to agree on a candidate on host 0 BEFORE every
    host enters the collective load (a per-host exception inside the load
    would desynchronize the barrier protocol on pods).

    When ``target_state`` is given, the checkpoint's schema manifest
    (header read, milliseconds) is statically diffed against it: a leaf-
    set or shape drift raises ``CheckpointStructureError`` — the same
    wrong-model-config protocol as the sharded precheck — so an
    incompatible resume dies here instead of mid-restore; a dtype drift
    is warned about (the restore path casts deliberately)."""
    path = Path(path)
    try:
        sidecar = _sidecar(path)
        if sidecar.exists():
            expected = sidecar.read_text().strip()
            if not verify_checksum(path, expected):
                return False, "checksum mismatch"
        elif verify:
            return False, f"checksum sidecar missing: {sidecar}"
        _walk_ckpt_frames(path)
    except Exception as e:
        return False, f"{type(e).__name__}: {e}"
    if target_state is not None:
        from pyrecover_tpu.analysis.shardcheck.manifest import (
            diff_manifests,
            manifest_from_ckpt_meta,
            state_manifest,
        )

        saved = manifest_from_ckpt_meta(
            read_ckpt_meta(path, check_version=False)
        )
        findings = diff_manifests(
            saved, state_manifest(target_state), locus=path.name,
            check_specs=False,
        )
        structural = [f for f in findings if f.rule_id in ("SC07", "SC08")]
        if structural:
            raise CheckpointStructureError(
                f"checkpoint {path.name} does not fit the configured "
                "model: "
                + "; ".join(f.message for f in structural[:3])
            )
        for f in findings:
            if f.rule_id == "SC09":
                log_host0(
                    "resume manifest: %s (restore will cast)", f.message,
                    level=30,  # WARNING
                )
                telemetry.emit(
                    "ckpt_manifest_dtype_drift", path=str(path),
                    detail=f.message,
                )
    return True, ""


def load_ckpt_vanilla(path, target_state, *, verify=False):
    """Restore a checkpoint into the structure/shardings of ``target_state``.

    Every host reads the file; each leaf is ``device_put`` onto the
    corresponding target leaf's sharding (resharding onto any topology —
    SURVEY hard-part #2's load half). Multi-host reads are STAGGERED by
    ``PYRECOVER_LOAD_STAGGER_S`` seconds × process index (default 3 s, the
    reference's per-rank stagger, checkpoint.py:139-141) so a pod doesn't
    stampede one shared filesystem. Checksum verification runs in a
    background thread overlapping deserialization (reference
    checkpoint.py:151-178). Returns (state, sampler_state, meta).
    """
    path = Path(path)
    t0 = time.monotonic()
    telemetry.emit("ckpt_restore_start", engine="vanilla", path=str(path))
    sync_global_devices("vanilla_load_enter")
    if jax.process_count() > 1 and jax.process_index() > 0:
        stagger = float(os.environ.get("PYRECOVER_LOAD_STAGGER_S", "3"))
        time.sleep(min(stagger * jax.process_index(), 60.0))

    verify_error = []
    verify_thread = None
    if verify:
        sidecar = _sidecar(path)

        def _verify():
            if not sidecar.exists():
                verify_error.append(f"checksum sidecar missing: {sidecar}")
                return
            expected = sidecar.read_text().strip()
            try:
                ok = verify_checksum(path, expected)
            except Exception as e:
                verify_error.append(f"checksum verification failed for {path}: {e}")
                return
            if not ok:
                verify_error.append(f"checksum mismatch for {path}: expected {expected}")

        verify_thread = threading.Thread(target=_verify, daemon=True)
        verify_thread.start()

    # the verify thread is joined on EVERY exit path: a decode error below
    # must not leak a thread still checksumming a (possibly corrupt) file —
    # the latest-resume fallback would pile one leaked reader per rejected
    # candidate (the CC05 leak class concur guards against)
    try:
        with telemetry.span(
            "ckpt_read", engine="vanilla", path=str(path),
            metric="ckpt_vanilla_read_s",
        ):
            meta, _, np_leaves = read_ckpt_raw(path)

        leaves, treedef = jax.tree_util.tree_flatten(target_state)
        if meta["num_leaves"] != len(leaves):
            raise CheckpointStructureError(
                f"Checkpoint has {meta['num_leaves']} leaves, target expects {len(leaves)}"
            )

        with telemetry.span(
            "ckpt_device_put", engine="vanilla",
            metric="ckpt_vanilla_device_put_s",
        ):
            restored = []
            for tgt, src in zip(leaves, np_leaves):
                if tuple(tgt.shape) != tuple(src.shape):
                    raise CheckpointStructureError(
                        f"Shape mismatch on restore: checkpoint {src.shape} vs target {tgt.shape}"
                    )
                src = src.astype(tgt.dtype)
                if isinstance(tgt, jax.Array) and hasattr(tgt, "sharding"):
                    restored.append(jax.device_put(src, tgt.sharding))
                else:
                    restored.append(jax.numpy.asarray(src))
            state = jax.tree_util.tree_unflatten(treedef, restored)
    except BaseException:
        if verify_thread is not None:
            # bounded: the checksum pass is finite (it reads the same
            # file), but a wedged disk must not turn a corrupt-checkpoint
            # fallback into a hang
            verify_thread.join(timeout=600)
        raise

    if verify_thread is not None:
        with telemetry.span(
            "ckpt_verify_wait", engine="vanilla",
            metric="ckpt_vanilla_verify_s",
        ):
            verify_thread.join()
        if verify_error:
            raise ValueError(verify_error[0])
        log_host0("Checkpoint checksum verified: %s", path)

    sync_global_devices("vanilla_load_exit")
    telemetry.emit(
        "ckpt_restore_done", engine="vanilla", path=str(path),
        seconds=round(time.monotonic() - t0, 4), verified=bool(verify),
        step=int(meta.get("step", 0)),
    )
    return state, meta.get("sampler", {}), meta


class VanillaEngine(HandleEngine):
    """This module's functions behind the engines' interface."""

    name = "vanilla"
    _save = staticmethod(save_ckpt_vanilla)
    _precheck = staticmethod(precheck_ckpt_vanilla)

    def load(self, path, target_state, *, prechecked=False):
        # single-process: the pre-check just checksummed the same bytes —
        # don't pay a second verification pass (multi-host keeps the
        # in-load verify: hosts != 0 read the file themselves). Elastic
        # execution for this engine: full global leaves on every host,
        # device_put onto the target shardings (reslice + scatter).
        verify = self.verify and not (
            prechecked and jax.process_count() == 1
        )
        return load_ckpt_vanilla(path, target_state, verify=verify)

    def read_params(self, path):
        # tamper gate: the framed container catches truncation and length
        # drift structurally, but a flipped byte INSIDE a tensor frame
        # decodes silently — when the save left a checksum sidecar, verify
        # it before any leaf is decoded (and long before placement)
        sidecar = _sidecar(path)
        if sidecar.exists():
            expected = sidecar.read_text().strip()
            if expected and not verify_checksum(path, expected):
                raise CheckpointIntegrityError(
                    f"checkpoint {Path(path).name} fails its checksum "
                    "sidecar — file tampered or bit-flipped after save"
                )
        _, paths, leaves = read_ckpt_raw(path)
        return nest_params(
            (p, np.asarray(leaf)) for p, leaf in zip(paths, leaves)
        )
