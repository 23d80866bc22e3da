"""ctypes binding for the native checkpoint-I/O engine (native/pyrecover_io.cpp).

Builds the shared library with g++ on first use (single translation unit,
~1 s) into the git-ignored ``native/build/`` — the library is never
committed. Staleness is keyed on the SOURCE'S CONTENT (a digest in the
library's file name), not on mtimes: after a copy or checkout both
mtimes are arbitrary, and an mtime rule would load a library built
elsewhere from other source. Every caller must handle ``available() ==
False`` (no compiler / unsupported platform), in which case the
pure-Python hashlib path in ``vanilla.py`` is used — and the failed build
is logged at WARNING once, with the compiler's output, so a slow save is
never a silent one. The binding is kept ctypes-only so no build step is
required at install time (pybind11 is deliberately not a dependency).
"""

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path

DEFAULT_CHUNK = 16 * 1024 * 1024

_lock = threading.Lock()
_lib = None
_tried = False

_SRC = Path(__file__).resolve().parent.parent.parent / "native" / "pyrecover_io.cpp"
_BUILD_DIR = _SRC.parent / "build"


def _so_path():
    """The library path for the CURRENT source: its content digest is in
    the name, so a changed source never finds an old build."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _BUILD_DIR / f"libpyrecover_io.{digest}.so"


def _build(so):  # faultcheck: tear-ok -- a build product, rebuilt on demand
    _BUILD_DIR.mkdir(parents=True, exist_ok=True)
    # build to a private name, then rename: a concurrent process either
    # sees no library (and builds its own) or a complete one
    tmp = so.with_name(f".{so.name}.{os.getpid()}.tmp")
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
        "-o", str(tmp), str(_SRC),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        # jaxlint: disable-next=torn-write -- the rename is for atomicity
        # against a concurrent builder, not durability: a library lost to
        # a crash is simply compiled again
        os.replace(tmp, so)
    finally:
        tmp.unlink(missing_ok=True)
    for stale in _BUILD_DIR.glob("libpyrecover_io*.so"):
        if stale != so:
            stale.unlink(missing_ok=True)


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            # concur: disable-next=blocking-under-lock -- one-time lazy
            # source digest + g++ build, guarded by exactly this lock to
            # prevent a double compile; it completes before the first
            # save can
            so = _so_path()
            if not so.exists():
                # concur: disable-next=blocking-under-lock -- (as above)
                _build(so)
            lib = ctypes.CDLL(str(so))
            lib.pr_xxh64.restype = ctypes.c_uint64
            lib.pr_xxh64.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
            lib.pr_tree_hash.restype = ctypes.c_uint64
            lib.pr_tree_hash.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int
            ]
            lib.pr_write_file.restype = ctypes.c_uint64
            lib.pr_write_file.argtypes = [
                ctypes.c_char_p, ctypes.c_void_p, ctypes.c_uint64,
                ctypes.c_uint64, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
            ]
            lib.pr_read_file.restype = ctypes.c_uint64
            lib.pr_read_file.argtypes = [
                ctypes.c_char_p, ctypes.c_void_p, ctypes.c_uint64,
                ctypes.c_uint64, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
            ]
            lib.pr_hash_file.restype = ctypes.c_uint64
            lib.pr_hash_file.argtypes = [
                ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
            ]
            lib.pr_file_size.restype = ctypes.c_uint64
            lib.pr_file_size.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_int)
            ]
            _lib = lib
        except Exception as e:
            # once per process (_tried): saves fall back to the hashlib
            # path, which is correct but slow — say so, with the reason
            detail = getattr(e, "stderr", None) or b""
            if isinstance(detail, bytes):
                detail = detail.decode("utf-8", "replace")
            from pyrecover_tpu.utils.logging import get_logger

            get_logger().warning(
                "native checkpoint-I/O engine unavailable (%s: %s); "
                "checkpoints use the pure-Python hashing path%s",
                type(e).__name__, e,
                f" — compiler output:\n{detail.strip()[-2000:]}"
                if detail.strip() else "",
            )
            _lib = None
        return _lib


def available():
    return _load() is not None


def _check(err, op, path):
    if err.value != 0:
        raise OSError(err.value, f"native {op} failed for {path}: "
                                 f"{os.strerror(err.value)}")


def xxh64(data: bytes) -> int:
    lib = _load()
    return int(lib.pr_xxh64(data, len(data)))


def tree_hash(data, chunk=DEFAULT_CHUNK, n_threads=0) -> int:
    lib = _load()
    buf = (ctypes.c_char * len(data)).from_buffer_copy(data) if not isinstance(
        data, (bytes, bytearray)) else data
    return int(lib.pr_tree_hash(bytes(buf) if not isinstance(buf, (bytes, bytearray)) else buf,
                                len(data), chunk, n_threads))


def write_file(path, data: bytes, chunk=DEFAULT_CHUNK, n_threads=0) -> int:
    """Parallel write + checksum-in-the-same-pass. Returns the tree hash."""
    from pyrecover_tpu.resilience import faults

    lib = _load()
    faults.check("ckpt_write", path=str(path), written=0)
    err = ctypes.c_int(0)
    digest = lib.pr_write_file(str(path).encode(), data, len(data), chunk,
                               n_threads, ctypes.byref(err))
    _check(err, "write", path)
    return int(digest)


def read_file(path, chunk=DEFAULT_CHUNK, n_threads=0):
    """Parallel read of the whole file. Returns (bytes, tree_hash)."""
    from pyrecover_tpu.resilience import faults

    lib = _load()
    faults.check("ckpt_read", path=str(path))
    err = ctypes.c_int(0)
    size = lib.pr_file_size(str(path).encode(), ctypes.byref(err))
    _check(err, "stat", path)
    buf = ctypes.create_string_buffer(size)
    digest = lib.pr_read_file(str(path).encode(), buf, size, chunk,
                              n_threads, ctypes.byref(err))
    _check(err, "read", path)
    return bytes(buf.raw), int(digest)


def hash_file(path, chunk=DEFAULT_CHUNK, n_threads=0) -> int:
    """Streaming parallel tree checksum of a file."""
    lib = _load()
    err = ctypes.c_int(0)
    digest = lib.pr_hash_file(str(path).encode(), chunk, n_threads,
                              ctypes.byref(err))
    _check(err, "hash", path)
    return int(digest)
