"""Native checkpoint-I/O engine tests: the C++ xxh64/tree-hash must agree
with the independent pure-Python implementation; parallel write/read must
roundtrip; the vanilla checkpoint path must verify across implementations."""

import os

import numpy as np
import pytest

from pyrecover_tpu.checkpoint import native_io
from pyrecover_tpu.utils import xxh

pytestmark = pytest.mark.skipif(
    not native_io.available(), reason="native engine unavailable (no g++?)"
)


@pytest.mark.parametrize("n", [0, 1, 3, 4, 7, 8, 31, 32, 33, 1000, 1 << 16])
def test_xxh64_matches_python(n):
    rng = np.random.default_rng(n)
    data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
    assert native_io.xxh64(data) == xxh.xxh64(data)


def test_xxh64_known_vector():
    # xxh64(seed=0) of the empty string — fixed by the algorithm
    assert xxh.xxh64(b"") == 0xEF46DB3751D8E999
    assert native_io.xxh64(b"") == 0xEF46DB3751D8E999


def test_tree_hash_matches_python():
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, 10_000_003, dtype=np.uint8).tobytes()
    chunk = 1 << 20
    assert native_io.tree_hash(data, chunk=chunk) == xxh.tree_hash_bytes(data, chunk)


def test_write_read_roundtrip(tmp_path):
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, 5_000_000, dtype=np.uint8).tobytes()
    path = tmp_path / "blob.bin"
    w_digest = native_io.write_file(path, data, chunk=1 << 20)
    assert path.stat().st_size == len(data)
    back, r_digest = native_io.read_file(path, chunk=1 << 20)
    assert back == data
    assert w_digest == r_digest == native_io.hash_file(path, chunk=1 << 20)
    assert w_digest == xxh.tree_hash_file(path, 1 << 20)


def test_hash_detects_corruption(tmp_path):
    data = bytes(range(256)) * 1000
    path = tmp_path / "blob.bin"
    digest = native_io.write_file(path, data, chunk=4096)
    raw = bytearray(path.read_bytes())
    raw[12345] ^= 0x01
    path.write_bytes(bytes(raw))
    assert native_io.hash_file(path, chunk=4096) != digest


def test_vanilla_ckpt_cross_implementation_verify(tmp_path, monkeypatch):
    """A checkpoint saved with the native engine must verify via the pure
    Python path too (hosts without g++)."""
    import jax

    from pyrecover_tpu.checkpoint import load_ckpt_vanilla, save_ckpt_vanilla
    from pyrecover_tpu.checkpoint.vanilla import verify_checksum, _sidecar
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.models import ModelConfig
    from pyrecover_tpu.optim import build_optimizer
    from pyrecover_tpu.train_state import create_train_state

    cfg = ModelConfig().tiny(max_seq_len=16)
    optimizer, _ = build_optimizer(TrainConfig(sequence_length=16))
    state = create_train_state(jax.random.key(0), cfg, optimizer)
    path = tmp_path / "ckpt_1.ckpt"
    save_ckpt_vanilla(path, state, verify=True)
    sidecar = _sidecar(path).read_text()
    assert sidecar.startswith("xxh64tree:")
    # native verify
    assert verify_checksum(path, sidecar)
    # forced pure-python verify
    monkeypatch.setattr(native_io, "available", lambda: False)
    assert verify_checksum(path, sidecar)
    # and the full load still works without the native engine
    target = create_train_state(jax.random.key(9), cfg, optimizer)
    restored, _, _ = load_ckpt_vanilla(path, target, verify=True)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- the library is BUILT from source, never found ------------------------


@pytest.fixture()
def fresh_loader(monkeypatch, tmp_path):
    """native_io with its load-once state reset and its source/build
    paths pointed at a private copy (restored afterwards)."""
    import shutil

    src = tmp_path / "native" / "pyrecover_io.cpp"
    src.parent.mkdir()
    shutil.copy2(native_io._SRC, src)
    monkeypatch.setattr(native_io, "_SRC", src)
    monkeypatch.setattr(native_io, "_BUILD_DIR", src.parent / "build")
    monkeypatch.setattr(native_io, "_lib", None)
    monkeypatch.setattr(native_io, "_tried", False)
    return src


def test_library_builds_from_source_keyed_on_content(fresh_loader):
    """No committed binary: first use compiles native/pyrecover_io.cpp
    into the (git-ignored) build dir, under a name carrying the source's
    content digest — so a changed source can never load a stale build,
    whatever a copy or checkout did to the mtimes."""
    import os

    src = fresh_loader
    build = src.parent / "build"
    assert not build.exists()
    first = native_io._so_path()
    assert native_io.available()
    assert [p.name for p in build.iterdir()] == [first.name]
    assert native_io.xxh64(b"") == 0xEF46DB3751D8E999

    # same content, arbitrary mtimes (what a checkout does): same library
    os.utime(src, (1, 1))
    assert native_io._so_path() == first
    # changed content: a different library name, and the rebuild drops
    # the stale one
    src.write_text(src.read_text() + "\n// edited\n")
    second = native_io._so_path()
    assert second != first
    native_io._tried = False
    native_io._lib = None
    assert native_io.available()
    assert [p.name for p in build.iterdir()] == [second.name]


def test_failed_build_is_a_warning_with_the_compilers_output(
    fresh_loader, caplog
):
    """A failed g++ build used to be swallowed (saves silently took the
    hashlib path). It is logged at WARNING, once, with the compiler's
    stderr — and callers still get available() == False."""
    import logging

    from pyrecover_tpu.utils.logging import get_logger

    fresh_loader.write_text("this is not C++ {\n")
    logger = get_logger()
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.WARNING, logger=logger.name):
            assert not native_io.available()
            assert not native_io.available()  # cached verdict, no second log
    finally:
        logger.removeHandler(caplog.handler)
    warned = [r for r in caplog.records if r.levelno == logging.WARNING]
    assert len(warned) == 1
    msg = warned[0].getMessage()
    assert "native checkpoint-I/O engine unavailable" in msg
    assert "compiler output" in msg and "error" in msg
