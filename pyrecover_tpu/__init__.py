"""pyrecover_tpu — a TPU-native resilient pre-training framework.

A brand-new JAX/XLA/Pallas implementation of the capabilities of the
PyRecover reference (distributed checkpointing + job-resilience harness for
LLM pre-training): data-parallel (and tensor/sequence-parallel) training of a
Llama-style decoder-only Transformer, dual-strategy checkpointing (host-0
single-file with checksum verification, and sharded multi-host async
checkpoints), `latest`-checkpoint discovery with retention pruning, bit-exact
resume (model, optimizer, LR schedule, RNG, and data-order state), time-aware
checkpointing that watches the job deadline / preemption notices, a Pallas
flash-attention kernel, and throughput/MFU observability.

Unlike the reference's `pyrecover/__init__.py:5-7` (which advertises
`setup_resubmission` / `monitor_timelimit` from modules that do not exist and
therefore breaks every import), this package only exports what is actually
implemented.
"""

from pyrecover_tpu.version import __version__

__all__ = ["__version__"]


def _place_compile_cache():
    """Point JAX's persistent compilation cache at its one place, before
    any entry point's first compile (every entry point imports this
    package first). ``JAX_COMPILATION_CACHE_DIR`` placed from outside
    wins — jax reads the variable itself, so nothing is set here.
    Otherwise the cache lives at a FIXED directory inside the checkout
    (git-ignored): the path is part of the cache key, so a temp/pid/
    timestamp directory would never hit. This is what lets a resumed
    process skip the train-step compile its predecessor already paid.

    A process held to the CPU (``JAX_PLATFORMS=cpu``: the tests, the CPU
    drills, the smoke's rehearsal) gets no default cache: XLA:CPU logs an
    error-level machine-feature line on every cache load, and nothing
    compiled there is what a user waits for."""
    import os

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    from pathlib import Path

    import jax

    if (jax.config.jax_platforms or "").strip().lower() == "cpu":
        return
    jax.config.update(
        "jax_compilation_cache_dir",
        str(Path(__file__).resolve().parent.parent / ".jax_cache"),
    )


_place_compile_cache()
