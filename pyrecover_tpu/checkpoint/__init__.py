"""The checkpoint engines and their shared naming.

A lazy package (PEP 562): importing it loads ``registry`` alone, and each
name below imports its engine module on first access, so a run loads
Orbax, Flax and an engine's module only when it opens that engine
(``engine.open_engine`` / ``engine_for_path``).
"""

import importlib

from pyrecover_tpu.checkpoint.registry import (
    checkpoint_path,
    engine_of,
    get_latest_checkpoint,
    list_checkpoints,
    prune_checkpoints,
)

# name -> the submodule that defines it, imported on first access
_LAZY = {
    "save_ckpt_vanilla": "vanilla",
    "load_ckpt_vanilla": "vanilla",
    "ShardedCheckpointer": "sharded",
    "save_ckpt_sharded": "sharded",
    "load_ckpt_sharded": "sharded",
    "precheck_ckpt_sharded": "sharded",
    "TopologyMismatchError": "elastic",
    "compute_reshard_plan": "elastic",
    "preflight_elastic": "elastic",
    "read_saved_meta": "elastic",
    "topologies_differ": "elastic",
    "save_ckpt_zerostall": "zerostall",
    "load_ckpt_zerostall": "zerostall",
    "precheck_ckpt_zerostall": "zerostall",
}

__all__ = [
    "checkpoint_path",
    "engine_of",
    "get_latest_checkpoint",
    "list_checkpoints",
    "prune_checkpoints",
    *_LAZY,
]


def __getattr__(name):
    if name not in _LAZY:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    module = importlib.import_module(f"{__name__}.{_LAZY[name]}")
    value = getattr(module, name)
    globals()[name] = value
    return value


def __dir__():
    return sorted({*globals(), *__all__})
