"""The checkpoint engines' one interface, and the one place that maps a
name or a path to an engine.

Three engines write checkpoints (``registry.ENGINES``): vanilla (one
streamed file), sharded (Orbax/tensorstore) and zerostall (snapshot
pipeline over a content-addressed chunk store). Each implements
``CheckpointEngine`` beside the code it wraps — ``VanillaEngine`` in
``vanilla.py``, ``ShardedCheckpointer`` in ``sharded.py``,
``ZerostallEngine`` in ``zerostall/`` — so the callers that only save,
resume or serve (``train.py``, ``serving/restore.py``) hold an engine
object and name none: ``open_engine(config)`` for the engine a run writes
with, ``engine_for_path(path)`` for the engine that wrote a checkpoint.
"""

import re
import time

from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint.registry import ENGINES, SUFFIXES, engine_of

PARAMS_PREFIX = ".params"
_KEY_RE = re.compile(r"\['([^']*)'\]|\.([A-Za-z_][A-Za-z0-9_]*)|\[(\d+)\]")


class CheckpointStructureError(ValueError):
    """The checkpoint decoded fine but does not FIT the target state
    (leaf count / shape mismatch) — a configuration error, not file
    corruption. The latest-resume fallback must NOT skip past these:
    every candidate would fail identically and the run would silently
    restart from step 0 with the wrong model."""


class CheckpointIntegrityError(ValueError):
    """``read_params`` found bytes that no longer match what the save
    recorded of them (checksum sidecar, content digest)."""


def keystr_parts(path_str):
    """``".params['layers']['wq']"`` -> ``["params", "layers", "wq"]``."""
    parts = []
    for m in _KEY_RE.finditer(path_str):
        parts.append(m.group(1) if m.group(1) is not None
                     else m.group(2) if m.group(2) is not None
                     else int(m.group(3)))
    return parts


def nest_params(leaves):
    """``[(manifest path, host array)]`` -> the nested dict tree of the
    leaves under ``.params`` (the params layout): what ``read_params``
    returns."""
    root = {}
    for path, arr in leaves:
        if not path.startswith(PARAMS_PREFIX):
            continue
        parts = keystr_parts(path)[1:]
        node = root
        for key in parts[:-1]:
            node = node.setdefault(key, {})
        node[parts[-1]] = arr
    return root


class CheckpointEngine:
    """What a caller may ask of an engine without knowing which it holds.

    ``save`` returns the seconds it blocked the caller and serialises
    behind the engine's own previous write; with ``final`` it leaves
    nothing in flight. ``join`` waits for whatever is in flight (bounded
    by ``timeout_s`` where the engine can bound it) and returns the
    background seconds that write took; ``shadow_s`` adds up those
    seconds over every join, the ones inside ``save`` included.
    ``precheck`` is host-local and returns ``(ok, why)``, raising
    ``CheckpointStructureError`` for a checkpoint of another model;
    ``load`` returns ``(state, sampler_meta, meta)`` on the shardings of
    ``target_state``. ``read_params`` returns the ``.params`` subtree as
    nested host arrays and raises ``CheckpointIntegrityError`` where the
    save left a record that the bytes now fail. ``ram_tier`` is None
    unless the engine keeps committed snapshots in host memory."""

    name = None
    shadow_s = 0.0

    @property
    def suffix(self):
        return SUFFIXES[self.name]

    def save(self, path, state, sampler_meta=None, *, extra_meta=None,
             final=False):
        raise NotImplementedError

    def join(self, timeout_s=None):
        return 0.0

    def precheck(self, path, target_state):
        raise NotImplementedError

    def load(self, path, target_state, *, prechecked=False):
        raise NotImplementedError

    def read_params(self, path):
        raise NotImplementedError

    def ram_tier(self, exp_dir):
        return None

    def close(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class HandleEngine(CheckpointEngine):
    """An engine whose background save returns a handle (``wait``,
    ``done``, ``error``, ``shadow_s``): it owns the one in flight."""

    def __init__(self, *, use_async=True, verify=False, max_keep=None):
        self.use_async = use_async
        self.verify = verify
        self.max_keep = max_keep
        self._pending = None

    # the module's ``save_ckpt_*`` (seconds, or ``(seconds, handle)`` with
    # ``background=True``) and ``precheck_ckpt_*``, as staticmethods
    _save = _precheck = None

    def save(self, path, state, sampler_meta=None, *, extra_meta=None,
             final=False):
        self.join()  # serialize with any in-flight write
        background = self.use_async and not final
        out = self._save(
            path, state, sampler_meta, verify=self.verify,
            max_keep=self.max_keep, extra_meta=extra_meta,
            background=background,
        )
        if not background:
            return out
        secs, self._pending = out
        return secs

    def precheck(self, path, target_state):
        return self._precheck(
            path, verify=self.verify, target_state=target_state
        )

    def join(self, timeout_s=None):
        """Mid-run callers pass no timeout (the next save must serialize
        behind the previous commit); the train() unwind passes a bounded
        one so a wedged disk cannot turn teardown into a hang. Every join
        emits a ``ckpt_bg_join`` event — the regression trail proving no
        non-daemon checkpoint work is abandoned at exit."""
        handle, self._pending = self._pending, None
        if handle is None:
            return 0.0
        t0 = time.monotonic()
        try:
            handle.wait(timeout=timeout_s)
        finally:
            telemetry.emit(
                "ckpt_bg_join", engine=self.name,
                waited_s=round(time.monotonic() - t0, 4),
                completed=bool(handle.done),
                ok=handle.error is None,
                bounded=timeout_s is not None,
            )
            # background seconds the train loop did NOT pay for: the
            # goodput ledger's recovered-overlap bucket
            shadow_s = getattr(handle, "shadow_s", 0.0) or 0.0
            self.shadow_s += shadow_s
        return shadow_s


def _engine_class(name):
    if name not in ENGINES:
        raise ValueError(f"unknown checkpoint engine {name!r}")
    if name == "sharded":
        from pyrecover_tpu.checkpoint.sharded import ShardedCheckpointer

        return ShardedCheckpointer
    if name == "zerostall":
        from pyrecover_tpu.checkpoint.zerostall import ZerostallEngine

        return ZerostallEngine
    from pyrecover_tpu.checkpoint.vanilla import VanillaEngine

    return VanillaEngine


def open_engine(config):
    """The engine a run configured by ``config`` saves and resumes with."""
    return _engine_class(config.checkpoint_engine)(
        use_async=config.async_checkpoint,
        verify=config.verify_checkpoints,
        max_keep=config.max_kept_checkpoints,
    )


def engine_for_path(path):
    """The engine that wrote the checkpoint at ``path``, to read it with:
    nothing of it runs in the background."""
    return _engine_class(engine_of(path))(use_async=False)
