"""Zero-downtime weight hot-swap: watcher + double-buffered swap.

A serving replica loads weights once and goes stale forever — this
module closes the train→serve loop. :class:`HotSwapper` attaches to a
live :class:`~pyrecover_tpu.serving.engine.ServingEngine` and an
experiment directory the trainer is writing checkpoints into, and:

  1. **Watches the registry** — a polling thread discovers newly
     committed checkpoints via ``registry.get_latest_checkpoint`` (the
     engine-scoped suffix rules make a half-written save invisible: a
     zerostall manifest exists only after its atomic rename, an Orbax
     dir only after finalization). The thread is join-bounded
     (``stop(timeout)``, the CC05 discipline) and never touches the
     serving engine's lock beyond the one staging-slot assignment.
  2. **Fetches incrementally** — for zerostall checkpoints, the loaded
     manifest's per-leaf chunk digests are diffed against the new one
     and ONLY changed chunks are read from the chunk store; unchanged
     chunks come from the replica's own cached host bytes. Every chunk
     is digest-verified before assembly (``hotswap/fetch.py``). The
     manifest is PINNED (``checkpoint/zerostall/pins.py``) for the
     duration of the fetch so the trainer's retention + GC cannot
     delete chunks out from under the read. Vanilla/sharded checkpoints
     fall back to a full ``load_serving_params`` read through the same
     preflight + integrity gates — hot-swap works on all three engines.
  3. **Swaps double-buffered** — assembly and ``shard_params`` placement
     run on the watcher thread; the engine flips its params reference
     at a step boundary (``engine.install_params``), so in-flight
     requests never see mixed weights and the shape-stable pytree means
     the compiled prefill/decode programs run on with ZERO retraces
     (a shape/dtype/structure drift is rejected before staging).

Failure is loud and non-fatal: any fetch/verify/placement error emits
``weights_swap_rejected`` naming the manifest and reason, the manifest
is remembered as rejected (no retry loop against a bad artifact — a
NEWER manifest resets the clock), and the replica keeps serving the old
weights. Telemetry: ``weights_swap_begin`` / ``swap_fetch_bytes`` /
``weights_swap_done`` / ``weights_swap_rejected`` (both catalogs).
"""

import threading
import time
from pathlib import Path

import numpy as np

from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint.registry import (
    engine_of,
    get_latest_checkpoint,
    parse_step,
)
from pyrecover_tpu.checkpoint.engine import (
    PARAMS_PREFIX,
    keystr_parts,
    nest_params,
)
from pyrecover_tpu.serving.restore import _place_params, load_serving_params
from pyrecover_tpu.utils.logging import log_host0


class HotSwapper:
    """Track a training run's checkpoint registry and hot-swap a live
    serving engine's weights. ``start()``/``stop()`` run the polling
    watcher; ``poll_once()``/``swap_to(path)`` are the synchronous
    surface (tests, manual control). Thread contract: all swap state
    (``loaded_step``, the manifest/host-byte caches, the rejected set)
    is mutated only under ``_lock``; the fetch + placement work runs
    outside every lock."""

    def __init__(self, engine, exp_dir, model_config, *, loaded_path=None,
                 loaded_step=None, mesh=None, device_kind=None,
                 poll_interval_s=1.0):
        self.engine = engine
        self.exp_dir = Path(exp_dir)
        self.model_config = model_config
        self.mesh = mesh
        self.device_kind = device_kind
        self.poll_interval_s = float(poll_interval_s)

        self._lock = threading.Lock()
        self._loaded_doc = None  # zerostall manifest doc of loaded weights
        self._host_cache = None  # {manifest path: np.ndarray host bytes}
        self._rejected = {}  # manifest name -> reason (no retry loop)
        self._loaded_step = -1
        if loaded_path is not None:
            step = parse_step(loaded_path)
            self._loaded_step = step if step is not None else -1
            if engine_of(loaded_path) == "zerostall":
                from pyrecover_tpu.checkpoint.zerostall.chunkstore import (
                    read_manifest,
                )

                self._loaded_doc = read_manifest(loaded_path)
        if loaded_step is not None:
            self._loaded_step = int(loaded_step)
        if engine.weights_step is None:
            engine.weights_step = (
                self._loaded_step if self._loaded_step >= 0 else None
            )
        if self._loaded_step >= 0:
            telemetry.metrics.gauge("hotswap_loaded_step").set(
                self._loaded_step
            )

        self._thread = None
        self._stop = threading.Event()

    @property
    def loaded_step(self):
        with self._lock:
            return self._loaded_step

    @property
    def rejected(self):
        """``{manifest name: reason}`` of manifests this swapper refused
        (copied; informational)."""
        with self._lock:
            return dict(self._rejected)

    # ---- watcher thread (bounded lifecycle, engine.py's pattern) ------

    def start(self):  # jaxlint: host-only
        """Poll the registry from a background thread until ``stop()``."""
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("hot-swap watcher already running")
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._watch_loop, name="hotswap-watcher",
        )
        self._thread.start()

    def stop(self, timeout=60.0):  # jaxlint: host-only
        """Stop and JOIN the watcher, bounded: a wedged fetch surfaces as
        a TimeoutError naming the thread instead of a silent leak."""
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError(
                f"hotswap-watcher thread did not stop within {timeout}s"
            )
        self._thread = None

    def _watch_loop(self):
        while not self._stop.is_set():
            try:
                self.poll_once()
            except Exception as e:  # a poll crash must not kill the watcher
                log_host0(
                    "hot-swap poll failed (%s: %s); retrying next interval",
                    type(e).__name__, e, level=30,  # WARNING
                )
            self._stop.wait(self.poll_interval_s)

    # ---- swap surface -------------------------------------------------

    def poll_once(self):  # jaxlint: host-only
        """One registry poll: swap to the newest committed checkpoint if
        it is newer than the loaded weights and not already rejected.
        Returns True when a swap was staged."""
        latest = get_latest_checkpoint(self.exp_dir)
        if latest is None:
            return False
        step = parse_step(latest)
        with self._lock:
            stale = (
                step is None
                or step <= self._loaded_step
                or latest.name in self._rejected
            )
        if stale:
            return False
        return self.swap_to(latest)

    def swap_to(self, path):  # jaxlint: host-only
        """Fetch + verify + place ``path``'s params and stage them for
        the engine's next step boundary. Returns True on success; on any
        failure emits ``weights_swap_rejected``, records the manifest as
        rejected, and leaves the engine serving its current weights."""
        path = Path(path)
        step = parse_step(path)
        ckpt_engine = engine_of(path)
        t0 = time.monotonic()
        with self._lock:
            from_step = self._loaded_step
        telemetry.emit(
            "weights_swap_begin", path=str(path), engine=ckpt_engine,
            from_step=from_step, to_step=step,
        )
        try:
            if ckpt_engine == "zerostall":
                placed, new_doc, new_cache, stats = self._fetch_zerostall(
                    path
                )
            else:
                placed, stats = self._fetch_full(path)
                new_doc, new_cache = None, None
            self._check_shape_stable(placed, path)
        except Exception as e:
            reason = f"{type(e).__name__}: {e}"
            with self._lock:
                self._rejected[path.name] = reason
                n_rejected = len(self._rejected)
            telemetry.metrics.counter("hotswap_rejected_total").inc()
            telemetry.metrics.gauge("hotswap_rejected").set(n_rejected)
            telemetry.emit(
                "weights_swap_rejected", path=str(path),
                engine=ckpt_engine, from_step=from_step, to_step=step,
                reason=reason[:500],
            )
            log_host0(
                "hot-swap to %s REJECTED (%s) — still serving step %s",
                path.name, reason, from_step, level=30,  # WARNING
            )
            return False
        self.engine.install_params(
            placed, step=step,
            info={"t_begin": t0, "path": str(path), "engine": ckpt_engine,
                  "from_step": from_step,
                  "fetched_bytes": stats["fetched_bytes"],
                  "reused_bytes": stats["reused_bytes"]},
        )
        with self._lock:
            self._loaded_step = step
            self._loaded_doc = new_doc
            self._host_cache = new_cache
        # live plane: the swap state the dashboard renders (the engine's
        # weights_swaps_total counter ticks when the flip lands)
        telemetry.metrics.gauge("hotswap_loaded_step").set(step)
        telemetry.metrics.gauge("hotswap_fetched_bytes").set(
            stats["fetched_bytes"]
        )
        return True

    # ---- fetch paths --------------------------------------------------

    def _fetch_zerostall(self, path):
        """Incremental chunk fetch under a pin lease; returns
        ``(placed_params, manifest_doc, host_cache, stats)``."""
        from pyrecover_tpu.checkpoint.zerostall import pins
        from pyrecover_tpu.checkpoint.zerostall.chunkstore import (
            read_manifest,
        )
        from pyrecover_tpu.serving.hotswap.fetch import (
            fetch_params_incremental,
        )

        doc = read_manifest(path)
        with self._lock:
            old_doc = self._loaded_doc
        old_host = self._ensure_host_cache(old_doc)
        # pin the manifest for the whole fetch: the trainer's retention +
        # GC may prune it mid-read, and the lease (a copy of the digest
        # map) keeps its chunks alive until we are done — or, if this
        # process dies mid-fetch, until the lease expires
        with pins.pin_manifest(self.exp_dir, path, doc,
                               owner=f"hotswap{id(self) & 0xffff:x}"):
            flat, stats = fetch_params_incremental(
                self.exp_dir, doc, old_doc, old_host, manifest_path=path,
            )
        telemetry.emit(
            "swap_fetch_bytes", path=str(path), incremental=True,
            **{k: stats[k] for k in (
                "fetched_bytes", "reused_bytes", "chunks_fetched",
                "chunks_reused", "changed_leaves", "leaves",
            )},
        )
        host_cache = {p: arr for p, arr in flat}
        nested = nest_params(flat)
        placed = _place_params(nested, self.mesh)
        return placed, doc, host_cache, stats

    def _fetch_full(self, path):
        """Vanilla/sharded fallback: the whole-checkpoint serving restore
        (elastic preflight + integrity verification + placement) —
        hot-swap through the same API the cold start used."""
        placed, info = load_serving_params(
            path, self.model_config, mesh=self.mesh,
            device_kind=self.device_kind,
        )
        stats = {"fetched_bytes": int(info.get("bytes", 0)),
                 "reused_bytes": 0}
        telemetry.emit(
            "swap_fetch_bytes", path=str(path), incremental=False,
            fetched_bytes=stats["fetched_bytes"], reused_bytes=0,
            chunks_fetched=0, chunks_reused=0,
            changed_leaves=int(info.get("leaves", 0)),
            leaves=int(info.get("leaves", 0)),
        )
        return placed, stats

    def _ensure_host_cache(self, old_doc):
        """Host bytes of the currently-served params, keyed by manifest
        path — the reuse side of the incremental fetch. Built lazily from
        the engine's own (device) params on the first incremental swap;
        a leaf whose bytes no longer digest-match the loaded manifest
        (e.g. a restore-time dtype cast) simply misses the cache and is
        fetched whole."""
        with self._lock:
            if self._host_cache is not None:
                return dict(self._host_cache)
        if old_doc is None:
            return {}
        cache = {}
        for entry in old_doc.get("leaves", []):
            path = entry["path"]
            if not path.startswith(PARAMS_PREFIX):
                continue
            leaf = self._params_leaf(keystr_parts(path)[1:])
            if leaf is None:
                continue
            cache[path] = np.asarray(leaf)
        with self._lock:
            if self._host_cache is None:
                self._host_cache = cache
            return dict(self._host_cache)

    def _params_leaf(self, parts):
        node = self.engine.params
        for key in parts:
            try:
                node = node[key]
            except (KeyError, TypeError, IndexError):
                return None
        return node

    def _check_shape_stable(self, placed, path):
        """The zero-retrace contract: the new params must match the
        serving params' tree structure, shapes, and dtypes exactly — a
        drifted checkpoint (wrong model config) is rejected BEFORE
        staging, never discovered as a recompile storm."""
        import jax

        old_s = jax.tree_util.tree_structure(self.engine.params)
        new_s = jax.tree_util.tree_structure(placed)
        if old_s != new_s:
            raise ValueError(
                f"{path.name}: params tree structure differs from the "
                "serving weights — not the same model"
            )
        for old, new in zip(
            jax.tree_util.tree_leaves(self.engine.params),
            jax.tree_util.tree_leaves(placed),
        ):
            if tuple(old.shape) != tuple(new.shape) or old.dtype != new.dtype:
                raise ValueError(
                    f"{path.name}: leaf {tuple(new.shape)}/{new.dtype} vs "
                    f"serving {tuple(old.shape)}/{old.dtype} — a swap must "
                    "be shape-stable (zero retraces)"
                )
