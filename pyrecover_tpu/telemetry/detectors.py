"""Silent-failure detectors for the hot path.

The failure modes that never raise: a recompile storm quietly eating
throughput after a shape drift, an implicit host transfer serializing the
dispatch pipeline, a job meant for an accelerator that resolved to CPU, HBM
creeping to the OOM line. Each detector converts one of these into loud
telemetry (events + counters/gauges) that ``doctor`` and the goodput
report can see.

``RecompileWatch``     wraps the jitted train step; a change in the
                       abstract argument signature (leaf shapes/dtypes)
                       is a genuine retrace → one ``recompile`` event +
                       ``recompile_total`` counter per change.
``transfer_watch``     a per-dispatch scope under
                       ``jax.transfer_guard("disallow")``: an implicit
                       host transfer emits ``implicit_transfer`` and
                       raises :class:`ImplicitTransferError` — the
                       runtime complement of jaxlint JX01.
``sample_hbm``         ``device.memory_stats()`` into ``hbm_*`` gauges
                       (flushed with every ``metrics_snapshot``);
                       ``hbm_run_summary`` folds peak-vs-budget into the
                       ``run_summary`` event (budget: the device's own
                       ``bytes_limit``, else the SC05 HBM table).
``check_expected_accelerator``
                       a launcher that declared an accelerator
                       (``$PYRECOVER_EXPECT_ACCELERATOR``) gets a
                       ``platform_fallback`` event and an ERROR when jax
                       resolved CPU — never a CPU run under device names.
"""

import contextlib
import os

import jax

from pyrecover_tpu.telemetry import bus, metrics

EXPECT_ACCELERATOR_ENV = "PYRECOVER_EXPECT_ACCELERATOR"


# ---- recompile detection ----------------------------------------------------

def _leaf_sig(leaf):
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None and dtype is None:
        # python scalar / static arg: its TYPE and VALUE are the signature
        # (jit retraces weak-typed scalars on value change only for
        # hashable statics; type covers the common drift)
        return (type(leaf).__name__, repr(leaf))
    return (tuple(shape) if shape is not None else None, str(dtype))


def _signature(args, kwargs):
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return str(treedef), tuple(_leaf_sig(x) for x in leaves)


class RecompileWatch:
    """Wrap a jitted callable; emit ``recompile`` when the abstract call
    signature changes after the first call.

    The signature is host-side metadata only (pytree structure + leaf
    shape/dtype) — no device syncs, ~microseconds per call. Fires exactly
    once per GENUINE change: the stored signature updates on every
    mismatch, so a steady-state of the new shape is silent until the next
    drift (flip-flopping shapes fire on every flip — each flip really is
    a retrace or a cache hit that once cost one).
    """

    def __init__(self, fn, name="train_step"):  # jaxlint: host-only
        self.fn = fn
        self.name = name
        self._sig = None
        self.recompiles = 0

    def __call__(self, *args, **kwargs):  # jaxlint: hot-loop
        sig = _signature(args, kwargs)
        if self._sig is None:
            self._sig = sig
        elif sig != self._sig:
            changed = _describe_change(self._sig, sig)
            self._sig = sig
            self.recompiles += 1
            metrics.counter("recompile_total").inc()
            bus.emit(
                "recompile", fn=self.name, count=self.recompiles,
                changed=changed,
            )
        return self.fn(*args, **kwargs)


def _describe_change(old, new):
    """Human-readable first difference between two signatures."""
    if old[0] != new[0]:
        return "pytree structure changed"
    for i, (a, b) in enumerate(zip(old[1], new[1])):
        if a != b:
            return f"leaf {i}: {a} -> {b}"
    if len(old[1]) != len(new[1]):
        return f"leaf count {len(old[1])} -> {len(new[1])}"
    return "signature changed"


# ---- implicit host-transfer detection ---------------------------------------

class ImplicitTransferError(RuntimeError):
    """An implicit host<->device transfer happened inside a
    ``transfer_watch`` scope (``--transfer-guard disallow``). The
    ``implicit_transfer`` telemetry event was already emitted."""


@contextlib.contextmanager
def transfer_watch(*, step=None, fn="train_step"):  # jaxlint: hot-loop
    """Disallow implicit transfers inside the scope; a violation becomes
    an ``implicit_transfer`` event + ``implicit_transfer_total`` counter
    + a typed :class:`ImplicitTransferError`. Thread-local (jax's guard
    config is context-scoped), so loader/writer threads are unaffected."""
    try:
        with jax.transfer_guard("disallow"):
            yield
    except Exception as e:
        msg = str(e)
        if "transfer" in msg.lower() and (
            "disallow" in msg.lower() or "guard" in msg.lower()
        ):
            metrics.counter("implicit_transfer_total").inc()
            bus.emit(
                "implicit_transfer", fn=fn, step=step, error=msg[:400],
            )
            raise ImplicitTransferError(msg) from e
        raise


# ---- HBM sampling -----------------------------------------------------------

_hbm_state = {"peak": None, "limit": None, "sampled": False}


def sample_hbm(device=None):  # jaxlint: host-only
    """Sample ``memory_stats`` into ``hbm_bytes_in_use`` /
    ``hbm_peak_bytes_in_use`` gauges. Returns bytes in use, or None when
    the backend exposes no stats (CPU). Host-local, no device sync."""
    if device is None:
        try:
            device = jax.local_devices()[0]
        except Exception:
            return None
    stats_fn = getattr(device, "memory_stats", None)
    try:
        stats = stats_fn() if stats_fn is not None else None
    except Exception:
        return None  # dead/teardown backend: a sample is never worth a raise
    if not stats:
        return None
    in_use = stats.get("bytes_in_use")
    if in_use is None:
        return None
    _hbm_state["sampled"] = True
    peak = stats.get("peak_bytes_in_use", in_use)
    prev = _hbm_state["peak"]
    _hbm_state["peak"] = peak if prev is None else max(prev, peak, in_use)
    limit = stats.get("bytes_limit")
    if limit:
        _hbm_state["limit"] = limit
    metrics.gauge("hbm_bytes_in_use").set(int(in_use))
    metrics.gauge("hbm_peak_bytes_in_use").set(int(_hbm_state["peak"]))
    return in_use


def hbm_run_summary(device=None):  # jaxlint: host-only
    """Peak-vs-budget fields for the ``run_summary`` event, or {} when HBM
    was never sampled. Budget preference: the device's own ``bytes_limit``
    (exact), else the SC05 per-generation HBM table."""
    if not _hbm_state["sampled"]:
        return {}
    budget = _hbm_state["limit"]
    if budget is None:
        from pyrecover_tpu.utils.perf import tpu_hbm_bytes

        try:
            budget = tpu_hbm_bytes(device=device)
        except Exception:
            budget = None
    out = {"hbm_peak_bytes": int(_hbm_state["peak"])}
    if budget:
        out["hbm_budget_bytes"] = int(budget)
        out["hbm_peak_pct"] = round(100.0 * _hbm_state["peak"] / budget, 2)
    return out


def reset_hbm():  # jaxlint: host-only
    """Forget sampled HBM state (test isolation / fresh run)."""
    _hbm_state.update(peak=None, limit=None, sampled=False)


# ---- expected accelerator ---------------------------------------------------

class PlatformFallbackError(RuntimeError):
    """An accelerator was declared (``$PYRECOVER_EXPECT_ACCELERATOR``) and
    jax resolved CPU. The ``platform_fallback`` event was already emitted."""


def check_expected_accelerator():  # jaxlint: host-only
    """If the launcher declared an accelerator expectation
    (``$PYRECOVER_EXPECT_ACCELERATOR`` truthy) and the resolved backend is
    CPU: emit ``platform_fallback`` (the doctor's classification hook) and
    RAISE — a job sized for a chip must not grind on, and report
    throughput from, a CPU it landed on by accident. Called by ``train()``
    once devices are known and the sinks are live."""
    expected = os.environ.get(EXPECT_ACCELERATOR_ENV, "")
    resolved = jax.devices()[0].platform
    if resolved != "cpu" or expected.lower() in ("", "0", "false", "no"):
        return
    reason = (
        "an accelerator platform was expected "
        f"(${EXPECT_ACCELERATOR_ENV}={expected!r}) but jax resolved cpu"
    )
    metrics.counter("platform_fallback_total").inc()
    bus.emit(
        "platform_fallback", reason=reason, resolved=resolved,
        expected=expected,
    )
    raise PlatformFallbackError(reason)
