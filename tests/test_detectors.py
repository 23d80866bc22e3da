"""Silent-failure detector tests (pyrecover_tpu/telemetry/detectors.py).

The recompile detector fires exactly once per GENUINE signature change;
the transfer guard converts an implicit host transfer into one typed
event + error; HBM sampling tracks peaks against a budget; a declared
accelerator that resolves to CPU is an error.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyrecover_tpu import telemetry
from pyrecover_tpu.telemetry import detectors
from pyrecover_tpu.telemetry.metrics import reset as metrics_reset


@pytest.fixture()
def mem_sink():
    metrics_reset()
    sink = telemetry.add_sink(telemetry.MemorySink())
    yield sink
    telemetry.remove_sink(sink)
    metrics_reset()


def events(sink, name):
    return [e for e in sink.events if e["event"] == name]


# ---- recompile detector -----------------------------------------------------

def test_recompile_fires_exactly_once_per_signature_change(mem_sink):
    fn = detectors.RecompileWatch(jax.jit(lambda x: x * 2), name="unit")
    a8 = jnp.zeros((4, 8), jnp.float32)
    a16 = jnp.zeros((4, 16), jnp.float32)
    fn(a8)
    fn(a8)
    fn(a8)
    assert events(mem_sink, "recompile") == []  # steady state is silent
    fn(a16)  # genuine retrace
    assert len(events(mem_sink, "recompile")) == 1
    fn(a16)
    fn(a16)  # new steady state: still one
    assert len(events(mem_sink, "recompile")) == 1
    fn(a8)  # flipping back is another genuine signature change
    assert len(events(mem_sink, "recompile")) == 2
    ev = events(mem_sink, "recompile")[0]
    assert ev["fn"] == "unit"
    assert "8" in ev["changed"] and "16" in ev["changed"]
    assert fn.recompiles == 2


def test_recompile_detects_dtype_drift(mem_sink):
    fn = detectors.RecompileWatch(jax.jit(lambda x: x + 1))
    fn(jnp.zeros((4,), jnp.float32))
    fn(jnp.zeros((4,), jnp.bfloat16))
    assert len(events(mem_sink, "recompile")) == 1


def test_recompile_sees_pytree_structure(mem_sink):
    fn = detectors.RecompileWatch(jax.jit(lambda d: d["a"]))
    fn({"a": jnp.zeros(3)})
    fn({"a": jnp.zeros(3), "b": jnp.zeros(3)})
    assert len(events(mem_sink, "recompile")) == 1
    assert "structure" in events(mem_sink, "recompile")[0]["changed"]


def test_recompile_counter_rides_along(mem_sink):
    from pyrecover_tpu.telemetry import metrics

    fn = detectors.RecompileWatch(jax.jit(lambda x: x))
    fn(jnp.zeros(2))
    fn(jnp.zeros(5))
    assert metrics.counter("recompile_total").value == 1


def test_recompile_result_passthrough(mem_sink):
    fn = detectors.RecompileWatch(jax.jit(lambda x: x * 3))
    assert float(fn(jnp.float32(2.0))) == 6.0


# ---- implicit transfer guard ------------------------------------------------

def test_transfer_watch_clean_dispatch_passes(mem_sink):
    x = jnp.arange(4.0)
    with detectors.transfer_watch(step=1):
        y = x + x  # device-resident operands only: no implicit transfer
    assert float(y.sum()) == 12.0
    assert events(mem_sink, "implicit_transfer") == []


def test_transfer_watch_flags_implicit_h2d(mem_sink):
    from pyrecover_tpu.telemetry import metrics

    host = np.arange(4, dtype=np.float32)
    with pytest.raises(detectors.ImplicitTransferError):
        with detectors.transfer_watch(step=9, fn="unit"):
            jnp.sin(host)  # numpy operand: implicit host->device transfer
    evs = events(mem_sink, "implicit_transfer")
    assert len(evs) == 1
    assert evs[0]["step"] == 9 and evs[0]["fn"] == "unit"
    assert "transfer" in evs[0]["error"].lower()
    assert metrics.counter("implicit_transfer_total").value == 1


def test_transfer_watch_unrelated_errors_pass_through(mem_sink):
    with pytest.raises(ValueError, match="unrelated"):
        with detectors.transfer_watch():
            raise ValueError("unrelated")
    assert events(mem_sink, "implicit_transfer") == []


# ---- HBM sampling -----------------------------------------------------------

class _FakeDev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_sample_hbm_gauges_and_peak(mem_sink):
    from pyrecover_tpu.telemetry import metrics

    detectors.reset_hbm()
    dev = _FakeDev({"bytes_in_use": 100, "peak_bytes_in_use": 150,
                    "bytes_limit": 1000})
    assert detectors.sample_hbm(device=dev) == 100
    dev._stats = {"bytes_in_use": 120, "peak_bytes_in_use": 140,
                  "bytes_limit": 1000}
    detectors.sample_hbm(device=dev)  # a LOWER reported peak never regresses
    assert metrics.gauge("hbm_bytes_in_use").value == 120
    assert metrics.gauge("hbm_peak_bytes_in_use").value == 150
    summary = detectors.hbm_run_summary()
    assert summary == {
        "hbm_peak_bytes": 150,
        "hbm_budget_bytes": 1000,
        "hbm_peak_pct": 15.0,
    }
    detectors.reset_hbm()
    assert detectors.hbm_run_summary() == {}


def test_sample_hbm_none_without_stats():
    detectors.reset_hbm()
    assert detectors.sample_hbm(device=_FakeDev(None)) is None
    assert detectors.sample_hbm(device=object()) is None
    assert detectors.hbm_run_summary() == {}
    # the CPU backend exposes no stats: the real call is a clean no-op
    assert detectors.sample_hbm() is None


# ---- platform expectation ---------------------------------------------------

def test_check_expected_accelerator(monkeypatch, mem_sink):
    """An expected accelerator that resolves to CPU is an ERROR (after the
    platform_fallback event the doctor classifies on), not a warning the
    run trains past."""
    monkeypatch.delenv(detectors.EXPECT_ACCELERATOR_ENV, raising=False)
    detectors.check_expected_accelerator()
    monkeypatch.setenv(detectors.EXPECT_ACCELERATOR_ENV, "0")
    detectors.check_expected_accelerator()
    assert events(mem_sink, "platform_fallback") == []

    monkeypatch.setenv(detectors.EXPECT_ACCELERATOR_ENV, "1")
    with pytest.raises(detectors.PlatformFallbackError, match="resolved cpu"):
        detectors.check_expected_accelerator()
    evs = events(mem_sink, "platform_fallback")
    assert len(evs) == 1 and evs[0]["resolved"] == "cpu"
