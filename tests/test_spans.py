"""Tracing spans + metrics registry: nesting, thread isolation, exception
paths, the no-sink zero-cost contract, histogram percentile math, and the
metrics_snapshot flush protocol."""

import threading

import pytest

from pyrecover_tpu import telemetry
from pyrecover_tpu.telemetry import metrics, spans


@pytest.fixture(autouse=True)
def clean_bus():
    telemetry.close()
    metrics.reset()
    yield
    telemetry.close()
    metrics.reset()


def by_event(sink, name):
    return [e for e in sink.events if e["event"] == name]


# ---- spans ------------------------------------------------------------------


def test_span_noop_without_sinks():
    """The zero-cost contract: with no sink, span() hands back ONE shared
    no-op object — no allocation, no id burn, no thread-local stack."""
    assert not telemetry.enabled()
    s1 = spans.span("anything", k=1)
    s2 = spans.begin("anything_else")
    assert s1 is spans._NULL and s2 is spans._NULL
    with s1:
        assert spans.current_span_id() is None
    s2.end()
    assert spans.record_span("retro", 1.0, 2.0) is None


def test_span_begin_end_pair_and_fields():
    sink = telemetry.add_sink(telemetry.MemorySink())
    with spans.span("ckpt_save", engine="vanilla", step=3):
        pass
    (b,) = by_event(sink, "span_begin")
    (e,) = by_event(sink, "span_end")
    assert b["name"] == e["name"] == "ckpt_save"
    assert b["span"] == e["span"] and b["parent"] is None
    assert b["engine"] == e["engine"] == "vanilla" and b["step"] == 3
    assert e["dur_s"] >= 0 and e["mono"] >= b["mono"]
    assert "ok" not in e  # success path stays lean


def test_span_nesting_parents():
    sink = telemetry.add_sink(telemetry.MemorySink())
    with spans.span("outer") as outer:
        assert spans.current_span_id() == outer.span_id
        with spans.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert spans.current_span_id() == inner.span_id
        assert spans.current_span_id() == outer.span_id
    assert spans.current_span_id() is None
    begins = {e["name"]: e for e in by_event(sink, "span_begin")}
    assert begins["inner"]["parent"] == begins["outer"]["span"]
    # end order: inner closes before outer
    ends = [e["name"] for e in by_event(sink, "span_end")]
    assert ends == ["inner", "outer"]


def test_span_exception_path_records_error_and_propagates():
    sink = telemetry.add_sink(telemetry.MemorySink())
    with pytest.raises(ValueError, match="boom"):
        with spans.span("doomed"):
            raise ValueError("boom")
    (e,) = by_event(sink, "span_end")
    assert e["ok"] is False and "ValueError: boom" in e["error"]
    assert spans.current_span_id() is None  # stack unwound


def test_span_end_idempotent_and_out_of_order():
    telemetry.add_sink(sink := telemetry.MemorySink())
    a = spans.begin("a")
    b = spans.begin("b")
    a.end()  # closes out-of-order: b is popped off the stack too
    a.end()  # idempotent
    b.end()  # still emits its own end event
    assert len(by_event(sink, "span_end")) == 2
    assert spans.current_span_id() is None


def test_note_adds_fields_known_at_the_end():
    sink = telemetry.add_sink(telemetry.MemorySink())
    with spans.span("ckpt_prune", engine="sharded") as sp:
        sp.note(removed=2)
    (b,) = by_event(sink, "span_begin")
    (e,) = by_event(sink, "span_end")
    assert "removed" not in b and e["removed"] == 2 and e["engine"] == "sharded"
    telemetry.close()
    spans.span("ckpt_prune").note(removed=2)  # the shared no-op takes it too


def test_live_spans_show_in_a_profiler_trace_by_name(tmp_path):
    """While a jax.profiler trace is taken, a live span also opens a
    TraceAnnotation of its name (retroactive spans cannot: their time is
    past); with the profiler off a span holds none."""
    import glob

    import jax
    from jax.profiler import ProfileData

    telemetry.add_sink(telemetry.MemorySink())
    with spans.span("outside_the_trace") as sp:
        assert sp._annotation is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("ckpt_save", step=1) as sp:
            assert sp._annotation is not None
            with spans.span("ckpt_digest"):
                pass
        spans.record_span("ckpt_write_background", 1.0, 2.0)
    finally:
        jax.profiler.stop_trace()
    (trace,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {
        e.name for p in ProfileData.from_file(trace).planes
        for ln in p.lines for e in ln.events
    }
    assert {"ckpt_save", "ckpt_digest"} <= names
    assert "ckpt_write_background" not in names
    assert "outside_the_trace" not in names


def test_spans_are_thread_isolated():
    """Each thread nests on its own stack: concurrent spans never parent
    across threads, and ids never collide."""
    sink = telemetry.add_sink(telemetry.MemorySink())
    ready = threading.Barrier(2)

    def work(tag):
        ready.wait()
        for _ in range(20):
            with spans.span(f"outer_{tag}"):
                with spans.span(f"inner_{tag}"):
                    pass

    threads = [
        threading.Thread(target=work, args=(i,)) for i in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    begins = by_event(sink, "span_begin")
    ids = [e["span"] for e in begins]
    assert len(ids) == len(set(ids)) == 80
    outer_ids = {
        e["span"]: e["name"] for e in begins if e["name"].startswith("outer")
    }
    for e in begins:
        if e["name"].startswith("inner"):
            tag = e["name"].rsplit("_", 1)[1]
            assert outer_ids[e["parent"]] == f"outer_{tag}"
        else:
            assert e["parent"] is None


def test_record_span_retroactive():
    sink = telemetry.add_sink(telemetry.MemorySink())
    with spans.span("parent") as p:
        sid = spans.record_span("step", 10.0, 10.5, step=7)
    (e,) = by_event(sink, "span")
    assert e["span"] == sid and e["parent"] == p.span_id
    assert e["mono"] == 10.0 and e["dur_s"] == pytest.approx(0.5)
    assert e["step"] == 7
    # explicit parent overrides the stack
    sid2 = spans.record_span("child", 10.0, 10.1, parent=sid)
    assert by_event(sink, "span")[-1]["parent"] == sid


def test_span_metric_feeds_histogram():
    telemetry.add_sink(telemetry.MemorySink())
    with spans.span("ckpt_fsync", metric="ckpt_fsync_s"):
        pass
    spans.record_span("w", 0.0, 2.0, metric="w_s")
    assert metrics.histogram("ckpt_fsync_s").count == 1
    assert metrics.histogram("w_s").count == 1
    assert metrics.histogram("w_s").max == pytest.approx(2.0)


# ---- metrics ----------------------------------------------------------------


def test_counter_and_gauge():
    metrics.counter("saves").inc()
    metrics.counter("saves").inc(2)
    metrics.gauge("queue_depth").set(4)
    snap = metrics.snapshot()
    assert snap["counters"]["saves"] == 3
    assert snap["gauges"]["queue_depth"] == 4


def test_histogram_percentiles_log_buckets():
    h = metrics.histogram("lat")
    for v in range(1, 101):  # 1..100, uniform
        h.observe(float(v))
    d = h.as_dict()
    assert d["count"] == 100 and d["min"] == 1.0 and d["max"] == 100.0
    # log-bucketed estimates: within one bucket width (~19%) of the truth
    assert d["p50"] == pytest.approx(50.0, rel=0.25)
    assert d["p95"] == pytest.approx(95.0, rel=0.25)
    assert d["p99"] == pytest.approx(99.0, rel=0.25)
    assert d["p50"] <= d["p95"] <= d["p99"] <= d["max"]


def test_histogram_zero_bucket_and_weights():
    h = metrics.histogram("wait")
    h.observe(0.0, n=99)  # a loader that almost never stalls
    h.observe(3.0)
    d = h.as_dict()
    assert d["count"] == 100
    assert d["p50"] == 0.0 and d["p95"] == 0.0
    assert d["p99"] == 0.0  # rank 99 still lands in the zero bucket
    assert d["max"] == 3.0


def test_flush_emits_snapshot_and_maybe_flush_rate_limits():
    sink = telemetry.add_sink(telemetry.MemorySink())
    metrics.counter("c").inc()
    metrics.histogram("h").observe(1.0)
    rec = metrics.flush(reason="test")
    assert rec["event"] == "metrics_snapshot" and rec["reason"] == "test"
    assert rec["counters"]["c"] == 1 and rec["hists"]["h"]["count"] == 1
    # immediately after a flush, maybe_flush is rate-limited
    assert metrics.maybe_flush(interval_s=60.0) is None
    assert len(by_event(sink, "metrics_snapshot")) == 1


def test_flush_without_sinks_is_noop_but_registry_accumulates():
    metrics.histogram("h").observe(5.0)
    assert metrics.flush() is None
    assert metrics.snapshot()["hists"]["h"]["count"] == 1


def test_empty_registry_flush_emits_nothing():
    sink = telemetry.add_sink(telemetry.MemorySink())
    assert metrics.flush() is None
    assert sink.events == []


def test_histogram_thread_safety():
    h = metrics.histogram("t")

    def work():
        for _ in range(1000):
            h.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == 4000
