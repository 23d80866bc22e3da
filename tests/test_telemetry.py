"""Telemetry subsystem tests: event bus + sinks (schema, host-0 gating,
torn-line read-back), goodput accounting (including replayed steps across a
kill/resume cycle), and the summarizer tool's round-trip."""

import json

import pytest

from pyrecover_tpu import telemetry
from pyrecover_tpu.metrics import WallTimeTotals
from pyrecover_tpu.telemetry import sinks as sinks_mod

# tools/ is on sys.path via conftest (anchored at the repo root)
from summarize_telemetry import aggregate, main as summarize_main  # noqa: E402


@pytest.fixture(autouse=True)
def clean_bus():
    telemetry.close()
    yield
    telemetry.close()


# ---- event bus --------------------------------------------------------------


def test_emit_noop_without_sinks():
    assert not telemetry.enabled()
    assert telemetry.emit("anything", x=1) is None


def test_emit_schema_and_memory_sink():
    sink = telemetry.add_sink(telemetry.MemorySink())
    assert telemetry.enabled()
    rec = telemetry.emit("hello", a=1, b="x")
    assert sink.events == [rec]
    e = sink.events[0]
    assert e["event"] == "hello" and e["a"] == 1 and e["b"] == "x"
    assert isinstance(e["ts"], float) and e["host"] == 0


def test_envelope_keys_win_over_fields():
    sink = telemetry.add_sink(telemetry.MemorySink())
    telemetry.emit("e", event="spoofed", host=99)
    assert sink.events[0]["event"] == "e"
    assert sink.events[0]["host"] == 0


def test_broken_sink_is_disabled_not_fatal():
    class Broken:
        def write(self, rec):
            raise OSError("disk on fire")

    good = telemetry.MemorySink()
    telemetry.add_sink(Broken())
    telemetry.add_sink(good)
    telemetry.emit("a")  # must not raise
    telemetry.emit("b")
    assert [e["event"] for e in good.events] == ["a", "b"]


def test_remove_sink_stops_delivery():
    sink = telemetry.add_sink(telemetry.MemorySink())
    telemetry.emit("a")
    telemetry.remove_sink(sink)
    telemetry.emit("b")
    assert [e["event"] for e in sink.events] == ["a"]
    assert not telemetry.enabled()


def test_flash_plan_event_once_a_traced_shape_never_from_the_loop(monkeypatch):
    """The flash kernels' plan (grid steps visited / interior / edge / above
    the diagonal, with the blocks and the shape) is told at TRACE time, once
    a shape: the compiled step that runs in the loop emits nothing."""
    import jax
    import jax.numpy as jnp

    from pyrecover_tpu.ops import flash_attention as fa

    monkeypatch.setenv("PYRECOVER_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(fa, "_plans_told", set())

    def attend(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, block_q=128,
                                  block_kv=128)

    q = jnp.ones((1, 512, 4, 64), jnp.float32)
    k = v = jnp.ones((1, 512, 2, 64), jnp.float32)
    # traced while no sink listens: nothing is lost, the next trace tells it
    jax.jit(attend).lower(q, k, v)
    assert fa._plans_told == set()

    sink = telemetry.add_sink(telemetry.MemorySink())
    step = jax.jit(jax.grad(lambda q, k, v: attend(q, k, v).sum(), (0, 1, 2)))
    for _ in range(3):  # the loop: one trace, three runs of the program
        jax.block_until_ready(step(q, k, v))
    jax.jit(attend)(q, k, v)  # the same shape traced again
    plans = [e for e in sink.events if e["event"] == "flash_plan"]
    assert len(plans) == 1
    e = plans[0]
    assert [e[n] for n in ("steps_visited", "steps_interior", "steps_edge",
                           "steps_above")] == [10, 6, 4, 6]
    assert {n: e[n] for n in (
        "seq_q", "seq_kv", "block_q", "block_kv", "causal", "batch", "heads",
        "kv_heads", "head_dim", "segments",
    )} == {"seq_q": 512, "seq_kv": 512, "block_q": 128, "block_kv": 128,
           "causal": True, "batch": 1, "heads": 4, "kv_heads": 2,
           "head_dim": 64, "segments": False}
    # another shape is another event: one block, the diagonal in it
    jax.jit(attend)(q[:, :128], k[:, :128], v[:, :128])
    plans = [e for e in sink.events if e["event"] == "flash_plan"]
    assert [p["steps_visited"] for p in plans] == [10, 1]
    assert (plans[1]["steps_edge"], plans[1]["steps_above"]) == (1, 0)


# ---- JSONL sink -------------------------------------------------------------


def test_jsonl_sink_roundtrip(tmp_path):
    path = tmp_path / "t.jsonl"
    telemetry.add_sink(telemetry.JsonlSink(path))
    telemetry.emit("a", x=1)
    telemetry.emit("b", y=2.5)
    telemetry.close()
    evs = telemetry.read_events(path)
    assert [e["event"] for e in evs] == ["a", "b"]
    assert evs[0]["x"] == 1 and evs[1]["y"] == 2.5


def test_jsonl_sink_flushes_per_event(tmp_path):
    """Durability contract: every event is on disk as soon as emit returns
    (a SIGTERM kill loses at most a torn final line, never whole batches)."""
    path = tmp_path / "t.jsonl"
    telemetry.add_sink(telemetry.JsonlSink(path))
    telemetry.emit("a", x=1)
    # read WITHOUT closing the sink
    assert [e["event"] for e in telemetry.read_events(path)] == ["a"]


def test_jsonl_sink_host0_gating(tmp_path, monkeypatch):
    monkeypatch.setattr(sinks_mod, "_process_index", lambda: 1)
    path = tmp_path / "t.jsonl"
    sink = telemetry.JsonlSink(path)
    sink.write({"event": "x", "ts": 0, "host": 1})
    sink.close()
    assert not path.exists()
    # host0_only=False writes everywhere (per-host local files)
    sink = telemetry.JsonlSink(path, host0_only=False)
    sink.write({"event": "x", "ts": 0, "host": 1})
    sink.close()
    assert len(telemetry.read_events(path)) == 1


def test_jsonl_sink_rotation_keeps_stream_readable(tmp_path):
    """Size-based rotation: the live file never grows unbounded, the
    shifted shards keep their order, and read_events merges them back
    into one continuous stream."""
    path = tmp_path / "t.jsonl"
    sink = telemetry.add_sink(
        telemetry.JsonlSink(path, max_bytes=200, keep=10)
    )
    for i in range(40):
        telemetry.emit("e", i=i)
    telemetry.close()
    rotated = telemetry.rotated_paths(path)
    assert rotated, "the byte cap must have rotated at least once"
    assert all(p.stat().st_size <= 400 for p in rotated + [path])
    evs = telemetry.read_events(path)
    assert [e["i"] for e in evs] == list(range(40))  # merged, in order
    # oldest-first: shard .N holds the lowest indices
    first = telemetry.read_events(rotated[0], include_rotated=False)
    assert first[0]["i"] == 0


def test_jsonl_sink_rotation_drops_beyond_keep(tmp_path):
    path = tmp_path / "t.jsonl"
    sink = telemetry.JsonlSink(path, max_bytes=80, keep=2)
    for i in range(50):
        sink.write({"event": "e", "ts": 0, "host": 0, "i": i})
    sink.close()
    assert len(telemetry.rotated_paths(path)) == 2  # .1 and .2 only
    evs = telemetry.read_events(path)
    # the tail survives contiguously; the oldest shards were dropped
    assert [e["i"] for e in evs] == list(range(evs[0]["i"], 50))
    assert evs[0]["i"] > 0


def test_jsonl_sink_fresh_run_clears_stale_rotated_shards(tmp_path):
    path = tmp_path / "t.jsonl"
    (tmp_path / "t.jsonl.1").write_text(
        '{"event":"stale","ts":0,"host":0}\n'
    )
    sink = telemetry.JsonlSink(path, append=False)
    sink.write({"event": "fresh", "ts": 1, "host": 0})
    sink.close()
    assert [e["event"] for e in telemetry.read_events(path)] == ["fresh"]


def test_jsonl_sink_rotation_env_defaults(tmp_path, monkeypatch):
    monkeypatch.setenv("PYRECOVER_TELEMETRY_MAX_BYTES", "150")
    monkeypatch.setenv("PYRECOVER_TELEMETRY_KEEP", "5")
    sink = telemetry.JsonlSink(tmp_path / "t.jsonl")
    assert sink.max_bytes == 150 and sink.keep == 5
    sink.close()


def test_read_events_tolerates_torn_lines(tmp_path):
    path = tmp_path / "t.jsonl"
    path.write_text(
        '{"event":"a","ts":1,"host":0,"step":3}\n'
        "\n"
        "not json at all\n"
        '["a","list","not","an","event"]\n'
        '{"event":"b","ts":2,"host":0,"step":7}\n'
        '{"event":"c","ts":3,"host":0,"step":9,"trunc'  # torn final line
    )
    evs = telemetry.read_events(path)
    assert [e["event"] for e in evs] == ["a", "b"]
    assert telemetry.last_recorded_step(path) == 7
    assert telemetry.read_events(tmp_path / "missing.jsonl") == []
    assert telemetry.last_recorded_step(tmp_path / "missing.jsonl") is None


# ---- goodput accounting -----------------------------------------------------


def test_walltime_totals_goodput_math():
    t = WallTimeTotals()
    t.train_s, t.step_s, t.wall_s = 110.0, 100.0, 120.0
    t.ckpt_save_s, t.ckpt_load_s, t.setup_s, t.eval_s = 5.0, 2.0, 3.0, 4.0
    t.replayed_steps, t.replayed_s = 4, 10.0
    assert t.productive_s() == 90.0
    assert t.lost_s() == 20.0
    assert t.goodput_pct() == pytest.approx(75.0)
    d = t.as_dict()
    for key in ("train_s", "step_s", "ckpt_save_s", "ckpt_load_s", "eval_s",
                "setup_s", "wall_s", "replayed_steps", "replayed_s",
                "productive_s", "lost_s", "goodput_pct"):
        assert key in d
    s = t.summary()
    assert "eval 4.0s" in s and "replayed 4 steps" in s and "goodput" in s


def _write_synthetic_stream(path):
    """A plausible two-segment (kill + resume) stream, hand-built so the
    summarizer test needs no jax training run."""
    events = [
        # segment 1: killed after step 6 (no run_summary)
        {"event": "run_start", "devices": 8, "resume": False},
        {"event": "step_time", "step": 1, "data_wait_s": 0.01,
         "dispatch_s": 0.002},
        {"event": "train_sync", "step": 2, "loss": 4.8, "steps": 2,
         "interval_s": 1.0, "iter_s": 0.5, "sync_s": 0.05},
        {"event": "ckpt_save_start", "engine": "vanilla", "path": "ckpt_3"},
        {"event": "ckpt_commit", "engine": "vanilla", "bytes": 1000,
         "write_s": 0.2, "checksum": True},
        {"event": "ckpt_save_blocking", "engine": "vanilla",
         "blocking_s": 0.3, "background": False},
        {"event": "train_sync", "step": 6, "loss": 4.4, "steps": 4,
         "interval_s": 2.0, "iter_s": 0.5, "sync_s": 0.04},
        # segment 2: resumed from step 3, replays 3 steps, finishes at 9
        {"event": "run_start", "devices": 8, "resume": True},
        {"event": "ckpt_restore_done", "engine": "vanilla", "seconds": 0.4,
         "step": 3},
        {"event": "resume_replay", "start_step": 3, "prior_step": 6,
         "replayed_steps": 3},
        {"event": "data_stall", "wait_s": 0.05, "depth": 0, "batch": 4},
        {"event": "train_sync", "step": 9, "loss": 4.1, "steps": 6,
         "interval_s": 3.0, "iter_s": 0.5, "sync_s": 0.04},
        {"event": "run_summary", "status": "finished", "step": 9,
         "wall_s": 10.0, "step_s": 5.0, "productive_s": 3.5,
         "replayed_s": 1.5, "replayed_steps": 3, "ckpt_save_s": 0.3,
         "ckpt_load_s": 0.4, "setup_s": 2.0, "eval_s": 0.0, "lost_s": 4.2,
         "goodput_pct": 35.0},
    ]
    with open(path, "w") as f:
        for i, e in enumerate(events):
            f.write(json.dumps({"ts": float(i), "host": 0, **e}) + "\n")
    return events


def test_summarizer_aggregate_roundtrip(tmp_path):
    path = tmp_path / "run.jsonl"
    _write_synthetic_stream(path)
    agg = aggregate(telemetry.read_events(path))
    assert agg["n_segments"] == 2
    assert agg["segments"][0]["status"].startswith("no summary")
    assert agg["segments"][1]["status"] == "finished"
    assert agg["totals"]["replayed_steps"] == 3
    assert agg["goodput_pct"] == pytest.approx(35.0)
    assert agg["ckpt"]["vanilla"]["saves"] == 1
    assert agg["ckpt"]["vanilla"]["restores"] == 1
    assert agg["data_stalls"]["count"] == 1
    assert agg["loss_first"] == 4.8 and agg["loss_last"] == 4.1


def test_summarizer_cli_smoke(tmp_path, capsys):
    """Tier-1 smoke of tools/summarize_telemetry.py: report + BENCH blob."""
    path = tmp_path / "run.jsonl"
    _write_synthetic_stream(path)
    out_json = tmp_path / "bench.json"
    assert summarize_main([str(path), "--json", str(out_json)]) == 0
    out = capsys.readouterr().out
    assert "GOODPUT" in out and "replayed 3 steps" in out
    assert "checkpoint lifecycle" in out
    blob = json.loads(out_json.read_text())
    assert blob["metric"] == "goodput_pct"
    assert blob["value"] == pytest.approx(35.0)
    assert blob["extra"]["totals"]["replayed_steps"] == 3
    # unreadable/empty stream → exit 2
    assert summarize_main([str(tmp_path / "missing.jsonl")]) == 2


def test_mfu_unknown_device_kind_emits_warning_event():
    from pyrecover_tpu.utils import perf

    sink = telemetry.add_sink(telemetry.MemorySink())
    perf._warned_unknown_kinds.clear()

    class Unknown:
        device_kind = "quantum-abacus-9000"

    assert perf.tpu_peak_flops(Unknown()) is None  # no stand-in, no MFU
    assert perf.tpu_peak_flops(Unknown()) is None
    evs = [e for e in sink.events if e["event"] == "mfu_peak_unknown"]
    assert len(evs) == 1  # once per kind, not per call
    assert evs[0]["device_kind"] == "quantum-abacus-9000"


def test_requeue_marker_roundtrip(tmp_path):
    from pyrecover_tpu.preempt import read_requeue_marker, write_requeue_marker

    assert read_requeue_marker(tmp_path) is None
    write_requeue_marker(tmp_path, done=False, step=42)
    m = read_requeue_marker(tmp_path)
    assert m["step"] == 42 and m["done"] is False
    write_requeue_marker(tmp_path, done=True, step=100)
    m = read_requeue_marker(tmp_path)
    assert m["step"] == 100 and m["done"] is True
    assert not (tmp_path / "REQUEUE").exists()
    # legacy bare-float marker content still parses
    (tmp_path / "DONE").write_text("1723456789.5")
    m = read_requeue_marker(tmp_path)
    assert m["done"] is True and m.get("step") is None


# ---- goodput across a real kill/resume cycle --------------------------------


@pytest.mark.slow
def test_resume_cycle_counts_replayed_steps(tmp_path, monkeypatch):
    """End-to-end: run to step 6 (ckpt at 3), simulate a crash by deleting
    everything after ckpt_3, resume to 9 — the resumed run must count the
    3 replayed steps in its goodput accounting and the summarizer must
    render the productive-vs-lost split. Telemetry rotation is forced via
    the env cap: the stream must survive rotation + a kill + a resume and
    still read back as one sequence."""
    monkeypatch.setenv("PYRECOVER_TELEMETRY_MAX_BYTES", "4096")
    monkeypatch.setenv("PYRECOVER_TELEMETRY_KEEP", "50")
    from pyrecover_tpu.config import TrainConfig
    from pyrecover_tpu.models import ModelConfig
    from pyrecover_tpu.train import train

    def cfg(steps, resume=None):
        c = TrainConfig(
            sequence_length=32, batch_size=8, training_samples=64,
            training_steps=steps, learning_rate=1e-3, seed=3,
            checkpoint_dir=str(tmp_path), checkpoint_frequency=3,
            experiment_name="exp", logging_frequency=2,
            telemetry=True, resume_from_checkpoint=resume,
            async_checkpoint=False,
        )
        c.model = ModelConfig().tiny(max_seq_len=32, vocab_size=128)
        c.__post_init__()
        return c

    train(cfg(6))
    exp_dir = tmp_path / "exp"
    for p in exp_dir.glob("ckpt_6*"):
        p.unlink()
    (exp_dir / "DONE").unlink(missing_ok=True)  # hard kill leaves no marker

    _, end_step, stopped = train(cfg(9, resume="latest"))
    assert end_step == 9 and not stopped

    tele = exp_dir / "exp_telemetry.jsonl"
    assert telemetry.rotated_paths(tele), "the 4 KiB cap must have rotated"
    evs = telemetry.read_events(tele)
    names = {e["event"] for e in evs}
    assert {"run_start", "step_time", "train_sync", "ckpt_save_start",
            "ckpt_commit", "ckpt_saved", "resume", "resume_replay",
            "run_summary", "span", "span_begin", "span_end",
            "metrics_snapshot"} <= names

    summaries = [e for e in evs if e["event"] == "run_summary"]
    # first attempt replays nothing; the resumed attempt replays 4..6
    assert summaries[0]["replayed_steps"] == 0
    assert summaries[-1]["replayed_steps"] == 3
    assert summaries[-1]["replayed_s"] > 0
    assert summaries[-1]["productive_s"] > 0
    assert summaries[-1]["status"] == "finished"

    agg = aggregate(evs)
    assert agg["totals"]["replayed_steps"] == 3
    assert agg["n_segments"] == 2
    assert summarize_main([str(tele)]) == 0
