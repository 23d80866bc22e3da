"""The spans inside the sharded engine's save: every blocking second of
``ShardedCheckpointer.save`` lies under ``ckpt_wait_previous`` (async
checkpointer only), ``ckpt_serialize`` or ``ckpt_prune``, children of
whatever span the caller holds; ``ckpt_digest`` lies inside
``ckpt_serialize``, between the snapshot and Orbax's call; and Orbax's
commit thread reports its own life as ``ckpt_write_background``."""

import threading
import time

import jax
import pytest

from pyrecover_tpu import telemetry
from pyrecover_tpu.checkpoint import checkpoint_path
from pyrecover_tpu.checkpoint.sharded import ShardedCheckpointer
from pyrecover_tpu.config import TrainConfig
from pyrecover_tpu.models import ModelConfig
from pyrecover_tpu.optim import build_optimizer
from pyrecover_tpu.telemetry import metrics, spans
from pyrecover_tpu.train_state import create_train_state

# in the order their ``span_end`` records are written: the digest's span
# closes inside ``ckpt_serialize``, before it
BLOCKING = ("ckpt_wait_previous", "ckpt_digest", "ckpt_serialize", "ckpt_prune")
IN_A_ROW = tuple(n for n in BLOCKING if n != "ckpt_digest")
COMMIT_DELAY_S = 0.3
ENGINES = pytest.mark.parametrize(
    "use_async", [True, False], ids=["async", "sync"])


@pytest.fixture(autouse=True)
def clean_bus():
    telemetry.close()
    metrics.reset()
    yield
    telemetry.close()
    metrics.reset()


@pytest.fixture(scope="module")
def state():
    optimizer, _ = build_optimizer(TrainConfig(sequence_length=32))
    return create_train_state(
        jax.random.key(0), ModelConfig().tiny(max_seq_len=32), optimizer)


@pytest.fixture()
def slow_commit():
    """Hold Orbax's commit thread for COMMIT_DELAY_S before it commits, so
    that the next save certainly finds the write in flight. Orbax reports
    the end of its writes on that thread; a listener that sleeps there
    delays the commit with no hand on Orbax's internals."""

    def hold(event, secs, **_):
        if event == "/jax/checkpoint/write/async/commit_duration_sec":
            time.sleep(COMMIT_DELAY_S)

    jax.monitoring.register_event_duration_secs_listener(hold)
    yield
    jax.monitoring.unregister_event_duration_listener(hold)


def run_saves(ckpt_dir, state, use_async, n, *, max_keep=1, outer=True):
    """``n`` saves in a row, each under a ``ckpt_save`` span as the trainer
    holds one (``outer``); returns (sink, [blocking_s], [outer span id])."""
    sink = telemetry.add_sink(telemetry.MemorySink())
    blocking, outers = [], []
    with ShardedCheckpointer(use_async=use_async) as ckptr:
        for step in range(1, n + 1):
            path = checkpoint_path(ckpt_dir, "exp", step, engine="sharded")
            sp = spans.begin("ckpt_save", step=step) if outer else spans._NULL
            blocking.append(ckptr.save(
                path, state, max_keep=max_keep, extra_meta={"step": step}))
            sp.end()
            outers.append(sp.span_id)
    return sink, blocking, outers


def ends(sink, step=None):
    """``span_end`` records of the blocking spans, in the order written."""
    return [e for e in sink.events
            if e["event"] == "span_end" and e["name"] in BLOCKING
            and (step is None or e["step"] == step)]


@ENGINES
def test_each_save_has_its_phases_in_order(tmp_ckpt_dir, state, use_async):
    sink, _, _ = run_saves(tmp_ckpt_dir, state, use_async, 3)
    want = [n for n in BLOCKING if use_async or n != "ckpt_wait_previous"]
    for step in (1, 2, 3):
        got = ends(sink, step)
        assert [e["name"] for e in got] == want
        # one after the other, never overlapping: the wait, then the
        # snapshot and Orbax's save, then the prune
        row = [e for e in got if e["name"] in IN_A_ROW]
        for a, b in zip(row, row[1:]):
            assert a["mono"] <= b["mono"] - b["dur_s"] + 1e-6
        # and the digests' share inside ckpt_serialize, after its snapshot
        by = {e["name"]: e for e in got}
        digest, serialize = by["ckpt_digest"], by["ckpt_serialize"]
        began = serialize["mono"] - serialize["dur_s"]
        assert began + serialize["snapshot_s"] <= (
            digest["mono"] - digest["dur_s"] + 1e-4)
        assert digest["mono"] <= serialize["mono"] + 1e-6
        assert all(e["engine"] == "sharded" for e in got)


@ENGINES
@pytest.mark.parametrize("outer", [True, False], ids=["in_ckpt_save", "bare"])
def test_phases_nest_under_the_callers_span(tmp_ckpt_dir, state, use_async,
                                            outer):
    sink, _, outers = run_saves(tmp_ckpt_dir, state, use_async, 2, outer=outer)
    me = threading.get_ident()
    for step, parent in zip((1, 2), outers):
        got = ends(sink, step)
        serialize = next(e for e in got if e["name"] == "ckpt_serialize")
        for e in got:
            assert e["tid"] == me
            if e["name"] == "ckpt_digest":
                assert e["parent"] == serialize["span"]
            else:
                assert e["parent"] == parent  # None when the caller holds none
    if outer:
        assert all(p is not None for p in outers)


@ENGINES
def test_fields_say_what_how_much_and_for_which_save(tmp_ckpt_dir, state,
                                                     use_async):
    sink, _, _ = run_saves(tmp_ckpt_dir, state, use_async, 3)
    params = jax.tree_util.tree_leaves(state.params)
    everything = jax.tree_util.tree_leaves(state)
    for step in (1, 2, 3):
        by = {e["name"]: e for e in ends(sink, step)}
        assert by["ckpt_digest"]["leaves"] == len(params)
        assert by["ckpt_digest"]["bytes"] == sum(x.nbytes for x in params)
        assert by["ckpt_serialize"]["bytes"] == sum(
            x.nbytes for x in everything)
        assert by["ckpt_serialize"]["bytes"] > by["ckpt_digest"]["bytes"] > 0
        assert by["ckpt_serialize"]["async_"] is use_async
    # one kept: a sync save is committed when the prune looks, an async one
    # is not yet, so its prune trails by a save
    removed = [e["removed"] for e in ends(sink) if e["name"] == "ckpt_prune"]
    assert removed == ([0, 0, 1] if use_async else [0, 1, 1])


def test_no_prune_span_without_retention(tmp_ckpt_dir, state):
    sink, _, _ = run_saves(tmp_ckpt_dir, state, False, 1, max_keep=None)
    assert [e["name"] for e in ends(sink)] == ["ckpt_digest", "ckpt_serialize"]
    assert "removed" not in ends(sink)[-1]


def test_second_async_save_waits_on_the_first_write(tmp_ckpt_dir, state,
                                                    slow_commit):
    sink, _, _ = run_saves(tmp_ckpt_dir, state, True, 2)
    first, second = (e for e in ends(sink) if e["name"] == "ckpt_wait_previous")
    assert first["waited"] is False and first["dur_s"] < COMMIT_DELAY_S / 2
    assert second["waited"] is True and second["dur_s"] > COMMIT_DELAY_S / 2


def test_sync_checkpointer_never_waits(tmp_ckpt_dir, state):
    sink, _, _ = run_saves(tmp_ckpt_dir, state, False, 2)
    names = {e["name"] for e in sink.events if e["event"].startswith("span")}
    assert "ckpt_wait_previous" not in names
    assert "ckpt_write_background" not in names


def test_one_background_write_span_per_committed_async_save(tmp_ckpt_dir,
                                                            state, slow_commit):
    sink, _, _ = run_saves(tmp_ckpt_dir, state, True, 2)
    # run_saves closed the checkpointer: both writes are committed
    bg = [e for e in sink.events
          if e["event"] == "span" and e["name"] == "ckpt_write_background"]
    assert len(bg) == 2
    me = threading.get_ident()
    for e in bg:
        assert e["tid"] != me and e["parent"] is None
        assert e["engine"] == "sharded" and e["dur_s"] >= COMMIT_DELAY_S
    # the first write was still going when the second save came, and the
    # second save's wait ends where that write's span ends
    wait = [e for e in ends(sink, 2) if e["name"] == "ckpt_wait_previous"][0]
    assert abs(wait["mono"] - (bg[0]["mono"] + bg[0]["dur_s"])) < 0.1
    assert metrics.histogram("ckpt_sharded_background_write_s").count == 2


@ENGINES
def test_phases_sum_to_the_blocking_seconds(tmp_ckpt_dir, state, use_async):
    sink, blocking, _ = run_saves(tmp_ckpt_dir, state, use_async, 3)
    for step, total in zip((1, 2, 3), blocking):
        parts = sum(e["dur_s"] for e in ends(sink, step)
                    if e["name"] in IN_A_ROW)
        assert 0 <= total - parts <= max(0.05 * total, 0.05)
        # the digests' span lies inside ckpt_serialize: counting it too,
        # as the benchmark's ckpt_unspanned_pct does, stays within a hair
        by = {e["name"]: e for e in ends(sink, step)}
        assert by["ckpt_digest"]["dur_s"] <= by["ckpt_serialize"]["dur_s"]
    for name in ("digest", "serialize", "prune"):
        assert metrics.histogram(f"ckpt_sharded_{name}_s").count == 3
    assert metrics.histogram("ckpt_sharded_wait_previous_s").count == (
        3 if use_async else 0)


@ENGINES
def test_without_a_sink_the_save_opens_no_span(tmp_ckpt_dir, state, use_async,
                                               monkeypatch):
    made = []
    real = spans.Span.__init__

    def counted(self, name, *a, **kw):
        made.append(name)
        real(self, name, *a, **kw)

    monkeypatch.setattr(spans.Span, "__init__", counted)
    assert not telemetry.enabled()
    with ShardedCheckpointer(use_async=use_async) as ckptr:
        for step in (1, 2):
            ckptr.save(checkpoint_path(tmp_ckpt_dir, "exp", step, engine="sharded"),
                       state, max_keep=1, extra_meta={"step": step})
    assert made == [] and spans.current_span_id() is None
    # and with one, the same calls do (the probe itself works)
    telemetry.add_sink(telemetry.MemorySink())
    with ShardedCheckpointer(use_async=use_async) as ckptr:
        ckptr.save(checkpoint_path(tmp_ckpt_dir, "exp", 3, engine="sharded"),
                   state, max_keep=1, extra_meta={"step": 3})
    assert "ckpt_digest" in made and "ckpt_serialize" in made
