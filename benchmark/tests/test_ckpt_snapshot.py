"""The reader of ``ckpt_snapshot_s`` on hand-made events: the mean
``snapshot_s`` noted on the window's ``ckpt_serialize`` spans, and its
entry in BENCHMARK.json."""

import pytest

from benchmark.lib.manifest import Manifest

CELL = "mistral-7b.save-every-8"
METRIC = "ckpt_snapshot_s"


class Run:
    def __init__(self, records):
        self.records = records

    def events(self, kind):
        return [r for r in self.records if r["event"] == kind]


def save(snapshot_s=None, serialize_s=6.0):
    """One save's events; without ``snapshot_s`` the span is the parent
    commit's, whose Orbax call copies the state itself."""
    span = {"event": "span_end", "name": "ckpt_serialize",
            "dur_s": serialize_s, "bytes": 6_800_000_000}
    if snapshot_s is not None:
        span.update(snapshot_s=snapshot_s, snapshot_bytes=6_799_990_000,
                    fallback_leaves=5, host_memory="pinned_host")
    return [
        {"event": "span_end", "name": "ckpt_digest", "dur_s": 0.001},
        {"event": "span_end", "name": "ckpt_wait_previous", "dur_s": 0.0001},
        span,
        {"event": "span_end", "name": "ckpt_prune", "dur_s": 0.5},
        # other spans that carry the field's name are none of this reader's
        {"event": "span", "name": "ckpt_dispatch_background", "dur_s": 0.9,
         "snapshot_s": 99.0},
        {"event": "ckpt_saved", "blocking_s": serialize_s + 0.6},
    ]


def test_mean_over_the_windows_saves():
    read = Manifest().reader(METRIC)
    assert read(Run(save(1.5))) == pytest.approx(1.5)
    three = save(1.5) + save(3.0, serialize_s=3.4) + save(0.75)
    assert read(Run(three)) == pytest.approx(5.25 / 3)


def test_it_is_the_noted_field_not_the_spans_length():
    read = Manifest().reader(METRIC)
    assert read(Run(save(1.5, serialize_s=9.0))) == pytest.approx(1.5)
    whole = Manifest().reader("ckpt_serialize_s")
    assert whole(Run(save(1.5, serialize_s=9.0))) == pytest.approx(9.0)


def test_saves_without_a_snapshot_are_left_out_of_the_mean():
    read = Manifest().reader(METRIC)
    assert read(Run(save(2.0) + save(None) + save(4.0))) == pytest.approx(3.0)


@pytest.mark.parametrize("records", [
    [],
    save(None),  # the parent commit: the span is there, the field is not
    [{"event": "span", "name": "ckpt_serialize", "dur_s": 2.0,
      "snapshot_s": 1.0}],  # a retroactive span of that name is not read
    [{"event": "span_end", "name": "loss_sync", "dur_s": 0.004},
     {"event": "ckpt_saved", "blocking_s": 14.0}],
], ids=["empty", "parent", "retroactive", "other_spans"])
def test_nothing_to_read_gives_none(records):
    assert Manifest().reader(METRIC)(Run(records)) is None


def test_the_entry_stands_in_the_manifest():
    man = Manifest()
    assert man.problems() == []
    m = {m["name"]: m for m in man.metrics_of(CELL, "per_layer")}[METRIC]
    assert m == {
        "name": METRIC, "unit": "s", "better": "lower",
        "source": "program_span", "layer": "checkpoint engine",
        "moves": "goodput_tok_s_per_chip", "workloads": [CELL],
    }
    assert man.doc["per_layer"][-1]["name"] == METRIC  # appended, last
    for cell in ("mistral-7b.steady", "ouro-2.6b.steady"):
        due = man.metrics_of(cell, "per_layer")
        assert METRIC not in {m["name"] for m in due}  # they hold no save
