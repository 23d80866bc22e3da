"""The six readers that split ``ckpt_blocking_s`` by the spans inside the
sharded engine's save, on hand-made events, and their entries in
BENCHMARK.json."""

import pytest

from benchmark.lib.manifest import Manifest

CELL = "mistral-7b.save-every-8"
PARTS = {  # metric -> (event kind, span name, seconds in a save)
    "ckpt_digest_s": ("span_end", "ckpt_digest", 3.0),
    "ckpt_wait_previous_s": ("span_end", "ckpt_wait_previous", 5.0),
    "ckpt_serialize_s": ("span_end", "ckpt_serialize", 6.0),
    "ckpt_prune_s": ("span_end", "ckpt_prune", 0.5),
    "ckpt_background_write_s": ("span", "ckpt_write_background", 12.0),
}
UNSPANNED_S = 0.25


class Run:
    """What a reader may ask of a run: the window's events by kind."""

    def __init__(self, records):
        self.records = records

    def events(self, kind):
        return [r for r in self.records if r["event"] == kind]


def save(scale=1.0):
    """The events of one save whose every part takes ``scale`` times its
    seconds in PARTS, between spans that are none of this split's."""
    out = [{"event": "span_end", "name": "loss_sync", "dur_s": 0.004}]
    for kind, name, secs in PARTS.values():
        out.append({"event": kind, "name": name, "dur_s": secs * scale})
    blocking = scale * (UNSPANNED_S + sum(
        secs for kind, _, secs in PARTS.values() if kind == "span_end"))
    out.append({"event": "span_end", "name": "ckpt_save", "dur_s": blocking})
    out.append({"event": "ckpt_saved", "blocking_s": blocking})
    return out


@pytest.mark.parametrize("metric", sorted(PARTS))
def test_mean_over_the_windows_saves(metric):
    read = Manifest().reader(metric)
    secs = PARTS[metric][2]
    assert read(Run(save())) == pytest.approx(secs)
    three = save(1.0) + save(2.0) + save(0.5)
    assert read(Run(three)) == pytest.approx(secs * 3.5 / 3)


def test_unspanned_share():
    read = Manifest().reader("ckpt_unspanned_pct")
    blocking = UNSPANNED_S + 3.0 + 5.0 + 6.0 + 0.5
    want = 100.0 * UNSPANNED_S / blocking
    assert read(Run(save())) == pytest.approx(want)
    assert read(Run(save(1.0) + save(2.0) + save(0.5))) == pytest.approx(want)
    # a part that loses its span shows as a large share
    no_digest = [r for r in save() if r.get("name") != "ckpt_digest"]
    assert read(Run(no_digest)) == pytest.approx(
        100.0 * (UNSPANNED_S + 3.0) / blocking)


@pytest.mark.parametrize("metric", sorted(PARTS) + ["ckpt_unspanned_pct"])
def test_nothing_to_read_gives_none(metric):
    """A program without the spans (the parent commit), or a window without
    a save: the reader returns nothing and does not raise."""
    read = Manifest().reader(metric)
    assert read(Run([])) is None
    other = [{"event": "span_end", "name": "loss_sync", "dur_s": 0.004},
             {"event": "span", "name": "step", "dur_s": 0.98},
             {"event": "ckpt_saved", "blocking_s": 14.0}]
    assert read(Run(other)) is None


def test_the_six_entries_stand_in_the_manifest():
    man = Manifest()
    assert man.problems() == []
    due = {m["name"]: m for m in man.metrics_of(CELL, "per_layer")}
    for name in list(PARTS) + ["ckpt_unspanned_pct"]:
        m = due[name]
        assert m["layer"] == "checkpoint engine"
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["moves"] == "goodput_tok_s_per_chip"
        assert m["workloads"] == [CELL]
        assert m["unit"] == ("%" if name.endswith("_pct") else "s")
    steady = man.metrics_of("mistral-7b.steady", "per_layer")
    assert {m["name"] for m in steady}.isdisjoint(PARTS)  # it holds no save
