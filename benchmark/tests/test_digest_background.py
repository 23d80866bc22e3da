"""The reader of ``ckpt_digest_background_s`` (the hash of the ``.params``
leaves on the async save's commit side, off the loop's thread) on hand-made
events, and its entry in BENCHMARK.json."""

import pytest

from benchmark.lib.manifest import Manifest

CELL = "mistral-7b.save-every-8"
METRIC = "ckpt_digest_background_s"
COPY_S, HASH_S = 2.0, 4.0


class Run:
    """What a reader may ask of a run: the window's events by kind."""

    def __init__(self, records):
        self.records = records

    def events(self, kind):
        return [r for r in self.records if r["event"] == kind]


def save(scale=1.0, deferred=True):
    """The events of one save: the loop's thread copies for COPY_S, and the
    hash takes HASH_S on the commit's side (``deferred``) or lies inside the
    ``ckpt_digest`` span, as on the parent commit."""
    digest = COPY_S + (0.0 if deferred else HASH_S)
    out = [{"event": "span_end", "name": "ckpt_digest",
            "dur_s": digest * scale, "leaves": 12,
            "deferred": 12 if deferred else 0}]
    if deferred:
        out.append({"event": "span", "name": "ckpt_digest_background",
                    "dur_s": HASH_S * scale, "leaves": 12})
    out.append({"event": "span", "name": "ckpt_write_background",
                "dur_s": 3.8 * scale})
    out.append({"event": "ckpt_saved", "blocking_s": (digest + 8.0) * scale})
    return out


def test_mean_over_the_windows_saves():
    read = Manifest().reader(METRIC)
    assert read(Run(save())) == pytest.approx(HASH_S)
    three = save(1.0) + save(2.0) + save(0.5)
    assert read(Run(three)) == pytest.approx(HASH_S * 3.5 / 3)


def test_the_hash_is_no_part_of_the_copys_metric():
    """``ckpt_digest_s`` keeps its reader: with the hash handed on it reads
    the copy, and the two together read what the parent's span held."""
    man = Manifest()
    copy, whole = man.reader("ckpt_digest_s"), man.reader(METRIC)
    assert copy(Run(save())) == pytest.approx(COPY_S)
    assert copy(Run(save())) + whole(Run(save())) == pytest.approx(
        copy(Run(save(deferred=False))))


@pytest.mark.parametrize("records", [
    [],
    save(deferred=False),  # the parent commit, or a sync save: hash inline
    [{"event": "span", "name": "ckpt_write_background", "dur_s": 3.8},
     {"event": "span_end", "name": "ckpt_digest_background", "dur_s": 4.0}],
], ids=["empty", "inline_hash", "other_spans"])
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
    steady = man.metrics_of("mistral-7b.steady", "per_layer")
    assert METRIC not in {m["name"] for m in steady}  # it holds no save
