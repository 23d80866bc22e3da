"""The trace reduction, on intervals made by hand and on one small trace
recorded on the chip (benchmark/tests/record_trace.py, TPU v5 lite)."""

import json
from pathlib import Path

import pytest

from benchmark.lib import xplane

DATA = Path(__file__).parent / "data"


def test_union_gaps_and_names():
    busy = xplane.union([(5, 7), (0, 2), (1, 3), (9, 10)])
    assert busy == [(0, 3), (5, 7), (9, 10)]
    assert xplane.total(busy) == 6
    idle = xplane.gaps(busy, 0, 12)
    assert idle == [(3, 5), (7, 9), (10, 12)]
    named = xplane.name_gaps(
        idle, [("ckpt_save", 2.5, 9.5), ("loss_sync", 7.2, 8.8)])
    # the first gap lies under the save; the second under both, and the sync
    # covers less of it than the save does; the third under neither
    assert named == {"ckpt_save": 4, "host, no span": 2}
    assert xplane.clip([(0, 4), (6, 9)], 3, 7) == [(3, 4), (6, 7)]


def test_self_time_of_nested_operations():
    ev = [("while", 0, 100), ("a", 0, 30), ("b", 40, 50), ("c", 120, 10)]
    assert xplane.self_times(ev) == pytest.approx(
        {"while": 20e-9, "a": 30e-9, "b": 50e-9, "c": 10e-9})
    assert [n for n, _, _ in xplane.leaf_events(ev)] == ["a", "b", "c"]
    assert xplane.kernel_time(ev, r"^[ab]$") == (pytest.approx(80e-9), 2)


def test_exposed_collectives():
    ops = [("%fusion.1 = f32[] fusion()", 0, 40),
           ("%all-gather-done.2 = f32[] all-gather-done()", 40, 10),
           ("%fusion.3 = f32[] fusion()", 50, 10),
           ("%all-reduce.4 = f32[] all-reduce()", 60, 20)]
    flights = [("%all-gather-start.2 = f32[] all-gather-start()", 10, 40)]
    exposed, in_flight = xplane.exposed_collectives(ops, flights, 0, 100)
    # in flight 10..50 and 60..80; compute covers 10..40
    assert in_flight == pytest.approx(60e-9)
    assert exposed == pytest.approx(30e-9)


def test_names():
    line = ("%closed_call.14 = (bf16[4,32,4096,128]{3,2,1,0:T(8,128)(2,1)}, "
            "f32[4,32,4096,8]{3,2,1,0:T(8,128)}) custom-call(bf16[4,32,4096,128]"
            "{3,2,1,0} %x), custom_call_target=\"tpu_custom_call\"")
    assert xplane.short(line) == "closed_call.14"
    assert xplane.custom_call_results(line) == [
        "bf16[4,32,4096,128]", "f32[4,32,4096,8]"]
    assert xplane.custom_call_results("%fusion.1 = f32[2] fusion()") is None
    from benchmark.lib.manifest import Manifest, load_module

    flash = load_module(Manifest().metric_file("flash_roofline"))
    assert flash.classify(line, 4, 32, 8, 4096, 128) == "fwd"
    dq = "%checkpoint.23 = bf16[4,32,4096,128]{3,2,1,0} custom-call(bf16[1] %a)"
    dkv = ("%checkpoint.22 = (bf16[4,8,4096,128]{3,2,1,0}, bf16[4,8,4096,128]"
           "{3,2,1,0:S(1)}) custom-call(bf16[1] %a)")
    assert flash.classify(dq, 4, 32, 8, 4096, 128) == "dq"
    assert flash.classify(dkv, 4, 32, 8, 4096, 128) == "dkv"
    assert flash.classify(dq, 2, 32, 8, 4096, 128) is None


def test_recorded_trace():
    meta = json.loads((DATA / "small_1chip.json").read_text())
    spans = [tuple(s) for s in meta["spans"]]
    r = xplane.reduce(DATA / "small_1chip.xplane.pb", meta["anchor_ns"],
                      meta["t0"], meta["t1"], spans, chips=1)
    assert r["window_s"] == pytest.approx(meta["t1"] - meta["t0"])
    # three calls of a few products each: tens of microseconds of device time,
    # all of it inside the host's three `work` spans
    work = sum(b - a for n, a, b in spans if n == "work")
    assert 10e-6 < r["busy_s"] < work
    ops = dict(r["device_ops"])
    assert sum(ops.values()) == pytest.approx(r["busy_s"], rel=0.05)
    assert any(n.startswith("convolution") or "fusion" in n for n in ops)
    # the device idles while the host sleeps: the pauses take most idle time
    idle = dict(r["idle_gaps"])
    assert max(idle, key=idle.get) == "pause"
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["collective_s"] == 0.0
