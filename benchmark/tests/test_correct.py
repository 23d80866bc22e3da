"""`correct` comes out false when the timed path is broken underneath.

Each case skips the harness's look for a chip (the rehearsal switch: toy width,
CPU) and drives the rest of a run: the trainer's own loop, the harness's
wrapper, the reference, the comparison with the limits the cell ships.
"""

import json

import pytest

from benchmark import run as bench


def drive(capsys, cell, *extra):
    rc = bench.main(["--workload", cell, "--seed", "23", "--seconds", "1",
                     "--trace", "0", "--rehearse-cpu", *extra])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failing(line):
    return sorted(k for k, c in line["check"].items()
                  if not c["value"] <= c["limit"])


def test_a_sound_run_is_correct(capsys):
    line = drive(capsys, "mistral-7b.steady")
    assert line["correct"] is True and failing(line) == []


def test_the_control_is_not_correct(capsys):
    """The reference in the precision below the configuration's (fp8 for
    bfloat16), in the reference's place: the limits the cell ships fail it."""
    line = drive(capsys, "mistral-7b.steady", "--reference-precision", "fp8")
    assert line["correct"] is False and failing(line), line["check"]


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch", "label_shift"])
def test_a_broken_step_is_not_correct(capsys, fault):
    line = drive(capsys, "mistral-7b.steady", "--fault", fault)
    assert line["correct"] is False
    assert failing(line), line["check"]


def test_the_exchange_left_out_is_not_correct(capsys, monkeypatch):
    """The four-chip cell is not in BENCHMARK.json yet (PERF.md, Open
    questions); its files are kept, and entered here they still drive the
    sharded path on four virtual devices and catch the fault across chips."""
    import benchmark.lib.manifest as mf

    init = mf.Manifest.__init__

    def with_the_unshipped_cell(self, *a, **kw):
        init(self, *a, **kw)
        self.cells["mixtral-8x7b.fsdp4-steady"] = {
            "name": "mixtral-8x7b.fsdp4-steady", "config": "mixtral-8x7b",
            "traffic": "fsdp4-steady", "chips": 4, "why": "kept for later"}

    monkeypatch.setattr(mf.Manifest, "__init__", with_the_unshipped_cell)
    line = drive(capsys, "mixtral-8x7b.fsdp4-steady", "--fault", "no_exchange")
    assert line["device"]["count"] == 4
    assert line["correct"] is False and failing(line)


def test_an_answer_altered_on_disk_is_not_correct(capsys, monkeypatch):
    """The save cell: one byte of what the last save wrote is flipped before
    it is read back."""
    from benchmark.runners import train_window as tw

    real = tw.readback

    def corrupt_then_read(sink, config, state, jax):
        import pathlib

        root = pathlib.Path(config.checkpoint_dir) / config.experiment_name
        last = [r for _, r in sink.records if r.get("event") == "ckpt_saved"][-1]
        data = sorted((root / last["path"]).rglob("d/*"),
                      key=lambda p: p.stat().st_size)[-1]
        raw = bytearray(data.read_bytes())
        raw[len(raw) // 2] ^= 0xFF
        data.write_bytes(bytes(raw))
        try:
            return real(sink, config, state, jax)
        except Exception:  # a read that fails outright has not read it back
            return {"readback_mismatch": float("inf")}

    monkeypatch.setattr(tw, "readback", corrupt_then_read)
    import benchmark.lib.manifest as mf

    monkeypatch.setattr(mf.Manifest, "runner", lambda self, kind: tw)
    line = drive(capsys, "mistral-7b.save-every-8")
    assert line["correct"] is False
    assert "readback_mismatch" in failing(line)
