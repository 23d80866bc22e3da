"""Mean seconds of the ``ckpt_write_background`` spans that ended in the
window: Orbax's commit thread from its start to the commit, after the save
call has returned. Not a part of ``ckpt_blocking_s``; where it is longer than
the steps between two saves, the next save waits (``ckpt_wait_previous_s``)."""


def read(run):
    durs = [e["dur_s"] for e in run.events("span")
            if e.get("name") == "ckpt_write_background"]
    return sum(durs) / len(durs) if durs else None
