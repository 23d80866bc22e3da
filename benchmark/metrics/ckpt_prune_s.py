"""Mean seconds of the ``ckpt_prune`` span over the window's saves: old
checkpoint directories deleted on the loop's thread. A part of
``ckpt_blocking_s``."""


def read(run):
    durs = [e["dur_s"] for e in run.events("span_end")
            if e.get("name") == "ckpt_prune"]
    return sum(durs) / len(durs) if durs else None
