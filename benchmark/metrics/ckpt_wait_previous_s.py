"""Mean seconds of the ``ckpt_wait_previous`` span over the window's saves: the
loop waiting, inside the save call, for the previous save's background write
to commit. A part of ``ckpt_blocking_s``; the only one the disk decides."""


def read(run):
    durs = [e["dur_s"] for e in run.events("span_end")
            if e.get("name") == "ckpt_wait_previous"]
    return sum(durs) / len(durs) if durs else None
