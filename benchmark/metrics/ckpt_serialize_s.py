"""Mean seconds of the ``ckpt_serialize`` span over the window's saves: Orbax's
save call, that is the device-to-host copy of the whole state and the
dispatch of its write (a program without ``ckpt_wait_previous`` also waits
for the previous write in here). A part of ``ckpt_blocking_s``."""


def read(run):
    durs = [e["dur_s"] for e in run.events("span_end")
            if e.get("name") == "ckpt_serialize"]
    return sum(durs) / len(durs) if durs else None
