"""Mean ``snapshot_s`` of the window's ``ckpt_serialize`` spans: the seconds
the save call waits for its one device-to-host copy of the whole state (into
pinned host memory where the leaf's devices offer it). A part of
``ckpt_serialize_s``, so of ``ckpt_blocking_s``. None where the program
takes no snapshot (there Orbax's call copies the state itself)."""


def read(run):
    secs = [e["snapshot_s"] for e in run.events("span_end")
            if e.get("name") == "ckpt_serialize" and "snapshot_s" in e]
    return sum(secs) / len(secs) if secs else None
