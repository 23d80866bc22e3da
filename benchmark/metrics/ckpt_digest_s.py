"""Mean seconds of the ``ckpt_digest`` span over the window's saves: the
sharded engine pulling every ``.params`` leaf to the host and hashing it
(BLAKE2b), before Orbax copies anything. A part of ``ckpt_blocking_s``."""


def read(run):
    durs = [e["dur_s"] for e in run.events("span_end")
            if e.get("name") == "ckpt_digest"]
    return sum(durs) / len(durs) if durs else None
