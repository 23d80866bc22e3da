"""Mean seconds of the ``ckpt_digest_background`` spans that ended in the
window: the BLAKE2b hash of the ``.params`` leaves' host copies, run by a
commit future of the async sharded save on a thread of its own, where no
step waits for it. Not a part of ``ckpt_blocking_s``. None where the program
hashes on the loop's thread (there the hash lies inside ``ckpt_digest_s``)."""


def read(run):
    durs = [e["dur_s"] for e in run.events("span")
            if e.get("name") == "ckpt_digest_background"]
    return sum(durs) / len(durs) if durs else None
