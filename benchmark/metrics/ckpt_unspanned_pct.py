"""The share of the saves' blocking seconds that lies under none of the four
spans inside the save call (manifest, topology, fault seams, the trainer's
own bookkeeping round the call). Large means a part has no span yet."""

PARTS = ("ckpt_digest", "ckpt_wait_previous", "ckpt_serialize", "ckpt_prune")


def read(run):
    blocking = sum(e["blocking_s"] for e in run.events("ckpt_saved"))
    durs = [e["dur_s"] for e in run.events("span_end")
            if e.get("name") in PARTS]
    if not blocking or not durs:
        return None
    return 100.0 * (blocking - sum(durs)) / blocking
