"""Mean ``ckpt_saved.blocking_s`` over the window's saves: what the loop
waited for each save call, a wait on the previous write included."""


def read(run):
    ev = run.events("ckpt_saved")
    return sum(e["blocking_s"] for e in ev) / len(ev) if ev else None
