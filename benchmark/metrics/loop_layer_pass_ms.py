"""Device busy time per layer pass, from the trace: busy seconds a step (the
busy union of the traced window / steps dispatched in it) / the
``layer_passes`` counter of the program's ``run_start`` event (loop steps x
layers held: 48 in ouro-2.6b.steady). The head, the optimizer and the
embedding are inside the numerator: it is the step's cost spread over its
layer passes, comparable across depths and pass counts, not a layer's own
time. A program that reports no such counter gives nothing."""


def read(run):
    n = run.traced_steps()
    if not (run.trace and n):
        return None
    passes = [r.get("layer_passes") for _, r in run.res["sink"].records
              if r.get("event") == "run_start"]
    if not passes or not passes[0]:
        return None
    return 1e3 * run.trace["busy_s"] / n / passes[0]
