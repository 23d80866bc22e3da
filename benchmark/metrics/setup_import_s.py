"""Seconds from the process's start to the end of the trainer's import
(``run_start.import_s``); None where the trainer does not report it."""


def read(run):
    for _, rec in run.res["sink"].records:
        if rec.get("event") == "run_start":
            return rec.get("import_s")
    return None
