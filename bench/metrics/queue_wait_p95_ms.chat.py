"""Scheduler queue wait, p95 over requests due in the window: from due
to the start of the round that admitted it to a slot (a request still
queued at the close counts with its wait so far)."""
from readers import p95, waits


def read(run):
    v = p95(waits(run, "admit"))
    return None if v is None else 1e3 * v
