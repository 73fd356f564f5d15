"""Scheduler queue wait from inside the program: the ``request.queued``
span (submit to slot assignment), p95 over requests submitted in the
window; one still queued at the close counts up to the close."""
import spans
from readers import p95


def read(run):
    sp = spans.load(run)
    if sp is None:
        return None
    w = run["seconds"]
    waits = []
    for r in sp["requests"].values():
        t = r.get("submit")
        if t is None or not 0.0 <= t < w:
            continue
        end = r["queued"][1] if "queued" in r else w
        waits.append(min(end, w) - t)
    v = p95(waits)
    return None if v is None else 1e3 * v
