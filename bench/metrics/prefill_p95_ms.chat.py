"""Chunked prefill time from inside the program: the ``request.prefill``
span (slot assignment to the first token sampled, over the rounds whose
``mixed_step`` ran the prompt's chunks), p95 over requests admitted in
the window; one still prefilling at the close counts up to the close."""
import spans
from readers import p95


def read(run):
    sp = spans.load(run)
    if sp is None:
        return None
    w = run["seconds"]
    out = []
    for r in sp["requests"].values():
        if "queued" not in r:
            continue
        t = r["queued"][1]                  # admitted: the prefill starts
        if not 0.0 <= t < w:
            continue
        end = r["prefill"][1] if "prefill" in r else w
        out.append(min(end, w) - t)
    v = p95(out)
    return None if v is None else 1e3 * v
