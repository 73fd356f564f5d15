"""Time to first token, p95 over every request due in the window: from
when it was due to the end of the round that delivered its first token
to the host; a request with no first token at the close counts with its
wait so far (host clock)."""
from readers import p95, waits


def read(run):
    v = p95(waits(run, "first"))
    return None if v is None else 1e3 * v
