"""Time per output token after the first, p95 over requests with two
tokens or more delivered by the close: (last delivery - first delivery)
/ (tokens - 1), on the host clock."""
from readers import p95, tpots


def read(run):
    v = p95(tpots(run))
    return None if v is None else 1e3 * v
