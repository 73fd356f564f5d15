"""KV positions the decode attention reads per position it attends: the
program's ``kv_read_positions`` over its ``kv_live_positions``, summed
over the ``sched.round`` spans of the rounds that ended in the window.
1 is a decode that reads only the live context."""
import spans


def read(run):
    sp = spans.load(run)
    if sp is None:
        return None
    ks = [r["counters"] for r in sp["rounds"] if r["end"] <= run["seconds"]]
    live = sum(k.get("kv_live_positions", 0) for k in ks)
    reads = sum(k.get("kv_read_positions", 0) for k in ks)
    return reads / live if live else None
