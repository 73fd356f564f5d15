"""Scheduler host time per round, from the program's own spans: a
``sched.round`` span less its ``sched.device_wait`` (the pull that waits
for the device), mean over the window's rounds that end before the
profiler starts, so the profiler's own host cost is not counted."""
import spans


def read(run):
    sp = spans.load(run)
    if sp is None:
        return None
    cut = run["seconds"] - float(run["cell"]["trace"]["last_s"])
    host = [r["end"] - r["start"] - r["phases"].get("sched.device_wait", 0.0)
            for r in sp["rounds"] if r["end"] <= cut]
    return 1e3 * sum(host) / len(host) if host else None
