"""The program's own spans, on the window's clock, for the readers in
``metrics/`` that see the scheduler from inside.

The serving runtime records spans in the process that served
(``repro.runtime.spans``), on ``time.perf_counter_ns``: the clock
``run.py`` times the window with.  ``load`` keeps the newest
scheduler's spans and puts them on the window's clock (seconds from the
window's start) by aligning its last ``len(run["rounds"])``
``sched.round`` spans with ``run["rounds"]``: the harness calls
``step`` once per recorded round, and each round span starts a few
microseconds after the harness's own start of that round.

A program without the recorder (or with no rounds recorded) gives
``None``, and the readers then report nothing.
"""
from __future__ import annotations

LIFE = ("queued", "prefill", "decode")


def load(run):
    """``None``, or ``dict(rounds=[...], requests={rid: {...}})``.

    Per window round, in order: ``start``, ``end``, ``counters`` and
    ``phases`` (child span name -> seconds).  Per request: ``submit``
    (the instant it was submitted) and, for each of ``queued``,
    ``prefill`` and ``decode`` that it finished, the first such span's
    ``[start, end]``.  Times are seconds from the window's start."""
    try:
        from repro.runtime import spans as recorder
    except ImportError:
        return None
    snap = recorder.snapshot()
    n = len(run["rounds"])
    rounds = [s for s in snap if s.name == "sched.round"]
    if not n or not rounds:
        return None
    sched = max(s.ids["sched"] for s in rounds)
    mine = sorted((s for s in snap if s.ids.get("sched") == sched),
                  key=lambda s: s.start_ns)
    rounds = [s for s in mine if s.name == "sched.round"][-n:]
    if len(rounds) < n:
        return None
    off = min(s.start_ns * 1e-9 - r["start"]
              for s, r in zip(rounds, run["rounds"]))

    def at(ns):
        return ns * 1e-9 - off

    phases: dict = {}
    requests: dict = {}
    for s in mine:
        if s.parent is not None:
            ph = phases.setdefault(s.parent, {})
            ph[s.name] = ph.get(s.name, 0.0) + s.seconds
        elif s.name.startswith("request."):
            r = requests.setdefault(s.ids["rid"], {})
            kind = s.name.split(".", 1)[1]
            if kind == "submit":
                r.setdefault("submit", at(s.start_ns))
            elif kind in LIFE:
                r.setdefault(kind, [at(s.start_ns), at(s.end_ns)])
    return dict(
        rounds=[dict(start=at(s.start_ns), end=at(s.end_ns),
                     counters=dict(s.counters), phases=phases.get(s.sid, {}))
                for s in rounds],
        requests=requests)
