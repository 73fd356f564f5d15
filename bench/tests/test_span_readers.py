"""The readers of the program's own spans (``spans.py`` and the
``program_span`` metrics) on a whole tiny traced run on the CPU: each
reads what the recorder holds, as computed here by hand from the
snapshot on the process clock, and reports nothing from a program that
has no recorder."""
import sys

import numpy as np
import pytest

import repro.runtime
import run
from conftest import tiny_cell
from repro.runtime import spans as recorder

METRICS = ("sched_host_ms.chat", "sched_queue_p95_ms.chat",
           "prefill_p95_ms.chat")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cell = tiny_cell("mla")
    cell["per_layer"] = [{"name": n, "unit": "ms"} for n in METRICS]
    with pytest.MonkeyPatch.context() as mp:
        # a trace directory of its own: other tests' runs may trace too
        mp.setattr(run, "TRACE_DIR", tmp_path_factory.mktemp("trace"))
        result, _, _, rec = run.run_cell(cell, 3, 6, True,
                                         require_chip=False)
    return result, rec, recorder.snapshot()


def _by_hand(rec, snap):
    """The three values straight from the snapshot: the window opened
    at ``T_PROCESS + setup_s`` on the same clock."""
    t0 = run.T_PROCESS + rec["setup_s"]
    w = rec["seconds"]
    cut = w - rec["cell"]["trace"]["last_s"]
    sched = max(s.ids["sched"] for s in snap if s.name == "sched.round")
    mine = [s for s in snap if s.ids.get("sched") == sched]

    def at(ns):
        return ns * 1e-9 - t0

    host = []
    for r in mine:
        if r.name != "sched.round" or at(r.start_ns) < 0 \
                or at(r.end_ns) > cut:
            continue
        wait = sum(c.seconds for c in mine if c.parent == r.sid
                   and c.name == "sched.device_wait")
        host.append(r.seconds - wait)

    def first(name, rid):
        got = [s for s in mine if s.name == name and s.ids["rid"] == rid]
        return min(got, key=lambda s: s.start_ns) if got else None

    queue, prefill = [], []
    for sub in (s for s in mine if s.name == "request.submit"):
        rid = sub.ids["rid"]
        q, p = first("request.queued", rid), first("request.prefill", rid)
        if 0 <= at(sub.start_ns) < w:
            queue.append(min(at(q.end_ns) if q else w, w) - at(sub.start_ns))
        if q is not None and 0 <= at(q.end_ns) < w:
            prefill.append(min(at(p.end_ns) if p else w, w) - at(q.end_ns))
    return {"sched_host_ms.chat": 1e3 * np.mean(host),
            "sched_queue_p95_ms.chat": 1e3 * np.percentile(queue, 95),
            "prefill_p95_ms.chat": 1e3 * np.percentile(prefill, 95)}


def test_readers_match_the_snapshot(traced):
    result, rec, snap = traced
    want = _by_hand(rec, snap)
    for name in METRICS:
        got = result["metrics"][name]["value"]
        # the readers align on the round spans, a few us after t0
        assert got == pytest.approx(want[name], abs=0.1), name
        assert got > 0


def test_no_recorder_no_value(traced, monkeypatch):
    _, rec, _ = traced
    monkeypatch.delattr(repro.runtime, "spans")
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    for name in METRICS:
        assert run.read_metric(name, rec) is None
