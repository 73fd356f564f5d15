"""The serving runtime's span and counter recorder (``runtime/spans.py``)
and what the scheduler and engine record into it.

A round is a ``sched.round`` span whose children are its phases; a
request's life is ``request.queued`` -> ``request.prefill`` ->
``request.decode``, tiled with no gaps.  The round counters must agree
with ``Scheduler.stats`` and with the tokens returned, ``compiled``
must mark exactly the rounds that compiled, the ring must stay bounded,
and under ``jax.profiler`` the same spans must land in the trace's host
plane with the same nesting.  ``Scheduler.progress`` must agree with
the benchmark's stopgap reading of the slot state.
"""
import importlib.util
import types
from pathlib import Path

import numpy as np
import pytest

import jax

from repro import configs
from repro.models import get_family
from repro.runtime import spans
from repro.runtime.engine import Engine
from repro.runtime.scheduler import Scheduler

ROOT = Path(__file__).resolve().parents[1]
PHASES = ("sched.admit", "sched.prepare", "engine.dispatch",
          "sched.device_wait", "sched.emit", "sched.first_tokens",
          "sched.retire")
LIFE = ("request.queued", "request.prefill", "request.decode")


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_config("minicpm3-4b").reduced(compute_dtype="float32")
    return cfg, get_family(cfg).init_params(jax.random.PRNGKey(0), cfg)


def _scheduler(model, chunked=True):
    cfg, params = model
    eng = Engine(cfg, params, max_len=64, paged=True, block_size=4)
    return Scheduler(eng, n_slots=2, chunk_size=4, chunked_prefill=chunked)


def _submit(sched, n=5, seed=0):
    rng = np.random.default_rng(seed)
    plens = [5, 11, 3, 9, 6, 2][:n]
    gens = [3, 6, 2, 5, 1, 4][:n]
    return [sched.submit(rng.integers(1, 256, p).tolist(), g)
            for p, g in zip(plens, gens)]


def _mine(sched):
    return [s for s in spans.snapshot() if s.ids.get("sched") == sched.sched_id]


def _serve(model, chunked=True):
    sched = _scheduler(model, chunked)
    rids = _submit(sched)
    done = sched.run(max_rounds=200)
    assert sorted(done) == sorted(rids)
    return sched, done, _mine(sched)


@pytest.mark.parametrize("chunked", [True, False],
                         ids=["chunked", "unchunked"])
def test_round_phases_nest_inside_their_round(model, chunked):
    sched, _, mine = _serve(model, chunked)
    rounds = {s.sid: s for s in mine if s.name == "sched.round"}
    assert sorted(s.ids["round"] for s in rounds.values()) == \
        list(range(len(rounds)))
    children = [s for s in mine if s.parent is not None]
    assert children
    for c in children:
        r = rounds[c.parent]
        assert c.name in PHASES
        assert c.ids["round"] == r.ids["round"]
        assert r.start_ns <= c.start_ns <= c.end_ns <= r.end_ns
    # phases of one round do not overlap, and come in the round's order
    for r in rounds.values():
        kids = sorted((c for c in children if c.parent == r.sid),
                      key=lambda c: c.start_ns)
        assert kids[0].name == "sched.admit"
        assert kids[-1].name == "sched.retire"
        for a, b in zip(kids, kids[1:]):
            assert a.end_ns <= b.start_ns
            assert PHASES.index(a.name) < PHASES.index(b.name)


@pytest.mark.parametrize("chunked", [True, False],
                         ids=["chunked", "unchunked"])
def test_request_spans_tile_each_life(model, chunked):
    sched, done, mine = _serve(model, chunked)
    for rid in done:
        life = sorted((s for s in mine if s.ids.get("rid") == rid
                       and s.name.startswith("request.")),
                      key=lambda s: (s.start_ns, s.sid))
        submit, *phases = life
        assert submit.name == "request.submit"
        assert submit.start_ns == submit.end_ns == phases[0].start_ns
        assert tuple(s.name for s in phases) == LIFE
        for a, b in zip(phases, phases[1:]):
            assert a.end_ns == b.start_ns          # no gap, no overlap
        assert phases[-1].counters["tokens"] == len(done[rid].tokens)
        assert phases[1].counters["prefill_rounds"] >= 1


@pytest.mark.parametrize("chunked", [True, False],
                         ids=["chunked", "unchunked"])
def test_round_counters_match_stats_and_tokens(model, chunked):
    sched, done, mine = _serve(model, chunked)
    rounds = sorted((s for s in mine if s.name == "sched.round"),
                    key=lambda s: s.ids["round"])
    st = sched.stats

    def total(k):
        return sum(r.counters[k] for r in rounds)

    assert total("admitted") == st["n_admitted"] == len(done)
    assert total("retired") == st["n_retired"] == len(done)
    assert total("prefill_tokens") == st["prefill_tokens"] > 0
    assert total("tokens_emitted") == \
        sum(len(c.tokens) for c in done.values())
    assert total("first_tokens") == len(done)
    assert total("compiled") == st["n_compiles"]
    assert rounds[-1].counters["queue_depth"] == 0
    assert all(r.counters["decode_rows"] <= sched.n_slots for r in rounds)
    if chunked:
        firsts = [s for s in mine if s.name == "sched.first_tokens"]
        assert sum(s.counters["syncs"] for s in firsts) == len(done)
    wall = [r.seconds * 1e3 for r in rounds]
    assert st["step_wall_p50_ms"] == pytest.approx(np.percentile(wall, 50))
    assert st["step_wall_p99_ms"] == pytest.approx(np.percentile(wall, 99))


def test_compiled_marks_only_the_round_that_compiles(model):
    """Chunked mode: the first round compiles ``mixed_step``; every later
    round, whatever prompt lengths it admits, compiles nothing."""
    sched, _, mine = _serve(model, chunked=True)
    rounds = sorted((s for s in mine if s.name == "sched.round"),
                    key=lambda s: s.ids["round"])
    assert rounds[0].counters["compiled"] > 0
    assert [r.counters["compiled"] for r in rounds[1:]] == \
        [0] * (len(rounds) - 1)
    # a second wave of new prompt lengths on the warm engine: still none
    _submit(sched, seed=1)
    sched.run(max_rounds=200)
    later = [s for s in _mine(sched) if s.name == "sched.round"
             and s.ids["round"] >= len(rounds)]
    assert later and all(r.counters["compiled"] == 0 for r in later)


def test_ring_stays_bounded():
    rec = spans.Recorder(capacity=8)
    for i in range(20):
        with rec.span("sched.round", round=i) as r:
            with rec.span("sched.admit") as a:
                pass
    snap = rec.snapshot()
    assert len(snap) == 8
    assert [s.ids["round"] for s in snap[::2]] == [16, 17, 18, 19]
    assert a.parent == r.sid and a.ids == {"round": 19}
    ev = rec.event("request.submit", rid=3)
    assert ev.start_ns == ev.end_ns and len(rec.snapshot()) == 8
    assert spans.CAPACITY == 65536


def test_spans_land_in_the_profiler_trace(model, tmp_path):
    from jax.profiler import ProfileData
    sched = _scheduler(model)
    _submit(sched, n=2)
    sched.step()                               # compile outside the trace
    _submit(sched, n=3, seed=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        while sched.has_work:
            sched.step()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    host = [ev for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for ev in line.events if ev.name.startswith("sched.")
            or ev.name == "engine.dispatch"]
    ev_rounds = sorted((e for e in host if e.name == "sched.round"),
                       key=lambda e: e.start_ns)
    ring_rounds = sorted((s for s in _mine(sched) if s.name == "sched.round"
                          and s.ids["round"] >= 1), key=lambda s: s.start_ns)
    assert len(ev_rounds) == len(ring_rounds) > 0

    def inside(e, r):
        return r.start_ns <= e.start_ns and \
            e.start_ns + e.duration_ns <= r.start_ns + r.duration_ns

    for er, rr in zip(ev_rounds, ring_rounds):
        got = [e.name for e in sorted(host, key=lambda e: e.start_ns)
               if e is not er and inside(e, er)]
        want = [s.name for s in sorted(_mine(sched),
                                       key=lambda s: s.start_ns)
                if s.parent == rr.sid]
        assert got == want


def _stopgap_progress():
    spec = importlib.util.spec_from_file_location(
        "bench_program", ROOT / "bench" / "program.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Server.progress


def test_progress_agrees_with_the_slot_reading(model):
    stopgap = _stopgap_progress()
    sched = _scheduler(model)
    _submit(sched)
    rounds = 0
    while sched.has_work:
        sched.step()
        rounds += 1
        assert sched.progress() == \
            stopgap(types.SimpleNamespace(sched=sched))
    assert rounds > 3
