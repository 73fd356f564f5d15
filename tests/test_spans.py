"""The serving runtime's span and counter recorder (``runtime/spans.py``)
and what the scheduler and engine record into it.

A round is a ``sched.round`` span whose children are its phases; a
request's life is ``request.queued`` -> ``request.prefill`` ->
``request.decode``, tiled with no gaps.  The round counters must agree
with ``Scheduler.stats`` and with the tokens returned, on the MLA and the dense-GQA lane;
``compiled`` must mark exactly the rounds that compiled, the ring must stay bounded,
and under ``jax.profiler`` the same spans must land in the trace's host
plane with the same nesting.  ``Scheduler.progress`` must agree with
the benchmark's stopgap reading of the slot state.  A round's
decode-read counters must equal a hand count on each attention lane,
and rows crossing a width step of the bounded decode gather must not
compile anything.
"""
import functools
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


@functools.lru_cache(maxsize=None)
def _model(lane):
    arch = "minicpm3-4b" if lane == "mla" else "phi3-medium-14b"
    cfg = configs.get_config(arch).reduced(compute_dtype="float32")
    return cfg, get_family(cfg).init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def model():
    return _model("mla")


def _scheduler(model):
    cfg, params = model
    eng = Engine(cfg, params, max_len=64, paged=True, block_size=4)
    return Scheduler(eng, n_slots=2, chunk_size=4)


def _submit(sched, n=5, seed=0):
    rng = np.random.default_rng(seed)
    plens = [5, 11, 3, 9, 6, 2][:n]
    gens = [3, 6, 2, 5, 1, 4][:n]
    return [sched.submit(rng.integers(1, 256, p).tolist(), g)
            for p, g in zip(plens, gens)]


def _mine(sched):
    return [s for s in spans.snapshot() if s.ids.get("sched") == sched.sched_id]


def _serve(model):
    sched = _scheduler(model)
    rids = _submit(sched)
    done = sched.run(max_rounds=200)
    assert sorted(done) == sorted(rids)
    return sched, done, _mine(sched)


@pytest.mark.parametrize("lane", ["mla", "dense"])
def test_round_phases_nest_inside_their_round(lane):
    sched, _, mine = _serve(_model(lane))
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


@pytest.mark.parametrize("lane", ["mla", "dense"])
def test_request_spans_tile_each_life(lane):
    sched, done, mine = _serve(_model(lane))
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


@pytest.mark.parametrize("lane", ["mla", "dense"])
def test_round_counters_match_stats_and_tokens(lane):
    sched, done, mine = _serve(_model(lane))
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
    firsts = [s for s in mine if s.name == "sched.first_tokens"]
    assert sum(s.counters["syncs"] for s in firsts) == len(done)
    wall = [r.seconds * 1e3 for r in rounds]
    assert st["step_wall_p50_ms"] == pytest.approx(np.percentile(wall, 50))
    assert st["step_wall_p99_ms"] == pytest.approx(np.percentile(wall, 99))


def test_compiled_marks_only_the_round_that_compiles(model):
    """The first round compiles ``mixed_step``; every later round,
    whatever prompt lengths it admits, compiles nothing."""
    sched, _, mine = _serve(model)
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


def test_one_program_serves_every_read_width(model):
    """A table of two width steps (1024 positions of
    blocks of 16): rows cross the first step (512 positions) in the
    middle of a round, and rounds read one step and two, yet no round
    after the first compiles: the gather's width is chosen on the
    device, inside the one ``mixed_step`` program."""
    cfg, params = model
    eng = Engine(cfg, params, max_len=1024, paged=True, block_size=16)
    sched = Scheduler(eng, n_slots=2, chunk_size=64)
    rng = np.random.default_rng(3)
    for plen, gen in ((480, 40), (40, 100), (500, 30)):
        sched.submit(rng.integers(1, 256, plen).tolist(), gen)
    done = sched.run(max_rounds=40)
    assert sorted(len(c.tokens) for c in done.values()) == [30, 40, 100]
    rounds = sorted((s for s in _mine(sched) if s.name == "sched.round"),
                    key=lambda s: s.ids["round"])
    per_step = {r.counters["kv_read_positions"] // 64 // 2 for r in rounds
                if r.counters["decode_steps"]}
    assert min(per_step) == 512 and max(per_step) > 512
    assert rounds[0].counters["compiled"] > 0
    assert [r.counters["compiled"] for r in rounds[1:]] == \
        [0] * (len(rounds) - 1)


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


# per round: (decode_steps, kv_live_positions, kv_read_positions) for
# requests A (prompt 3, 2 tokens), B (prompt 5, 9 tokens) and C (prompt
# 4, 3 tokens) on 2 slots of 64 positions, chunk 4:
#   0: A and B admitted; A's prompt completes, B's first 4 -> no decode
#   1: A decodes from lens 3 (attends 4, 5, 6, 7) and finishes after
#      its first step; B's last prompt token
#   2: C admitted to A's slot, prefills; B decodes from lens 5 (6..9)
#   3: B from lens 9 (10..13) and C from lens 4 (5..8), C finishing
#      mid-round
# Each decode round reads 4 steps x 2 rows x 64 positions; with a
# window of 8 a row attends at most 8 and reads its ring of 3 blocks.
FULL = [(0, 0, 0), (4, 22, 512), (4, 30, 512), (4, 46 + 26, 512)]
# A table of two width steps (1024 positions of blocks of 16; a step
# reads 512 or 1024 positions a row), chunk 64: A (prompt 480, 40
# tokens) and B (prompt 40, 100 tokens) on 2 slots:
#   0: both admitted; B's prompt completes -> no decode
#   1, 2: B decodes from lens 40 (41..104), then 104 (105..168) and
#      retires; every step reads the first width step
#   3-7: A prefills to 480 -> no decode
#   8: A decodes from lens 480 (481..544) and retires: 32 steps read
#      one width step, the 32 past 512 read two
BOUNDED = ([(0, 0, 0), (64, 64 * 41 + 2016, 2 * 64 * 512),
            (64, 64 * 105 + 2016, 2 * 64 * 512)] + [(0, 0, 0)] * 5
           + [(64, 64 * 481 + 2016, 2 * (32 * 512 + 32 * 1024))])
# requests (prompt, tokens), max_len, block_size, chunk, and each
# round's admitted and retired counts
ONE_STEP = (((3, 2), (5, 9), (4, 3)), 64, 4, 4,
            [2, 0, 1, 0], [0, 1, 0, 2])
TWO_STEPS = (((480, 40), (40, 100)), 1024, 16, 64,
             [2] + [0] * 8, [0, 0, 1] + [0] * 5 + [1])
DECODE_READS = {
    "gqa": ("phi3-medium-14b", 0, ONE_STEP, FULL),
    "mla": ("minicpm3-4b", 0, ONE_STEP, FULL),
    "window": ("phi3-medium-14b", 8, ONE_STEP,
               [(0, 0, 0), (4, 22, 96), (4, 6 + 7 + 8 + 8, 96),
                (4, 4 * 8 + 26, 96)]),
    "gqa-bounded": ("phi3-medium-14b", 0, TWO_STEPS, BOUNDED),
}


@pytest.mark.parametrize("lane", sorted(DECODE_READS))
def test_decode_read_counters_match_a_hand_count(lane):
    """``decode_steps``, ``kv_live_positions`` and ``kv_read_positions``
    on every round, on each attention lane: every step the device
    runs counts, a row's finished steps included, and the gather reads
    every row's table up to the width steps that the step's longest
    decoding row reaches (the whole table when it is one step)."""
    arch, window, setup, want = DECODE_READS[lane]
    reqs, max_len, bs, chunk, admitted, retired = setup
    cfg = configs.get_config(arch).reduced(compute_dtype="float32",
                                           sliding_window=window or None)
    params = get_family(cfg).init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, max_len=max_len, paged=True, block_size=bs)
    sched = Scheduler(eng, n_slots=2, chunk_size=chunk)
    rng = np.random.default_rng(0)
    for plen, gen in reqs:
        sched.submit(rng.integers(1, 256, plen).tolist(), gen)
    done = sched.run(max_rounds=20)
    assert sorted(len(c.tokens) for c in done.values()) == \
        sorted(gen for _, gen in reqs)
    rounds = sorted((s for s in _mine(sched) if s.name == "sched.round"),
                    key=lambda s: s.ids["round"])
    got = [tuple(r.counters[k] for k in ("decode_steps", "kv_live_positions",
                                         "kv_read_positions"))
           for r in rounds]
    assert got == want
    assert [r.counters["admitted"] for r in rounds] == admitted
    assert [r.counters["retired"] for r in rounds] == retired
