"""Continuous-batching scheduler regression tests.

The load-bearing invariant: a request scheduled into a slot pool —
admitted mid-stream, its prompt fed through the decode lane in
fixed-size chunks, retired early, its slot and blocks handed to the
next request — must emit the BYTE-IDENTICAL token stream it would emit
alone through ``Engine.generate``.  Pinned on all three transformer
attention lanes (dense, MLA, sliding-window ring buffer), together with
the one-dispatch-per-round property that keeps admissions
recompile-free: the engine's compile count stays FLAT across
arbitrarily ragged prompt lengths.  The policy layer: EDF admission
must honor deadlines, and preemption-by-block-release must restart a
request token-identically without leaking a single block under the
sanitizer.  The scheduler has one mode and refuses an engine without a
paged arena.
"""
import dataclasses

import numpy as np
import pytest

import jax

from repro import configs
from repro.models import get_family
from repro.models import transformer as T
from repro.runtime.engine import Engine
from repro.runtime.scheduler import Scheduler


def _cfg(lane):
    if lane == "mla":
        return configs.get_config("minicpm3-4b").reduced(
            compute_dtype="float32")
    cfg = configs.get_config("phi3-medium-14b").reduced(
        compute_dtype="float32")
    if lane == "window":
        cfg = dataclasses.replace(cfg, sliding_window=8, attn_chunk_kv=8)
    return cfg


def _params(cfg, seed=0):
    return get_family(cfg).init_params(jax.random.PRNGKey(seed), cfg)


def _paged(cfg, params, max_len, **kw):
    return Engine(cfg, params, max_len=max_len, seed=0, paged=True,
                  block_size=4, **kw)


# ---------------------------------------------------------------------------
# token identity: continuous batch == isolated generation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lane", ["dense", "mla", "window"])
def test_token_identity_with_midstream_admissions(lane):
    """Six requests through a two-slot pool (so admissions/retirements
    interleave with live decodes, and retired slots are recycled) must
    reproduce each request's isolated greedy stream byte for byte."""
    cfg = _cfg(lane)
    params = _params(cfg)
    rng = np.random.default_rng(3)
    plens = [5, 9, 3, 7, 4, 6]
    gens = [4, 8, 4, 8, 4, 8]
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in plens]

    ref_eng = Engine(cfg, params, max_len=32, seed=0)     # greedy: key unused
    refs = [ref_eng.generate([p], g).tokens[0]
            for p, g in zip(prompts, gens)]

    sched = Scheduler(_paged(cfg, params, 32), n_slots=2, chunk_size=4)
    rids = [sched.submit(p, g) for p, g in zip(prompts, gens)]
    done = sched.run(max_rounds=100)

    assert sched.n_admitted == 6 and sched.n_retired == 6
    for rid, ref, g in zip(rids, refs, gens):
        got = done[rid].tokens
        assert got.shape == (g,)
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("lane", ["dense", "mla", "window"])
def test_token_identity_when_a_tight_pool_refills_slots(lane):
    """An arena of about two requests' worst case under three slots:
    admissions defer until retirements return blocks, so rows retire
    and their slots and blocks refill mid-trace — and every stream
    still matches its isolated generation, with no block leaked."""
    cfg = _cfg(lane)
    params = _params(cfg)
    rng = np.random.default_rng(4)
    plens = [5, 9, 3, 7, 4, 6]
    gens = [6, 12, 4, 9, 5, 7]
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in plens]
    ref_eng = Engine(cfg, params, max_len=24, seed=0)
    refs = [ref_eng.generate([p], g).tokens[0]
            for p, g in zip(prompts, gens)]

    n_blocks = 2 * T.paged_table_width(cfg, 4, 24)
    eng = _paged(cfg, params, 24, n_blocks=n_blocks, sanitize=True)
    sched = Scheduler(eng, n_slots=3, chunk_size=4)
    deferred = []
    admit = sched._admit

    def watched():
        admit()
        deferred.append(bool(sched._queue) and None in sched._slots)

    sched._admit = watched
    rids = [sched.submit(p, g) for p, g in zip(prompts, gens)]
    done = sched.run(max_rounds=200)
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].tokens, ref)
    # the pool, not the slots, held requests back, and freed slots refilled
    assert any(deferred)
    first_out = min(c.finished_step for c in done.values())
    assert max(c.admitted_step for c in done.values()) >= first_out > 0
    assert sched.pool.peak_in_use <= n_blocks
    assert sched.pool.in_use == 0 and sched.n_leaked == 0


def test_eos_stops_early_and_frees_the_slot():
    """Submitting with eos_id = a token of the request's own greedy
    stream must truncate the stream at that token's FIRST occurrence and
    retire the slot for the next request.  The EOS is a token that first
    appears at index >= 1, so the stream really stops early (a random-
    weight stream may repeat one token from the start; such a prompt has
    no usable EOS and the next one is drawn)."""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(5)
    ref_eng = Engine(cfg, params, max_len=32, seed=0)
    for _ in range(8):
        prompt = rng.integers(1, cfg.vocab, 6).tolist()
        ref = ref_eng.generate([prompt], 8)
        stream = [int(t) for t in ref.tokens[0]]
        cut = next((i for i in range(1, len(stream))
                    if stream[i] not in stream[:i]), None)
        if cut is not None:
            break
    assert cut is not None, "no prompt with a usable EOS token"
    eos = stream[cut]

    sched = Scheduler(_paged(cfg, params, 32), n_slots=1, chunk_size=4)
    rid = sched.submit(prompt, 8, eos_id=eos)
    rid2 = sched.submit(prompt, 8)            # queued behind the 1-slot pool
    done = sched.run(max_rounds=50)
    np.testing.assert_array_equal(done[rid].tokens, ref.tokens[0][:cut + 1])
    np.testing.assert_array_equal(done[rid2].tokens, ref.tokens[0])
    assert done[rid2].admitted_step >= done[rid].finished_step


# ---------------------------------------------------------------------------
# one compiled dispatch per round
# ---------------------------------------------------------------------------

def test_each_chunk_is_one_compiled_dispatch():
    """Admissions and retirements between rounds must never change the
    compiled computation: the whole run reuses ONE ``mixed_step``
    callable, called exactly once per scheduling round that dispatches,
    and it compiles once."""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in [4, 6, 5]]

    eng = _paged(cfg, params, 32)
    calls = {"n": 0}
    real = eng._mixed_fn(4)

    def counted(*a):
        calls["n"] += 1
        return real(*a)

    eng._decode_jit[("mixed", 4, 4)] = counted
    sched = Scheduler(eng, n_slots=2, chunk_size=4)
    for p in prompts:
        sched.submit(p, 6)
    sched.run(max_rounds=50)
    assert calls["n"] == sched.n_chunks > 0
    assert list(eng._decode_jit) == [("mixed", 4, 4)] and \
        eng._decode_jit[("mixed", 4, 4)] is counted, \
        "scheduler must reuse the cached mixed_step callable"
    assert real._cache_size() == 1


# ---------------------------------------------------------------------------
# chunked prefill: one compiled shape serves every request
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lane", ["dense", "mla", "window"])
def test_chunked_prefill_token_identity(lane):
    """Prompts fed through the decode lane in fixed-size chunks emit
    exactly the streams whole-prompt prefill emits, per lane, with the
    sanitizer armed and zero leaks."""
    cfg = _cfg(lane)
    params = _params(cfg)
    rng = np.random.default_rng(12)
    plens = [5, 9, 3, 7, 4, 6]
    gens = [4, 8, 4, 8, 4, 8]
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in plens]
    ref_eng = Engine(cfg, params, max_len=32, paged=True, block_size=4)
    refs = [ref_eng.generate([p], g).tokens[0]
            for p, g in zip(prompts, gens)]

    eng = Engine(cfg, params, max_len=32, paged=True, block_size=4,
                 n_blocks=64, sanitize=True)
    sched = Scheduler(eng, n_slots=2, chunk_size=4)
    rids = [sched.submit(p, g) for p, g in zip(prompts, gens)]
    done = sched.run(max_rounds=200)
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].tokens, ref)
    assert sched.n_leaked == 0 and not sched.leak_report()


def test_compile_count_flat_across_ragged_admissions():
    """Eight distinct prompt lengths through the scheduler add
    ZERO lowered programs after warmup — the mixed dispatch shape
    depends only on (n_slots, chunk_size), never on a prompt length."""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(13)
    eng = Engine(cfg, params, max_len=48, paged=True, block_size=4,
                 n_blocks=96)
    sched = Scheduler(eng, n_slots=2, chunk_size=4)
    for n in (3, 11):
        sched.submit(rng.integers(1, cfg.vocab, n).tolist(), 4)
    sched.run(max_rounds=200)
    warm_compiles = eng.n_compiles
    assert warm_compiles >= 1
    for n in (2, 5, 7, 9, 13, 17, 21, 26):      # 8 fresh distinct lengths
        sched.submit(rng.integers(1, cfg.vocab, n).tolist(), 6)
    sched.run(max_rounds=400)
    assert eng.n_compiles == warm_compiles
    assert sched.stats["n_compiles"] == warm_compiles


# ---------------------------------------------------------------------------
# policy layer: deadlines, EDF admission, preemption
# ---------------------------------------------------------------------------

def test_edf_admission_order():
    """A 1-slot pool admits by earliest deadline, not arrival order;
    best-effort (deadline-less) requests go last."""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(14)
    prompts = [rng.integers(1, cfg.vocab, 5).tolist() for _ in range(3)]
    eng = Engine(cfg, params, max_len=32, paged=True, block_size=4,
                 n_blocks=32)
    sched = Scheduler(eng, n_slots=1, chunk_size=4)
    r_be = sched.submit(prompts[0], 4)               # best-effort, first in
    r_late = sched.submit(prompts[1], 4, deadline=100)
    r_soon = sched.submit(prompts[2], 4, deadline=50)
    done = sched.run(max_rounds=200)
    assert done[r_soon].admitted_step < done[r_late].admitted_step \
        < done[r_be].admitted_step


def test_preemption_restores_token_identity_and_leaks_nothing():
    """Overload: a deadline request that cannot fit preempts the
    best-effort row (block release is refcount-safe, sanitizer armed
    and poisoning the reclaims); the preempted request restarts from
    scratch and still emits its isolated greedy stream, and no block
    leaks."""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(15)
    p_a = rng.integers(1, cfg.vocab, 8).tolist()
    p_b = rng.integers(1, cfg.vocab, 8).tolist()
    ref_eng = Engine(cfg, params, max_len=32, paged=True, block_size=4)
    ref_a = ref_eng.generate([p_a], 8).tokens[0]
    ref_b = ref_eng.generate([p_b], 8).tokens[0]

    # 6-block pool: one request's worst case is 5 blocks, so two can
    # never be resident together — the deadline MUST preempt
    eng = Engine(cfg, params, max_len=32, paged=True, block_size=4,
                 n_blocks=6, sanitize=True)
    sched = Scheduler(eng, n_slots=2, chunk_size=4)
    ra = sched.submit(p_a, 8)                    # best-effort
    sched.step()                                 # admitted, prefilling
    rb = sched.submit(p_b, 8, deadline=20)       # urgent, pool is full
    done = sched.run(max_rounds=300)
    assert sched.n_preempted >= 1
    assert done[rb].admitted_step < done[ra].admitted_step  # b cut in
    np.testing.assert_array_equal(done[ra].tokens, ref_a)
    np.testing.assert_array_equal(done[rb].tokens, ref_b)
    assert sched.n_leaked == 0 and not sched.leak_report()


def test_best_effort_never_preempts_best_effort():
    """Without deadlines the same overload just queues: no preemption
    (so no livelock risk), strict FIFO, streams untouched."""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(16)
    p_a = rng.integers(1, cfg.vocab, 8).tolist()
    p_b = rng.integers(1, cfg.vocab, 8).tolist()
    eng = Engine(cfg, params, max_len=32, paged=True, block_size=4,
                 n_blocks=6, sanitize=True)
    sched = Scheduler(eng, n_slots=2, chunk_size=4)
    ra = sched.submit(p_a, 8)
    sched.step()
    rb = sched.submit(p_b, 8)                    # also best-effort
    done = sched.run(max_rounds=300)
    assert sched.n_preempted == 0
    assert done[rb].admitted_step >= done[ra].finished_step
    assert sched.n_leaked == 0 and not sched.leak_report()


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------

def test_scheduler_rejects_unservable_request():
    cfg = _cfg("dense")
    params = _params(cfg)
    sched = Scheduler(_paged(cfg, params, 16), n_slots=1, chunk_size=4)
    sched.submit([1, 2, 3], 10)                 # 3 + 10 - 1 + 4 = 16 fits
    with pytest.raises(ValueError, match="max_len"):
        sched.submit([1, 2, 3], 11)             # 17 > 16
    with pytest.raises(ValueError, match="empty"):
        sched.submit([], 4)


def test_scheduler_rejects_non_transformer_family():
    cfg = configs.get_config("rwkv6-7b").reduced(compute_dtype="float32")
    params = _params(cfg)
    with pytest.raises(ValueError, match="transformer"):
        Scheduler(Engine(cfg, params, max_len=16), n_slots=2)


def test_scheduler_refuses_an_engine_without_a_paged_arena():
    cfg = _cfg("dense")
    params = _params(cfg)
    with pytest.raises(ValueError, match=r"Engine\(paged=True\)"):
        Scheduler(Engine(cfg, params, max_len=16), n_slots=2)


def test_scheduler_has_one_mode():
    cfg = _cfg("dense")
    params = _params(cfg)
    with pytest.raises(ValueError, match="one mode"):
        Scheduler(_paged(cfg, params, 16), n_slots=2,
                  chunked_prefill=False)
