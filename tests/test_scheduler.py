"""Continuous-batching scheduler regression tests.

The load-bearing invariant: a request scheduled into a slot pool —
admitted mid-stream at an arbitrary shared frontier, compacted around,
and retired early — must emit the BYTE-IDENTICAL token stream it would
emit alone through ``Engine.generate``.  Pinned on all three transformer
attention lanes (dense, MLA, sliding-window ring buffer), plus the cache
surgery ops (``reset_slots`` / ``compact`` / ``adopt_row``) and the
one-dispatch-per-chunk property that keeps admissions recompile-free.

PR 8 adds the chunked-prefill lane and the policy layer: prompts fed
through the decode lane in fixed-size chunks must stay byte-identical
with a FLAT engine compile count across arbitrarily ragged prompt
lengths, EDF admission must honor deadlines, and preemption-by-block-
release must restart a request token-identically without leaking a
single block under the sanitizer.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.compress import kvcache as kvc
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

    sched = Scheduler(Engine(cfg, params, max_len=32, seed=0),
                      n_slots=2, chunk_size=4)
    rids = [sched.submit(p, g) for p, g in zip(prompts, gens)]
    done = sched.run(max_rounds=100)

    assert sched.n_admitted == 6 and sched.n_retired == 6
    for rid, ref, g in zip(rids, refs, gens):
        got = done[rid].tokens
        assert got.shape == (g,)
        np.testing.assert_array_equal(got, ref)


def test_token_identity_under_forced_compaction():
    """A max_len tight enough that the shared frontier must be pulled
    back between chunks (retired long rows unpin it) — identity must
    survive the cache rolls."""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(4)
    plens = [5, 9, 3, 7, 4, 6]
    gens = [6, 12, 4, 9, 5, 7]
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in plens]
    ref_eng = Engine(cfg, params, max_len=24, seed=0)
    refs = [ref_eng.generate([p], g).tokens[0]
            for p, g in zip(prompts, gens)]

    sched = Scheduler(Engine(cfg, params, max_len=24, seed=0),
                      n_slots=3, chunk_size=4)
    rids = [sched.submit(p, g) for p, g in zip(prompts, gens)]
    done = sched.run(max_rounds=100)
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].tokens, ref)


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

    sched = Scheduler(Engine(cfg, params, max_len=32, seed=0),
                      n_slots=1, chunk_size=4)
    rid = sched.submit(prompt, 8, eos_id=eos)
    rid2 = sched.submit(prompt, 8)            # queued behind the 1-slot pool
    done = sched.run(max_rounds=50)
    np.testing.assert_array_equal(done[rid].tokens, ref.tokens[0][:cut + 1])
    np.testing.assert_array_equal(done[rid2].tokens, ref.tokens[0])
    assert done[rid2].admitted_step >= done[rid].finished_step


# ---------------------------------------------------------------------------
# one compiled dispatch per decode chunk
# ---------------------------------------------------------------------------

def test_each_chunk_is_one_compiled_dispatch():
    """Admissions and retirements between chunks must never change the
    compiled computation: the whole run reuses ONE chunk callable, called
    exactly once per scheduling round that decodes."""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in [4, 6, 5]]

    eng = Engine(cfg, params, max_len=32, seed=0)
    calls = {"n": 0}
    real = eng._chunk_fn(4)

    def counted(*a):
        calls["n"] += 1
        return real(*a)

    eng._decode_jit[("chunk", 4)] = counted
    sched = Scheduler(eng, n_slots=2, chunk_size=4)
    for p in prompts:
        sched.submit(p, 6)
    sched.run(max_rounds=50)
    assert calls["n"] == sched.n_chunks > 0
    assert ("chunk", 4) in eng._decode_jit and \
        eng._decode_jit[("chunk", 4)] is counted, \
        "scheduler must reuse the cached chunk callable across admissions"


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
    sched = Scheduler(eng, n_slots=2, chunk_size=4, chunked_prefill=True)
    rids = [sched.submit(p, g) for p, g in zip(prompts, gens)]
    done = sched.run(max_rounds=200)
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].tokens, ref)
    assert sched.n_leaked == 0 and not sched.leak_report()


def test_compile_count_flat_across_ragged_admissions():
    """Eight distinct prompt lengths through the chunked scheduler add
    ZERO lowered programs after warmup — the mixed dispatch shape
    depends only on (n_slots, chunk_size), never on a prompt length.
    (The unchunked admission path compiles one prefill per length;
    that specialization family no longer exists in chunked mode.)"""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(13)
    eng = Engine(cfg, params, max_len=48, paged=True, block_size=4,
                 n_blocks=96)
    sched = Scheduler(eng, n_slots=2, chunk_size=4, chunked_prefill=True)
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
    sched = Scheduler(eng, n_slots=1, chunk_size=4, chunked_prefill=True)
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
    sched = Scheduler(eng, n_slots=2, chunk_size=4, chunked_prefill=True)
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
    sched = Scheduler(eng, n_slots=2, chunk_size=4, chunked_prefill=True)
    ra = sched.submit(p_a, 8)
    sched.step()
    rb = sched.submit(p_b, 8)                    # also best-effort
    done = sched.run(max_rounds=300)
    assert sched.n_preempted == 0
    assert done[rb].admitted_step >= done[ra].finished_step
    assert sched.n_leaked == 0 and not sched.leak_report()


# ---------------------------------------------------------------------------
# cache surgery ops
# ---------------------------------------------------------------------------

def _prefill_cache(cfg, params, rng, b, s, ml):
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, (b, s)), jnp.int32)
    cache, logits = T.prefill(params, tokens, cfg, max_len=ml)
    return cache, logits


@pytest.mark.parametrize("lane", ["dense", "window"])
def test_compact_preserves_decode_logits(lane):
    """Rolling the frontier back and forth must not change what decode
    sees: logits after compaction == logits without it (both layouts)."""
    cfg = _cfg(lane)
    params = _params(cfg, seed=1)
    rng = np.random.default_rng(7)
    cache, logits = _prefill_cache(cfg, params, rng, 2, 10, 24)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)

    ref_logits, _ = T.decode_step(params, cache, tok, cfg)

    grown = kvc.compact(cache, target_len=17)     # push frontier up
    assert int(grown["len"]) == 17
    back = kvc.compact(grown)                     # default: max(lens) = 10
    assert int(back["len"]) == 10
    got_logits, _ = T.decode_step(params, back, tok, cfg)
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(ref_logits), rtol=1e-5,
                               atol=1e-5)


def test_compact_rejects_target_beyond_max_len():
    cfg = _cfg("dense")
    params = _params(cfg, seed=1)
    cache, _ = _prefill_cache(cfg, params, np.random.default_rng(8),
                              1, 6, 16)
    with pytest.raises(ValueError, match="max_len"):
        kvc.compact(cache, target_len=17)


def test_reset_slots_zeroes_rows_and_lens():
    cfg = _cfg("dense")
    params = _params(cfg, seed=1)
    cache, _ = _prefill_cache(cfg, params, np.random.default_rng(9),
                              3, 8, 16)
    out = kvc.reset_slots(cache, jnp.asarray([True, False, True]))
    assert np.asarray(out["lens"]).tolist() == [0, 8, 0]
    assert int(np.abs(np.asarray(out["k"][:, 0])).sum()) == 0
    assert int(np.abs(np.asarray(out["k"][:, 2])).sum()) == 0
    # the surviving row and the shared metadata are untouched
    np.testing.assert_array_equal(np.asarray(out["k"][:, 1]),
                                  np.asarray(cache["k"][:, 1]))
    assert int(out["len"]) == int(cache["len"])
    assert int(out["max_len"]) == int(cache["max_len"])


def test_adopt_row_requires_frontier_headroom():
    cfg = _cfg("dense")
    params = _params(cfg, seed=1)
    rng = np.random.default_rng(10)
    pool, _ = _prefill_cache(cfg, params, rng, 2, 4, 16)
    row, _ = _prefill_cache(cfg, params, rng, 1, 7, 16)
    with pytest.raises(ValueError, match="frontier"):
        kvc.adopt_row(pool, row, 0)             # 7 > pool frontier 4
    pool = kvc.compact(pool, target_len=7)
    out = kvc.adopt_row(pool, row, 0)
    assert np.asarray(out["lens"]).tolist() == [7, 4]
    assert int(out["len"]) == 7


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------

def test_scheduler_rejects_unservable_request():
    cfg = _cfg("dense")
    params = _params(cfg)
    sched = Scheduler(Engine(cfg, params, max_len=16, seed=0),
                      n_slots=1, chunk_size=4)
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
