"""Paged KV cache regression tests.

The load-bearing invariant of the paged memory model: swapping the dense
``slots x max_len`` cache for a block arena + per-row block tables must
be invisible to the tokens.  Pinned here on all three transformer
attention lanes (dense, MLA, sliding-window — where the ring buffer
becomes block recycling), through ``Engine.generate`` (scan AND the
per-step reference loop) and through the continuous-batching scheduler,
which must reproduce each request's isolated stream token-for-token
while admissions defer on a tight arena and released rows hand their
slots on.  Plus the ``BlockPool`` allocator invariants (no leak /
double-alloc / over-capacity on random traces), row release, and the
explicit pattern/metadata leaf tagging.
"""
import dataclasses
import random

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

LANES = ["dense", "mla", "window"]


def _cfg(lane, **kw):
    if lane == "mla":
        return configs.get_config("minicpm3-4b").reduced(
            compute_dtype="float32", **kw)
    cfg = configs.get_config("phi3-medium-14b").reduced(
        compute_dtype="float32", **kw)
    if lane == "window":
        cfg = dataclasses.replace(cfg, sliding_window=8, attn_chunk_kv=8)
    return cfg


def _params(cfg, seed=0):
    return get_family(cfg).init_params(jax.random.PRNGKey(seed), cfg)


# ---------------------------------------------------------------------------
# BlockPool allocator invariants (property-style, stdlib random)
# ---------------------------------------------------------------------------

def test_block_pool_random_traces_never_leak_or_double_allocate():
    """Random submit/retire traces: every handed-out id is unique among
    live allocations, usage never exceeds the arena, frees return
    capacity exactly, and the high-water mark is faithful."""
    rng = random.Random(1234)
    for _ in range(50):
        n_blocks = rng.randint(1, 64)
        pool = kvc.BlockPool(n_blocks)
        live = {}                       # handle -> ids
        peak = 0
        for step in range(200):
            assert pool.n_free + pool.in_use == n_blocks   # conservation
            if live and (rng.random() < 0.4 or pool.n_free == 0):
                ids = live.pop(rng.choice(list(live)))
                pool.free(ids)
            else:
                n = rng.randint(0, n_blocks)
                if n > pool.n_free:
                    with pytest.raises(MemoryError):
                        pool.alloc(n)
                    continue
                ids = pool.alloc(n)
                assert len(set(ids)) == len(ids)
                flat = [i for v in live.values() for i in v]
                assert not set(ids) & set(flat)            # no double-alloc
                assert all(0 <= i < n_blocks for i in ids)
                live[step] = ids
            in_use = sum(len(v) for v in live.values())
            assert pool.in_use == in_use
            peak = max(peak, in_use)
            assert pool.peak_in_use == peak
        for ids in live.values():
            pool.free(ids)
        assert pool.n_free == n_blocks and pool.in_use == 0


def test_block_pool_rejects_double_free_and_foreign_ids():
    pool = kvc.BlockPool(4)
    ids = pool.alloc(2)
    pool.free(ids)
    with pytest.raises(ValueError, match="not allocated"):
        pool.free(ids)                  # double free
    with pytest.raises(ValueError, match="not allocated"):
        pool.free([99])                 # never existed


# ---------------------------------------------------------------------------
# arena sanitizer (BlockPool(sanitize=True) + device poisoning)
# ---------------------------------------------------------------------------

def test_sanitizer_diagnoses_double_free_use_after_free_and_cow_skip():
    """Seeded misuse, one of each class the sanitizer exists to catch:
    double free, use-after-free (write/read/share of a freed id), a
    COW-skip (write into a refcount > 1 block), and a wild id."""
    pool = kvc.BlockPool(6, sanitize=True)
    a, b, c = pool.alloc(3)

    assert pool.free([a]) == [a]        # physically reclaimed
    with pytest.raises(kvc.BlockSanitizerError, match="double free"):
        pool.free([a])
    with pytest.raises(kvc.BlockSanitizerError, match="use-after-free"):
        pool.check_write([a])
    with pytest.raises(kvc.BlockSanitizerError, match="use-after-free"):
        pool.check_read([a])
    with pytest.raises(kvc.BlockSanitizerError, match="use-after-free"):
        pool.share([a])

    pool.share([b])                     # now refcount 2: COW required
    with pytest.raises(kvc.BlockSanitizerError, match="COW violation"):
        pool.check_write([b])
    pool.check_read([b])                # reads of shared blocks are fine
    pool.release([b])
    pool.check_write([b])               # exclusive again

    with pytest.raises(kvc.BlockSanitizerError, match="wild"):
        pool.check_write([42])

    # free returns ONLY physically reclaimed ids (refcount hit zero)
    pool.share([c])
    assert pool.free([c]) == []
    assert pool.free([c]) == [c]

    # reallocation clears the freed mark: the id is healthy again
    fresh = pool.alloc(1)
    pool.check_write(fresh)
    assert pool.allocated_ids() == sorted([b] + fresh)


def test_sanitizer_off_keeps_plain_allocator_errors():
    pool = kvc.BlockPool(4)             # sanitize defaults to False
    ids = pool.alloc(1)
    assert pool.free(ids) == ids
    with pytest.raises(ValueError) as ei:
        pool.free(ids)
    assert not isinstance(ei.value, kvc.BlockSanitizerError)


def test_paged_poison_blocks_patterns_and_sentinel_drop():
    """Device half: float leaves poison to a finite absurd value (NaN
    would leak through masked-softmax zeros as 0 * NaN), unsigned posit
    pattern leaves to maxpos, untouched blocks stay intact, and the
    sentinel id drops its write."""
    from repro.models import layers as L
    arena_f = jnp.ones((2, 4, 3, 2, 2), jnp.float32)       # (L,nb,bs,H,D)
    out = L.paged_poison_blocks(arena_f, [1, 3])
    assert np.all(np.asarray(out[:, (1, 3)]) == -1e30)
    assert np.all(np.asarray(out[:, (0, 2)]) == 1.0)
    assert np.all(np.isfinite(np.asarray(out)))

    arena_u = jnp.zeros((2, 4, 3, 2, 2), jnp.uint8)
    out = L.paged_poison_blocks(arena_u, [0])
    assert np.all(np.asarray(out[:, 0]) == 0x7F)           # posit8 maxpos
    assert np.all(np.asarray(out[:, 1:]) == 0)

    out = L.paged_poison_blocks(arena_f, [4])              # sentinel: drop
    np.testing.assert_array_equal(np.asarray(out), np.asarray(arena_f))


# ---------------------------------------------------------------------------
# token identity: paged engine == linear/ring engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lane", LANES)
def test_paged_generate_token_identity(lane):
    """Ragged batch, generation long enough to cross block boundaries
    (and, on the window lane, to recycle blocks through full ring
    wraparounds): the paged engine must emit byte-identical tokens to
    the linear/ring-buffer engine, in the scan AND the per-step loop."""
    cfg = _cfg(lane)
    params = _params(cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (7, 10, 4)]

    lin = Engine(cfg, params, max_len=32, seed=0)
    pag = Engine(cfg, params, max_len=32, seed=0, paged=True, block_size=4)
    ref = lin.generate(prompts, 14).tokens
    got = pag.generate(prompts, 14).tokens
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(
        pag.generate_stepwise(prompts, 14).tokens, ref)
    # the engine records the arena's actual high-water mark
    assert 0 < pag.pool.peak_in_use <= pag.pool.n_blocks


@pytest.mark.parametrize("lane", LANES)
def test_paged_decode_in_place_matches_per_layer_slices(lane):
    """The decode step's layer scan carries the stacked arenas and
    writes/reads them at ``(layer, ...)``; it must equal, bit for bit,
    the per-layer ``xs``/``ys`` formulation (``paged_reference``) in
    logits, both arenas and ``lens``, with every dropped write still
    dropped: an inactive row, a sentinel table entry under a row's
    write position, and a row at ``max_len``."""
    from paged_reference import decode_step_xs_ys
    cfg = _cfg(lane, kv_posit="posit16")
    params = _params(cfg)
    rng = np.random.default_rng(14)
    eng = Engine(cfg, params, max_len=16, seed=0, paged=True, block_size=4)
    cache, _, _ = eng.prefill(
        [rng.integers(1, cfg.vocab, n).tolist() for n in (5, 7, 6, 9)],
        reserve_tokens=4)
    nb = cache["k_rope" if cfg.mla else "k"].shape[1]
    lens = np.asarray(cache["lens"]).copy()
    tables = np.asarray(cache["block_tables"]).copy()
    w = tables.shape[1]
    tables[2, (lens[2] // 4) % w] = nb       # row 2 writes through a sentinel
    lens[3] = 16                             # row 3 sits at max_len
    cache = dict(cache, lens=jnp.asarray(lens),
                 block_tables=jnp.asarray(tables))
    active = jnp.asarray([True, False, True, True])   # row 1 inactive

    step = jax.jit(lambda c, t: T.decode_step(params, c, t, cfg,
                                              active=active))
    ref_step = jax.jit(lambda c, t: decode_step_xs_ys(params, c, t, cfg,
                                                      active))
    got, ref = cache, cache
    for tok in rng.integers(1, cfg.vocab, (3, 4)):
        tok = jnp.asarray(tok, jnp.int32)
        logits, got = step(got, tok)
        ref_logits, ref = ref_step(ref, tok)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
    assert got.keys() == ref.keys()
    for key in got:
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(ref[key]), err_msg=key)
    # the dropped writes: rows 1 and 3 left the arena as it was where
    # their next write would have landed, and row 2's sentinel block
    # took nothing
    keys = ("c_kv", "k_rope") if cfg.mla else ("k", "v")
    for key in keys:
        before = np.asarray(cache[key])
        after = np.asarray(got[key])
        live = np.unique(tables[[0, 2]][tables[[0, 2]] < nb])
        untouched = np.setdiff1d(np.arange(nb), live)
        np.testing.assert_array_equal(after[:, untouched],
                                      before[:, untouched], err_msg=key)
    np.testing.assert_array_equal(np.asarray(got["lens"]),
                                  lens + np.asarray(active, np.int32) * 3)


def test_paged_generate_token_identity_posit_kv():
    """The paged layout must compose with the posit KV codec: patterns
    round-trip through arena blocks bit-identically."""
    cfg = _cfg("dense", kv_posit="posit8")
    params = _params(cfg)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (6, 9)]
    ref = Engine(cfg, params, max_len=32, seed=0).generate(prompts, 10)
    pag = Engine(cfg, params, max_len=32, seed=0, paged=True,
                 block_size=4).generate(prompts, 10)
    np.testing.assert_array_equal(pag.tokens, ref.tokens)


# ---------------------------------------------------------------------------
# the scheduler on a paged arena
# ---------------------------------------------------------------------------

def _run_sched(sched, prompts, gens):
    rids = [sched.submit(p, g) for p, g in zip(prompts, gens)]
    done = sched.run(max_rounds=200)
    return rids, done


def test_paged_scheduler_defers_admission_when_pool_is_tight():
    """A pool too small to hold every concurrent request must DEFER
    admissions (FIFO) instead of corrupting or failing — each stream
    still matches its isolated reference."""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(7)
    plens = [5, 9, 3, 7]
    gens = [4, 8, 4, 8]
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in plens]
    ref_eng = Engine(cfg, params, max_len=32, seed=0)
    refs = [ref_eng.generate([p], g).tokens[0]
            for p, g in zip(prompts, gens)]

    # 5 blocks of 4 slots: roughly one request's worst case at a time
    sched = Scheduler(Engine(cfg, params, max_len=32, seed=0, paged=True,
                             block_size=4, n_blocks=5),
                      n_slots=2, chunk_size=4)
    rids, done = _run_sched(sched, prompts, gens)
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].tokens, ref)
    assert sched.pool.peak_in_use <= 5


def test_stats_always_carry_the_committed_peak():
    """``peak_committed`` (allocated + reserved blocks at the high-water
    mark) is in every scheduler's stats, never above the arena, and
    without prefix sharing equals the logical peak."""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (5, 9, 3)]
    sched = Scheduler(Engine(cfg, params, max_len=32, seed=0, paged=True,
                             block_size=4, n_blocks=8),
                      n_slots=2, chunk_size=4)
    assert sched.stats["peak_committed"] == 0
    _run_sched(sched, prompts, [4, 8, 4])
    st = sched.stats
    assert 0 < st["peak_committed"] <= sched.n_blocks == 8
    assert st["peak_logical"] == st["peak_committed"]
    assert st["peak_committed"] >= sched.pool.peak_in_use


def test_slot_reuse_reads_none_of_the_previous_rows_kv():
    """One slot, two requests, sanitizer armed, and an arena of exactly
    the first request's worst case: the second is admitted into the
    slot and onto the blocks the first just left, whose reclaimed
    blocks were poisoned.  It must reproduce its isolated first-token
    logits and stream — any read of the old row's KV would show — and
    no block leaks."""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(13)
    p_a = rng.integers(1, cfg.vocab, 9).tolist()
    p_b = rng.integers(1, cfg.vocab, 5).tolist()
    ref = Engine(cfg, params, max_len=32, seed=0).generate([p_b], 6)

    sched = Scheduler(Engine(cfg, params, max_len=32, seed=0, paged=True,
                             block_size=4, n_blocks=5, sanitize=True),
                      n_slots=1, chunk_size=4)
    (ra, rb), done = _run_sched(sched, [p_a, p_b], [8, 6])
    assert done[rb].admitted_step >= done[ra].finished_step
    np.testing.assert_allclose(done[rb].first_logits,
                               ref.prefill_logits[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(done[rb].tokens, ref.tokens[0])
    assert sched.n_leaked == 0 and not sched.leak_report()
    assert sched.pool.n_sanitizer_checks > 0


def test_paged_scheduler_rejects_request_larger_than_pool():
    cfg = _cfg("dense")
    params = _params(cfg)
    sched = Scheduler(Engine(cfg, params, max_len=32, seed=0, paged=True,
                             block_size=4, n_blocks=3),
                      n_slots=1, chunk_size=4)
    with pytest.raises(ValueError, match="block"):
        sched.submit(list(range(1, 13)), 8)   # needs ceil(23/4)=6 > 3


# ---------------------------------------------------------------------------
# guarded writes / capacity
# ---------------------------------------------------------------------------

def test_paged_decode_past_capacity_raises_eagerly():
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(9)
    eng = Engine(cfg, params, max_len=8, seed=0, paged=True, block_size=4)
    prompts = [rng.integers(1, cfg.vocab, 6).tolist()]
    cache, logits, _ = eng.prefill(prompts, reserve_tokens=2)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(2):                       # positions 6, 7 fit
        logits, cache = T.decode_step(params, cache, tok, cfg)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    with pytest.raises(ValueError, match="capacity"):
        T.decode_step(params, cache, tok, cfg)   # position 8 == max_len


def test_paged_sentinel_tables_drop_writes():
    """A released row's sentinel table entries must route decode writes
    into the drop lane: the arena is bit-unchanged afterwards."""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(10)
    eng = Engine(cfg, params, max_len=16, seed=0, paged=True, block_size=4)
    cache, logits, _ = eng.prefill(
        [rng.integers(1, cfg.vocab, 5).tolist()], reserve_tokens=4)
    released = kvc.paged_release_rows(cache, jnp.asarray([True]))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    _, after = T.decode_step(params, released, tok, cfg,
                             active=jnp.asarray([False]))
    np.testing.assert_array_equal(np.asarray(after["k"]),
                                  np.asarray(released["k"]))
    assert int(after["lens"][0]) == 0        # frozen, not advanced


def test_release_rows_touches_only_the_released_rows():
    """``paged_release_rows`` zeroes ``lens`` and sentinels the tables of
    the masked rows only; the other rows and the arena content (not
    wiped: reclaimed blocks are overwritten on reuse) are untouched."""
    cfg = _cfg("dense")
    params = _params(cfg)
    rng = np.random.default_rng(14)
    eng = Engine(cfg, params, max_len=16, seed=0, paged=True, block_size=4)
    cache, _, lens = eng.prefill(
        [rng.integers(1, cfg.vocab, n).tolist() for n in (5, 7, 3)],
        reserve_tokens=4)
    out = kvc.paged_release_rows(cache, jnp.asarray([True, False, True]))
    tables, before = np.asarray(out["block_tables"]), \
        np.asarray(cache["block_tables"])
    sentinel = cache["k"].shape[1]
    assert np.asarray(out["lens"]).tolist() == [0, int(lens[1]), 0]
    assert (tables[[0, 2]] == sentinel).all()
    np.testing.assert_array_equal(tables[1], before[1])
    assert (before[[0, 2]] != sentinel).any()
    np.testing.assert_array_equal(np.asarray(out["k"]),
                                  np.asarray(cache["k"]))


# ---------------------------------------------------------------------------
# explicit pattern/metadata leaf tagging
# ---------------------------------------------------------------------------

def test_scale_cache_leaves_paged_block_tables_alone():
    cfg = _cfg("dense", kv_posit="posit16")
    params = _params(cfg)
    rng = np.random.default_rng(11)
    eng = Engine(cfg, params, max_len=16, seed=0, paged=True, block_size=4)
    cache, _, _ = eng.prefill([rng.integers(1, cfg.vocab, 6).tolist()])
    scaled = kvc.scale_cache(cache, 0.5, "posit16")
    np.testing.assert_array_equal(np.asarray(scaled["block_tables"]),
                                  np.asarray(cache["block_tables"]))
    np.testing.assert_array_equal(np.asarray(scaled["lens"]),
                                  np.asarray(cache["lens"]))
    assert not (np.asarray(scaled["k"]) == np.asarray(cache["k"])).all()


def test_unknown_unsigned_leaf_raises_instead_of_guessing():
    """The old dtype-sniffing heuristic would have 'scaled' any unsigned
    bookkeeping leaf as posit patterns; the explicit schema refuses."""
    cache = {"k": jnp.zeros((4, 8), jnp.uint16),
             "my_table": jnp.zeros((4,), jnp.uint32)}
    with pytest.raises(ValueError, match="my_table"):
        kvc.scale_cache(cache, 0.5, "posit16")
    with pytest.raises(ValueError, match="my_table"):
        kvc.dequantize_cache(cache, "posit16")
    # ...and quantize refuses to silently SKIP an unregistered float
    # leaf (the codec would otherwise quietly stop compressing it)
    with pytest.raises(ValueError, match="conv_state"):
        kvc.quantize_cache({"k": jnp.zeros((4, 8), jnp.float32),
                            "conv_state": jnp.zeros((4,), jnp.float32)},
                           "posit16")


# ---------------------------------------------------------------------------
# full ragged-trace comparison (slow, main lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("lane", LANES)
def test_paged_trace_identity_all_lanes(lane):
    """A full Poisson trace through the scheduler: every completion
    matches its request's isolated generation, on every lane, and the
    arena drains."""
    from repro.launch.serve import drive_trace, poisson_trace
    cfg = _cfg(lane)
    params = _params(cfg)
    trace = poisson_trace(np.random.default_rng(21), 10, 0.8,
                          cfg.vocab, 10, 8)
    max_len = 10 + 8 - 1 + 4

    pag = Scheduler(Engine(cfg, params, max_len=max_len, seed=0,
                           paged=True, block_size=4),
                    n_slots=2, chunk_size=4)
    done, order = drive_trace(pag, trace)

    ref_eng = Engine(cfg, params, max_len=max_len, seed=0)
    assert len(done) == len(trace)
    for rid, i in order.items():
        _, prompt, gen = trace[i]
        np.testing.assert_array_equal(
            done[rid].tokens, ref_eng.generate([prompt], gen).tokens[0])
    assert pag.pool.in_use == 0
