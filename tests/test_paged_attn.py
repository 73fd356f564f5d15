"""Fused paged-decode attention kernel tests.

The tentpole invariant: ``kernels/posit_paged_attn.py`` — one Pallas
kernel walking each row's block table with a sequential grid dimension,
posit decode in-kernel, online-softmax state carried in VMEM scratch —
must be invisible to the numbers.  Pinned three ways:

* layer level, fused vs the gather+``decode_attention`` reference on
  the dense/GQA, sliding-window block-ring (including wraparound) and
  MLA latent lanes, across posit8/posit16/f32 KV and ragged ``lens``
  (fast seeded subset here, ``slow``-marked exhaustive sweep below);
* engine level, token identity fused vs gather vs the LINEAR ring on
  all three lanes through ``Engine.generate``, and through the
  scheduler's preemption-restart path;
* the all-masked-row regression: a row with no valid slot (an inactive
  or preempted scheduler slot whose sentinel table entries alias real
  blocks through the gather clamp) must yield EXACT ZEROS — the old
  ``exp(_NEG - _NEG) == 1`` path returned a uniform average of garbage
  — on the linear path, the paged gather path and the fused kernel.

The bounded gather path (a decode step reads only the width steps of
``layers.PAGED_READ_STEP`` positions that its decoding rows reach) must
match the whole-table form for every decoding row, on the dense and MLA
lanes, and the model's step must take the narrowest covering branch.

Everything runs the kernel in Pallas interpret mode (CPU container);
the CI fast lane executes this file explicitly.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.kernels import ops as kops
from repro.kernels.posit_paged_attn import paged_decode_kv_bytes
from repro.models import get_family
from repro.models import layers as L
from repro.models import transformer as T
from repro.runtime.engine import Engine
from repro.runtime.scheduler import Scheduler

LANES = ["dense", "mla", "window"]
KV_FORMATS = [None, "posit16", "posit8"]


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


def _arena(rng, shape, kv):
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    return kops.quantize(x, L.pcfg(kv)) if kv else x


# ---------------------------------------------------------------------------
# layer-level identity: fused kernel vs gather + decode_attention
# ---------------------------------------------------------------------------

def _dense_case(rng, kv, window, lens):
    """A small dense/window paged-decode problem with one sentinel tail."""
    cfg = dataclasses.replace(_cfg("window" if window else "dense"),
                              kv_posit=kv)
    g, h, d, bs = cfg.n_kv_heads, cfg.n_heads, cfg.head_dim, 4
    lens = jnp.asarray(lens, jnp.int32)
    b = lens.shape[0]
    w = L.paged_window_blocks(window, bs) if window else 5
    nb = b * w
    tables = jnp.arange(nb, dtype=jnp.int32).reshape(b, w)
    tables = tables.at[-1, -1].set(nb)          # unallocated tail: sentinel
    k_arena = _arena(rng, (nb, bs, g, d), kv)
    v_arena = _arena(rng, (nb, bs, g, d), kv)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    return cfg, q, k_arena, v_arena, tables, lens


def _dense_both(case, window):
    cfg, q, k_arena, v_arena, tables, lens = case
    return [L.decode_attention_paged(
        q, k_arena, v_arena, tables, lens, cfg=cfg, kv_posit=cfg.kv_posit,
        window=window, kernel=kern) for kern in ("fused", "gather")]


@pytest.mark.parametrize("kv", [None, "posit16"])
def test_fused_matches_gather_dense(kv):
    rng = np.random.default_rng(5)
    fused, ref = _dense_both(
        _dense_case(rng, kv, 0, [9, 2, 17]), 0)
    np.testing.assert_allclose(fused, ref, atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("kv", [None, "posit8"])
def test_fused_matches_gather_ring_wraparound(kv):
    """Window lane with frontiers past the ring capacity: the stale half
    of the frontier's own block must be masked identically in-kernel."""
    window = 8
    rng = np.random.default_rng(6)
    # 13 and 22 both wrap the W=3-block ring (capacity 12 slots); 2 does
    # not — the same kernel grid must honor both regimes per-row
    fused, ref = _dense_both(
        _dense_case(rng, kv, window, [13, 2, 22]), window)
    np.testing.assert_allclose(fused, ref, atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("kv", [None, "posit16"])
def test_fused_matches_gather_mla(kv):
    rng = np.random.default_rng(7)
    cfg = dataclasses.replace(_cfg("mla"), kv_posit=kv)
    h, rank, rope, bs, w = (cfg.n_heads, cfg.kv_lora_rank,
                            cfg.qk_rope_dim, 4, 5)
    lens = jnp.array([9, 14, 0], jnp.int32)
    b = lens.shape[0]
    nb = b * w
    tables = jnp.arange(nb, dtype=jnp.int32).reshape(b, w)
    tables = tables.at[0, -1].set(nb)
    c_arena = _arena(rng, (nb, bs, rank), kv)
    r_arena = _arena(rng, (nb, bs, rope), kv)
    qe = jnp.asarray(rng.normal(size=(b, h, rank)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(b, h, rope)), jnp.float32)
    outs = [L.decode_attention_paged_mla(
        qe, qr, c_arena, r_arena, tables, lens, cfg=cfg,
        kv_posit=kv, kernel=kern) for kern in ("fused", "gather")]
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-6, rtol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("kv", KV_FORMATS)
@pytest.mark.parametrize("window", [0, 8])
def test_fused_matches_gather_exhaustive(kv, window):
    """Exhaustive ragged sweep: every frontier from empty to deep ring
    wraparound, all KV formats, both dense and window lanes."""
    rng = np.random.default_rng(8)
    for lo in range(0, 24, 3):
        lens = [lo, lo + 1, lo + 7]
        fused, ref = _dense_both(
            _dense_case(rng, kv, window, lens), window)
        np.testing.assert_allclose(fused, ref, atol=2e-6, rtol=1e-5,
                                   err_msg=f"kv={kv} lens={lens}")


# ---------------------------------------------------------------------------
# all-masked-row regression (the bug this kernel builds on)
# ---------------------------------------------------------------------------

def test_all_masked_row_returns_zeros_linear():
    """A row whose every cache slot is masked (cache_len 0) used to get
    ``exp(_NEG - _NEG) == 1`` everywhere — a uniform average of garbage
    cache content.  It must be exact zeros, and rows WITH valid slots
    must be bit-identical to before the guard."""
    cfg = _cfg("dense")
    rng = np.random.default_rng(9)
    b, t, g, h, d = 2, 8, cfg.n_kv_heads, cfg.n_heads, cfg.head_dim
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    # garbage-heavy cache: any leak through the softmax is loud
    k = jnp.asarray(rng.normal(size=(b, t, g, d)) * 1e3, jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, g, d)) * 1e3, jnp.float32)
    out = L.decode_attention(q, k, v, jnp.array([5, 0], jnp.int32),
                             cfg=cfg)
    assert float(jnp.abs(out[1]).max()) == 0.0
    assert float(jnp.abs(out[0]).max()) > 0.0
    solo = L.decode_attention(q[:1], k[:1], v[:1],
                              jnp.array([5], jnp.int32), cfg=cfg)
    np.testing.assert_array_equal(out[0], solo[0])


@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_all_masked_row_returns_zeros_paged(kernel):
    """An all-sentinel block table (a preempted slot after its blocks
    were released) aliases arbitrary real blocks through the gather
    clamp / the kernel's DMA clamp; both paths must return zeros."""
    cfg = _cfg("dense")
    rng = np.random.default_rng(10)
    g, h, d, bs, w, nb = cfg.n_kv_heads, cfg.n_heads, cfg.head_dim, 4, 3, 6
    k_arena = _arena(rng, (nb, bs, g, d), None) * 1e3
    v_arena = _arena(rng, (nb, bs, g, d), None) * 1e3
    q = jnp.asarray(rng.normal(size=(2, 1, h, d)), jnp.float32)
    tables = jnp.stack([jnp.arange(w, dtype=jnp.int32),
                        jnp.full((w,), nb, jnp.int32)])   # row 1: sentinel
    out = L.decode_attention_paged(
        q, k_arena, v_arena, tables, jnp.array([5, 5], jnp.int32),
        cfg=cfg, kernel=kernel)
    assert float(jnp.abs(out[1]).max()) == 0.0
    assert float(jnp.abs(out[0]).max()) > 0.0


@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_all_masked_row_returns_zeros_mla(kernel):
    cfg = _cfg("mla")
    rng = np.random.default_rng(11)
    h, rank, rope, bs, w, nb = (cfg.n_heads, cfg.kv_lora_rank,
                                cfg.qk_rope_dim, 4, 3, 6)
    c_arena = _arena(rng, (nb, bs, rank), None) * 1e3
    r_arena = _arena(rng, (nb, bs, rope), None) * 1e3
    qe = jnp.asarray(rng.normal(size=(2, h, rank)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(2, h, rope)), jnp.float32)
    tables = jnp.stack([jnp.arange(w, dtype=jnp.int32),
                        jnp.full((w,), nb, jnp.int32)])
    out = L.decode_attention_paged_mla(
        qe, qr, c_arena, r_arena, tables, jnp.array([5, 5], jnp.int32),
        cfg=cfg, kernel=kernel)
    assert float(jnp.abs(out[1]).max()) == 0.0
    assert float(jnp.abs(out[0]).max()) > 0.0


# ---------------------------------------------------------------------------
# engine-level token identity: fused vs gather vs the linear ring
# ---------------------------------------------------------------------------

def _gen_tokens(cfg, params, prompts, gen, **eng_kw):
    eng = Engine(cfg, params, max_len=32, seed=0, **eng_kw)
    return eng.generate(prompts, gen).tokens


@pytest.mark.parametrize("lane", LANES)
def test_engine_fused_token_identity(lane):
    """Fused paged decode == gather paged decode == the LINEAR cache
    (ring buffer on the window lane), token for token, on ragged
    prompts with generation long enough to wrap the block ring."""
    cfg = _cfg(lane, kv_posit="posit16")
    params = _params(cfg)
    rng = np.random.default_rng(12)
    prompts = [list(rng.integers(1, cfg.vocab, size=n)) for n in (5, 9, 3)]
    gen = 20              # window=8, block=4: wraps the W=3 ring twice
    linear = _gen_tokens(cfg, params, prompts, gen)
    gather = _gen_tokens(cfg, params, prompts, gen, paged=True,
                         block_size=4, decode_kernel="gather")
    fused = _gen_tokens(cfg, params, prompts, gen, paged=True,
                        block_size=4, decode_kernel="fused")
    np.testing.assert_array_equal(gather, linear)
    np.testing.assert_array_equal(fused, gather)


@pytest.mark.slow
@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("kv", KV_FORMATS)
def test_engine_fused_token_identity_exhaustive(lane, kv):
    cfg = _cfg(lane, kv_posit=kv)
    params = _params(cfg)
    rng = np.random.default_rng(13)
    prompts = [list(rng.integers(1, cfg.vocab, size=n))
               for n in (7, 2, 11, 4)]
    gather = _gen_tokens(cfg, params, prompts, 20, paged=True,
                         block_size=4, decode_kernel="gather")
    fused = _gen_tokens(cfg, params, prompts, 20, paged=True,
                        block_size=4, decode_kernel="fused")
    np.testing.assert_array_equal(fused, gather)


def test_fused_survives_preemption_restart():
    """Preemption-by-block-release then restart, decoding through the
    fused kernel: the preempted request's stream must match isolated
    greedy generation and no arena block may leak (the released rows'
    all-sentinel tables hit the kernel's masked path every step)."""
    cfg = _cfg("dense", kv_posit="posit16")
    params = _params(cfg)
    rng = np.random.default_rng(14)
    p_a = rng.integers(1, cfg.vocab, 8).tolist()
    p_b = rng.integers(1, cfg.vocab, 8).tolist()
    ref_eng = Engine(cfg, params, max_len=32, paged=True, block_size=4,
                     decode_kernel="fused")
    ref_a = ref_eng.generate([p_a], 8).tokens[0]
    ref_b = ref_eng.generate([p_b], 8).tokens[0]

    # 6-block pool: two requests can never be resident together, so the
    # deadline submission MUST preempt the best-effort one
    eng = Engine(cfg, params, max_len=32, paged=True, block_size=4,
                 n_blocks=6, sanitize=True, decode_kernel="fused")
    sched = Scheduler(eng, n_slots=2, chunk_size=4)
    ra = sched.submit(p_a, 8)
    sched.step()
    rb = sched.submit(p_b, 8, deadline=20)
    done = sched.run(max_rounds=300)
    assert sched.n_preempted >= 1
    np.testing.assert_array_equal(done[ra].tokens, ref_a)
    np.testing.assert_array_equal(done[rb].tokens, ref_b)
    assert sched.n_leaked == 0 and not sched.leak_report()


# ---------------------------------------------------------------------------
# decode-bytes ledger
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("kv", KV_FORMATS)
def test_fused_moves_strictly_fewer_bytes(lane, kv):
    """The point of the kernel: one pattern-width pass over KV instead
    of gather + dequant round-trips, for every lane and KV format."""
    cfg = _cfg(lane, kv_posit=kv)
    fused = paged_decode_kv_bytes(cfg, table_width=8, block_size=4,
                                  kernel="fused")
    gather = paged_decode_kv_bytes(cfg, table_width=8, block_size=4,
                                   kernel="gather")
    assert 0 < fused < gather
    if kv == "posit8":        # posit8 patterns: half an f16 cache's bytes
        f16_read = paged_decode_kv_bytes(
            dataclasses.replace(cfg, kv_posit=None), table_width=8,
            block_size=4, kernel="fused") // 2
        assert fused * 2 == f16_read


# ---------------------------------------------------------------------------
# bounded decode gather: a step reads the width steps its decoding rows reach
# ---------------------------------------------------------------------------

WIDE_LEN, WIDE_BS = 2048, 16          # 128 table columns: 4 width steps
STEP_COLS = L.PAGED_READ_STEP // WIDE_BS
# rows 0-2 decode with lens + 1 at 511, 512 and 513; row 3 is dead (all
# sentinel); row 4 does not decode and is longer than every decoding row
WIDE_LENS = [510, 511, 512, 0, 1500]
DECODING = [0, 1, 2]


def _wide_tables():
    used = [0 if i == 3 else -(-(n + 1) // WIDE_BS)
            for i, n in enumerate(WIDE_LENS)]
    nb = sum(used)
    tables = np.full((len(used), WIDE_LEN // WIDE_BS), nb, np.int32)
    for i, u in enumerate(used):
        tables[i, :u] = sum(used[:i]) + np.arange(u)
    return jnp.asarray(tables), nb


def _wide_layer(lane, rng, kv="posit16"):
    """One layer's attention at the wide table: ``run(read_steps)`` is
    the paged gather path, ``ref`` today's whole-table form."""
    cfg = dataclasses.replace(_cfg(lane), kv_posit=kv)
    tables, nb = _wide_tables()
    lens = jnp.asarray(WIDE_LENS, jnp.int32)
    b, bs = len(WIDE_LENS), WIDE_BS
    if lane == "mla":
        c_arena = _arena(rng, (nb, bs, cfg.kv_lora_rank), kv)
        r_arena = _arena(rng, (nb, bs, cfg.qk_rope_dim), kv)
        qe = jnp.asarray(rng.normal(size=(b, cfg.n_heads, cfg.kv_lora_rank)),
                         jnp.float32)
        qr = jnp.asarray(rng.normal(size=(b, cfg.n_heads, cfg.qk_rope_dim)),
                         jnp.float32)

        def run(read_steps):
            return L.decode_attention_paged_mla(
                qe, qr, c_arena, r_arena, tables, lens, cfg=cfg,
                kv_posit=kv, read_steps=read_steps)
        return run, run(None)
    g, h, d = cfg.n_kv_heads, cfg.n_heads, cfg.head_dim
    k_arena = _arena(rng, (nb, bs, g, d), kv)
    v_arena = _arena(rng, (nb, bs, g, d), kv)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)

    def run(read_steps):
        return L.decode_attention_paged(
            q, k_arena, v_arena, tables, lens, cfg=cfg, kv_posit=kv,
            read_steps=read_steps)
    ref = L.decode_attention(
        q, L.paged_gather(k_arena, tables), L.paged_gather(v_arena, tables),
        lens + 1, cfg=cfg, kv_posit=kv,
        apos=L.paged_apos(tables, lens, bs, nb))
    return run, ref


@pytest.mark.parametrize("lane", ["dense", "mla"])
def test_bounded_gather_matches_the_whole_table(lane):
    """At the covering width (two steps for an extent of 513) every
    decoding row equals today's whole-table form to f32 rounding, and
    the dead row stays exact zeros.  One step short, the 513-position
    row loses its last position while the rows that fit are unchanged:
    the bound really narrows the read."""
    run, ref = _wide_layer(lane, np.random.default_rng(20))
    k = -(-max(WIDE_LENS[i] + 1 for i in DECODING) // L.PAGED_READ_STEP)
    assert k == 2
    got = run(jnp.int32(k))
    np.testing.assert_allclose(got[np.array(DECODING)],
                               ref[np.array(DECODING)],
                               atol=2e-6, rtol=1e-5)
    assert float(jnp.abs(got[3]).max()) == 0.0
    short = run(jnp.int32(k - 1))
    np.testing.assert_allclose(short[:2], ref[:2], atol=2e-6, rtol=1e-5)
    assert float(jnp.abs(short[2] - ref[2]).max()) > 1e-3
    np.testing.assert_array_equal(run(jnp.int32(4)), run(None))


@pytest.mark.parametrize("extent, cols", [(0, 1), (1, 1), (511, 1),
                                          (512, 1), (513, 2), (1501, 3),
                                          (2048, 4), (5000, 4)])
def test_read_positions_round_up_to_width_steps(extent, cols):
    """The one rule: ``ceil(extent / step)`` steps, at least one, at most
    the table, the same on the host and traced."""
    w, bs = WIDE_LEN // WIDE_BS, WIDE_BS
    want = cols * L.PAGED_READ_STEP
    assert L.paged_read_positions(extent, w, bs) == want
    assert int(jax.jit(lambda e: L.paged_read_positions(e, w, bs))(
        jnp.int32(extent))) == want
    # the window lane's ring, and a table of one step, are read whole
    ring = L.paged_window_blocks(600, bs)
    assert L.paged_read_positions(extent, ring, bs, window=600) == ring * bs
    assert L.paged_read_positions(extent, 8, bs) == 8 * bs


def _wide_cache(cfg, rng):
    tables, nb = _wide_tables()
    cache = T.init_paged_cache(cfg, len(WIDE_LENS), WIDE_LEN, WIDE_BS, nb)
    keys = ("c_kv", "k_rope") if cfg.mla else ("k", "v")
    for key in keys:
        cache[key] = _arena(rng, cache[key].shape, cfg.kv_posit)
    return dict(cache, block_tables=tables,
                lens=jnp.asarray(WIDE_LENS, jnp.int32))


@pytest.mark.parametrize("lane", ["dense", "mla"])
def test_decode_step_reads_the_covering_width_steps(lane, monkeypatch):
    """Through the model's paged decode step: every gather of the step
    reads ``ceil(extent / step)`` width steps, ``extent`` being the
    largest ``lens + 1`` of the decoding rows alone (the longer
    non-decoding row and the dead row do not widen it), and the
    decoding rows' logits equal the whole-table reference step's.  One
    compiled step serves every width."""
    from paged_reference import decode_step_xs_ys
    cfg = _cfg(lane, kv_posit="posit16")
    params = _params(cfg)
    rng = np.random.default_rng(21)
    cache = _wide_cache(cfg, rng)
    tok = jnp.asarray(rng.integers(1, cfg.vocab, len(WIDE_LENS)), jnp.int32)
    seen = []
    real = L.paged_gather

    def spy(arena, tables):
        w = tables.shape[1]
        jax.debug.callback(lambda: seen.append(w))
        return real(arena, tables)

    monkeypatch.setattr(L, "paged_gather", spy)
    step = jax.jit(lambda c, t, a: T.decode_step(params, c, t, cfg,
                                                 active=a))
    ref = jax.jit(lambda c, t, a: decode_step_xs_ys(params, c, t, cfg, a))
    for rows, steps in (([], 1), ([0], 1), ([0, 1], 1), ([0, 1, 2], 2),
                        ([0, 4], 3)):
        active = jnp.asarray(np.isin(np.arange(len(WIDE_LENS)), rows))
        seen.clear()
        got, _ = step(cache, tok, active)
        jax.block_until_ready(got)
        jax.effects_barrier()
        assert seen and set(seen) == {steps * STEP_COLS}, (rows, seen)
        if rows:
            want, _ = ref(cache, tok, active)
            np.testing.assert_allclose(got[np.array(rows)],
                                       want[np.array(rows)],
                                       atol=1e-4, rtol=1e-5)
    assert step._cache_size() == 1


@pytest.mark.parametrize("lane", ["dense", "mla"])
def test_bounded_gather_token_identity_across_a_width_step(lane):
    """Paged gather decode == the LINEAR cache, token for token, on
    ragged rows whose generation crosses the first width step (512
    positions) at different steps, at a table of two steps, the second
    partial."""
    cfg = _cfg(lane, kv_posit="posit16")
    params = _params(cfg)
    rng = np.random.default_rng(22)
    prompts = [list(rng.integers(1, cfg.vocab, size=n))
               for n in (505, 300, 498)]
    linear = Engine(cfg, params, max_len=640, seed=0).generate(
        prompts, 16).tokens
    paged = Engine(cfg, params, max_len=640, seed=0, paged=True,
                   block_size=16).generate(prompts, 16).tokens
    np.testing.assert_array_equal(paged, linear)


# ---------------------------------------------------------------------------
# the stacked arena's read rule: lane-dense arenas read flat, no layer slice
# ---------------------------------------------------------------------------

def _lane_dense_cfg(lane, **kw):
    """The lane at its arenas' served minor widths: dense K/V heads of
    128, MLA's 256-wide latent beside its 32-wide RoPE part."""
    if lane == "mla":
        return _cfg(lane, kv_lora_rank=256, qk_rope_dim=32, **kw)
    return _cfg(lane, head_dim=128, **kw)


@pytest.mark.parametrize("minor, kernel, flat", [
    (128, "gather", True), (256, "gather", True), (384, "gather", True),
    (32, "gather", False), (16, "gather", False), (96, "gather", False),
    (128, "fused", False), (256, "fused", False)])
def test_read_rule_reads_lane_dense_arenas_flat(minor, kernel, flat):
    """Flat where the gather path reads an arena whose last axis fills
    whole 128-lane tiles; a whole-layer slice for a narrower arena and
    on the fused kernel.  The flat view is the stacked arena with its
    two leading axes merged, and maps layer ``l``'s live entries to
    ``l * n_blocks + entry`` and every sentinel to ``L * n_blocks``."""
    assert L.paged_reads_flat(minor, kernel) is flat
    n_layers, nb = 3, 5
    arena = jnp.arange(n_layers * nb * 2 * minor, dtype=jnp.float32
                       ).reshape(n_layers, nb, 2, minor)
    tables = jnp.asarray([[4, 0, nb], [2, nb + 3, 1]], jnp.int32)
    view, to_view = L.paged_layer_view(arena, jnp.int32(1), kernel=kernel)
    if flat:
        assert view.shape == (n_layers * nb, 2, minor)
        np.testing.assert_array_equal(
            to_view(tables), [[nb + 4, nb, 3 * nb], [nb + 2, 3 * nb, nb + 1]])
    else:
        assert view.shape == (nb, 2, minor)
        np.testing.assert_array_equal(to_view(tables), tables)
    live = np.asarray(tables) < nb
    got = np.asarray(L.paged_gather(view, to_view(tables)))
    want = np.asarray(L.paged_gather(arena[1], tables))
    np.testing.assert_array_equal(got.reshape(2, 3, 2, minor)[live],
                                  want.reshape(2, 3, 2, minor)[live])


def _paged_engine_case(cfg, seed):
    """A paged cache mid-decode with every dropped write of the in-place
    guard: row 1 inactive, row 2's write position under a sentinel
    entry, row 3 at ``max_len``."""
    eng = Engine(cfg, _params(cfg), max_len=16, seed=0, paged=True,
                 block_size=4)
    rng = np.random.default_rng(seed)
    cache, _, _ = eng.prefill(
        [rng.integers(1, cfg.vocab, n).tolist() for n in (5, 7, 6, 9)],
        reserve_tokens=4)
    nb = cache["k_rope" if cfg.mla else "k"].shape[1]
    lens = np.asarray(cache["lens"]).copy()
    tables = np.asarray(cache["block_tables"]).copy()
    tables[2, (lens[2] // 4) % tables.shape[1]] = nb
    lens[3] = 16
    cache = dict(cache, lens=jnp.asarray(lens),
                 block_tables=jnp.asarray(tables))
    toks = rng.integers(1, cfg.vocab, (3, 4))
    return eng.params, cache, jnp.asarray([True, False, True, True]), toks


@pytest.mark.parametrize("lane", LANES)
def test_flat_read_matches_per_layer_slices(lane):
    """At lane-dense widths the decode step reads the dense K/V and the
    MLA latent straight off the stacked arena; logits, both arenas and
    ``lens`` still equal the per-layer ``xs``/``ys`` step bit for bit
    over three steps, every dropped write included."""
    from paged_reference import decode_step_xs_ys
    cfg = _lane_dense_cfg(lane, kv_posit="posit16")
    params, cache, active, toks = _paged_engine_case(cfg, 23)
    step = jax.jit(lambda c, t: T.decode_step(params, c, t, cfg,
                                              active=active))
    ref_step = jax.jit(lambda c, t: decode_step_xs_ys(params, c, t, cfg,
                                                      active))
    got, ref = cache, cache
    for tok in toks:
        tok = jnp.asarray(tok, jnp.int32)
        logits, got = step(got, tok)
        ref_logits, ref = ref_step(ref, tok)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
    for key in got:
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(ref[key]), err_msg=key)


@pytest.mark.parametrize("lane", ["dense", "mla"])
def test_flat_read_is_the_sliced_read_at_every_width(lane, monkeypatch):
    """The bounded gather's branches at the wide table (a dead row, a
    longer non-decoding row): the flat read gives the logits and arenas
    of the whole-layer slice read, bit for bit, at one, two and three
    width steps."""
    cfg = _lane_dense_cfg(lane, kv_posit="posit16")
    params = _params(cfg)
    rng = np.random.default_rng(24)
    cache = _wide_cache(cfg, rng)
    tok = jnp.asarray(rng.integers(1, cfg.vocab, len(WIDE_LENS)), jnp.int32)

    actives = [jnp.asarray(np.isin(np.arange(len(WIDE_LENS)), rows))
               for rows in ([0, 1], [0, 1, 2], [0, 4])]

    def run():                          # traced anew: reads the rule
        step = jax.jit(lambda c, t, a: T.decode_step(params, c, t, cfg,
                                                     active=a))
        return [jax.device_get(step(cache, tok, a)) for a in actives]

    flat = run()
    monkeypatch.setattr(L, "paged_reads_flat", lambda minor, kernel: False)
    for got, want in zip(flat, run()):
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(g, w)


def _layer_slices(jaxpr, layer_shapes):
    """Whole-layer ``dynamic_slice`` ops of the stacked arenas in a
    jaxpr, its scan, switch and call bodies included."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dynamic_slice" and \
                eqn.outvars[0].aval.shape in layer_shapes:
            n += 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _layer_slices(sub, layer_shapes)
    return n


# lane, arenas at served minor widths, arenas a layer-step slices
LAYER_SLICES = [("dense", False, 2), ("dense", True, 0), ("mla", False, 2),
                ("mla", True, 1), ("window", False, 2), ("window", True, 0)]


@pytest.mark.parametrize("lane, lane_dense, want", LAYER_SLICES)
def test_kv_layer_slices_counts_the_steps_slices(lane, lane_dense, want):
    """``kv_layer_slices`` on every ``sched.round`` is ``decode_steps x
    n_layers x`` the arenas a layer-step slices, and the decode step
    traces exactly that many whole-layer slices: none on the dense
    lanes at heads of 128, the RoPE arena's alone at MLA's served
    widths, both arenas below a lane tile."""
    from repro.runtime import spans
    cfg = (_lane_dense_cfg if lane_dense else _cfg)(lane)
    eng = Engine(cfg, _params(cfg), max_len=64, paged=True, block_size=4)
    sched = Scheduler(eng, n_slots=2, chunk_size=4)
    arenas = [sched.cache[k] for k in
              (("c_kv", "k_rope") if cfg.mla else ("k", "v"))]
    jaxpr = jax.make_jaxpr(lambda c, t: T.decode_step(
        eng.params, c, t, cfg, active=jnp.ones((2,), bool)))(
            sched.cache, jnp.ones((2,), jnp.int32))
    assert _layer_slices(jaxpr.jaxpr, {(1,) + a.shape[1:]
                                       for a in arenas}) == want
    rng = np.random.default_rng(0)
    for plen, gen in ((3, 2), (5, 9), (4, 3)):
        sched.submit(rng.integers(1, cfg.vocab, plen).tolist(), gen)
    sched.run(max_rounds=20)
    rounds = [s for s in spans.snapshot() if s.name == "sched.round"
              and s.ids.get("sched") == sched.sched_id]
    assert any(r.counters["decode_steps"] for r in rounds)
    for r in rounds:
        assert r.counters["kv_layer_slices"] == \
            r.counters["decode_steps"] * cfg.n_layers * want
