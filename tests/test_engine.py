"""Serving-engine regression tests.

The seed's decode path was numerically wrong: prefill returned a cache
whose time axis equalled the prompt length, and decode_step wrote new
K/V at absolute position ``pos`` with ``dynamic_update_slice_in_dim``,
whose index-CLAMPING semantics silently overwrote the final cache slot
on every step past the first.  These tests pin the fix on all three
transformer lanes (dense, MLA, sliding-window ring buffer), the guarded
out-of-capacity behaviour, the one-scan decode, and ragged batching.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro import configs
from repro.models import get_family
from repro.models import layers as L
from repro.models import transformer as T
from repro.runtime.engine import Engine


def _dense_cfg(**kw):
    return configs.get_config("phi3-medium-14b").reduced(
        compute_dtype="float32", **kw)


def _mla_cfg(**kw):
    return configs.get_config("minicpm3-4b").reduced(
        compute_dtype="float32", **kw)


def _params(cfg, seed=0):
    return get_family(cfg).init_params(jax.random.PRNGKey(seed), cfg)


def _seed_decode_step(params, cache, token, cfg):
    """The SEED's dense decode semantics, reproduced verbatim as the
    broken reference: absolute-position writes that clamp onto the last
    slot once ``pos`` reaches the cache capacity."""
    pos = cache["len"]
    x = params["tok_embed"][token][:, None, :].astype(L.cdtype(cfg))

    def body(h, layer):
        lp, k_c, v_c = layer
        p = lp["attn"]
        b = h.shape[0]
        xin = L.rms_norm(lp["ln1"], h, cfg)
        q = L.dense(p["wq"], xin, cfg).reshape(
            b, 1, cfg.n_heads, cfg.head_dim)
        k = L.dense(p["wk"], xin, cfg).reshape(
            b, 1, cfg.n_kv_heads, cfg.head_dim)
        v = L.dense(p["wv"], xin, cfg).reshape(
            b, 1, cfg.n_kv_heads, cfg.head_dim)
        q = L.apply_rope(q, pos[None, None], cfg.rope_theta)
        k = L.apply_rope(k, pos[None, None], cfg.rope_theta)
        k_c = lax.dynamic_update_slice_in_dim(          # the clamping bug
            k_c, T._maybe_quant_kv(k, cfg), pos, 1)
        v_c = lax.dynamic_update_slice_in_dim(
            v_c, T._maybe_quant_kv(v, cfg), pos, 1)
        a = L.decode_attention(q, k_c, v_c, pos + 1, cfg=cfg,
                               kv_posit=cfg.kv_posit)
        h = h + L.dense(p["wo"], a.reshape(b, 1, -1), cfg)
        hh = L.rms_norm(lp["ln2"], h, cfg)
        return h + L.mlp(lp["mlp"], hh, cfg), (k_c, v_c)

    x, (k_new, v_new) = lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]))
    x = L.rms_norm(params["final_norm"], x, cfg)
    logits = x[:, 0, :] @ T._unembed_weight(params, cfg).astype(x.dtype)
    return logits.astype(jnp.float32), dict(cache, k=k_new, v=v_new,
                                            len=pos + 1)


# ---------------------------------------------------------------------------
# the clamp-overwrite regression (dense, MLA, sliding-window lanes)
# ---------------------------------------------------------------------------

def test_dense_decode_no_clamp_overwrite_and_differs_from_broken():
    """Prefill s tokens, decode 3: slot s-1 must stay untouched and the
    logits must differ from the seed's clamp-overwrite behaviour."""
    cfg = _dense_cfg()
    params = _params(cfg)
    rng = np.random.default_rng(0)
    b, s = 2, 8
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, (b, s)), jnp.int32)

    cache, logits = T.prefill(params, tokens, cfg, max_len=s + 8)
    slot = np.asarray(cache["k"][:, :, s - 1])
    assert np.abs(slot).sum() > 0                    # a real prompt key

    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    fixed_logits = []
    for _ in range(3):
        logits, cache = T.decode_step(params, cache, tok, cfg)
        fixed_logits.append(np.asarray(logits))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)

    # the last prompt KV slot is untouched; decode landed in headroom
    np.testing.assert_array_equal(np.asarray(cache["k"][:, :, s - 1]), slot)
    assert np.abs(np.asarray(cache["k"][:, :, s:s + 3])).sum() > 0
    assert int(cache["len"]) == s + 3

    # broken reference: prompt-sized cache + clamping writes (the seed).
    # Feed it the SAME token sequence; by the second step its logits must
    # diverge — it has been overwriting slot s-1.
    bcache, blogits = T.prefill(params, tokens, cfg)     # no headroom
    bcache = {"k": bcache["k"], "v": bcache["v"], "len": bcache["len"]}
    broken_logits = []
    toks = [jnp.argmax(blogits, -1).astype(jnp.int32)]
    for i in range(3):
        lg, bcache = _seed_decode_step(params, bcache, toks[-1], cfg)
        broken_logits.append(np.asarray(lg))
        toks.append(jnp.argmax(lg, -1).astype(jnp.int32))
    assert not (np.asarray(bcache["k"][:, :, s - 1]) == slot).all(), \
        "broken reference should have clobbered slot s-1"
    assert np.abs(broken_logits[-1] - fixed_logits[-1]).max() > 1e-4, \
        "fixed decode should differ from the clamp-overwrite behaviour"


def test_mla_decode_no_clamp_overwrite():
    cfg = _mla_cfg()
    params = _params(cfg)
    rng = np.random.default_rng(1)
    b, s = 2, 8
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, (b, s)), jnp.int32)
    cache, logits = T.prefill(params, tokens, cfg, max_len=s + 8)
    slot_c = np.asarray(cache["c_kv"][:, :, s - 1])
    slot_r = np.asarray(cache["k_rope"][:, :, s - 1])
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(3):
        logits, cache = T.decode_step(params, cache, tok, cfg)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(cache["c_kv"][:, :, s - 1]), slot_c)
    np.testing.assert_array_equal(
        np.asarray(cache["k_rope"][:, :, s - 1]), slot_r)
    assert np.abs(np.asarray(cache["c_kv"][:, :, s:s + 3])).sum() > 0
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ["phi3-medium-14b", "minicpm3-4b",
                                  "whisper-tiny"])
def test_decode_past_capacity_raises(arch):
    """Out-of-capacity decode writes must raise, not clamp-overwrite."""
    cfg = configs.get_config(arch).reduced(compute_dtype="float32")
    fam = get_family(cfg)
    params = _params(cfg, seed=2)
    rng = np.random.default_rng(2)
    b, cap = 2, 4
    cache = fam.init_cache(cfg, b, cap)
    tok = jnp.asarray(rng.integers(1, cfg.vocab, (b,)), jnp.int32)
    for _ in range(cap):
        logits, cache = fam.decode_step(params, cache, tok, cfg)
    with pytest.raises(ValueError, match="capacity"):
        fam.decode_step(params, cache, tok, cfg)


def test_traced_out_of_capacity_write_drops_not_clamps():
    """Under jit the guard cannot raise; it must DROP the write (never
    clamp onto the last slot)."""
    cfg = _dense_cfg()
    params = _params(cfg, seed=3)
    rng = np.random.default_rng(3)
    b, cap = 1, 4
    fam = get_family(cfg)
    cache = fam.init_cache(cfg, b, cap)
    tok = jnp.asarray(rng.integers(1, cfg.vocab, (b,)), jnp.int32)
    step = jax.jit(lambda c, t: fam.decode_step(params, c, t, cfg))
    for _ in range(cap):
        logits, cache = step(cache, tok)
    last = np.asarray(cache["k"][:, :, cap - 1])
    logits, cache = step(cache, tok)              # past capacity, traced
    np.testing.assert_array_equal(np.asarray(cache["k"][:, :, cap - 1]),
                                  last)


# ---------------------------------------------------------------------------
# sliding-window ring buffer: golden vs full-length reference
# ---------------------------------------------------------------------------

def test_sliding_window_ring_matches_full_length_reference():
    """Ring-buffer cache (capacity = window, pos % window writes,
    rotation-aware masks) must reproduce a full-length reference cache
    bit-for-tolerance across >2 wraparounds, including a prompt longer
    than the window (prefill ring packing)."""
    cfg = _dense_cfg(sliding_window=8, attn_chunk_kv=8)
    params = _params(cfg, seed=4)
    rng = np.random.default_rng(4)
    b, s, ml = 2, 12, 40
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, (b, s)), jnp.int32)

    def gen(window_ring):
        cache, logits = T.prefill(params, tokens, cfg, max_len=ml,
                                  window_ring=window_ring)
        step = jax.jit(lambda c, t: T.decode_step(params, c, t, cfg))
        outs = [np.asarray(logits)]
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for _ in range(20):
            logits, cache = step(cache, tok)
            outs.append(np.asarray(logits))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return outs, cache

    ring_outs, ring_cache = gen(True)
    full_outs, full_cache = gen(False)
    assert ring_cache["k"].shape[2] == cfg.sliding_window   # ring-sized
    assert full_cache["k"].shape[2] == ml                   # reference
    for i, (a, bb) in enumerate(zip(ring_outs, full_outs)):
        np.testing.assert_allclose(a, bb, rtol=1e-4, atol=1e-4,
                                   err_msg=f"step {i}")


def test_hymba_decode_no_clamp_overwrite():
    """Hymba's hybrid cache used raw ``dynamic_update_slice_in_dim`` for
    its SWA ring writes (the PVU001 bug class): once ``pos % window``
    computed a slot past the clamp bound the write would silently pile
    onto the last ring slot.  Pin the guarded semantics on both lanes:
    every decode step must touch exactly ring slot ``pos % window`` (no
    clamp pile-up), and the global layer's last prompt slot must survive
    decode into headroom, mirroring the dense clamp test above."""
    cfg = configs.get_config("hymba-1.5b").reduced(
        compute_dtype="float32", sliding_window=4)
    H = get_family(cfg)
    params = _params(cfg, seed=3)
    rng = np.random.default_rng(3)
    b, s, steps = 2, 6, 5
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, (b, s)), jnp.int32)

    cache, logits = H.prefill(params, tokens, cfg, max_len=s + 8)
    w = cache["k_swa"].shape[2]
    assert w == cfg.sliding_window                       # ring-sized
    # global_layers=(0,) -> layer 1 is the SWA ring lane
    gslot = np.asarray(cache["k_glb"][0][:, s - 1])
    assert np.abs(gslot).sum() > 0                       # a real prompt key

    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(steps):
        pos = int(cache["len"])
        before = np.asarray(cache["k_swa"][1])
        logits, cache = H.decode_step(params, cache, tok, cfg)
        after = np.asarray(cache["k_swa"][1])
        for t in range(w):
            if t == pos % w:
                assert not (after[:, t] == before[:, t]).all(), \
                    f"pos {pos}: ring slot {t} should have been written"
            else:
                np.testing.assert_array_equal(
                    after[:, t], before[:, t],
                    err_msg=f"pos {pos}: ring slot {t} clobbered "
                            f"(clamp pile-up?)")
        tok = jnp.argmax(logits, -1).astype(jnp.int32)

    # global lane: last prompt slot untouched, decode landed in headroom
    np.testing.assert_array_equal(np.asarray(cache["k_glb"][0][:, s - 1]),
                                  gslot)
    assert np.abs(np.asarray(cache["k_glb"][0][:, s:s + steps])).sum() > 0
    assert int(cache["len"]) == s + steps


# ---------------------------------------------------------------------------
# engine: one-scan decode, ragged batching, capacity enforcement
# ---------------------------------------------------------------------------

def test_scan_decode_64_steps_matches_stepwise_in_one_compiled_call():
    """>= 64 scan-decoded tokens must equal the per-step jitted loop,
    with the whole scan generation running as ONE compiled dispatch
    while the loop dispatches once per token."""
    cfg = _dense_cfg()
    params = _params(cfg, seed=5)
    rng = np.random.default_rng(5)
    prompts = rng.integers(1, cfg.vocab, (2, 8))

    def counted(fn, counter):
        def wrapped(*a):
            counter["n"] += 1
            return fn(*a)
        return wrapped

    e_scan = Engine(cfg, params, max_len=80, seed=0)
    scan_calls = {"n": 0}
    e_scan._decode_jit[64] = counted(e_scan._decode_fn(64), scan_calls)
    r_scan = e_scan.generate(prompts, 64)

    e_step = Engine(cfg, params, max_len=80, seed=0)
    step_calls = {"n": 0}
    fam = get_family(cfg)
    e_step._decode_jit["step"] = counted(
        jax.jit(lambda p, c, t: fam.decode_step(p, c, t, cfg)), step_calls)
    r_step = e_step.generate_stepwise(prompts, 64)

    assert (r_scan.tokens == r_step.tokens).all()
    assert r_scan.tokens.shape == (2, 64)
    # scan: the full generation is one compiled call; loop: one dispatch
    # per generated token
    assert scan_calls["n"] == 1, scan_calls
    assert step_calls["n"] == 63, step_calls


def _idle_round(b, c=4):
    """``mixed_step`` inputs for a round in which no row prefills."""
    return np.zeros((b, c), np.int32), np.zeros((b,), np.int32)


@pytest.mark.parametrize("lane", ["dense", "mla"])
def test_mixed_step_decode_rounds_match_one_scan(lane):
    """Two ``mixed_step`` rounds with no prefilling row, seeded from the
    prefill token, must emit exactly what generate's single 9-token scan
    emits — the equivalence the scheduler's decode quantum rests on."""
    cfg = _dense_cfg() if lane == "dense" else _mla_cfg()
    params = _params(cfg, seed=11)
    rng = np.random.default_rng(11)
    prompts = rng.integers(1, cfg.vocab, (2, 6))
    ref = Engine(cfg, params, max_len=24, seed=0).generate(prompts, 9).tokens

    eng = Engine(cfg, params, max_len=24, seed=0, paged=True, block_size=4)
    cache, logits, _ = eng.prefill(prompts, reserve_tokens=8)
    tok0 = np.asarray(jnp.argmax(logits, -1), np.int32)
    chunk, nv = _idle_round(2)
    act = np.ones((2,), bool)
    cache, _, c1 = eng.mixed_step(cache, chunk, nv, tok0, 4,
                                  decode_active=act)
    cache, _, c2 = eng.mixed_step(cache, chunk, nv, np.asarray(c1)[:, -1],
                                  4, decode_active=act)
    got = np.concatenate([tok0[:, None], np.asarray(c1),
                          np.asarray(c2)], axis=1)
    np.testing.assert_array_equal(got, ref)


def test_mixed_step_decode_active_freezes_inactive_lens():
    """Rows outside ``decode_active`` ride along in the batch but their
    ``lens`` must not advance (their writes are dropped)."""
    cfg = _dense_cfg()
    params = _params(cfg, seed=12)
    rng = np.random.default_rng(12)
    eng = Engine(cfg, params, max_len=24, seed=0, paged=True, block_size=4)
    cache, logits, _ = eng.prefill(rng.integers(1, cfg.vocab, (2, 5)),
                                   reserve_tokens=3)
    tok0 = np.asarray(jnp.argmax(logits, -1), np.int32)
    chunk, nv = _idle_round(2)
    cache, _, _ = eng.mixed_step(cache, chunk, nv, tok0, 3,
                                 decode_active=np.array([True, False]))
    assert np.asarray(cache["lens"]).tolist() == [8, 5]


def test_mixed_step_refuses_a_prefill_chunk_past_max_len():
    """A prefill chunk that would write past max_len must raise before
    dispatch — the traced writes would otherwise be silently dropped."""
    cfg = _dense_cfg()
    params = _params(cfg, seed=13)
    rng = np.random.default_rng(13)
    eng = Engine(cfg, params, max_len=12, seed=0, paged=True, block_size=4)
    cache, _, _ = eng.prefill(rng.integers(1, cfg.vocab, (1, 9)))
    chunk = rng.integers(1, cfg.vocab, (1, 4)).astype(np.int32)
    tok = np.zeros((1,), np.int32)
    eng.mixed_step(cache, chunk, np.array([3]), tok, 1)   # 9 + 3 = 12 fits
    with pytest.raises(ValueError, match="prefill chunk frontier 13"):
        eng.mixed_step(cache, chunk, np.array([4]), tok, 1)


def test_mixed_step_refuses_decode_steps_past_max_len():
    """A decode quantum that would run a decoding row past max_len must
    raise before dispatch."""
    cfg = _dense_cfg()
    params = _params(cfg, seed=13)
    rng = np.random.default_rng(13)
    eng = Engine(cfg, params, max_len=12, seed=0, paged=True, block_size=4)
    cache, logits, _ = eng.prefill(rng.integers(1, cfg.vocab, (1, 6)),
                                   reserve_tokens=6)
    tok0 = np.asarray(jnp.argmax(logits, -1), np.int32)
    chunk, nv = _idle_round(1)
    act = np.ones((1,), bool)
    cache, _, _ = eng.mixed_step(cache, chunk, nv, tok0, 6,
                                 decode_active=act)       # 6 + 6 = 12 fits
    with pytest.raises(ValueError, match="decode frontier 12"):
        eng.mixed_step(cache, chunk, nv, tok0, 1, decode_active=act)


def test_ragged_batch_matches_singleton_generations():
    """Unequal-length prompts share one batch (left-padding + masks) and
    generate the same tokens as each prompt alone."""
    cfg = _dense_cfg()
    params = _params(cfg, seed=6)
    rng = np.random.default_rng(6)
    p1 = rng.integers(1, cfg.vocab, (5,)).tolist()
    p2 = rng.integers(1, cfg.vocab, (9,)).tolist()

    eng = Engine(cfg, params, max_len=32, seed=0)
    batched = eng.generate([p1, p2], 8)
    assert batched.prompt_lens.tolist() == [5, 9]

    solo1 = Engine(cfg, params, max_len=32, seed=0).generate([p1], 8)
    solo2 = Engine(cfg, params, max_len=32, seed=0).generate([p2], 8)
    np.testing.assert_allclose(batched.prefill_logits[0],
                               solo1.prefill_logits[0],
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(batched.prefill_logits[1],
                               solo2.prefill_logits[0],
                               rtol=5e-4, atol=5e-4)
    assert (batched.tokens[0] == solo1.tokens[0]).all()
    assert (batched.tokens[1] == solo2.tokens[0]).all()


def test_ragged_rejected_outside_transformer_family():
    cfg = configs.get_config("rwkv6-7b").reduced(compute_dtype="float32")
    params = _params(cfg, seed=7)
    eng = Engine(cfg, params, max_len=16)
    with pytest.raises(ValueError, match="ragged"):
        eng.generate([[1, 2], [3, 4, 5]], 2)


def test_engine_refuses_requests_beyond_max_len():
    cfg = _dense_cfg()
    params = _params(cfg, seed=8)
    eng = Engine(cfg, params, max_len=12)
    rng = np.random.default_rng(8)
    prompts = rng.integers(1, cfg.vocab, (1, 8))
    eng.generate(prompts, 5)                        # 8 + 5 - 1 = 12 fits
    with pytest.raises(ValueError, match="max_len"):
        eng.generate(prompts, 6)                    # 13 > 12
    with pytest.raises(ValueError, match="max_len"):
        eng.generate_stepwise(prompts, 6)           # same guard, both paths


@pytest.mark.parametrize("arch,kw", [
    ("whisper-tiny", "frames"),
    ("internvl2-1b", "visual"),
])
def test_engine_routes_encoder_state(arch, kw):
    """frames/visual must flow through prefill while decode runs off the
    cached encoder state — the old serve.py dropped them."""
    cfg = configs.get_config(arch).reduced(compute_dtype="float32")
    params = _params(cfg, seed=9)
    rng = np.random.default_rng(9)
    b = 2
    if kw == "frames":
        aux = jnp.asarray(rng.standard_normal(
            (b, cfg.encoder_seq, cfg.d_model)), jnp.float32)
    else:
        aux = jnp.asarray(rng.standard_normal(
            (b, cfg.n_visual_tokens, cfg.d_model)), jnp.float32)
    eng = Engine(cfg, params, max_len=24)
    res = eng.generate(rng.integers(1, cfg.vocab, (b, 8)), 8, **{kw: aux})
    assert res.tokens.shape == (b, 8)
    assert np.isfinite(res.prefill_logits).all()


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["hymba-1.5b", "rwkv6-7b",
                                  "minicpm3-4b", "gemma-7b"])
def test_engine_scan_matches_stepwise_all_families(arch):
    cfg = configs.get_config(arch).reduced(compute_dtype="float32")
    params = _params(cfg, seed=10)
    rng = np.random.default_rng(10)
    prompts = rng.integers(1, cfg.vocab, (2, 8))
    r1 = Engine(cfg, params, max_len=48, seed=1).generate(prompts, 16)
    r2 = Engine(cfg, params, max_len=48, seed=1).generate_stepwise(
        prompts, 16)
    assert (r1.tokens == r2.tokens).all()
