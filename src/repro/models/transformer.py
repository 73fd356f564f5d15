"""Decoder-only transformer LM (covers 7 of the 10 assigned archs).

Options: GQA/MQA dense attention, MLA (multi-head latent attention,
minicpm3), MoE FFN (granite-moe, dbrx), GeGLU/SwiGLU, vision-stub prefix
(internvl2).  Layer stack is lax.scan'd over stacked params so an
88-layer model lowers to the same HLO size as a 2-layer one.

Protocol (shared by every family in ``repro.models``):
    init_params(key, cfg)                        -> params pytree
    train_loss(params, batch, cfg)               -> scalar loss
    init_cache(cfg, batch, max_len)              -> cache pytree
    prefill(params, tokens, cfg, visual=None,
            max_len=None, ...)                   -> (cache, last_logits)
    decode_step(params, cache, token, cfg)       -> (logits, cache)

``prefill(max_len=...)`` preallocates decode headroom in the returned
cache; without it the cache is prompt-sized and decode_step refuses to
write past it (see the serving section below for the cache layout and
the ring-buffer sliding-window lane).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.convert import f32_to_posit
from . import layers as L
from .config import ModelConfig


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_attention(key, cfg: ModelConfig):
    d = cfg.d_model
    if cfg.mla:
        k = jax.random.split(key, 8)
        qh = cfg.qk_nope_dim + cfg.qk_rope_dim
        return {
            "wdq": L.init_dense(k[0], d, cfg.q_lora_rank),
            "q_norm": L.init_rms_norm(cfg.q_lora_rank, cfg),
            "wuq": L.init_dense(k[1], cfg.q_lora_rank, cfg.n_heads * qh),
            "wdkv": L.init_dense(k[2], d, cfg.kv_lora_rank + cfg.qk_rope_dim),
            "kv_norm": L.init_rms_norm(cfg.kv_lora_rank, cfg),
            "wuk": L.init_dense(k[3], cfg.kv_lora_rank,
                                cfg.n_heads * cfg.qk_nope_dim),
            "wuv": L.init_dense(k[4], cfg.kv_lora_rank,
                                cfg.n_heads * cfg.v_head_dim),
            "wo": L.init_dense(k[5], cfg.n_heads * cfg.v_head_dim, d),
        }
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": L.init_dense(k1, d, cfg.n_heads * cfg.head_dim),
        "wk": L.init_dense(k2, d, cfg.n_kv_heads * cfg.head_dim),
        "wv": L.init_dense(k3, d, cfg.n_kv_heads * cfg.head_dim),
        "wo": L.init_dense(k4, cfg.n_heads * cfg.head_dim, d),
    }


def _init_block(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": L.init_rms_norm(cfg.d_model, cfg),
        "attn": _init_attention(k1, cfg),
        "ln2": L.init_rms_norm(cfg.d_model, cfg),
    }
    if cfg.is_moe:
        p["moe"] = L.init_moe(k2, cfg)
    else:
        p["mlp"] = L.init_mlp(k2, cfg)
    return p


def init_params(key, cfg: ModelConfig):
    keys = jax.random.split(key, 4)
    layer_keys = jax.random.split(keys[0], cfg.n_layers)
    layers = jax.vmap(lambda k: _init_block(k, cfg))(layer_keys)
    params = {
        "tok_embed": jax.random.normal(
            keys[1], (cfg.vocab, cfg.d_model), jnp.float32) * 0.02,
        "layers": layers,
        "final_norm": L.init_rms_norm(cfg.d_model, cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_dense(keys[2], cfg.d_model, cfg.vocab)
    return params


# ---------------------------------------------------------------------------
# attention forward (dense + MLA)
# ---------------------------------------------------------------------------

def _attn_forward(p, x, positions, cfg: ModelConfig, kv_mask=None):
    b, s, d = x.shape
    if cfg.mla:
        q_lat = L.rms_norm(p["q_norm"], L.dense(p["wdq"], x, cfg), cfg)
        q = L.dense(p["wuq"], q_lat, cfg).reshape(
            b, s, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
        q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
        q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)

        dkv = L.dense(p["wdkv"], x, cfg)
        c_kv, k_rope = jnp.split(dkv, [cfg.kv_lora_rank], axis=-1)
        c_kv = L.rms_norm(p["kv_norm"], c_kv, cfg)
        k_rope = L.apply_rope(k_rope[:, :, None, :], positions,
                              cfg.rope_theta)                   # (B,S,1,r)
        k_nope = L.dense(p["wuk"], c_kv, cfg).reshape(
            b, s, cfg.n_heads, cfg.qk_nope_dim)
        v = L.dense(p["wuv"], c_kv, cfg).reshape(
            b, s, cfg.n_heads, cfg.v_head_dim)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(
                k_rope, (b, s, cfg.n_heads, cfg.qk_rope_dim))], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        out = L.flash_attention(q, k, v, causal=True, cfg=cfg,
                                kv_mask=kv_mask)
        out = out.reshape(b, s, cfg.n_heads * cfg.v_head_dim)
        return L.dense(p["wo"], out, cfg), (c_kv, k_rope[:, :, 0, :])

    q = L.dense(p["wq"], x, cfg).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = L.dense(p["wk"], x, cfg).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = L.dense(p["wv"], x, cfg).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    out = L.flash_attention(q, k, v, causal=True, cfg=cfg,
                            window=cfg.sliding_window, kv_mask=kv_mask)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return L.dense(p["wo"], out, cfg), (k, v)


def _block_forward(p, x, positions, cfg: ModelConfig, kv_mask=None):
    a, kv = _attn_forward(p["attn"], L.rms_norm(p["ln1"], x, cfg),
                          positions, cfg, kv_mask=kv_mask)
    x = x + a
    h = L.rms_norm(p["ln2"], x, cfg)
    f = L.moe(p["moe"], h, cfg) if cfg.is_moe else L.mlp(p["mlp"], h, cfg)
    return x + f, kv


def _sp_constraint(x, cfg: ModelConfig):
    """Megatron-SP: keep residual activations sequence-sharded over the
    'model' axis between blocks (no-op without a mesh context)."""
    if not cfg.seq_shard_activations:
        return x
    try:
        from jax.sharding import PartitionSpec as P
        return lax.with_sharding_constraint(
            x, P(tuple(cfg.batch_axes), "model", None))
    except (ValueError, RuntimeError, TypeError, NameError):
        return x


def _run_layers(params, x, positions, cfg: ModelConfig):
    def body(h, lp):
        h = _sp_constraint(h, cfg)
        h, _ = _block_forward(lp, h, positions, cfg)
        return h, None

    if cfg.remat == "layer":
        # save the (small) MoE output so backward does not replay the
        # dispatch gathers/scatters (§Perf, dbrx train)
        policy = jax.checkpoint_policies.save_only_these_names("moe_out") \
            if cfg.is_moe else None
        body = jax.checkpoint(body, policy=policy)
    x, _ = lax.scan(body, x, params["layers"])
    return x


def _embed(params, tokens, cfg: ModelConfig, visual=None):
    x = params["tok_embed"][tokens].astype(L.cdtype(cfg))
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.n_visual_tokens and visual is not None:
        # prepend the (stub) patch embeddings; total length stays S
        nv = cfg.n_visual_tokens
        x = jnp.concatenate(
            [visual.astype(x.dtype), x[:, : x.shape[1] - nv]], axis=1)
    return x


def _unembed_weight(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["tok_embed"].T
    return L.maybe_dequant(params["lm_head"]["w"], cfg)


# ---------------------------------------------------------------------------
# training loss (chunked over sequence to bound logits memory)
# ---------------------------------------------------------------------------

def train_loss(params, batch, cfg: ModelConfig):
    """batch: {'tokens': (B,S) int32, 'mask': (B,S) f32, ['visual': ...]}
    Next-token cross entropy, vocab projection chunked over the sequence."""
    tokens = batch["tokens"]
    mask = batch.get("mask")
    b, s = tokens.shape
    positions = jnp.arange(s)[None, :]
    x = _embed(params, tokens, cfg, batch.get("visual"))
    x = _run_layers(params, x, positions, cfg)
    x = L.rms_norm(params["final_norm"], x, cfg)

    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    label_mask = jnp.ones((b, s), jnp.float32)
    label_mask = label_mask.at[:, -1].set(0.0)
    if mask is not None:
        label_mask = label_mask * mask
    if cfg.n_visual_tokens:
        label_mask = label_mask.at[:, : cfg.n_visual_tokens].set(0.0)

    w = _unembed_weight(params, cfg).astype(x.dtype)
    ck = min(cfg.loss_chunk, s)
    n_chunks = s // ck
    assert s % ck == 0

    def chunk_loss(ci):
        xs = lax.dynamic_slice_in_dim(x, ci * ck, ck, 1)
        ls = lax.dynamic_slice_in_dim(labels, ci * ck, ck, 1)
        ms = lax.dynamic_slice_in_dim(label_mask, ci * ck, ck, 1)
        logits = (xs @ w).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ls[..., None], -1)[..., 0]
        return ((logz - gold) * ms).sum(), ms.sum()

    losses, counts = lax.map(chunk_loss, jnp.arange(n_chunks))
    return losses.sum() / jnp.maximum(counts.sum(), 1.0)


def logits_fn(params, tokens, cfg: ModelConfig, visual=None):
    """Full-sequence logits (small models / examples only)."""
    b, s = tokens.shape
    positions = jnp.arange(s)[None, :]
    x = _embed(params, tokens, cfg, visual)
    x = _run_layers(params, x, positions, cfg)
    x = L.rms_norm(params["final_norm"], x, cfg)
    return (x @ _unembed_weight(params, cfg).astype(x.dtype)).astype(
        jnp.float32)


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode_step
#
# Cache layout (engine-shaped):
#   * K/V time axis is PREALLOCATED to ``max_len`` (or to the sliding
#     window, run as a ring buffer written at ``pos % window``) — decode
#     writes land in headroom instead of clamping onto the last slot.
#   * ``len``     — scalar int32 write frontier (padded coordinates).
#   * ``lens``    — (B,) int32 per-sequence valid token counts; with
#     left-padded ragged prompts ``len - lens[b]`` is row b's padding
#     offset and masks its pad slots out of decode attention.
#   * ``max_len`` — int32 scalar, the preallocated absolute-position
#     budget (cache maintenance ops must pass it through unchanged).
# ---------------------------------------------------------------------------

def _cache_dtype(cfg: ModelConfig):
    if cfg.kv_posit:
        return L.pcfg(cfg.kv_posit).storage_dtype
    return L.cdtype(cfg)


def _cache_meta(batch: int, frontier: int, max_len: int, lens=None):
    if lens is None:
        lens = jnp.full((batch,), frontier, jnp.int32)
    return {
        "len": jnp.asarray(frontier, jnp.int32),
        "lens": jnp.asarray(lens, jnp.int32),
        "max_len": jnp.asarray(max_len, jnp.int32),
    }


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               window_ring: bool = True):
    """Preallocated decode cache.  ``window_ring=False`` forces a
    full-``max_len`` cache even under a sliding window (the golden
    reference layout the ring buffer is tested against)."""
    meta = _cache_meta(batch, 0, max_len)
    if cfg.mla:
        shape_c = (cfg.n_layers, batch, max_len, cfg.kv_lora_rank)
        shape_r = (cfg.n_layers, batch, max_len, cfg.qk_rope_dim)
        return {
            "c_kv": jnp.zeros(shape_c, _cache_dtype(cfg)),
            "k_rope": jnp.zeros(shape_r, _cache_dtype(cfg)),
            **meta,
        }
    window = cfg.sliding_window or 0
    t = min(max_len, window) if (window and window_ring) else max_len
    shape = (cfg.n_layers, batch, t, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, _cache_dtype(cfg)),
        "v": jnp.zeros(shape, _cache_dtype(cfg)),
        **meta,
    }


def _maybe_quant_kv(x, cfg: ModelConfig):
    if cfg.kv_posit:
        return f32_to_posit(x.astype(jnp.float32), L.pcfg(cfg.kv_posit))
    return x.astype(L.cdtype(cfg))


# ---------------------------------------------------------------------------
# Paged cache lane: block arena + per-row block tables (row-LOCAL
# addressing, so there is no shared padded frontier and nothing to
# compact; see models/layers.py for the layout contract).
# ---------------------------------------------------------------------------

def paged_table_width(cfg: ModelConfig, block_size: int,
                      max_len: int) -> int:
    """Block-table width W: the window ring's ``ceil(window/bs)+1`` when
    a sliding window is active and strictly smaller than the dense
    ``ceil(max_len/bs)``; the dense width otherwise (MLA has no
    window)."""
    dense = -(-int(max_len) // int(block_size))
    if cfg.sliding_window and not cfg.mla:
        ring = L.paged_window_blocks(cfg.sliding_window, block_size)
        if ring < dense:
            return ring
    return dense


def _paged_window(cfg: ModelConfig) -> int:
    return 0 if cfg.mla else (cfg.sliding_window or 0)


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int,
                     block_size: int, n_blocks: int):
    """Empty paged pool cache: zeroed arenas, sentinel block tables
    (entries == ``n_blocks``, so writes drop), ``lens`` all zero."""
    w = paged_table_width(cfg, block_size, max_len)
    meta = {
        "block_tables": jnp.full((batch, w), n_blocks, jnp.int32),
        "lens": jnp.zeros((batch,), jnp.int32),
        "max_len": jnp.asarray(max_len, jnp.int32),
    }
    dt = _cache_dtype(cfg)
    if cfg.mla:
        return {
            "c_kv": jnp.zeros(
                (cfg.n_layers, n_blocks, block_size, cfg.kv_lora_rank), dt),
            "k_rope": jnp.zeros(
                (cfg.n_layers, n_blocks, block_size, cfg.qk_rope_dim), dt),
            **meta,
        }
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt), **meta}


def _is_ring(cfg: ModelConfig, capacity: int) -> bool:
    """Window-sized caches run as ring buffers; full-length caches (the
    reference layout, or window >= max_len) stay linear.  When capacity
    equals both the window and max_len the two layouts coincide (the
    frontier never wraps), so the ambiguity is harmless."""
    return bool(cfg.sliding_window) and capacity == cfg.sliding_window


_pad_time = L.pad_cache_time


def _ring_pack(kv, w: int):
    """Fold prompt KV (L,B,S,...) with S > w into ring layout: slot i
    holds the latest absolute position q <= S-1 with q % w == i."""
    s = kv.shape[2]
    idx = jnp.arange(w)
    abs_q = (s - 1) - lax.rem((s - 1) - idx, w)           # all >= s - w >= 0
    return jnp.take(kv, abs_q, axis=2)


def prefill(params, tokens, cfg: ModelConfig, visual=None, *,
            max_len=None, prompt_lens=None, window_ring: bool = True,
            block_size: int = 0, n_blocks: int = 0, block_tables=None):
    """Run the full prompt, return (cache, logits at the last position).

    ``max_len`` preallocates decode headroom (default: no headroom, the
    cache is exactly prompt-sized — decode_step will then refuse to
    write past it instead of clamp-overwriting the last slot).

    ``prompt_lens`` (B,) enables ragged batches: ``tokens`` is
    LEFT-padded to a common length, row b's real tokens occupy the last
    ``prompt_lens[b]`` slots, get RoPE positions 0..len-1, and pad keys
    are masked out of attention for that row only.

    ``block_tables`` (B, W) switches on the PAGED lane: instead of a
    dense (B, max_len) cache, each row's KV is packed into the arena
    blocks its table names (``block_size``/``n_blocks`` size the arena;
    unassigned = sentinel ``n_blocks``, whose scatter is dropped).  The
    KV *values* are identical to the linear lane's — only the storage
    layout changes.
    """
    b, s = tokens.shape
    ml = s if max_len is None else int(max_len)
    if ml < s:
        raise ValueError(f"prefill max_len={ml} < prompt length {s}")
    if prompt_lens is None:
        lens = jnp.full((b,), s, jnp.int32)
        positions = jnp.arange(s)[None, :]
        kv_mask = None
    else:
        lens = jnp.asarray(prompt_lens, jnp.int32)
        positions = jnp.arange(s)[None, :] - (s - lens)[:, None]
        kv_mask = positions >= 0
    x = _embed(params, tokens, cfg, visual)

    def body(h, lp):
        h2, kv = _block_forward(lp, h, positions, cfg, kv_mask=kv_mask)
        return h2, tuple(_maybe_quant_kv(t, cfg) for t in kv)

    body = jax.checkpoint(body) if cfg.remat == "layer" else body
    x, kvs = lax.scan(body, x, params["layers"])
    x = L.rms_norm(params["final_norm"], x, cfg)
    last = x[:, -1:, :]
    logits = (last @ _unembed_weight(params, cfg).astype(x.dtype))
    logits = logits[:, 0, :].astype(jnp.float32)

    if block_tables is not None:
        tables = jnp.asarray(block_tables, jnp.int32)
        empty = init_paged_cache(cfg, b, ml, int(block_size),
                                 int(n_blocks))
        cache = dict(empty, block_tables=tables, lens=lens)
        shift = (s - lens) if prompt_lens is not None else None
        keys = ("c_kv", "k_rope") if cfg.mla else ("k", "v")
        for key, kv in zip(keys, kvs):
            cache[key] = L.paged_pack(
                cache[key], kv, tables, lens,
                window=_paged_window(cfg), src_shift=shift)
        return cache, logits

    meta = _cache_meta(b, s, ml, lens)
    if cfg.mla:
        cache = {"c_kv": _pad_time(kvs[0], ml),
                 "k_rope": _pad_time(kvs[1], ml), **meta}
    else:
        window = cfg.sliding_window or 0
        cap = min(ml, window) if (window and window_ring) else ml
        pack = _ring_pack if s > cap else _pad_time
        cache = {"k": pack(kvs[0], cap), "v": pack(kvs[1], cap), **meta}
    return cache, logits


def _zero_invalid(x, mask):
    """Zero time-axis slots whose (B, T) mask is False.  Gathered arena
    garbage (evicted ring blocks, sentinel clamps, sanitizer poison) is
    finite-but-absurd; zeroing keeps the dead slots' downstream matmuls
    finite, and valid slots are untouched, so it cannot perturb the
    chunked-prefill identity."""
    return jnp.where(mask.reshape(mask.shape + (1,) * (x.ndim - 2)), x, 0)


def _chunk_virtual_tables(tables, lens, bs: int, window: int,
                          virtual_width: int, n_blocks: int):
    """Position-ORDERED virtual block tables for the chunked-prefill
    gather: virtual block ``vb`` of row ``b`` names the physical block
    holding absolute positions ``[vb*bs, (vb+1)*bs)``, or the sentinel.

    Dense/MLA tables are already position-ordered (pad with sentinels to
    the virtual width).  The window ring stores logical block ``q`` at
    slot ``q % W``; pre-chunk, exactly blocks ``lb_max-W+1 .. lb_max``
    (``lb_max = (lens-1)//bs``) hold their latest content, so those map
    through the ring and everything else is the sentinel.  The ring
    invariant ``W*bs >= window + bs`` puts every position below
    ``lb_min*bs`` strictly out of the window of every query at position
    ``>= lens`` — evicted content is never needed.

    Returns ``(vtables (B, virtual_width), low_pos (B,))`` where
    ``low_pos`` is the first position the gather actually covers."""
    b, w = tables.shape
    vw = int(virtual_width)
    if L.paged_is_window_lane(window, bs, w):
        lens = jnp.asarray(lens, jnp.int32)
        lb_max = (lens - 1) // bs                         # -1 at lens == 0
        lb_min = jnp.maximum(lb_max - w + 1, 0)
        vb = jnp.arange(vw, dtype=jnp.int32)[None, :]
        slot = jnp.broadcast_to(lax.rem(vb, w), (b, vw))
        phys = jnp.take_along_axis(tables, slot, axis=1)
        resident = (vb >= lb_min[:, None]) & (vb <= lb_max[:, None])
        vtables = jnp.where(resident, phys, n_blocks)
        return vtables, lb_min * bs
    if vw < w:
        raise ValueError(
            f"chunked prefill virtual width {vw} < table width {w}")
    if vw > w:
        tables = jnp.concatenate(
            [tables, jnp.full((b, vw - w), n_blocks, jnp.int32)], axis=1)
    return tables, jnp.zeros((b,), jnp.int32)


def prefill_chunk(params, cache, tokens, cfg: ModelConfig, n_valid, *,
                  virtual_width: int, write_tables=None):
    """Process ``C`` prompt tokens per row against the PAGED cache — the
    chunked-prefill step that makes every prompt length flow through one
    compiled dispatch shape.

    ``tokens``: (B, C) int32 — row b's next prompt tokens for absolute
    positions ``lens[b] .. lens[b]+C-1``; only the first ``n_valid[b]``
    are real (pad the tail with any valid token id — its KV is computed
    but neither written nor attended).  Rows with ``n_valid == 0`` (idle
    or decode-only slots) are exact no-ops: nothing is written and their
    ``lens`` is unchanged.

    ``virtual_width``: static ``ceil(max_len / block_size)`` — the
    position-ordered virtual cache width every lane gathers (the window
    ring is unfolded into it, see ``_chunk_virtual_tables``).

    ``write_tables``: optional (B, W) tables for the arena WRITE
    (``paged_pack_range``); defaults to ``cache['block_tables']``.  The
    prefix-sharing scheduler passes a copy with borrowed entries
    sentineled so a shared block never takes even a byte-identical
    write-back.

    Returns ``(new_cache, logits)`` with ``logits`` (B, V) taken at each
    row's LAST VALID chunk position (meaningful only for rows whose
    prefill completes in this chunk).

    Numerics — the chunked = whole-prompt identity: ``flash_attention``
    groups KV in fixed ``[i*kc, (i+1)*kc)`` blocks regardless of total
    KV length, every resident position's bytes equal what the full
    prefill computed (exactly, when the KV storage dtype is the compute
    dtype), fresh chunk KV is inserted into the virtual buffer BEFORE
    attention (so it is read pre-codec, like a full prefill), and every
    non-resident slot is replace-masked to the same ``-1e30`` a full
    prefill's causal/window bias produces.  Hence each chunk position's
    hidden state is bit-identical to the whole-prompt path (pinned in
    ``tests/test_paged.py`` / ``tests/test_prefix.py``); with a posit KV
    codec, prior-chunk context is read back through the codec (exactly
    what decode reads), so later chunks can differ from a from-scratch
    prefill in the last ulp.  MoE capacity dispatch sees ``C`` tokens
    per call instead of the whole prompt, so under capacity-pressure
    token dropping the identity only holds for non-MoE configs.
    """
    from repro.core.convert import posit_to_f32
    from repro.core.tracing import is_tracer

    b, c = tokens.shape
    tables = cache["block_tables"]
    arena_key = "c_kv" if cfg.mla else "k"
    nb, bs = cache[arena_key].shape[1], cache[arena_key].shape[2]
    window = _paged_window(cfg)
    lens = jnp.asarray(cache["lens"], jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    lens_after = lens + n_valid
    if not is_tracer(lens_after) and not is_tracer(cache["max_len"]):
        import numpy as _np
        la = _np.asarray(lens_after)
        if la.size and int(la.max()) > int(cache["max_len"]):
            raise ValueError(
                f"prefill_chunk: row frontier {int(la.max())} would "
                f"exceed max_len {int(cache['max_len'])}")

    positions = lens[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    vtables, low_pos = _chunk_virtual_tables(
        tables, lens, bs, window, virtual_width, nb)
    t_len = int(virtual_width) * bs
    apos = jnp.arange(t_len, dtype=jnp.int32)[None, :]    # (1, T)
    resident = (apos < lens[:, None]) & (apos >= low_pos[:, None])
    kv_mask = (apos < lens_after[:, None]) & (apos >= low_pos[:, None])
    bidx = jnp.arange(b)[:, None]

    def load(arena):
        g = L.paged_gather(arena, vtables)                # (B, T, ...)
        if cfg.kv_posit:
            with jax.named_scope("kv_decode"):
                g = posit_to_f32(g, L.pcfg(cfg.kv_posit))
        return _zero_invalid(g.astype(L.cdtype(cfg)), resident)

    def insert(ctx, fresh):
        # scatter row b's fresh chunk at virtual slots lens[b]+j; pad
        # positions past the virtual buffer drop (never clamp)
        return ctx.at[bidx, positions].set(fresh, mode="drop")

    x = _embed(params, tokens, cfg)

    if cfg.mla:
        def body(h, layer):
            lp, c_a, r_a = layer
            hn = L.rms_norm(lp["ln1"], h, cfg)
            q_lat = L.rms_norm(lp["attn"]["q_norm"],
                               L.dense(lp["attn"]["wdq"], hn, cfg), cfg)
            q = L.dense(lp["attn"]["wuq"], q_lat, cfg).reshape(
                b, c, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
            q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
            q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)
            q = jnp.concatenate([q_nope, q_rope], -1)

            dkv = L.dense(lp["attn"]["wdkv"], hn, cfg)
            c_suf, r_suf = jnp.split(dkv, [cfg.kv_lora_rank], axis=-1)
            c_suf = L.rms_norm(lp["attn"]["kv_norm"], c_suf, cfg)
            r_suf = L.apply_rope(r_suf[:, :, None, :], positions,
                                 cfg.rope_theta)[:, :, 0, :]

            c_all = insert(load(c_a), c_suf)              # (B, T, rank)
            r_all = insert(load(r_a), r_suf)
            k_nope = L.dense(lp["attn"]["wuk"], c_all, cfg).reshape(
                b, t_len, cfg.n_heads, cfg.qk_nope_dim)
            v = L.dense(lp["attn"]["wuv"], c_all, cfg).reshape(
                b, t_len, cfg.n_heads, cfg.v_head_dim)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(
                    r_all[:, :, None, :],
                    (b, t_len, cfg.n_heads, cfg.qk_rope_dim))], -1)
            out = L.flash_attention(q, k, v, causal=True, cfg=cfg,
                                    kv_mask=kv_mask, q_positions=positions)
            out = out.reshape(b, c, cfg.n_heads * cfg.v_head_dim)
            h = h + L.dense(lp["attn"]["wo"], out, cfg)
            hh = L.rms_norm(lp["ln2"], h, cfg)
            with jax.named_scope("mlp"):
                f = L.moe(lp["moe"], hh, cfg) if cfg.is_moe else \
                    L.mlp(lp["mlp"], hh, cfg)
            return h + f, (_maybe_quant_kv(c_suf, cfg),
                           _maybe_quant_kv(r_suf, cfg))

        x, kv_new = lax.scan(
            body, x, (params["layers"], cache["c_kv"], cache["k_rope"]))
        keys = ("c_kv", "k_rope")
    else:
        def body(h, layer):
            lp, k_a, v_a = layer
            hn = L.rms_norm(lp["ln1"], h, cfg)
            q = L.dense(lp["attn"]["wq"], hn, cfg).reshape(
                b, c, cfg.n_heads, cfg.head_dim)
            k_suf = L.dense(lp["attn"]["wk"], hn, cfg).reshape(
                b, c, cfg.n_kv_heads, cfg.head_dim)
            v_suf = L.dense(lp["attn"]["wv"], hn, cfg).reshape(
                b, c, cfg.n_kv_heads, cfg.head_dim)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k_suf = L.apply_rope(k_suf, positions, cfg.rope_theta)
            k = insert(load(k_a), k_suf)                  # (B, T, G, D)
            v = insert(load(v_a), v_suf)
            out = L.flash_attention(q, k, v, causal=True, cfg=cfg,
                                    window=cfg.sliding_window,
                                    kv_mask=kv_mask, q_positions=positions)
            out = out.reshape(b, c, cfg.n_heads * cfg.head_dim)
            h = h + L.dense(lp["attn"]["wo"], out, cfg)
            hh = L.rms_norm(lp["ln2"], h, cfg)
            with jax.named_scope("mlp"):
                f = L.moe(lp["moe"], hh, cfg) if cfg.is_moe else \
                    L.mlp(lp["mlp"], hh, cfg)
            return h + f, (_maybe_quant_kv(k_suf, cfg),
                           _maybe_quant_kv(v_suf, cfg))

        x, kv_new = lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        keys = ("k", "v")

    wt = tables if write_tables is None else \
        jnp.asarray(write_tables, jnp.int32)
    new_cache = dict(cache, lens=lens_after)
    for key, kv in zip(keys, kv_new):
        new_cache[key] = L.paged_pack_range(
            cache[key], kv, wt, lens, lens_after, window=window)

    with jax.named_scope("lm_head"):
        x = L.rms_norm(params["final_norm"], x, cfg)
        last = jnp.take_along_axis(
            x, jnp.clip(n_valid - 1, 0, c - 1)[:, None, None], axis=1)
        logits = (last @ _unembed_weight(params, cfg).astype(x.dtype))
    return new_cache, logits[:, 0, :].astype(jnp.float32)


def _decode_attn_dense(p, x, k_cache, v_cache, pos, lens, cfg: ModelConfig):
    b = x.shape[0]
    capacity = k_cache.shape[1]
    window = cfg.sliding_window or 0
    ring = _is_ring(cfg, capacity)
    q = L.dense(p["wq"], x, cfg).reshape(b, 1, cfg.n_heads, cfg.head_dim)
    k = L.dense(p["wk"], x, cfg).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    v = L.dense(p["wv"], x, cfg).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    q = L.apply_rope(q, lens[:, None], cfg.rope_theta)
    k = L.apply_rope(k, lens[:, None], cfg.rope_theta)

    slot = lax.rem(pos, capacity) if ring else pos
    k_cache = L.guarded_cache_update(
        k_cache, _maybe_quant_kv(k, cfg), slot, 1)
    v_cache = L.guarded_cache_update(
        v_cache, _maybe_quant_kv(v, cfg), slot, 1)
    out = L.decode_attention(
        q, k_cache, v_cache, pos + 1, cfg=cfg, kv_posit=cfg.kv_posit,
        window=window, start=pos - lens, ring=ring)
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    return L.dense(p["wo"], out, cfg), k_cache, v_cache


def _decode_attn_mla(p, x, c_cache, r_cache, pos, lens, cfg: ModelConfig):
    """Absorbed-matrix MLA decode: attend in the compressed latent space."""
    b = x.shape[0]
    q_lat = L.rms_norm(p["q_norm"], L.dense(p["wdq"], x, cfg), cfg)
    q = L.dense(p["wuq"], q_lat, cfg).reshape(
        b, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = L.apply_rope(q_rope[:, None], lens[:, None],
                          cfg.rope_theta)[:, 0]

    dkv = L.dense(p["wdkv"], x, cfg)                      # (B,1,rank+rope)
    c_new, r_new = jnp.split(dkv, [cfg.kv_lora_rank], axis=-1)
    c_new = L.rms_norm(p["kv_norm"], c_new, cfg)
    r_new = L.apply_rope(r_new[:, :, None, :], lens[:, None],
                         cfg.rope_theta)[:, :, 0, :]
    c_cache = L.guarded_cache_update(
        c_cache, _maybe_quant_kv(c_new, cfg), pos, 1)
    r_cache = L.guarded_cache_update(
        r_cache, _maybe_quant_kv(r_new, cfg), pos, 1)

    c = c_cache
    r = r_cache
    if cfg.kv_posit:
        from repro.core.convert import posit_to_f32
        c = posit_to_f32(c, L.pcfg(cfg.kv_posit))
        r = posit_to_f32(r, L.pcfg(cfg.kv_posit))
    c = c.astype(jnp.float32)
    r = r.astype(jnp.float32)

    wuk = L.maybe_dequant(p["wuk"]["w"], cfg).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim)
    # absorb: q_eff[h] = q_nope[h] @ wuk[:,h,:].T  -> latent-space query
    q_lat_eff = jnp.einsum("bhd,rhd->bhr", q_nope.astype(jnp.float32), wuk)
    scores = jnp.einsum("bhr,btr->bht", q_lat_eff, c)
    scores += jnp.einsum("bhd,btd->bht", q_rope.astype(jnp.float32), r)
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    t_len = c.shape[1]
    t_pos = jnp.arange(t_len)
    valid = (t_pos[None, :] <= pos) & \
        (t_pos[None, :] >= (pos - lens)[:, None])         # (B,T)
    scores = jnp.where(valid[:, None, :], scores * scale, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx_lat = jnp.einsum("bht,btr->bhr", probs, c)        # (B,H,rank)
    wuv = L.maybe_dequant(p["wuv"]["w"], cfg).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.v_head_dim)
    out = jnp.einsum("bhr,rhv->bhv", ctx_lat, wuv)
    out = out.reshape(b, 1, cfg.n_heads * cfg.v_head_dim).astype(x.dtype)
    return L.dense(p["wo"], out, cfg), c_cache, r_cache


def _decode_attn_dense_paged(p, x, k_arena, v_arena, layer, tables, lens,
                             ok, cfg: ModelConfig, read_steps=None):
    """Paged dense/GQA decode: per-row write position ``lens[b]`` into the
    row's block, then attention straight off the block tables
    (``cfg.paged_attn_kernel`` picks the fused Pallas table walk or the
    gather+jnp reference).  The same projections, RoPE positions
    (content-relative ``lens``) and softmax math as the linear lane —
    only the storage addressing differs, so the scores over valid
    positions are identical.  ``layer`` indexes the stacked
    ``(L, n_blocks, ...)`` arenas; None takes one layer's arenas.
    ``read_steps`` bounds the gather to the step's extent
    (``layers.decode_attention_paged``)."""
    b = x.shape[0]
    window = _paged_window(cfg)
    q = L.dense(p["wq"], x, cfg).reshape(b, 1, cfg.n_heads, cfg.head_dim)
    k = L.dense(p["wk"], x, cfg).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    v = L.dense(p["wv"], x, cfg).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    q = L.apply_rope(q, lens[:, None], cfg.rope_theta)
    k = L.apply_rope(k, lens[:, None], cfg.rope_theta)

    k_arena = L.paged_cache_update(
        k_arena, _maybe_quant_kv(k, cfg)[:, 0], tables, lens, ok,
        window=window, layer=layer)
    v_arena = L.paged_cache_update(
        v_arena, _maybe_quant_kv(v, cfg)[:, 0], tables, lens, ok,
        window=window, layer=layer)
    out = L.decode_attention_paged(
        q, k_arena, v_arena, tables, lens, cfg=cfg, kv_posit=cfg.kv_posit,
        window=window, kernel=cfg.paged_attn_kernel, read_steps=read_steps,
        layer=layer)
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    return L.dense(p["wo"], out, cfg), k_arena, v_arena


def _decode_attn_mla_paged(p, x, c_arena, r_arena, layer, tables, lens, ok,
                           cfg: ModelConfig, read_steps=None):
    """Paged absorbed-matrix MLA decode (row-local positions);
    ``cfg.paged_attn_kernel`` picks the fused latent-space table walk
    or the gather+jnp reference.  ``layer`` and ``read_steps`` as in
    :func:`_decode_attn_dense_paged`."""
    b = x.shape[0]
    q_lat = L.rms_norm(p["q_norm"], L.dense(p["wdq"], x, cfg), cfg)
    q = L.dense(p["wuq"], q_lat, cfg).reshape(
        b, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = L.apply_rope(q_rope[:, None], lens[:, None],
                          cfg.rope_theta)[:, 0]

    dkv = L.dense(p["wdkv"], x, cfg)                      # (B,1,rank+rope)
    c_new, r_new = jnp.split(dkv, [cfg.kv_lora_rank], axis=-1)
    c_new = L.rms_norm(p["kv_norm"], c_new, cfg)
    r_new = L.apply_rope(r_new[:, :, None, :], lens[:, None],
                         cfg.rope_theta)[:, :, 0, :]
    c_arena = L.paged_cache_update(
        c_arena, _maybe_quant_kv(c_new, cfg)[:, 0], tables, lens, ok,
        layer=layer)
    r_arena = L.paged_cache_update(
        r_arena, _maybe_quant_kv(r_new, cfg)[:, 0], tables, lens, ok,
        layer=layer)

    wuk = L.maybe_dequant(p["wuk"]["w"], cfg).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim)
    q_lat_eff = jnp.einsum("bhd,rhd->bhr", q_nope.astype(jnp.float32), wuk)
    ctx_lat = L.decode_attention_paged_mla(
        q_lat_eff, q_rope, c_arena, r_arena, tables, lens, cfg=cfg,
        kv_posit=cfg.kv_posit, kernel=cfg.paged_attn_kernel,
        read_steps=read_steps, layer=layer)
    wuv = L.maybe_dequant(p["wuv"]["w"], cfg).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.v_head_dim)
    out = jnp.einsum("bhr,rhv->bhv", ctx_lat, wuv)
    out = out.reshape(b, 1, cfg.n_heads * cfg.v_head_dim).astype(x.dtype)
    return L.dense(p["wo"], out, cfg), c_arena, r_arena


def _decode_step_paged(params, cache, token, cfg: ModelConfig, active):
    """Paged decode: every row writes at its OWN position ``lens[b]`` (no
    shared frontier), inactive rows' writes are dropped and their
    ``lens`` frozen.  Out-of-capacity positions drop too (the no-clamp
    guarantee); concrete frontiers raise eagerly like the linear lane.

    The layer scan carries the two stacked arenas whole: each layer
    writes its new KV with one scatter at ``(layer, block, offset)``,
    so the arenas are updated in place.  Passing the arenas through the
    scan as ``xs``/``ys`` instead rewrites each layer's slice around
    the scatter and restacks it into a fresh arena, which the caller's
    step scan then copies back: a whole-arena copy every decode
    step.  Each layer reads its blocks by the one read rule
    (``layers.paged_reads_flat``): a lane-dense arena (dense K/V, the
    MLA latent) with one one-index gather straight off the carried
    arena, flattened to ``(L*n_blocks, ...)`` with the layer's table
    entries offset by ``layer * n_blocks``; the 32-wide RoPE arena from
    a slice of its layer, since its block-minor layout would relayout
    the whole stacked arena for a flat read, and a two-index gather at
    ``(layer, table)`` ran 20x slower than the slice on a v5e.

    Every layer's gather reads only the width steps that cover the
    decoding rows' longest content (``layers.paged_read_positions`` of
    their largest ``lens + 1``), chosen once per step on the device.
    Rows that do not decode (prefilling, idle) do not widen the read;
    their outputs are discarded."""
    from repro.core.tracing import is_tracer

    b = token.shape[0]
    lens = jnp.asarray(cache["lens"], jnp.int32)
    tables = cache["block_tables"]
    adv = jnp.ones((b,), jnp.int32) if active is None \
        else jnp.asarray(active).astype(jnp.int32)
    if not is_tracer(lens) and not is_tracer(cache["max_len"]):
        import numpy as _np
        live = _np.asarray(adv).astype(bool)
        if live.any():
            L.check_cache_capacity(
                int(_np.asarray(lens)[live].max()),
                int(cache["max_len"]), "paged KV cache")
    ok = (adv > 0) & (lens < jnp.asarray(cache["max_len"], jnp.int32))
    x = params["tok_embed"][token][:, None, :].astype(L.cdtype(cfg))
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

    keys = ("c_kv", "k_rope") if cfg.mla else ("k", "v")
    attn = _decode_attn_mla_paged if cfg.mla else _decode_attn_dense_paged
    w, bs = tables.shape[1], cache[keys[0]].shape[2]
    window = _paged_window(cfg)
    extent = jnp.max(jnp.where(ok, lens + 1, 0))
    read_steps = -(-L.paged_read_positions(extent, w, bs, window=window)
                   // L.paged_read_step(w, bs, window=window))

    def body(carry, layer):
        h, a_arena, b_arena = carry
        lp, li = layer
        with jax.named_scope("decode_attn"):
            a, a_arena, b_arena = attn(
                lp["attn"], L.rms_norm(lp["ln1"], h, cfg), a_arena, b_arena,
                li, tables, lens, ok, cfg, read_steps)
        h = h + a
        hh = L.rms_norm(lp["ln2"], h, cfg)
        with jax.named_scope("mlp"):
            f = L.moe(lp["moe"], hh, cfg) if cfg.is_moe else \
                L.mlp(lp["mlp"], hh, cfg)
        return (h + f, a_arena, b_arena), None

    n_layers = cache[keys[0]].shape[0]
    (x, a_new, b_new), _ = lax.scan(
        body, (x, cache[keys[0]], cache[keys[1]]),
        (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)))
    new_cache = dict(cache, lens=lens + adv)
    new_cache.update(zip(keys, (a_new, b_new)))

    with jax.named_scope("lm_head"):
        x = L.rms_norm(params["final_norm"], x, cfg)
        logits = (x[:, 0, :] @ _unembed_weight(params, cfg).astype(x.dtype))
    return logits.astype(jnp.float32), new_cache


def _decode_lens(cache, pos, batch: int):
    lens = cache.get("lens")
    if lens is None:                         # legacy cache without metadata
        lens = jnp.broadcast_to(pos, (batch,))
    return lens


def decode_step(params, cache, token, cfg: ModelConfig, active=None):
    """token: (B,) int32 -> (logits (B,V) f32, new cache).

    ``active``: optional (B,) bool — rows that hold a live request.  The
    shared padded frontier ``len`` always advances (every row is written
    at the same slot), but an inactive row's ``lens`` stays put, so an
    empty scheduler slot never accretes phantom valid tokens: its
    attention window stays pinned to the (masked) frontier and, crucially,
    its ``lens`` cannot hold ``compact`` back from reclaiming headroom.
    Inactive rows still produce (discarded) logits — batched decode has
    no per-row early exit.

    Paged caches (a ``block_tables`` leaf) take the row-local lane:
    every row writes at its own ``lens[b]`` inside its own blocks, so
    there is no shared frontier to advance (and no ``len`` leaf).
    """
    if "block_tables" in cache:
        return _decode_step_paged(params, cache, token, cfg, active)
    pos = cache["len"]
    b = token.shape[0]
    lens = _decode_lens(cache, pos, b)
    adv = jnp.ones((b,), jnp.int32) if active is None \
        else jnp.asarray(active).astype(jnp.int32)
    x = params["tok_embed"][token][:, None, :].astype(L.cdtype(cfg))
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

    if cfg.mla:
        L.check_cache_capacity(pos, cache["c_kv"].shape[2],
                               "MLA latent cache")

        def body(h, layer):
            lp, c_c, r_c = layer
            a, c_c, r_c = _decode_attn_mla(
                lp["attn"], L.rms_norm(lp["ln1"], h, cfg), c_c, r_c,
                pos, lens, cfg)
            h = h + a
            hh = L.rms_norm(lp["ln2"], h, cfg)
            f = L.moe(lp["moe"], hh, cfg) if cfg.is_moe else \
                L.mlp(lp["mlp"], hh, cfg)
            return h + f, (c_c, r_c)

        x, (c_new, r_new) = lax.scan(
            body, x, (params["layers"], cache["c_kv"], cache["k_rope"]))
        new_cache = dict(cache, c_kv=c_new, k_rope=r_new,
                         len=pos + 1, lens=lens + adv)
    else:
        capacity = cache["k"].shape[2]
        if not _is_ring(cfg, capacity):
            L.check_cache_capacity(pos, capacity)

        def body(h, layer):
            lp, k_c, v_c = layer
            a, k_c, v_c = _decode_attn_dense(
                lp["attn"], L.rms_norm(lp["ln1"], h, cfg), k_c, v_c,
                pos, lens, cfg)
            h = h + a
            hh = L.rms_norm(lp["ln2"], h, cfg)
            f = L.moe(lp["moe"], hh, cfg) if cfg.is_moe else \
                L.mlp(lp["mlp"], hh, cfg)
            return h + f, (k_c, v_c)

        x, (k_new, v_new) = lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        new_cache = dict(cache, k=k_new, v=v_new, len=pos + 1,
                         lens=lens + adv)

    x = L.rms_norm(params["final_norm"], x, cfg)
    logits = (x[:, 0, :] @ _unembed_weight(params, cfg).astype(x.dtype))
    return logits.astype(jnp.float32), new_cache
