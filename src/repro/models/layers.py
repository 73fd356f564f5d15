"""Shared neural layers (pure functional, pjit/GSPMD-friendly).

Conventions
-----------
* params are plain dict pytrees of jnp arrays; init functions take an rng
  key and a ModelConfig and are ``jax.eval_shape``-safe (used by the
  dry-run to build ShapeDtypeStruct trees without allocating).
* activations run in ``cfg.compute_dtype``; params stay float32 unless a
  serving transform quantized them to posit patterns (unsigned dtypes), in
  which case every consumer dequantizes on the fly (the paper's technique
  as a storage dtype).
* attention is chunked-flash (online softmax over KV blocks) in pure JAX
  so the same code lowers on TPU *and* CPU; the causal variant can skip
  future blocks with lax.cond (cfg.causal_skip='cond').
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.convert import posit_to_f32
from repro.core.types import POSIT8, POSIT16
from .config import ModelConfig

_PCFGS = {"posit16": POSIT16, "posit8": POSIT8}


def pcfg(name: str):
    return _PCFGS[name]


def cdtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32


def maybe_dequant(w, cfg: ModelConfig):
    """Posit-quantized weights (unsigned ints) decode on the fly."""
    if jnp.issubdtype(w.dtype, jnp.unsignedinteger):
        return posit_to_f32(w, pcfg(cfg.weight_posit or "posit16"))
    return w


def dense(p, x, cfg: ModelConfig):
    if cfg.posit_exact_linear:
        return dense_posit_exact(p, x, cfg)
    w = maybe_dequant(p["w"], cfg).astype(x.dtype)
    y = x @ w
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def dense_posit_exact(p, x, cfg: ModelConfig,
                      interpret: Optional[bool] = None):
    """Bit-exact posit linear for numerics audits (cfg.posit_exact_linear).

    Runs the paper's §IV-E datapath end to end in the posit domain:
    activations quantize once, ``kernels.ops.pgemm`` reduces every output
    element through the streaming quire (one rounding each), the bias
    adds with the fused single-rounding ``vadd``, and the result
    dequantizes once.  Exactly three roundings per output regardless of
    K — the float path rounds per f32 op — so this is the ground truth
    the throughput ``dense`` is audited against.  Orders of magnitude
    slower; never use it on a serving path.
    """
    from repro.kernels import ops as kops   # keep pallas out of model import
    pc = pcfg(cfg.weight_posit or "posit16")
    w = p["w"]
    wq = (w if jnp.issubdtype(w.dtype, jnp.unsignedinteger)
          else kops.quantize(w.astype(jnp.float32), pc, interpret=interpret))
    xq = kops.quantize(x.astype(jnp.float32), pc, interpret=interpret)
    yq = kops.pgemm(xq, wq, pc, interpret=interpret)
    if "b" in p:
        bq = kops.quantize(p["b"].astype(jnp.float32), pc,
                           interpret=interpret)
        yq = kops.vadd(yq, bq, pc, interpret=interpret)
    return posit_to_f32(yq, pc).astype(x.dtype)


def init_dense(key, d_in, d_out, bias=False, scale=None):
    scale = (d_in ** -0.5) if scale is None else scale
    p = {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def rms_norm(p, x, cfg: ModelConfig):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * lax.rsqrt(var + cfg.norm_eps)
    w = p["scale"].astype(jnp.float32)
    if cfg.norm_plus_one:
        w = 1.0 + w
    return (x * w).astype(dt)


def init_rms_norm(d, cfg: ModelConfig):
    init = jnp.zeros if cfg.norm_plus_one else jnp.ones
    return {"scale": init((d,), jnp.float32)}


def layer_norm(p, x, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(dt)


def init_layer_norm(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D) with D even; positions: (..., S)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                     # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked flash attention (pure JAX online softmax)
# ---------------------------------------------------------------------------

_NEG = -1e30


def _pick_chunk(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (static shapes only)."""
    c = min(target, n)
    while n % c:
        c -= 1
    return c


def _attn_block(q, k, v, bias):
    """q: (B,G,R,Qc,D) k: (B,G,Kc,D) v: (B,G,Kc,Dv) bias: (Qc,Kc) or None."""
    s = jnp.einsum("bgrqd,bgkd->bgrqk", q, k).astype(jnp.float32)
    if bias is not None:
        s = s + bias
    return s


def _attn_context_parallel(q, k, v, cfg: ModelConfig):
    """Context-parallel attention sharding: q's sequence dim over 'model',
    k/v replicated over 'model'.

    Rationale (§Perf iteration 1): when head counts do not divide TP=16
    (kv=8, H=14/24/25/40...), GSPMD falls back to sharding the *head_dim
    contraction* of the score einsum, inserting an all-reduce of every
    (qc, kc) score block — 13 TB/chip on granite-moe prefill.  The
    sequence dim always divides, keeps the contraction local, and
    composes with the Megatron-SP residual constraint (same layout, no
    resharding between layers).  No-op outside a mesh context.
    """
    if not cfg.seq_shard_activations:
        return q, k, v
    try:
        from jax.sharding import PartitionSpec as P
        baxes = tuple(cfg.batch_axes)
        q = lax.with_sharding_constraint(q, P(baxes, "model", None, None))
        k = lax.with_sharding_constraint(k, P(baxes, None, None, None))
        v = lax.with_sharding_constraint(v, P(baxes, None, None, None))
    except (ValueError, RuntimeError, TypeError, NameError):
        pass
    return q, k, v


def flash_attention(q, k, v, *, causal: bool, cfg: ModelConfig,
                    window: int = 0, q_offset: int = 0, kv_mask=None,
                    q_positions=None):
    """q: (B,S,H,D); k,v: (B,T,G,D[v]) grouped-query; returns (B,S,H,Dv).

    Scans KV in blocks with an online-softmax carry; the causal variant
    optionally skips strictly-future blocks with lax.cond.

    ``kv_mask``: optional (B, T) bool — False keys are excluded for that
    batch row (left-padded ragged prompts in the serving engine, padded
    or garbage cache slots in the chunked-prefill lane).

    ``q_positions``: optional (B, S) int32 absolute position per query
    (chunked prefill: every batch row sits at its own cache frontier);
    overrides the shared ``q_offset + arange`` positions, making the
    causal/window bias per-row.

    KV is always padded up to a multiple of ``cfg.attn_chunk_kv``, so
    KV block ``i`` covers absolute positions ``[i*kc, (i+1)*kc)`` no
    matter the total KV length: a full-prompt prefill and a chunked
    prefill reading back the same positions reduce in bitwise-identical
    groups (the scheduler's chunked-mode identity guarantee).  Padded
    keys are excluded via ``kv_mask``; once the running max is finite a
    fully-masked block is an exact no-op (``exp`` underflows to 0), and
    leading fully-masked blocks are annihilated exactly by the first
    valid block's ``alpha = exp(-1e30 - m) == 0`` rescale.
    """
    q, k, v = _attn_context_parallel(q, k, v, cfg)
    b, s_len, h, d = q.shape
    t_len, g = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    r = h // g
    scale = d ** -0.5
    qc = _pick_chunk(s_len, cfg.attn_chunk_q)
    kc = int(cfg.attn_chunk_kv)
    t_pad = -(-t_len // kc) * kc
    if t_pad != t_len:
        pad = t_pad - t_len
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_mask is None:
            kv_mask = jnp.ones((b, t_len), bool)
        kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad)))
    n_q, n_k = s_len // qc, t_pad // kc

    qg = (q.reshape(b, n_q, qc, g, r, d).transpose(1, 0, 3, 4, 2, 5)
          * scale)                                          # (nq,B,G,R,qc,D)
    kg = k.reshape(b, n_k, kc, g, d).transpose(1, 0, 3, 2, 4)
    vg = v.reshape(b, n_k, kc, g, dv).transpose(1, 0, 3, 2, 4)
    km = (kv_mask.reshape(b, n_k, kc).transpose(1, 0, 2)
          if kv_mask is not None else None)                 # (nk,B,kc)

    if q_positions is not None:
        q_pos = jnp.asarray(q_positions, jnp.int32) \
            .reshape(b, n_q, qc).transpose(1, 0, 2)         # (nq,B,qc)
    else:
        q_pos = q_offset + jnp.arange(s_len).reshape(n_q, qc)
    k_pos = jnp.arange(t_pad).reshape(n_k, kc)

    def one_q_chunk(qi):
        qblk = qg[qi]
        qp = q_pos[qi]                                      # (qc,) | (B,qc)

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk, kp = kg[ki], vg[ki], k_pos[ki]

            def compute(args):
                m, l, acc = args
                # qp[..., :, None] - kp broadcasts to (qc,kc) for shared
                # positions or (B,qc,kc) for per-row positions
                bias = jnp.zeros((qc, kc), jnp.float32)
                if causal:
                    bias = jnp.where(
                        qp[..., :, None] >= kp, 0.0, _NEG)
                if window:
                    bias = bias + jnp.where(
                        qp[..., :, None] - kp < window, 0.0, _NEG)
                if bias.ndim == 3:                          # per-row bias
                    bias = bias[:, None, None]              # (B,1,1,qc,kc)
                sblk = _attn_block(qblk, kblk, vblk, bias)  # (B,G,R,qc,kc)
                if km is not None:
                    sblk = jnp.where(
                        km[ki][:, None, None, None, :], sblk, _NEG)
                m_new = jnp.maximum(m, sblk.max(-1))
                p = jnp.exp(sblk - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l_new = l * alpha + p.sum(-1)
                acc_new = acc * alpha[..., None] + jnp.einsum(
                    "bgrqk,bgkv->bgrqv", p.astype(vblk.dtype), vblk
                ).astype(jnp.float32)
                return m_new, l_new, acc_new

            if causal and cfg.causal_skip == "cond":
                relevant = kp[0] <= qp.max()
                if window:
                    relevant &= (qp.min() - kp[-1]) < window
                m, l, acc = lax.cond(relevant, compute,
                                     lambda a: a, (m, l, acc))
            else:
                m, l, acc = compute((m, l, acc))
            return (m, l, acc), None

        m0 = jnp.full((b, g, r, qc), _NEG, jnp.float32)
        l0 = jnp.zeros((b, g, r, qc), jnp.float32)
        a0 = jnp.zeros((b, g, r, qc, dv), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), jnp.arange(n_k))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out                                          # (B,G,R,qc,Dv)

    outs = lax.map(one_q_chunk, jnp.arange(n_q))            # (nq,B,G,R,qc,Dv)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, s_len, h, dv)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, cfg: ModelConfig,
                     kv_posit: Optional[str] = None, window: int = 0,
                     start=None, ring: bool = False, apos=None):
    """Single-token decode: q (B,1,H,D); caches (B,T,G,D) possibly posit
    patterns; positions >= cache_len are masked.

    Single-shot formulation (§Perf, dbrx decode): one score einsum over
    the full cache.  The earlier chunked scan sliced the seq-sharded
    cache at a *traced* offset, which GSPMD can only lower by
    all-gathering the entire cache every step (21.5 GB/chip/token on
    dbrx).  With one einsum the T axis stays sharded end-to-end: the
    contraction is local and the softmax reductions across shards are
    (B,H)-sized scalars.  Decode scores are tiny (B*H*T f32), so no
    chunking is needed for memory.

    Masking is rotation- and batch-aware:
    * ``cache_len`` — scalar or (B,) — absolute write frontier; a (B,)
      value gives each batch row its own visible length (ragged batches).
    * ``start`` — scalar or (B,) — first valid absolute position (the
      left-padding offset of each row); positions before it are masked.
    * ``ring=True`` — the cache is a ring buffer of capacity T written at
      ``pos % T``: slot ``i`` holds absolute position
      ``p - ((p - i) mod T)`` for frontier ``p = cache_len - 1``, and the
      validity/window tests run on those rotated absolute positions.
    * ``apos`` — optional (B, T) precomputed absolute positions per cache
      slot (the paged lanes supply these from the block-table layout);
      overrides the linear/ring position computation, everything else —
      masking, softmax, value reduction — is the same math.
    """
    b, _, h, d = q.shape
    t_len, g = k_cache.shape[1], k_cache.shape[2]
    r = h // g
    scale = d ** -0.5

    # §Perf iteration 2 (dbrx decode): materialize the dequantized cache
    # in bf16, not f32 — halves the dominant HBM traffic; the score
    # einsum still accumulates in f32.  (On real TPUs the Pallas
    # posit-codec kernel streams u8->VMEM and this materialization
    # disappears entirely; see kernels/posit_gemm.py.)
    ks, vs = k_cache, v_cache
    if kv_posit is not None:
        with jax.named_scope("kv_decode"):
            ks = posit_to_f32(ks, pcfg(kv_posit))
            vs = posit_to_f32(vs, pcfg(kv_posit))
    ks = ks.astype(cdtype(cfg))
    vs = vs.astype(cdtype(cfg))

    qg = (q.reshape(b, g, r, d) * scale).astype(cdtype(cfg))
    # (refuted §Perf iteration: a bf16 softmax was both slightly *slower*
    # on the memory term (+3%, XLA re-materialized converts) and broke
    # decode-vs-prefill agreement; scores stay f32.)
    scores = jnp.einsum("bgrd,btgd->bgrt", qg, ks,
                        preferred_element_type=jnp.float32)  # (B,G,R,T)
    t_pos = jnp.arange(t_len, dtype=jnp.int32)
    cl = jnp.asarray(cache_len, jnp.int32)
    cl = jnp.broadcast_to(cl, (b,)) if cl.ndim == 0 else cl
    st = jnp.asarray(0 if start is None else start, jnp.int32)
    st = jnp.broadcast_to(st, (b,)) if st.ndim == 0 else st
    if apos is not None:
        apos = jnp.asarray(apos, jnp.int32)
    elif ring:
        p = (cl - 1)[:, None]                               # write frontier
        apos = p - lax.rem(p - t_pos[None, :], t_len)       # (B,T) absolute
    else:
        apos = jnp.broadcast_to(t_pos[None, :], (b, t_len))
    valid = (apos < cl[:, None]) & (apos >= st[:, None])
    if ring:
        valid &= apos >= 0                                  # unwritten slots
    if window:
        valid &= apos >= (cl[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, _NEG)
    m = scores.max(-1, keepdims=True)
    # All-masked guard: a row with NO valid slot (an inactive scheduler
    # slot whose sentinel table entries alias real blocks through the
    # gather clamp) has m == _NEG, so exp(scores - m) == 1 EVERYWHERE —
    # a uniform average of garbage.  Zeroing invalid slots makes such a
    # row finalize to exact zeros (l == 0); rows with a valid slot are
    # bit-identical (finite m already underflowed their masked exp to 0).
    p = jnp.where(valid[:, None, None, :], jnp.exp(scores - m), 0.0)
    l = p.sum(-1)
    out = jnp.einsum("bgrt,btgv->bgrv", p.astype(cdtype(cfg)), vs,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, 1, h, -1).astype(q.dtype)


# ---------------------------------------------------------------------------
# Guarded decode-time cache writes
# ---------------------------------------------------------------------------
# ``lax.dynamic_update_slice_in_dim`` CLAMPS out-of-range start indices, so
# an unguarded decode write past the cache capacity silently overwrites the
# last slot — the original serving bug.  Every decode-time cache write goes
# through these two helpers instead: a concrete out-of-capacity index
# raises, a traced one (inside jit/scan, where the engine has already
# checked capacity statically) drops the write rather than clamping.

def check_cache_capacity(pos, capacity: int, what: str = "KV cache"):
    """Raise on a concrete decode position past the cache capacity.

    Traced positions (under jit/scan) cannot raise; there the guarded
    write below degrades to a dropped write — never a clamp-overwrite —
    and the serving engine enforces capacity statically up front.
    """
    from repro.core.tracing import is_tracer
    if is_tracer(pos):
        return
    if int(pos) >= capacity:
        raise ValueError(
            f"decode_step past {what} capacity: position {int(pos)} >= "
            f"{capacity}. Preallocate headroom with init_cache(..., "
            "max_len) / prefill(..., max_len=...) or use "
            "repro.runtime.engine.Engine, which sizes caches up front.")


def guarded_cache_update(arr, upd, idx, axis: int):
    """``dynamic_update_slice_in_dim`` that refuses to clamp: writes at
    ``idx >= capacity`` leave ``arr`` unchanged instead of silently
    overwriting the final slot."""
    new = lax.dynamic_update_slice_in_dim(arr, upd, idx, axis)
    return jnp.where(idx < arr.shape[axis], new, arr)


def roll_cache_time(kv, shift):
    """Circularly shift a stacked-layer KV time axis (L, B, T, ...) by
    ``shift`` slots (traced shifts allowed).

    This is the one primitive behind cache *compaction* and *admission*
    in the continuous-batching scheduler, and it is correct for BOTH
    cache layouts:

    * linear caches: content occupying padded slots ``[len - l, len)``
      moves to ``[len + shift - l, len + shift)``; slots vacated at
      either end hold stale data that the per-row ``lens`` masks already
      exclude (and that a later admission overwrites wholesale);
    * ring buffers (capacity T, writes at ``pos % T``): a frontier move
      of ``shift`` relabels slot ``q % T`` to ``(q + shift) % T`` — the
      circular roll IS that relabelling, no second case needed.
    """
    return jnp.roll(kv, shift, axis=2)


def reset_cache_rows(kv, row_mask, batch_axis: int = 1):
    """Zero the given batch rows of a stacked cache leaf.

    ``row_mask``: (B,) bool, True = clear.  Retired serving slots are
    wiped so a freed row never leaks a previous request's KV into
    reports or debugging dumps (attention already masks it out).
    """
    shape = [1] * kv.ndim
    shape[batch_axis] = row_mask.shape[0]
    return jnp.where(row_mask.reshape(shape), jnp.zeros_like(kv), kv)


def pad_cache_time(kv, t: int):
    """Zero-pad the stacked-layer KV time axis (L,B,S,...) up to ``t`` —
    how prefill turns exactly-prompt-sized KV into a cache with decode
    headroom."""
    s = kv.shape[2]
    if s == t:
        return kv
    pad = [(0, 0)] * kv.ndim
    pad[2] = (0, t - s)
    return jnp.pad(kv, pad)


# ---------------------------------------------------------------------------
# Paged KV cache primitives (block arenas + per-row block tables)
#
# Layout contract (shared with ``compress/kvcache.py`` and the transformer
# paged lanes): arena leaves are (n_blocks, block_size, ...) per layer —
# (L, n_blocks, block_size, ...) stacked — and ``block_tables`` is (B, W)
# int32 with the OUT-OF-RANGE sentinel ``n_blocks`` in unassigned entries.
# Addressing is ROW-LOCAL: row b's token p lives in logical block
# ``p // block_size`` at offset ``p % block_size``.
#
# Two mappings from logical block to table slot:
#   * dense/MLA lane: identity — table slot i IS logical block i
#     (W = ceil(max_len / block_size));
#   * sliding-window lane: a RING over table slots — logical block q maps
#     to slot ``q % W`` with ``W = ceil(window / block_size) + 1``, so a
#     block falling out of the window is recycled in place (the paged
#     re-expression of the ring buffer).  The +1 spare block guarantees
#     every partially-overwritten block's stale half is already outside
#     the window, so masking stale slots as "future" is exact.
# ---------------------------------------------------------------------------


def paged_window_blocks(window: int, block_size: int) -> int:
    """Table width of the sliding-window block ring."""
    return -(-window // block_size) + 1


def paged_is_window_lane(window: int, block_size: int,
                         table_width: int) -> bool:
    """Static lane rule, derivable on both the host (which sizes tables)
    and inside jit (from the table's shape): a paged cache runs the
    block-ring mapping iff its table width equals the window ring's.
    When the dense width coincides the two mappings agree everywhere the
    frontier can reach, so the ambiguity is harmless."""
    return bool(window) and table_width == paged_window_blocks(
        window, block_size)


def paged_positions(frontier, table_width: int, block_size: int, *,
                    window: int = 0):
    """(B,) per-row frontier (last-written position) -> (B, W*bs) absolute
    position of every virtual slot of the gathered paged cache.

    Dense lane: identity.  Window lane: table slot s holds logical block
    ``lb = pb - ((pb - s) mod W)`` for frontier block ``pb``; slots ahead
    of the frontier (or before position 0) get out-of-range positions the
    caller's validity mask excludes — including the stale tail of the
    frontier's own block, whose true (previous-epoch) content is already
    outside the window.
    """
    w, bs = table_width, block_size
    frontier = jnp.asarray(frontier, jnp.int32)
    b = frontier.shape[0]
    offs = jnp.arange(bs, dtype=jnp.int32)
    if paged_is_window_lane(window, bs, w):
        pb = frontier[:, None] // bs                      # (B, 1)
        sblk = jnp.arange(w, dtype=jnp.int32)[None, :]
        lb = pb - lax.rem(pb - sblk, w)                   # (B, W)
        apos = lb[:, :, None] * bs + offs[None, None, :]
    else:
        blk = jnp.arange(w, dtype=jnp.int32)
        apos = (blk[:, None] * bs + offs[None, :])[None]
        apos = jnp.broadcast_to(apos, (b, w, bs))
    return apos.reshape(b, w * bs)


def _arena_head_constraint(x):
    """Pin the head axis of dense paged-KV tensors to the 'model' mesh
    axis: the arena is device_put with heads on 'model'
    (``runtime/sharding.py::paged_cache_specs``), and this constraint on
    the gathered/updated views keeps every paged read and write
    shard-local — decode never all-gathers KV.  MLA latents (no head
    axis, rank-3 views) pass through untouched, as does everything
    outside a mesh context (same no-op contract as
    ``_attn_context_parallel``).  When 'model' does not divide the head
    count the arena itself fell back to replicated
    (``paged_cache_specs``' filter), so the constraint is skipped too —
    a mismatched pin would force GSPMD into full rematerializations."""
    if x.ndim != 4:
        return x
    try:
        from jax.sharding import PartitionSpec as P, get_abstract_mesh
        mesh = get_abstract_mesh()
        n_model = dict(zip(mesh.axis_names, mesh.axis_sizes)).get("model", 1)
        if n_model <= 1 or x.shape[2] % n_model:
            return x
        return lax.with_sharding_constraint(
            x, P(None, None, "model", None))
    except (ValueError, RuntimeError, TypeError, NameError,
            AttributeError, ImportError):
        return x


def paged_gather(arena, tables):
    """arena (n_blocks, bs, ...) + tables (B, W) -> the row-contiguous
    virtual cache (B, W*bs, ...).  Sentinel entries clamp into an
    arbitrary real block; the positions from ``paged_positions`` (or a
    row-local ``lens`` mask) exclude whatever they alias."""
    nb, bs = arena.shape[0], arena.shape[1]
    b, w = tables.shape
    g = jnp.take(arena, jnp.clip(tables, 0, nb - 1), axis=0)
    return _arena_head_constraint(
        g.reshape((b, w * bs) + arena.shape[2:]))


def paged_apos(tables, lens, block_size: int, n_blocks: int, *,
               window: int = 0):
    """Per-slot absolute positions of the virtual paged cache, with dead
    slots marked ``-1``: the one masking contract BOTH paged decode
    paths (fused kernel and gather fallback) consume, so they cannot
    skew.  A slot is dead when its table entry is the sentinel — the
    gather's clamp would alias an arbitrary real block there, and the
    fused kernel's DMA clamps the same way, so both paths must exclude
    it by position.  Live slots keep ``paged_positions``'s row-local
    layout (window lane included)."""
    b, w = tables.shape
    apos = paged_positions(lens, w, block_size, window=window)
    live = jnp.repeat(tables < n_blocks, block_size, axis=1)  # (B, W*bs)
    return jnp.where(live, apos, -1)


def decode_attention_paged(q, k_arena, v_arena, tables, lens, *,
                           cfg: ModelConfig, kv_posit: Optional[str] = None,
                           window: int = 0, kernel: str = "gather",
                           interpret: Optional[bool] = None):
    """Paged decode attention straight off the block tables.

    q: (B, 1, H, D); arenas (n_blocks, bs, G, D[v]) posit patterns or
    floats; tables (B, W) int32; lens (B,) int32 row frontiers (the
    step's token is already written at ``lens[b]``).

    ``kernel="fused"`` walks the tables inside one Pallas kernel
    (``kernels/posit_paged_attn.py``): posit decode on the VPU, online
    softmax carried in VMEM scratch, sentinel/window masks resolved
    in-kernel — KV patterns cross HBM once.  ``kernel="gather"`` is the
    jnp reference: ``paged_gather`` + :func:`decode_attention`.  Both
    paths consume :func:`paged_apos`, so sentinel-backed slots are
    masked identically and a fully-sentinel row (preempted slot)
    returns exact zeros on either path.
    """
    b, _, h, d = q.shape
    nb, bs, g = k_arena.shape[0], k_arena.shape[1], k_arena.shape[2]
    apos = paged_apos(tables, lens, bs, nb, window=window)
    if kernel == "fused":
        from repro.kernels import posit_paged_attn as K  # lazy: pallas
        qg = (q.reshape(b, g, h // g, d) * d ** -0.5).astype(jnp.float32)
        out = K.paged_decode_attention(
            qg, k_arena, v_arena, tables, apos, lens,
            pcfg=pcfg(kv_posit) if kv_posit else None,
            window=window, interpret=interpret)
        return out.reshape(b, 1, h, -1).astype(q.dtype)
    if kernel != "gather":
        raise ValueError(f"unknown paged decode kernel {kernel!r}")
    return decode_attention(
        q, paged_gather(k_arena, tables), paged_gather(v_arena, tables),
        lens + 1, cfg=cfg, kv_posit=kv_posit, window=window, apos=apos)


def decode_attention_paged_mla(q_lat_eff, q_rope, c_arena, r_arena, tables,
                               lens, *, cfg: ModelConfig,
                               kv_posit: Optional[str] = None,
                               kernel: str = "gather",
                               interpret: Optional[bool] = None):
    """Absorbed-matrix MLA paged decode: latent-space attention off the
    block tables; returns the latent context (B, H, rank) f32 (the
    caller applies ``wuv``).

    Same kernel dispatch contract as :func:`decode_attention_paged`;
    the fused kernel concatenates the latent (``c``) and decoupled-RoPE
    (``r``) blocks in VMEM and uses the latent block as V.  The gather
    fallback carries the same all-masked guard as
    :func:`decode_attention`: a fully-masked row yields zeros, not the
    uniform garbage average ``jax.nn.softmax`` would produce.
    """
    b, h, _ = q_lat_eff.shape
    nb, bs = c_arena.shape[0], c_arena.shape[1]
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    apos = paged_apos(tables, lens, bs, nb)
    if kernel == "fused":
        from repro.kernels import posit_paged_attn as K  # lazy: pallas
        return K.paged_decode_attention_mla(
            q_lat_eff.astype(jnp.float32), q_rope.astype(jnp.float32),
            c_arena, r_arena, tables, apos, lens,
            pcfg=pcfg(kv_posit) if kv_posit else None,
            scale=scale, interpret=interpret)
    if kernel != "gather":
        raise ValueError(f"unknown paged decode kernel {kernel!r}")
    c = paged_gather(c_arena, tables)                 # (B, W*bs, rank)
    r = paged_gather(r_arena, tables)
    if kv_posit:
        with jax.named_scope("kv_decode"):
            c = posit_to_f32(c, pcfg(kv_posit))
            r = posit_to_f32(r, pcfg(kv_posit))
    c = c.astype(jnp.float32)
    r = r.astype(jnp.float32)
    scores = jnp.einsum("bhr,btr->bht", q_lat_eff.astype(jnp.float32), c)
    scores += jnp.einsum("bhd,btd->bht", q_rope.astype(jnp.float32), r)
    valid = (apos >= 0) & (apos <= lens[:, None])     # content [0, lens]
    scores = jnp.where(valid[:, None, :], scores * scale, _NEG)
    m = scores.max(-1, keepdims=True)
    p = jnp.where(valid[:, None, :], jnp.exp(scores - m), 0.0)
    probs = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bht,btr->bhr", probs, c)       # (B, H, rank)


def paged_cache_update(arena, upd, tables, pos, ok, *, window: int = 0):
    """Scatter one new KV vector per row into its block: row b writes
    ``upd[b]`` at logical position ``pos[b]``.

    The paged guarded write: rows with ``ok=False`` (inactive scheduler
    slots, out-of-capacity positions) and writes through sentinel table
    entries are DROPPED — never clamped onto someone else's block.
    ``upd``: (B, ...) matching the arena's per-slot trailing dims.
    """
    nb, bs = arena.shape[0], arena.shape[1]
    w = tables.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    blk = pos // bs
    if paged_is_window_lane(window, bs, w):
        slot = lax.rem(blk, w)
    else:
        slot = blk
        ok = ok & (blk < w)
    phys = jnp.take_along_axis(
        tables, jnp.clip(slot, 0, w - 1)[:, None], axis=1)[:, 0]
    phys = jnp.where(ok, phys, nb)              # sentinel: scatter drops
    return _arena_head_constraint(
        arena.at[phys, lax.rem(pos, bs)].set(upd, mode="drop"))


def paged_pack(arena, kvs, tables, lens, *, window: int = 0,
               src_shift=None, src_ring: bool = False):
    """Pack prompt KV (L, B, S, ...) into arena blocks (L, nb, bs, ...).

    Row b's content positions ``0..lens[b]-1`` land in the blocks named
    by ``tables[b]`` (sentinel entries drop their scatter — unallocated
    table tails carry garbage that never reaches the arena).  ``src_shift``
    (B,) gives the time-axis index of each row's content start in ``kvs``
    (``S - lens`` for the engine's LEFT-padded prompt batches; default 0
    for batch-1 right-padded caches); ``src_ring`` instead reads a
    ring-layout source at ``pos % S``.  Window-lane tables pack only the
    ring's block span; slots whose positions precede the prompt (or fall
    out of the window) receive garbage that the attention masks exclude,
    exactly as the linear ring does.
    """
    nb, bs = arena.shape[1], arena.shape[2]
    b, s = kvs.shape[1], kvs.shape[2]
    w = tables.shape[1]
    lens = jnp.asarray(lens, jnp.int32)
    # the SAME slot->position mapping decode attention will use, with
    # the prompt's last token as the frontier (one shared definition of
    # the block-ring relabelling, so prefill and decode cannot skew)
    cpos = paged_positions(jnp.maximum(lens - 1, 0), w, bs,
                           window=window).reshape(b, w, bs)
    if src_ring:
        tpos = lax.rem(cpos, s)
    elif src_shift is not None:
        tpos = cpos + jnp.asarray(src_shift, jnp.int32)[:, None, None]
    else:
        tpos = cpos
    tpos = jnp.clip(tpos, 0, s - 1).reshape(b, w * bs)
    idx = tpos.reshape((1, b, w * bs) + (1,) * (kvs.ndim - 3))
    gathered = jnp.take_along_axis(kvs, idx, axis=2)        # (L,B,W*bs,..)
    blocks = gathered.reshape(
        (kvs.shape[0], b * w, bs) + kvs.shape[3:])
    ids = jnp.asarray(tables, jnp.int32).reshape(-1)
    return arena.at[:, ids].set(blocks, mode="drop")


def paged_copy_blocks(arena, src_ids, dst_ids):
    """Copy whole arena blocks: ``arena[:, dst_ids[i]] = arena[:, src_ids[i]]``.

    The device half of copy-on-write: a writer about to touch a block
    it does not exclusively own (refcount > 1) first duplicates the
    posit-pattern leaves block-for-block — no dequantize round-trip,
    the stored patterns move verbatim — then swaps its table entry to
    the private copy.  Sentinel ids in ``dst_ids`` drop their write
    (the usual paged no-clamp rule); ``src_ids`` sentinels clamp into
    an arbitrary block the caller must not reference.
    """
    nb = arena.shape[1]
    src = jnp.clip(jnp.asarray(src_ids, jnp.int32), 0, nb - 1)
    dst = jnp.asarray(dst_ids, jnp.int32)
    blocks = jnp.take(arena, src, axis=1)       # (L, n, bs, ...)
    return arena.at[:, dst].set(blocks, mode="drop")


def paged_poison_blocks(arena, block_ids):
    """Overwrite whole arena blocks with a loud poison pattern.

    The device half of the arena sanitizer: after the :class:`BlockPool`
    physically reclaims blocks, the scheduler poisons them so a stale
    block-table entry (use-after-free the host checks missed) detonates
    the logits instead of silently serving freed KV.  The poison is
    FINITE but absurd — ``-1e30`` for float leaves, the posit maxpos
    pattern for unsigned pattern leaves — because masked-softmax
    correctness relies on ``0 * poison == 0``: NaN poison would leak
    through the ``exp(_NEG) = 0`` attention weights of properly masked
    slots and corrupt healthy rows.  Sentinel ids drop (no-op), so the
    OUT-OF-RANGE entry is always safe to pass.
    """
    if jnp.issubdtype(arena.dtype, jnp.unsignedinteger):
        bits = jnp.iinfo(arena.dtype).bits
        poison = jnp.asarray((1 << (bits - 1)) - 1, arena.dtype)  # maxpos
    else:
        poison = jnp.asarray(-1e30, arena.dtype)
    ids = jnp.asarray(block_ids, jnp.int32)
    return arena.at[:, ids].set(poison, mode="drop")


def paged_pack_range(arena, kvs, tables, start, lens, *, window: int = 0):
    """Pack ONLY positions ``[start, lens)`` of suffix KV into arena
    blocks, preserving every other slot of the touched blocks.

    ``kvs`` is (L, B, S, ...) holding the SUFFIX content: time index
    ``t`` of ``kvs`` is absolute position ``start + t``.  Unlike
    :func:`paged_pack` (which overwrites whole blocks, correct for
    freshly allocated ones), the touched blocks here may already hold
    live content — a COW copy of a shared prefix block whose tail this
    request's recomputed tokens overwrite — so out-of-range slots are
    read back from the arena and written unchanged.  Sentinel table
    entries drop their scatter; the prefix-sharing admission passes the
    sentinel for BORROWED entries so a shared block is never written
    through this path (writes reach borrowed blocks only after COW has
    replaced the table entry).
    """
    nb, bs = arena.shape[1], arena.shape[2]
    b, s = kvs.shape[1], kvs.shape[2]
    w = tables.shape[1]
    lens = jnp.asarray(lens, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    start = jnp.broadcast_to(start, (b,)) if start.ndim == 0 else start
    cpos = paged_positions(jnp.maximum(lens - 1, 0), w, bs,
                           window=window).reshape(b, w, bs)
    tpos = jnp.clip(cpos - start[:, None, None], 0, s - 1)
    tpos = tpos.reshape(b, w * bs)
    idx = tpos.reshape((1, b, w * bs) + (1,) * (kvs.ndim - 3))
    gathered = jnp.take_along_axis(kvs, idx, axis=2)        # (L,B,W*bs,..)
    new = gathered.reshape((kvs.shape[0], b * w, bs) + kvs.shape[3:])
    ids = jnp.asarray(tables, jnp.int32).reshape(-1)
    old = jnp.take(arena, jnp.clip(ids, 0, nb - 1), axis=1)  # (L,B*W,bs,..)
    keep = ((cpos >= start[:, None, None]) &
            (cpos < lens[:, None, None])).reshape(b * w, bs)
    keep = keep.reshape((1, b * w, bs) + (1,) * (kvs.ndim - 3))
    blocks = jnp.where(keep, new, old)
    return arena.at[:, ids].set(blocks, mode="drop")


# ---------------------------------------------------------------------------
# Feed-forward (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": init_dense(k1, d, f),
        "wg": init_dense(k2, d, f),
        "wo": init_dense(k3, f, d),
    }


def mlp(p, x, cfg: ModelConfig):
    gate = dense(p["wg"], x, cfg)
    act = jax.nn.gelu(gate) if cfg.act == "gelu" else jax.nn.silu(gate)
    return dense(p["wo"], act * dense(p["wi"], x, cfg), cfg)


# ---------------------------------------------------------------------------
# Mixture of Experts: sort-based capacity dispatch (no fake-FLOP one-hot
# matmuls), experts sharded over the 'model' axis (EP)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "router": init_dense(k1, d, e, scale=s),
        "wi": jax.random.normal(k2, (e, d, f), jnp.float32) * s,
        "wg": jax.random.normal(k3, (e, d, f), jnp.float32) * s,
        "wo": jax.random.normal(k4, (e, f, d), jnp.float32) * (f ** -0.5),
    }


def _moe_row(p, xt, cfg: ModelConfig):
    """Route one batch row: xt (S, D) -> (S, D).

    Dispatch (top-k -> sort -> fixed-capacity buffers) is row-local, so
    under DP the argsort/bincount/gather never cross devices; only the
    expert einsum (whose capacity dim is sharded over 'model' by the
    caller) touches the TP axis.
    """
    s, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k

    logits = dense(p["router"], xt, cfg).astype(jnp.float32)  # (S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_i = lax.top_k(probs, k)                       # (S, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    flat_e = gate_i.reshape(-1)                                # (S*k,)
    order = jnp.argsort(flat_e)                # stable; groups by expert
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    offsets = jnp.cumsum(counts) - counts                      # exclusive
    pos_in_e = jnp.arange(s * k) - offsets[sorted_e]

    cap = _moe_capacity(s, cfg)
    keep = pos_in_e < cap
    dest = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)  # overflow

    tok_sorted = order // k
    xg = xt[tok_sorted]                                        # (S*k, D)
    buf = jnp.zeros((e * cap + 1, d), xt.dtype).at[dest].set(
        jnp.where(keep[:, None], xg, 0))
    xe = buf[:-1].reshape(e, cap, d)
    return xe, (order, dest, keep, gate_w)


def _moe_capacity(s: int, cfg: ModelConfig) -> int:
    return int(max(1, (s * cfg.top_k / cfg.n_experts)
                   * cfg.capacity_factor))


def _moe_combine(ye, aux, s, d, dtype, cfg: ModelConfig):
    e, k = cfg.n_experts, cfg.top_k
    cap = ye.shape[1]
    order, dest, keep, gate_w = aux
    y_sorted = ye.reshape(e * cap, d)[jnp.minimum(dest, e * cap - 1)]
    y_sorted = jnp.where(keep[:, None], y_sorted, 0)
    y_flat = jnp.zeros((s * k, d), dtype).at[order].set(y_sorted)
    return (y_flat.reshape(s, k, d)
            * gate_w[..., None].astype(dtype)).sum(axis=1)


def moe(p, x, cfg: ModelConfig):
    """x: (B, S, D) -> (B, S, D).  Row-local top-k dispatch + batched
    expert einsum with the expert/capacity dim sharded over 'model'."""
    b, s, d = x.shape
    # (refuted §Perf iteration: replicating tokens over 'model' before
    # dispatch did NOT remove the backward scatter-add all-reduces —
    # GSPMD reshards the cotangents back to the seq layout regardless.)
    xe, aux = jax.vmap(lambda r: _moe_row(p, r, cfg))(x)   # (B,E,C,D)
    xe = _moe_shard_capacity(xe, cfg)

    wi = maybe_dequant(p["wi"], cfg).astype(x.dtype)
    wg = maybe_dequant(p["wg"], cfg).astype(x.dtype)
    wo = maybe_dequant(p["wo"], cfg).astype(x.dtype)
    hg = jnp.einsum("becd,edf->becf", xe, wg)
    hi = jnp.einsum("becd,edf->becf", xe, wi)
    act = jax.nn.gelu(hg) if cfg.act == "gelu" else jax.nn.silu(hg)
    # §Perf iteration 2: emit the expert output in the compute dtype
    # directly — XLA otherwise runs this dot with an f32 result and
    # defers the bf16 convert until *after* the combine's capacity
    # all-gather, doubling the dominant collective's bytes.
    ye = jnp.einsum("becf,efd->becd", act * hi, wo,
                    preferred_element_type=x.dtype)        # (B,E,C,D)
    ye = _moe_shard_capacity(ye, cfg)

    y = jax.vmap(
        lambda yr, ar: _moe_combine(yr, ar, s, d, x.dtype, cfg))(ye, aux)
    # named so the layer remat policy can SAVE the MoE output: without
    # this, backward re-runs the whole dispatch (gathers + scatter-adds)
    # a second time (§Perf iteration, dbrx train)
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(y, "moe_out")


def _moe_replicate_tokens(x, cfg: ModelConfig):
    try:
        from jax.sharding import PartitionSpec as P
        return lax.with_sharding_constraint(
            x, P(tuple(cfg.batch_axes), None, None))
    except (ValueError, RuntimeError, TypeError, NameError):
        return x


def _moe_shard_capacity(xe, cfg: ModelConfig):
    """Expert-parallel buffer sharding.

    When the expert count divides the TP axis (dbrx: 16 @ 16), shard the
    expert dim — true EP: dispatch becomes an all-to-all against the
    seq-sharded activations and each chip runs only its experts.
    Otherwise (granite-moe: 40 @ 16) shard the *capacity* dim, which
    still splits the expert FLOPs 16 ways with replicated weights.
    """
    try:
        from jax.sharding import PartitionSpec as P, get_abstract_mesh
        mesh = get_abstract_mesh()
        n_model = dict(zip(mesh.axis_names, mesh.axis_sizes)).get("model", 1)
        if n_model > 1 and cfg.n_experts % n_model == 0:
            spec = P(tuple(cfg.batch_axes), "model", None, None)
        else:
            spec = P(tuple(cfg.batch_axes), None, "model", None)
        return lax.with_sharding_constraint(xe, spec)
    except (ValueError, RuntimeError, TypeError, NameError,
            AttributeError, ImportError):
        return xe
