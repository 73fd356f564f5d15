"""Pallas TPU kernel: fused paged-decode attention (posit KV in-kernel).

The serving engine's paged decode previously ran in two host-visible
passes: ``layers.paged_gather`` materialized every row's blocks into a
contiguous virtual cache, then ``layers.decode_attention`` dequantized
the WHOLE cache to f32/bf16 before computing a single score — exactly
the IEEE round-trip the paper's PVU argument is against.  This kernel
fuses the walk: a sequential grid dimension steps through each row's
block table, every block's posit8/16 K/V patterns decode on the VPU
inside VMEM (the same ``core.convert`` bit manipulation the codec
kernel ``posit_codec.py`` runs), and the online-softmax state (running
max ``m``, denominator ``l``, accumulator ``acc``) is carried across
table slots in VMEM scratch — the streaming pattern ``posit_dot.py``
uses for the K-tiled quire.  KV bytes are read from HBM exactly once,
as patterns: half the bytes of an f16 cache for posit16, a quarter for
posit8, with zero host-visible gather or dequantized materialization
(:func:`paged_decode_kv_bytes` is the analytic ledger both ends of
``bench_serve.py``'s comparison report).

Masking is resolved ENTIRELY in-kernel from the scalar-prefetched
block tables and frontiers: sentinel table entries (``id >= n_blocks``)
contribute nothing even though their DMA clamps into an arbitrary real
block, and the per-slot absolute positions ``apos`` (the caller builds
them with ``layers.paged_positions``; ``-1`` marks dead slots) carry
the ragged-length and sliding-window-ring validity.  A row with NO
valid slot — a preempted scheduler slot whose table is all sentinels —
produces exact zeros, the same all-masked guard ``decode_attention``
applies (``p`` is zeroed where invalid, so ``l == 0`` instead of a
uniform average of garbage).

Grid: ``(B, W)`` with the table-walk dimension sequential
(``dimension_semantics=("arbitrary",)``), so the carried scratch is
legal; block ``tables[b, w]`` of the arena is DMA'd per step via a
scalar-prefetch BlockSpec index map — no gather copy ever exists.

Target: TPU (compiled natively there); validation: the Pallas
interpreter on the CPU backend (``_compat.resolve_interpret``),
bit-for-bit against ``posit_codec.py``'s decode because both call the
same ``core.convert.posit_to_f32``.  ``apos`` enters as ``(B, W, 1, bs)``
so each step's ``(1, bs)`` block satisfies the TPU tiling rule (the last
two block dims equal the array's).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.convert import posit_to_f32
from repro.core.types import PositConfig

from ._compat import resolve_interpret

_NEG = -1e30


def _decode_block(x, pcfg: Optional[PositConfig]):
    """One block of KV, patterns -> f32 (or a float cache, cast)."""
    if pcfg is None:
        return x.astype(jnp.float32)
    return posit_to_f32(x.astype(jnp.uint32), pcfg)


def _slot_valid(tables_ref, lens_ref, apos, *, nb: int, window: int):
    """In-kernel validity of each slot of the current block: row-local
    position in ``[0, lens]`` (the frontier's just-written token is
    visible), inside the sliding window when one is set, and never
    through a sentinel table entry."""
    b, w = pl.program_id(0), pl.program_id(1)
    cl = lens_ref[b] + 1
    valid = (apos >= 0) & (apos < cl)
    if window:
        valid &= apos >= cl - window
    return valid & (tables_ref[b, w] < nb)


def _online_update(s, valid, v, m_ref, l_ref, acc_ref, contract: str):
    """One table-slot step of the carried online softmax.

    ``s``: (..., bs) f32 scores; ``valid``: (bs,) bool; ``v``: (bs, ...)
    f32 values.  Invalid slots are zeroed in ``p`` (not just pushed to
    ``exp(_NEG - m)``), so a row whose every slot is masked keeps
    ``l == 0`` and finalizes to zeros — the all-masked guard.  With at
    least one valid slot the zeroing is a no-op: ``m`` is finite and
    the masked ``exp`` already underflowed to exactly 0.0.
    """
    s = jnp.where(valid, s, _NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1))
    p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
        contract, p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _paged_attn_kernel(tables_ref, lens_ref, q_ref, apos_ref, k_ref, v_ref,
                       o_ref, m_ref, l_ref, acc_ref, *,
                       pcfg: Optional[PositConfig], nw: int, nb: int,
                       window: int):
    """Dense/GQA lane: one (batch row, table slot) step."""
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, m_ref.dtype)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                    # (G, R, D) pre-scaled
    k = _decode_block(k_ref[0], pcfg)               # (bs, G, D)
    v = _decode_block(v_ref[0], pcfg)               # (bs, G, Dv)
    s = jnp.einsum("grd,tgd->grt", q, k,
                   preferred_element_type=jnp.float32)
    valid = _slot_valid(tables_ref, lens_ref, apos_ref[0, 0],
                        nb=nb, window=window)[None]         # (1, 1, bs)
    _online_update(s, valid, v, m_ref, l_ref, acc_ref, "grt,tgv->grv")

    @pl.when(w == nw - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[..., None]
                    ).astype(o_ref.dtype)


def _paged_attn_mla_kernel(tables_ref, lens_ref, qc_ref, qr_ref, apos_ref,
                           c_ref, r_ref, o_ref, m_ref, l_ref, acc_ref, *,
                           pcfg: Optional[PositConfig], nw: int, nb: int,
                           scale: float):
    """MLA lane: absorbed-matrix attention in the compressed latent
    space.  K is the in-kernel concatenation of the latent (``c``) and
    decoupled-RoPE (``r``) arenas; V IS the latent block, so the
    context accumulates in latent space (the caller applies ``wuv``)."""
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, m_ref.dtype)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = _decode_block(c_ref[0], pcfg)               # (bs, rank)
    r = _decode_block(r_ref[0], pcfg)               # (bs, rope)
    # leading singleton keeps the carried scratch 2-D/3-D (TPU layout)
    s = (jnp.einsum("ghr,tr->ght", qc_ref[...], c,
                    preferred_element_type=jnp.float32) +
         jnp.einsum("ghd,td->ght", qr_ref[...], r,
                    preferred_element_type=jnp.float32)) * scale
    valid = _slot_valid(tables_ref, lens_ref, apos_ref[0, 0],
                        nb=nb, window=0)[None]              # (1, 1, bs)
    _online_update(s, valid, c, m_ref, l_ref, acc_ref, "ght,tr->ghr")

    @pl.when(w == nw - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)[..., None]
                      ).astype(o_ref.dtype)


def _table_walk_specs(tables, apos, arena_specs, out_block, scratch):
    """Shared grid spec: (B, W) grid, W sequential; tables and lens are
    scalar-prefetched so the arena BlockSpecs can DMA ``tables[b, w]``
    (sentinels clamp; the kernel masks their contribution)."""
    b, w = tables.shape
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, w),
        in_specs=arena_specs,
        out_specs=pl.BlockSpec(out_block, lambda b, w, tab, ln: (b,) + (0,) * (len(out_block) - 1)),
        scratch_shapes=scratch,
    )


def _block_index(nb):
    """Index map for an arena operand: table entry, sentinel-clamped
    (the kernel's validity mask excludes whatever the clamp aliases)."""
    def index(b, w, tab, ln, *, _nd):
        return (jnp.minimum(tab[b, w], nb - 1),) + (0,) * (_nd - 1)
    return index


@functools.partial(jax.jit,
                   static_argnames=("pcfg", "window", "interpret"))
def paged_decode_attention(q, k_arena, v_arena, tables, apos, lens, *,
                           pcfg: Optional[PositConfig] = None,
                           window: int = 0, interpret: Optional[bool] = None):
    """Fused paged decode attention (dense/GQA and sliding-window lanes).

    q: (B, G, R, D) f32, already scaled by ``D**-0.5``; arenas
    (nb, bs, G, D) / (nb, bs, G, Dv) posit patterns (``pcfg`` set) or
    floats; tables (B, W) int32 block tables (sentinel ``nb``); apos
    (B, W*bs) int32 absolute position per virtual slot (``-1`` = dead);
    lens (B,) int32 row frontiers.  Returns (B, G, R, Dv) f32.
    """
    b, g, r, d = q.shape
    nb, bs = k_arena.shape[0], k_arena.shape[1]
    w = tables.shape[1]
    dv = v_arena.shape[-1]
    kidx = _block_index(nb)
    grid_spec = _table_walk_specs(
        tables, apos,
        [
            pl.BlockSpec((1, g, r, d), lambda b, w, tab, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, bs), lambda b, w, tab, ln: (b, w, 0, 0)),
            pl.BlockSpec((1, bs, g, d), functools.partial(kidx, _nd=4)),
            pl.BlockSpec((1, bs, g, dv), functools.partial(kidx, _nd=4)),
        ],
        (1, g, r, dv),
        [
            pltpu.VMEM((g, r), jnp.float32),        # running max m
            pltpu.VMEM((g, r), jnp.float32),        # denominator l
            pltpu.VMEM((g, r, dv), jnp.float32),    # accumulator
        ])
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, pcfg=pcfg, nw=w, nb=nb,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, r, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(tables, lens,
      q.astype(jnp.float32), apos.reshape(b, w, 1, bs), k_arena, v_arena)


@functools.partial(jax.jit,
                   static_argnames=("pcfg", "scale", "interpret"))
def paged_decode_attention_mla(q_lat_eff, q_rope, c_arena, r_arena, tables,
                               apos, lens, *,
                               pcfg: Optional[PositConfig] = None,
                               scale: float = 1.0,
                               interpret: Optional[bool] = None):
    """Fused paged MLA decode: latent-space scores and context straight
    off the block tables.

    q_lat_eff: (B, H, rank) f32 absorbed query; q_rope: (B, H, rope)
    f32; arenas (nb, bs, rank) / (nb, bs, rope); ``scale`` multiplies
    the summed scores (the absorbed-attention convention).  Returns the
    latent context (B, H, rank) f32 — the caller applies ``wuv``.
    """
    b, h, rank = q_lat_eff.shape
    rope = q_rope.shape[-1]
    nb, bs = c_arena.shape[0], c_arena.shape[1]
    w = tables.shape[1]
    kidx = _block_index(nb)
    grid_spec = _table_walk_specs(
        tables, apos,
        [
            pl.BlockSpec((1, h, rank), lambda b, w, tab, ln: (b, 0, 0)),
            pl.BlockSpec((1, h, rope), lambda b, w, tab, ln: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1, bs), lambda b, w, tab, ln: (b, w, 0, 0)),
            pl.BlockSpec((1, bs, rank), functools.partial(kidx, _nd=3)),
            pl.BlockSpec((1, bs, rope), functools.partial(kidx, _nd=3)),
        ],
        (1, h, rank),
        [
            pltpu.VMEM((1, h), jnp.float32),        # running max m
            pltpu.VMEM((1, h), jnp.float32),        # denominator l
            pltpu.VMEM((1, h, rank), jnp.float32),  # latent accumulator
        ])
    return pl.pallas_call(
        functools.partial(_paged_attn_mla_kernel, pcfg=pcfg, nw=w, nb=nb,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(tables, lens,
      q_lat_eff.astype(jnp.float32), q_rope.astype(jnp.float32),
      apos.reshape(b, w, 1, bs), c_arena, r_arena)


# ---------------------------------------------------------------------------
# Analytic decode-bytes ledger
# ---------------------------------------------------------------------------

_KV_ITEMSIZE = {None: 4, "posit16": 2, "posit8": 1}


def paged_decode_kv_bytes(cfg, table_width: int, block_size: int,
                          kernel: str = "fused") -> int:
    """HBM bytes of KV traffic one decode step moves per batch row,
    summed over layers (the metric ``bench_serve.py`` reports as
    ``decode_kv_B_tok``).

    The fused kernel reads each row's arena blocks ONCE, as stored
    patterns, and everything else lives in VMEM.  The gather path reads
    the arena, writes + reads the gathered virtual-cache copy, and (for
    posit KV) writes + reads the dequantized compute-dtype cache on top
    — the round-trip this kernel deletes.  Scores/probabilities and the
    (B, H, D)-sized q/out tensors are excluded from both sides: they
    are identical traffic and orders of magnitude smaller than KV.
    """
    itemsize = _KV_ITEMSIZE[cfg.kv_posit]
    slots = table_width * block_size
    if cfg.mla:
        kv_elems = slots * (cfg.kv_lora_rank + cfg.qk_rope_dim)
    else:
        kv_elems = slots * cfg.n_kv_heads * 2 * cfg.head_dim
    pattern_bytes = kv_elems * itemsize
    if kernel == "fused":
        per_layer = pattern_bytes                  # one arena read
    elif kernel == "gather":
        # arena read + gathered-copy write + gathered read ...
        per_layer = 3 * pattern_bytes
        if cfg.kv_posit is not None:
            # ... + dequantized compute-dtype cache write + read
            cbytes = 2 if cfg.compute_dtype == "bfloat16" else 4
            per_layer += 2 * kv_elems * cbytes
    else:
        raise ValueError(f"unknown paged decode kernel {kernel!r}")
    return per_layer * cfg.n_layers
