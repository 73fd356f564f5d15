"""Shape-polymorphic jit wrappers around the Pallas kernels.

These are what the rest of the framework calls: they accept arbitrary
array ranks, pad to tile boundaries, dispatch to the kernel, and undo the
padding.  ``interpret=None`` (the default) lets the backend decide
(``_compat.resolve_interpret``): the Pallas interpreter on the CPU,
native Mosaic kernels on a TPU; an explicit bool overrides it.
"""
from __future__ import annotations

from typing import Optional

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.types import PositConfig
from . import posit_codec, posit_dot, posit_ew, posit_gemm, posit_qgemm
# the fused paged-decode attention entries are cache-layout specific
# (block arenas + tables), not tile-shape polymorphic like the wrappers
# below — no padding shim to add, so they re-export as-is to keep one
# public kernel surface
from .posit_paged_attn import (paged_decode_attention,        # noqa: F401
                               paged_decode_attention_mla,    # noqa: F401
                               paged_decode_kv_bytes)         # noqa: F401


def _as_2d(x):
    """Flatten to (rows, cols) with cols = trailing dim (padded separately)."""
    if x.ndim == 0:
        return x.reshape(1, 1), x.shape
    if x.ndim == 1:
        return x.reshape(1, -1), x.shape
    return x.reshape(-1, x.shape[-1]), x.shape


def _pad_to(x, bm, bn):
    m, n = x.shape
    pm = (-m) % bm
    pn = (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x, m, n


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def quantize(x, cfg: PositConfig, interpret: Optional[bool] = None):
    """f32 array (any rank) -> posit patterns, via the codec kernel."""
    x2, shape = _as_2d(jnp.asarray(x, jnp.float32))
    bm, bn = posit_codec.DEFAULT_BLOCK
    bm = min(bm, x2.shape[0])
    bn = min(bn, x2.shape[1])
    xp, m, n = _pad_to(x2, bm, bn)
    out = posit_codec.quantize_2d(xp, cfg, block=(bm, bn),
                                  interpret=interpret)
    return out[:m, :n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def dequantize(p, cfg: PositConfig, interpret: Optional[bool] = None):
    """posit patterns (any rank) -> f32 array, via the codec kernel."""
    p2, shape = _as_2d(jnp.asarray(p))
    bm, bn = posit_codec.DEFAULT_BLOCK
    bm = min(bm, p2.shape[0])
    bn = min(bn, p2.shape[1])
    pp, m, n = _pad_to(p2, bm, bn)
    out = posit_codec.dequantize_2d(pp, cfg, block=(bm, bn),
                                    interpret=interpret)
    return out[:m, :n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def gemm(a, w_patterns, cfg: PositConfig, interpret: Optional[bool] = None):
    """f32 (..., K) @ posit (K, N) -> f32 (..., N)."""
    a2, shape = _as_2d(jnp.asarray(a, jnp.float32))
    k, n = w_patterns.shape
    bm, bk, bn = posit_gemm.DEFAULT_BLOCKS
    bm = min(bm, a2.shape[0])
    bk = min(bk, k)
    bn = min(bn, n)
    ap, m, _ = _pad_to(a2, bm, bk)
    wp, _, _ = _pad_to(w_patterns, bk, bn)
    out = posit_gemm.posit_gemm(ap, wp, cfg, blocks=(bm, bk, bn),
                                interpret=interpret)
    return out[:m, :n].reshape(shape[:-1] + (n,))


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def dot(a_patterns, b_patterns, cfg: PositConfig,
        interpret: Optional[bool] = None):
    """Bit-exact PVU dot product over the trailing axis, any rank.

    Operands broadcast like jnp (a rank-1 vector against a batched
    stack works); the result drops the contracted axis: (L,) -> scalar,
    (R, L) -> (R,), (B, R, L) -> (B, R).  Reduction length is unbounded
    (streamed through the K-tiled quire kernel, one rounding total).
    """
    a = jnp.asarray(a_patterns)
    b = jnp.asarray(b_patterns)
    if a.ndim == 0 or b.ndim == 0:
        raise ValueError("dot needs rank >= 1 operands (a reduction axis)")
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape).astype(cfg.storage_dtype)
    b = jnp.broadcast_to(b, shape).astype(cfg.storage_dtype)
    r = math.prod(shape[:-1])     # explicit: -1 can't infer past 0-dims
    if r == 0 or shape[-1] == 0:  # empty quire -> posit zero pattern
        return jnp.zeros(shape[:-1], cfg.storage_dtype)
    a2 = a.reshape(r, shape[-1])
    b2 = b.reshape(r, shape[-1])
    out = posit_dot.vpdot_rows(a2, b2, cfg, interpret=interpret)
    return out.reshape(shape[:-1])


def dot_rows(a_patterns, b_patterns, cfg: PositConfig,
             interpret: Optional[bool] = None):
    """Bit-exact PVU dot product per row: (..., L) -> (...,).

    Historic name for :func:`dot` (originally (R, L)-only); now fully
    shape-polymorphic — rank-1 vectors and batched leading dims included.
    """
    return dot(a_patterns, b_patterns, cfg, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def pgemm(a_patterns, w_patterns, cfg: PositConfig,
          interpret: Optional[bool] = None):
    """Bit-exact posit matmul: posit (..., K) @ posit (K, N) -> posit
    (..., N), one quire rounding per output element.

    The posit-in -> posit-out counterpart of :func:`gemm` (which
    dequantizes and rounds per k-tile in f32 on the MXU): use ``pgemm``
    for numerics audits, ``gemm`` for throughput.
    """
    a = jnp.asarray(a_patterns).astype(cfg.storage_dtype)
    w = jnp.asarray(w_patterns).astype(cfg.storage_dtype)
    if w.ndim != 2:
        raise ValueError(f"pgemm weights must be (K, N), got {w.shape}")
    if a.ndim == 0:
        raise ValueError("pgemm needs rank >= 1 activations")
    k, n = w.shape
    if a.shape[-1] != k:
        raise ValueError(
            f"pgemm contraction mismatch: {a.shape} @ {w.shape}")
    a2 = a.reshape(math.prod(a.shape[:-1]), k)
    out = posit_qgemm.posit_qgemm(a2, w, cfg, interpret=interpret)
    return out.reshape(a.shape[:-1] + (n,))


# ---------------------------------------------------------------------------
# Fused elementwise PVU ops (posit patterns in -> posit patterns out)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("cfg", "op", "div_mode", "interpret"))
def _elementwise(a, b, cfg: PositConfig, op: str, div_mode: str = "nr3",
                 interpret: Optional[bool] = None):
    """Shared pad-to-block wrapper: broadcast, flatten to 2D, dispatch."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape).astype(cfg.storage_dtype)
    b = jnp.broadcast_to(b, shape).astype(cfg.storage_dtype)
    a2, _ = _as_2d(a)
    b2, _ = _as_2d(b)
    bm, bn = posit_ew.DEFAULT_BLOCK
    bm = min(bm, a2.shape[0])
    bn = min(bn, a2.shape[1])
    ap, m, n = _pad_to(a2, bm, bn)
    bp, _, _ = _pad_to(b2, bm, bn)
    out = posit_ew.elementwise_2d(ap, bp, cfg, op, div_mode=div_mode,
                                  block=(bm, bn), interpret=interpret)
    return out[:m, :n].reshape(shape)


def vadd(a, b, cfg: PositConfig, interpret: Optional[bool] = None):
    """Fused posit add: patterns (any rank, broadcastable) -> patterns."""
    return _elementwise(a, b, cfg, "add", interpret=interpret)


def vsub(a, b, cfg: PositConfig, interpret: Optional[bool] = None):
    """Fused posit subtract on patterns."""
    return _elementwise(a, b, cfg, "sub", interpret=interpret)


def vmul(a, b, cfg: PositConfig, interpret: Optional[bool] = None):
    """Fused posit multiply on patterns."""
    return _elementwise(a, b, cfg, "mul", interpret=interpret)


def vdiv(a, b, cfg: PositConfig, mode: str = "nr3",
         interpret: Optional[bool] = None):
    """Fused posit divide on patterns.

    mode='nr3' is the paper-faithful Newton-Raphson divider;
    mode='exact' the beyond-paper exactly-rounded restoring divider.
    """
    return _elementwise(a, b, cfg, "div", div_mode=mode,
                        interpret=interpret)
