"""Pallas TPU kernel: bit-exact posit matmul through the streaming quire.

``pgemm``: posit patterns (M, K) x posit patterns (K, N) -> posit
patterns (M, N), each output element reduced through the §IV-E quire-lite
accumulator with *exactly one* rounding — the blocked-matmul analogue of
``posit_dot.vpdot_rows``, complementing the dequant+MXU throughput path
in ``posit_gemm`` (which is f32-in/f32-out and rounds per k-tile).

Blocking: grid (M/bm, N/bn, K/bk) with K innermost (sequential).  Each
step pairs every row of an A tile (bm, bk) with every column of a W tile
— W arrives transposed, so the tile is (bn, bk) — as ``bm * bn`` rows of
pattern pairs, and runs the dot kernel's tile step (``vpdot_tile``) on
them: decode, product placement and 128-bit column sums per row, quire
states carried across K in VMEM scratch, one rounding on the last step.
K tiles are ``MAX_DOT_LENGTH`` wide, the same chunks ``core.vpdot``
streams, so every output element is bit-identical to ``ref.pgemm_ref``.
The ``bm * bn`` pair rows come out as a column and are scattered back
into (M, N) outside the kernel.

``bm * bn`` rows of ``bk`` lanes bound the working set (about 20 u32
planes per element), so the defaults keep 32 pair rows per step — this
is the numerics-audit matmul, not the throughput one.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dot as dot_mod
from repro.core.types import PositConfig

from ._compat import resolve_interpret
from .posit_dot import quire_scratch, vpdot_tile

# bm rides a leading block axis (any size); bn is W^T's sublane axis, so
# it stays a multiple of 8
DEFAULT_BLOCKS = (4, dot_mod.MAX_DOT_LENGTH, 8)  # bm, bk, bn


def _qgemm_kernel(a_ref, wt_ref, o_ref, *scratch, cfg: PositConfig, nk: int):
    _, bm, bk = a_ref.shape
    bn = wt_ref.shape[0]
    a = a_ref[0].astype(jnp.uint32)                       # (bm, bk)
    wt = wt_ref[...].astype(jnp.uint32)                   # (bn, bk)
    # pair row m * bn + n holds a[m, :] against w[:, n]
    a_pairs = jnp.broadcast_to(a[:, None, :], (bm, bn, bk)).reshape(
        bm * bn, bk)
    w_pairs = jnp.broadcast_to(wt[None, :, :], (bm, bn, bk)).reshape(
        bm * bn, bk)
    vpdot_tile(a_pairs, w_pairs, o_ref, *scratch, cfg=cfg,
               k=pl.program_id(2), nk=nk)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "blocks", "interpret"))
def posit_qgemm(a_patterns, w_patterns, cfg: PositConfig,
                blocks=DEFAULT_BLOCKS, interpret: Optional[bool] = None):
    """a: posit (M, K); w: posit (K, N) -> posit (M, N), quire-exact."""
    m, k = a_patterns.shape
    k2, n = w_patterns.shape
    if k != k2:
        raise ValueError(
            f"pgemm contraction mismatch: {a_patterns.shape} @ "
            f"{w_patterns.shape}")
    if m == 0 or n == 0 or k == 0:
        # empty contraction -> posit zero (pattern 0); nothing to launch
        return jnp.zeros((m, n), cfg.storage_dtype)
    bm, bk, bn = blocks
    bk = min(bk, k)
    if bk > dot_mod.MAX_DOT_LENGTH:
        raise ValueError(
            f"pgemm block_k {bk} exceeds MAX_DOT_LENGTH="
            f"{dot_mod.MAX_DOT_LENGTH} (uint32 half-limb column-sum bound)")
    # zero patterns decode to posit zero: padding never perturbs the quire
    pm, pk, pn = (-m) % bm, (-k) % bk, (-n) % bn
    ap = jnp.pad(a_patterns, ((0, pm), (0, pk)))
    wt = jnp.pad(w_patterns, ((0, pk), (0, pn))).T
    mt, nt, nk = (m + pm) // bm, (n + pn) // bn, (k + pk) // bk
    rows = bm * bn
    out = pl.pallas_call(
        functools.partial(_qgemm_kernel, cfg=cfg, nk=nk),
        grid=(mt, nt, nk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda i, j, kk: (i, 0, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((rows, 1), lambda i, j, kk: (i * nt + j, 0)),
        out_shape=jax.ShapeDtypeStruct((mt * nt * rows, 1),
                                       cfg.storage_dtype),
        scratch_shapes=quire_scratch(rows),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(ap.reshape(mt, bm, k + pk), wt)
    out = out.reshape(mt, nt, bm, bn).transpose(0, 2, 1, 3)
    return out.reshape(m + pm, n + pn)[:m, :n]
