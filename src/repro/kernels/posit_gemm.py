"""Pallas TPU kernel: fused posit-dequant matmul (posit weights -> MXU).

The TPU-native analogue of running the paper's conv inner loop on posit
operands (Listing 2): weights stay in posit16/posit8 in HBM (2-4x less
bandwidth), each (bk, bn) tile is decoded to f32 *in VMEM* on the VPU, and
the MXU consumes it immediately.  K is the innermost (sequential) grid
dimension accumulating into the output block.

Blocking: (bm, bk) x (bk, bn) -> (bm, bn), all MXU-aligned multiples of
128 by default; the f32 working set is 3 tiles + the posit tile, sized
well under VMEM (16 MiB/core).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.convert import posit_to_f32
from repro.core.types import PositConfig

from ._compat import resolve_interpret

DEFAULT_BLOCKS = (256, 256, 256)  # bm, bk, bn


def _gemm_kernel(a_ref, w_ref, o_ref, *, cfg: PositConfig):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = posit_to_f32(w_ref[...].astype(jnp.uint32), cfg)   # VPU decode
    o_ref[...] += jnp.dot(a_ref[...], w,
                          preferred_element_type=jnp.float32)  # MXU


@functools.partial(jax.jit,
                   static_argnames=("cfg", "blocks", "interpret"))
def posit_gemm(a, w_patterns, cfg: PositConfig, blocks=DEFAULT_BLOCKS,
               interpret=None):
    """a: f32 (M, K); w_patterns: posit (K, N) -> f32 (M, N)."""
    m, k = a.shape
    k2, n = w_patterns.shape
    assert k == k2, (a.shape, w_patterns.shape)
    bm = min(blocks[0], m)
    bk = min(blocks[1], k)
    bn = min(blocks[2], n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    return pl.pallas_call(
        functools.partial(_gemm_kernel, cfg=cfg),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(a, w_patterns)
