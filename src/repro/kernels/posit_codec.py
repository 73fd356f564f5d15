"""Pallas TPU kernel: fused posit quantize / dequantize (the PVU codec).

This is the framework's bandwidth-boundary kernel: gradients crossing the
pod interconnect, weight tiles feeding the MXU, and KV-cache blocks all
pass through it.  Elementwise over VMEM tiles; the bit manipulation runs
on the VPU (8x128 lanes), which is exactly the "vector posit unit"
adaptation of the paper (DESIGN.md §2).

Target: TPU (compiled via pl.pallas_call with explicit BlockSpecs).
Validation: interpret mode on the CPU against ``ref.py`` / the golden model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.convert import f32_to_posit, posit_to_f32
from repro.core.types import PositConfig

from ._compat import resolve_interpret

# VPU-aligned default tile: 8 sublanes x 128 lanes times a few registers.
DEFAULT_BLOCK = (256, 512)


def _quant_kernel(x_ref, o_ref, *, cfg: PositConfig):
    o_ref[...] = f32_to_posit(x_ref[...], cfg).astype(o_ref.dtype)


def _dequant_kernel(p_ref, o_ref, *, cfg: PositConfig):
    o_ref[...] = posit_to_f32(p_ref[...].astype(jnp.uint32), cfg)


def _grid(shape, block):
    bm = min(block[0], shape[0])
    bn = min(block[1], shape[1])
    return (pl.cdiv(shape[0], bm), pl.cdiv(shape[1], bn)), (bm, bn)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "block", "interpret"))
def quantize_2d(x, cfg: PositConfig, block=DEFAULT_BLOCK, interpret=None):
    """f32 (M, N) -> posit patterns (M, N) in cfg.storage_dtype."""
    grid, (bm, bn) = _grid(x.shape, block)
    return pl.pallas_call(
        functools.partial(_quant_kernel, cfg=cfg),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, cfg.storage_dtype),
        interpret=resolve_interpret(interpret),
    )(x)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "block", "interpret"))
def dequantize_2d(p, cfg: PositConfig, block=DEFAULT_BLOCK, interpret=None):
    """posit patterns (M, N) -> f32 (M, N)."""
    grid, (bm, bn) = _grid(p.shape, block)
    return pl.pallas_call(
        functools.partial(_dequant_kernel, cfg=cfg),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(p.shape, jnp.float32),
        interpret=resolve_interpret(interpret),
    )(p)
