"""Pallas TPU kernel: fused elementwise PVU ops (vadd/vsub/vmul/vdiv).

The paper's headline datapath (§IV-B/C/D) is the *vector* add/sub/mul/div
unit; this kernel runs one pass of that pipeline per VMEM tile, entirely
in the posit domain:

    decode (Logic 1) -> PIR arithmetic (core.arith) -> single-RNE encode

No f32 round-trip anywhere: inputs and outputs are posit bit patterns
(uint8/uint16/uint32 per ``cfg.storage_dtype``), so results are exactly
rounded once — the fused kernel is never *less* accurate than the
``dequantize -> f32 op -> quantize`` composition, and for add/sub/mul
(and ``div mode='exact'``) it is correctly rounded by construction.

Shares the decode/encode helpers of ``posit_codec``/``posit_dot``
(``repro.core.pir``) so there is one datapath, not three.  Division
supports both the paper's 3-iteration Newton-Raphson (``mode='nr3'``,
~95.8 % exact-match) and the beyond-paper exactly-rounded restoring
divider (``mode='exact'``).

Target: TPU via pl.pallas_call (VPU elementwise, 8x128 lanes);
interpret mode (the CPU backend's) validates against ``core.softposit_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import arith
from repro.core.pir import decode, encode_pir
from repro.core.types import PositConfig

from ._compat import resolve_interpret

# VPU-aligned default tile, matching the codec kernel: the PIR working set
# is ~6 u32 planes per operand, so (256, 512) stays well under VMEM.
DEFAULT_BLOCK = (256, 512)

OPS = ("add", "sub", "mul", "div")
DIV_MODES = ("nr3", "exact")


def _ew_kernel(a_ref, b_ref, o_ref, *, cfg: PositConfig, op: str,
               div_mode: str):
    a = decode(a_ref[...].astype(jnp.uint32), cfg)
    b = decode(b_ref[...].astype(jnp.uint32), cfg)
    if op == "add":
        pir, sticky = arith.vpadd(a, b, cfg)
    elif op == "sub":
        pir, sticky = arith.vpsub(a, b, cfg)
    elif op == "mul":
        pir, sticky = arith.vpmul(a, b, cfg)
    elif op == "div":
        pir, sticky = arith.vpdiv(a, b, cfg, mode=div_mode)
    else:
        raise ValueError(f"unknown elementwise op {op!r}")
    o_ref[...] = encode_pir(pir, cfg, sticky).astype(o_ref.dtype)


def _grid(shape, block):
    bm = min(block[0], shape[0])
    bn = min(block[1], shape[1])
    return (pl.cdiv(shape[0], bm), pl.cdiv(shape[1], bn)), (bm, bn)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "op", "div_mode", "block",
                                    "interpret"))
def elementwise_2d(a, b, cfg: PositConfig, op: str, div_mode: str = "nr3",
                   block=DEFAULT_BLOCK, interpret=None):
    """Fused posit elementwise op on (M, N) pattern arrays.

    a, b : posit patterns in ``cfg.storage_dtype``; same shape.
    op   : one of ``OPS``; ``div_mode`` selects the divider datapath.
    """
    assert a.shape == b.shape, (a.shape, b.shape)
    assert op in OPS, op
    assert div_mode in DIV_MODES, div_mode
    grid, (bm, bn) = _grid(a.shape, block)
    return pl.pallas_call(
        functools.partial(_ew_kernel, cfg=cfg, op=op, div_mode=div_mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(a.shape, cfg.storage_dtype),
        interpret=resolve_interpret(interpret),
    )(a, b)
