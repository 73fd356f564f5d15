"""Posit <-> PIR (Posit Intermediate Representation) codecs.

Faithful vectorized implementation of the paper's decode pipeline
(``Logic 1``) and its inverse (``§IV-G Encode``):

    decode:  sign extract -> two's-complement abs -> LZC over the regime ->
             barrel-shift out regime/terminator -> exponent field ->
             fraction with implicit bit -> PIR(sign, exp, sig)
    encode:  clamp scale -> split scale into (regime r, exponent e) ->
             emit regime/exponent/fraction into a 64-bit stream ->
             round-to-nearest-even on the pattern (posit patterns are
             monotone in value, so pattern-RNE == value-RNE; this is the
             SoftPosit rounding rule) -> saturate -> two's complement sign.

PIR conventions
---------------
sign : uint32 {0,1}
exp  : int32, the *combined* binary scale  r * 2^es + e
sig  : uint32, Q1.31 significand (bit 31 is the implicit leading 1);
       sig == 0 only for zero.
sticky : uint32 {0,1}; 1 iff the true value has nonzero bits strictly
       below sig's LSB (needed for exact RNE after arithmetic).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from . import u64
from .bits import clz32, i32, sll, srl, u32
from .types import PositConfig


class PIR(NamedTuple):
    sign: jnp.ndarray     # uint32 {0,1}
    exp: jnp.ndarray      # int32 combined scale
    sig: jnp.ndarray      # uint32 Q1.31 (bit31 = implicit 1)
    is_zero: jnp.ndarray  # bool
    is_nar: jnp.ndarray   # bool


def decode(p, cfg: PositConfig) -> PIR:
    """Logic 1 of the paper, vectorized over uint32 lanes."""
    n, es = cfg.nbits, cfg.es
    x = u32(p) & u32(cfg.mask)
    is_zero = x == 0
    is_nar = x == u32(cfg.nar_pattern)

    sign = (x >> u32(n - 1)) & u32(1)
    # two's-complement absolute value (paper: "modified to its two's
    # complement representation")
    ax = jnp.where(sign == 1, (~x + u32(1)) & u32(cfg.mask), x)

    # place the sign at bit 31 so field positions are width-independent
    y = ax << u32(32 - n) if n < 32 else ax

    r0 = (y >> u32(30)) & u32(1)
    t = jnp.where(r0 == 1, ~y, y) & u32(0x7FFFFFFF)
    t = t << u32(1)  # regime run now starts at bit 31
    # run length (the LZC module); a full-width run (maxpos/minpos extremes
    # at n == 32) makes t == 0 -> clz 32, so clamp to the legal max n-1.
    k = jnp.minimum(clz32(t), n - 1)
    r = jnp.where(r0 == 1, k - 1, -k)

    # shift off sign + regime run + terminator -> exponent at the top
    body = sll(y, k + 2)
    if es > 0:
        e = body >> u32(32 - es)
    else:
        e = jnp.zeros_like(body)
    frac_body = sll(body, i32(es))
    sig = u32(0x80000000) | (frac_body >> u32(1))

    exp = r * i32(1 << es) + e.astype(jnp.int32)

    sig = jnp.where(is_zero | is_nar, u32(0), sig)
    exp = jnp.where(is_zero | is_nar, i32(0), exp)
    sign = jnp.where(is_nar, u32(0), sign)
    return PIR(sign=sign, exp=exp, sig=sig, is_zero=is_zero, is_nar=is_nar)


def encode(sign, exp, sig, sticky, is_zero, is_nar, cfg: PositConfig):
    """PIR -> posit pattern with exact round-to-nearest-even.

    ``sig`` must be normalized (bit 31 set) whenever the value is nonzero.
    Returns a uint32 pattern (low ``nbits`` bits used).
    """
    n, es = cfg.nbits, cfg.es
    sign = u32(sign)
    exp = i32(exp)
    sig = u32(sig)
    sticky = u32(sticky)

    too_big = exp > cfg.max_scale
    too_small = exp < cfg.min_scale
    expc = jnp.clip(exp, cfg.min_scale, cfg.max_scale)

    r = expc >> es if es > 0 else expc       # arithmetic shift: floor div
    e = expc - (r << es) if es > 0 else jnp.zeros_like(expc)

    # regime field (with terminator) as a value + length
    reg_pos = r >= 0
    reg_len = jnp.where(reg_pos, r + 2, 1 - r)          # <= n
    # r >= 0: (r+1) ones then a 0  -> 2^(r+2) - 2 ; r < 0: (-r) zeros then 1
    v_pos = sll(u32(2), r + 1) - u32(2)                 # 2^(r+2) - 2, r+2<=32
    # sll gives 0 when r+2 == 32 => wrap: handle r == 30 case exactly:
    v_pos = jnp.where(r + 2 >= 32, u32(0xFFFFFFFE), v_pos)
    v_reg = jnp.where(reg_pos, v_pos, u32(1))

    stream = u64.shl(u64.from32(v_reg), 64 - reg_len)
    if es > 0:
        stream = u64.bor(stream, u64.shl(u64.from32(u32(e)), 64 - reg_len - es))
    frac31 = sig & u32(0x7FFFFFFF)
    fsh = 33 - reg_len - es  # position of fraction LSB in the stream
    f_in = u64.select(fsh >= 0,
                      u64.shl(u64.from32(frac31), fsh),
                      u64.shr(u64.from32(frac31), -fsh))
    stream = u64.bor(stream, f_in)
    # fraction bits pushed below the stream (fsh < 0) are sticky
    drop_mask = sll(u32(1), -fsh) - u32(1)
    sticky = sticky | jnp.where((fsh < 0) & ((frac31 & drop_mask) != 0),
                                u32(1), u32(0))
    # fold external sticky into bit 0 (strictly below the round position
    # 64-n >= 32 for all n <= 32, so this never corrupts kept bits)
    stream = u64.bor(stream, u64.from32(sticky))

    body = u64.shr(stream, 64 - (n - 1)).lo              # top n-1 bits
    round_bit = u64.bit(stream, 64 - n)
    below = u64.band(stream, u64.sub(u64.shl(u64.from32(u32(1)), 64 - n),
                                     u64.from32(u32(1))))
    sticky_rest = jnp.where((below.hi | below.lo) != 0, u32(1), u32(0))
    inc = round_bit & (sticky_rest | (body & u32(1)))
    p = body + inc

    maxpos = u32(cfg.maxpos_pattern)
    # compare + select: Mosaic has no unsigned min/max (arith.minui)
    p = jnp.where(p > maxpos, maxpos, p)       # never round past maxpos
    p = jnp.where(p < u32(1), u32(1), p)       # never round a nonzero to 0
    p = jnp.where(too_big, maxpos, p)
    p = jnp.where(too_small, u32(1), p)        # nonzero tiny -> minpos

    p = jnp.where(sign == 1, (~p + u32(1)) & u32(cfg.mask), p)
    p = jnp.where(is_zero, u32(0), p)
    p = jnp.where(is_nar, u32(cfg.nar_pattern), p)
    return p


def encode_pir(pir: PIR, cfg: PositConfig, sticky=None):
    if sticky is None:
        sticky = jnp.zeros_like(pir.sign)
    return encode(pir.sign, pir.exp, pir.sig, sticky, pir.is_zero,
                  pir.is_nar, cfg)
