"""PVU dot product (§IV-E): wide-accumulator vector reduction.

The paper multiplies element-wise, aligns *all* products to the max
exponent, converts to two's complement, and accumulates in a CSA with a
wider bit width, rounding once at the end.  We reproduce that with a
128-bit "quire-lite":

* products are kept unrounded in Q2.62 (u64),
* placed at bits 95..32 of a 128-bit window: 32 bits of carry headroom on
  top (sums of up to 2^31 terms cannot wrap), 32+ alignment bits below
  (only exponent spreads beyond 95 bits fall to a sticky flag),
* accumulated by 16-bit half-limb column sums (the vectorized equivalent
  of the CSA tree: column sums defer carry propagation exactly like
  carry-save addition, with a single propagation at the end),
* normalized and rounded to the target posit exactly once.

The quire is *streamable*: the accumulator state (limb columns +
alignment exponent + sticky + NaR flag) is a first-class value
(``QuireState``) produced per tile by ``quire_partial``, carried across
K-tiles by ``quire_combine`` (re-align to the larger max exponent, add
the 128-bit sums), and rounded exactly once by ``quire_finalize``.
``vpdot`` composes the three, chunking internally, so reduction lengths
are unbounded (up to the 2^31-term carry headroom of the window).

A single *tile* must stay <= ``MAX_DOT_LENGTH`` so the half-limb column
sums stay far from uint32 overflow (bound: L * 0xFFFF + carry < 2^32).

Combine semantics: re-aligning a partial sum floors the *tile subtotal*
(arithmetic shift right, dropped bits -> sticky) where the monolithic
path floors each product individually.  The two agree bit for bit
whenever no nonzero bit is actually dropped by the combine shift — in
particular always for a single tile, and for any data whose product
exponent spread stays inside the 128-bit window.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from . import u64
from .bits import clz32, i32, sll, srl, u32
from .pir import PIR
from .types import PositConfig

_EXP_SENTINEL = -(1 << 28)
MAX_DOT_LENGTH = 4096
_NLIMB = 4  # 128-bit accumulator


def _place_product(p: u64.U64, d):
    """(p * 2^32) >> d as 128-bit limbs [x3..x0] + sticky; d in [0, 95]."""
    d = i32(d)
    # case d <= 63: shift within the top-64 window, spill into x0
    top = u64.shr(p, d)
    spill = u64.shl(p, 64 - d)           # dropped bits, MSB-aligned
    st1 = jnp.where(spill.lo != 0, u32(1), u32(0))
    # case 64 <= d <= 95: whole value lands in (x1, x0)
    low, st2 = u64.shr_sticky(p, d - 32)
    zero = jnp.zeros_like(p.hi)
    x3 = zero
    x2 = jnp.where(d < 64, top.hi, u32(0))
    x1 = jnp.where(d < 64, top.lo, low.hi)
    x0 = jnp.where(d < 64, spill.hi, low.lo)
    st = jnp.where(d < 64, st1, st2)
    return [x3, x2, x1, x0], st


def _neg128(limbs):
    """128-bit two's complement, limbs MSB-first."""
    out = []
    carry = u32(1)
    for x in reversed(limbs):
        t = (~x) + carry
        carry = jnp.where((x == 0) & (carry == 1), u32(1), u32(0))
        out.append(t)
    return list(reversed(out))


def _sub1_128(limbs, dec):
    """Subtract a {0,1} uint32 from 128-bit limbs (MSB-first)."""
    out = []
    borrow = dec
    for x in reversed(limbs):
        t = x - borrow
        borrow = jnp.where(x < borrow, u32(1), u32(0))
        out.append(t)
    return list(reversed(out))


def _column_sum(half, axis):
    """Sum 16-bit half-limbs along ``axis`` as uint32.  Summed as int32:
    a tile holds at most MAX_DOT_LENGTH halves, so the column sum stays
    below 2^28 and the two agree bit for bit — and Mosaic implements no
    reduction over unsigned integers."""
    return jnp.sum(half.astype(jnp.int32), axis=axis).astype(jnp.uint32)


def _any(flags, axis):
    """``jnp.any`` along ``axis`` as an int32 max (no bool reductions in
    Mosaic)."""
    return jnp.max(flags.astype(jnp.int32), axis=axis) != 0


def _sum128(limbs, axis):
    """Sum 128-bit two's-complement limb vectors along ``axis`` (mod 2^128)."""
    halves = []
    for x in reversed(limbs):            # LSB-first halves
        halves.append(x & u32(0xFFFF))
        halves.append(x >> u32(16))
    sums = [_column_sum(x, axis) for x in halves]
    carry = u32(0)
    out16 = []
    for s in sums:
        t = s + carry
        out16.append(t & u32(0xFFFF))
        carry = t >> u32(16)
    out = []
    for j in range(_NLIMB):
        out.append(out16[2 * j] | (out16[2 * j + 1] << u32(16)))
    return list(reversed(out))           # back to MSB-first


def _clz128(limbs):
    result = jnp.full(limbs[0].shape, 32 * _NLIMB, jnp.int32)
    found = jnp.zeros(limbs[0].shape, bool)
    off = 0
    for x in limbs:                      # MSB-first
        take = (~found) & (x != 0)
        result = jnp.where(take, off + clz32(x), result)
        found = found | (x != 0)
        off += 32
    return result


def _top_and_rest(limbs, lz):
    """Given 128-bit limbs shifted left by ``lz`` (MSB lands at bit 127),
    return (bits 127..96, any-bit-below-96?)."""
    top = jnp.zeros_like(limbs[0])
    rest_nonzero = jnp.zeros(limbs[0].shape, bool)
    nbits = 32 * _NLIMB
    for idx, x in enumerate(limbs):      # MSB-first
        off = 32 * (_NLIMB - 1 - idx)    # limb bit offset: 96, 64, 32, 0
        s = off + lz - (nbits - 32)      # alignment into the top word
        top = top | jnp.where(s >= 0, sll(x, s), srl(x, -s))
        # bits of x*2^(off+lz) below bit 96: width of the low mask.
        # w <= 0 means the whole limb lands at/above bit 96 — nothing
        # below — and MUST short-circuit: sll(1, w) - 1 underflows to
        # all-ones for negative w, which set a spurious sticky on every
        # normalized value and broke round-to-nearest-even ties (caught
        # by the exhaustive posit8 conformance sweep).
        w = (nbits - 32) - (off + lz)
        mask = sll(u32(1), w) - u32(1)
        nz = ((w >= 32) & (x != 0)) | (
            (w > 0) & (w < 32) & ((x & mask) != 0))
        rest_nonzero = rest_nonzero | nz
    return top, rest_nonzero


def _add_n(a, b):
    """Add two equal-width limb vectors (MSB-first) mod 2^(32*n)."""
    out = []
    carry = u32(0)
    for x, y in zip(reversed(a), reversed(b)):    # LSB-first
        t = x + y
        c1 = jnp.where(t < x, u32(1), u32(0))
        t = t + carry
        c2 = jnp.where(t < carry, u32(1), u32(0))
        out.append(t)
        carry = c1 | c2                 # x+y+carry <= 2^33 - 1: at most one
    return list(reversed(out))


def _asr128_sticky(limbs, s):
    """Arithmetic (two's-complement, i.e. floor) shift right of a 128-bit
    value by ``s`` >= 0 (clamped at 128), limbs MSB-first.

    Returns (shifted limbs, sticky) where sticky is 1 iff any dropped bit
    was set — exactly ``x != floor(x / 2^s) * 2^s``.
    """
    s = jnp.clip(i32(s), 0, 32 * _NLIMB)
    fill = jnp.where((limbs[0] >> u32(31)) != 0, u32(0xFFFFFFFF), u32(0))
    lsb = list(reversed(limbs))          # lsb[j] covers bits 32j..32j+31
    w = s >> 5                           # whole-limb shift, 0..4
    r = s & 31
    out_lsb = []
    for idx in range(_NLIMB):
        res = jnp.broadcast_to(fill, limbs[0].shape)
        for wv in range(_NLIMB + 1):
            lo = lsb[idx + wv] if idx + wv < _NLIMB else fill
            hi = lsb[idx + wv + 1] if idx + wv + 1 < _NLIMB else fill
            val = srl(lo, r) | sll(hi, 32 - r)    # r == 0: sll(hi,32) == 0
            res = jnp.where(w == wv, val, res)
        out_lsb.append(res)
    sticky = jnp.zeros_like(limbs[0])
    for j in range(_NLIMB):              # bits of lsb[j] strictly below s
        t = s - 32 * j
        mask = sll(u32(1), jnp.clip(t, 0, 31)) - u32(1)
        below = ((t >= 32) & (lsb[j] != 0)) | (
            (t < 32) & ((lsb[j] & mask) != 0))
        sticky = sticky | jnp.where(below, u32(1), u32(0))
    return list(reversed(out_lsb)), sticky


# ---------------------------------------------------------------------------
# Streamable quire-lite: QuireState + partial / combine / finalize
# ---------------------------------------------------------------------------

class QuireState(NamedTuple):
    """Streaming 128-bit quire-lite accumulator state.

    acc    : uint32 (..., 4) — two's-complement limb columns, MSB-first
             along the last axis; the max-exp product's MSB sits at bit 95.
    m_exp  : int32 — the alignment (max product) exponent; the sentinel
             ``-(1 << 28)`` marks an empty/all-zero accumulation.
    sticky : uint32 {0,1} — nonzero bits lost below the window.
    nar    : bool — any NaR operand seen.
    """
    acc: jnp.ndarray
    m_exp: jnp.ndarray
    sticky: jnp.ndarray
    nar: jnp.ndarray


def _unstack_acc(acc):
    return [acc[..., j] for j in range(_NLIMB)]


def quire_partial(a: PIR, b: PIR, axis: int = -1) -> QuireState:
    """Accumulate one K-tile of ``sum_i a_i * b_i`` into a QuireState.

    Bit-identical to the first half of the monolithic paper pipeline:
    elementwise Q2.62 significand products, aligned to the *tile* max
    exponent, floored (sticky) per product, 128-bit column-summed.
    """
    length = a.sig.shape[axis]
    if length > MAX_DOT_LENGTH:
        raise ValueError(
            f"quire_partial tile length {length} exceeds MAX_DOT_LENGTH="
            f"{MAX_DOT_LENGTH} (uint32 half-limb column-sum bound); chunk "
            "the reduction — vpdot / the tiled kernels do this for you")
    psign = a.sign ^ b.sign
    pexp = a.exp + b.exp
    pzero = a.is_zero | b.is_zero
    any_nar = _any(a.is_nar | b.is_nar, axis)

    prod = u64.mul_32x32(a.sig, b.sig)                   # Q2.62
    prod = u64.select(pzero, u64.zeros_like(prod), prod)
    pexp = jnp.where(pzero, i32(_EXP_SENTINEL), pexp)

    m_exp = jnp.max(pexp, axis=axis, keepdims=True)
    d = jnp.clip(m_exp - pexp, 0, 95)
    limbs, st = _place_product(prod, d)
    st = jnp.where(pzero, u32(0), st)
    sticky = _any(st, axis).astype(jnp.uint32)

    neg = psign == 1
    nlimbs = _neg128(limbs)
    limbs = [jnp.where(neg, n, p) for n, p in zip(nlimbs, limbs)]
    # a negative contribution with truncated tail: true = -(mag + delta),
    # floor = -(mag) - 1 (the sticky flag carries the fractional part).
    dec = jnp.where(neg & (st == 1), u32(1), u32(0))
    limbs = _sub1_128(limbs, dec)

    acc = _sum128(limbs, axis)
    return QuireState(acc=jnp.stack(acc, axis=-1),
                      m_exp=jnp.squeeze(m_exp, axis=axis),
                      sticky=sticky, nar=any_nar)


def quire_combine(s: QuireState, t: QuireState) -> QuireState:
    """Merge two partial quire states (associative up to the floor of
    re-alignment; exact whenever no nonzero bit is dropped).

    Each 128-bit subtotal is floor-shifted (arithmetic >>) to the larger
    alignment exponent, dropped bits fold into sticky, and the aligned
    subtotals add mod 2^128.  Empty states (sentinel m_exp, zero acc)
    are absorbed untouched.
    """
    m = jnp.maximum(s.m_exp, t.m_exp)
    sa, st_a = _asr128_sticky(_unstack_acc(s.acc), m - s.m_exp)
    tb, st_b = _asr128_sticky(_unstack_acc(t.acc), m - t.m_exp)
    acc = _add_n(sa, tb)
    return QuireState(acc=jnp.stack(acc, axis=-1), m_exp=m,
                      sticky=s.sticky | t.sticky | st_a | st_b,
                      nar=s.nar | t.nar)


def quire_finalize(state: QuireState):
    """Normalize + extract the significand: QuireState -> (PIR, sticky).

    The single rounding happens afterwards, at posit encode
    (``pir.encode_pir``) — exactly once per reduction, as in the paper.
    """
    acc = _unstack_acc(state.acc)
    sticky = state.sticky

    sign_out = (acc[0] >> u32(31)) & u32(1)
    nacc = _neg128(acc)
    acc = [jnp.where(sign_out == 1, n, p) for n, p in zip(nacc, acc)]

    nonzero = acc[0]
    for x in acc[1:]:
        nonzero = nonzero | x
    is_zero = (nonzero == 0) & (sticky == 0)

    # normalize: value = mag128 * 2^(m_exp - 94); MSB -> bit 127,
    # significand = bits 127..96.
    lz = _clz128(acc)
    exp_out = state.m_exp + 33 - lz
    top, rest_nz = _top_and_rest(acc, lz)
    sticky = sticky | jnp.where(rest_nz, u32(1), u32(0))

    sig = jnp.where(is_zero, u32(0), top)
    sign_out = jnp.where(is_zero, u32(0), sign_out)
    exp_out = jnp.where(is_zero, i32(0), exp_out)
    pir = PIR(sign=sign_out, exp=exp_out, sig=sig,
              is_zero=is_zero, is_nar=state.nar)
    return pir, sticky


def _move_last(p: PIR, axis: int) -> PIR:
    return PIR(*(jnp.moveaxis(f, axis, -1) for f in p))


# ---------------------------------------------------------------------------
# Exact 512-bit quire (Posit Standard 2022) — beyond-paper mode
# ---------------------------------------------------------------------------
# For posit<32,2>, product bit weights span 2^(exp-62) with exp in
# [-240, 240]; a fixed-point register over [2^-302, 2^178) plus 32 carry
# bits is exactly the standard's 512-bit quire.  Products are placed at
# absolute positions (no alignment, no sticky — the sum is *exact*),
# accumulated by 16-bit half-limb column sums, and rounded once.

_QLIMB = 16                      # 512 bits
_QBIAS = 302                     # shift = exp + _QBIAS in [0, 480]


def _quire_place(p: u64.U64, exp):
    """Place the Q2.62 product at absolute bit offset exp+_QBIAS.
    Returns 16 uint32 limbs (MSB-first)."""
    s = i32(exp) + i32(_QBIAS)
    limbs = []
    for j in range(_QLIMB - 1, -1, -1):     # MSB-first output order
        lo_bit = 32 * j
        d = lo_bit - s
        # window_j = low32( (P << s) >> 32j ) = low32(P >> d) | low32(P << -d)
        right = u64.shr(p, jnp.clip(d, 0, 63)).lo
        right = jnp.where((d >= 0) & (d < 64), right, u32(0))
        left = u64.shl(p, jnp.clip(-d, 0, 63)).lo
        left = jnp.where((d < 0) & (d > -64), left, u32(0))
        limbs.append(right | left)
    return limbs


def _neg_n(limbs):
    out = []
    carry = u32(1)
    for x in reversed(limbs):
        t = (~x) + carry
        carry = jnp.where((x == 0) & (carry == 1), u32(1), u32(0))
        out.append(t)
    return list(reversed(out))


def _sum_n(limbs, axis):
    halves = []
    for x in reversed(limbs):
        halves.append(x & u32(0xFFFF))
        halves.append(x >> u32(16))
    sums = [_column_sum(x, axis) for x in halves]
    carry = u32(0)
    out16 = []
    for s in sums:
        t = s + carry
        out16.append(t & u32(0xFFFF))
        carry = t >> u32(16)
    n = len(limbs)
    out = [out16[2 * j] | (out16[2 * j + 1] << u32(16)) for j in range(n)]
    return list(reversed(out))


def _quire_exact_partial(a: PIR, b: PIR, axis: int):
    """One <= MAX_DOT_LENGTH tile into the exact 512-bit quire.

    Returns (limbs list[16] MSB-first, any_nar).  Placement is at
    absolute bit positions, so partial sums combine by plain 512-bit
    addition — the exact quire stream is fully associative.
    """
    if a.sig.shape[axis] > MAX_DOT_LENGTH:
        raise ValueError(
            f"_quire_exact_partial tile length {a.sig.shape[axis]} exceeds "
            f"MAX_DOT_LENGTH={MAX_DOT_LENGTH}; chunk the reduction")
    psign = a.sign ^ b.sign
    pexp = a.exp + b.exp
    pzero = a.is_zero | b.is_zero
    any_nar = _any(a.is_nar | b.is_nar, axis)

    prod = u64.mul_32x32(a.sig, b.sig)
    prod = u64.select(pzero, u64.zeros_like(prod), prod)
    limbs = _quire_place(prod, jnp.where(pzero, i32(0), pexp))
    limbs = [jnp.where(pzero, u32(0), x) for x in limbs]
    neg = (psign == 1) & ~pzero
    nl = _neg_n(limbs)
    limbs = [jnp.where(neg, n, p) for n, p in zip(nl, limbs)]
    return _sum_n(limbs, axis), any_nar


def _quire_exact_finalize(acc, any_nar):
    """512-bit quire -> (PIR, sticky); round once at posit encode."""
    sign_out = (acc[0] >> u32(31)) & u32(1)
    nacc = _neg_n(acc)
    acc = [jnp.where(sign_out == 1, n, p) for n, p in zip(nacc, acc)]

    nonzero = acc[0]
    for x in acc[1:]:
        nonzero = nonzero | x
    is_zero = nonzero == 0

    # clz over 512 bits
    lz = jnp.full(acc[0].shape, 32 * _QLIMB, jnp.int32)
    found = jnp.zeros(acc[0].shape, bool)
    off = 0
    for x in acc:
        take = (~found) & (x != 0)
        lz = jnp.where(take, off + clz32(x), lz)
        found = found | (x != 0)
        off += 32
    msb = 511 - lz
    exp_out = msb - (_QBIAS + 62)

    # significand = bits [msb .. msb-31]; sticky = anything below
    sh = msb - 31                             # >= -31
    sig = jnp.zeros_like(acc[0])
    sticky = jnp.zeros_like(acc[0])
    for j in range(_QLIMB):                   # limb j covers bits 32j..+31
        x = acc[_QLIMB - 1 - j]
        d = sh - 32 * j
        hit = srl(x, d) | jnp.where((d < 0) & (d > -32),
                                    sll(x, -d), u32(0))
        sig = sig | jnp.where((d > -32) & (d < 32), hit, u32(0))
        below = jnp.where(d >= 32, x != 0,
                          jnp.where(d > 0, (x & (sll(u32(1), d) - 1)) != 0,
                                    False))
        sticky = sticky | jnp.where(below, u32(1), u32(0))

    sig = jnp.where(is_zero, u32(0), sig)
    sign_out = jnp.where(is_zero, u32(0), sign_out)
    exp_out = jnp.where(is_zero, i32(0), exp_out)
    return PIR(sign=sign_out, exp=exp_out, sig=sig,
               is_zero=is_zero, is_nar=any_nar), sticky


def _iter_chunks(a: PIR, b: PIR, length: int):
    for start in range(0, length, MAX_DOT_LENGTH):
        stop = min(start + MAX_DOT_LENGTH, length)
        yield (PIR(*(f[..., start:stop] for f in a)),
               PIR(*(f[..., start:stop] for f in b)))


def vpdot_quire(a: PIR, b: PIR, cfg: PositConfig, axis: int = -1):
    """Exact dot product through the 512-bit standard quire -> (PIR,
    sticky).  Every real sum in quire range is represented exactly; the
    single rounding happens at posit encode.

    Any reduction length: tiles of MAX_DOT_LENGTH stream through the
    quire by exact 512-bit addition (no alignment, order-independent).
    """
    if cfg.nbits > 32 or cfg.es > 2:
        raise ValueError("quire sizing assumes posit<=32, es<=2")
    length = a.sig.shape[axis]
    if length <= MAX_DOT_LENGTH:
        return _quire_exact_finalize(*_quire_exact_partial(a, b, axis))
    a = _move_last(a, axis)
    b = _move_last(b, axis)
    acc, nar = None, None
    for ac, bc in _iter_chunks(a, b, length):
        part, pnar = _quire_exact_partial(ac, bc, -1)
        acc = part if acc is None else _add_n(acc, part)
        nar = pnar if nar is None else (nar | pnar)
    return _quire_exact_finalize(acc, nar)


def vpdot(a: PIR, b: PIR, cfg: PositConfig, axis: int = -1):
    """Reduce ``sum_i a_i * b_i`` along ``axis`` -> (PIR, sticky); rounded
    once (the paper's single-rounding wide accumulator).

    Any reduction length: tiles of MAX_DOT_LENGTH stream through
    ``quire_partial`` / ``quire_combine`` — bit-identical to the
    monolithic pipeline for lengths <= MAX_DOT_LENGTH (a single tile).
    """
    del cfg
    length = a.sig.shape[axis]
    if length <= MAX_DOT_LENGTH:
        return quire_finalize(quire_partial(a, b, axis=axis))
    a = _move_last(a, axis)
    b = _move_last(b, axis)
    state = None
    for ac, bc in _iter_chunks(a, b, length):
        part = quire_partial(ac, bc, axis=-1)
        state = part if state is None else quire_combine(state, part)
    return quire_finalize(state)
