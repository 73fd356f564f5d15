"""PVU arithmetic on the PIR domain (add/sub/mul/div).

Mirrors the paper's datapath (§IV-B/C/D):

* add/sub — comparator picks the max exponent, the smaller operand is
  barrel-shifted with guard/sticky, magnitudes combine, and the result is
  renormalized.  With the default ``align_width=63`` every add/sub is
  *exactly rounded* (the emulated 64-bit datapath keeps 31 guard bits plus a
  sticky, which the analysis in DESIGN.md shows is sufficient).
* mul — full 32x32 significand product via 16-bit limb partial products
  (the TPU-native stand-in for the radix-4 Booth + CSA tree), single RNE.
* div — sign/exponent like mul; significand reciprocal via the paper's
  3-iteration Newton-Raphson in truncating fixed point (this faithfully
  reproduces the paper's ~95.8 % exact-match characteristic), then reuse of
  the multiplier.  ``mode='exact'`` swaps in a restoring long division
  (beyond-paper; 100 % exactly rounded).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import u64
from .bits import i32, u32
from .pir import PIR
from .types import PositConfig

_EXP_SENTINEL = -(1 << 28)  # stands in for -inf when an operand is zero


def negate(p: PIR) -> PIR:
    """Posit negation is exact: flip the sign (zero/NaR unchanged)."""
    sign = jnp.where(p.is_zero | p.is_nar, p.sign, p.sign ^ u32(1))
    return p._replace(sign=sign)


def _sig_to_u64(sig):
    """Q1.31 sig -> u64 with the implicit 1 at bit 62 (31 guard bits)."""
    return u64.U64(sig >> u32(1), sig << u32(31))


def _normalize_u64(mag: u64.U64, exp, sticky):
    """Renormalize so the MSB sits at bit 62; return (sig, exp, sticky).

    Handles both the carry-out case (MSB at 63) and cancellation (MSB
    anywhere below 62).  DESIGN.md shows sticky can only be nonzero when
    the left-shift is <= 1, so tail handling stays exact.
    """
    lz = u64.clz64(mag)                       # 0..64
    sh_l = jnp.maximum(lz - 1, 0)
    left = u64.shl(mag, sh_l)
    right, st_r = u64.shr_sticky(mag, i32(1))
    out = u64.select(lz == 0, right, left)
    sticky = sticky | jnp.where(lz == 0, st_r, u32(0))
    exp_out = exp + 1 - lz
    sig = (out.hi << u32(1)) | (out.lo >> u32(31))
    sticky = sticky | jnp.where((out.lo & u32(0x7FFFFFFF)) != 0, u32(1), u32(0))
    return sig, exp_out, sticky


def vpadd(a: PIR, b: PIR, cfg: PositConfig):
    """Vector posit add on PIRs -> (PIR, sticky)."""
    ea = jnp.where(a.is_zero, i32(_EXP_SENTINEL), a.exp)
    eb = jnp.where(b.is_zero, i32(_EXP_SENTINEL), b.exp)
    exp_t = jnp.maximum(ea, eb)

    d_a = jnp.clip(exp_t - ea, 0, 63)
    d_b = jnp.clip(exp_t - eb, 0, 63)
    m_a, st_a = u64.shr_sticky(_sig_to_u64(a.sig), d_a)
    m_b, st_b = u64.shr_sticky(_sig_to_u64(b.sig), d_b)
    # hardware aligner width (paper's third parameter): shifts beyond it
    # flush the operand entirely (value survives only through sticky).
    if cfg.align_width < 63:
        over_a = d_a > cfg.align_width
        over_b = d_b > cfg.align_width
        st_a = jnp.where(over_a & (a.sig != 0), u32(1), st_a)
        st_b = jnp.where(over_b & (b.sig != 0), u32(1), st_b)
        m_a = u64.select(over_a, u64.zeros_like(m_a), m_a)
        m_b = u64.select(over_b, u64.zeros_like(m_b), m_b)

    same = a.sign == b.sign
    a_ge_b = u64.ge(m_a, m_b)
    ssum = u64.add(m_a, m_b)
    diff = u64.select(a_ge_b, u64.sub(m_a, m_b), u64.sub(m_b, m_a))
    st = st_a | st_b  # at most one is nonzero (only the smaller shifts)
    # subtraction with a truncated tail: true = diff - delta, delta in (0,1)
    # ulp -> floor is diff-1 with sticky set.
    diff = u64.select((~same) & (st == 1), u64.sub(diff, u64.from32(u32(1))),
                      diff)
    mag = u64.select(same, ssum, diff)
    sign = jnp.where(same, a.sign, jnp.where(a_ge_b, a.sign, b.sign))

    sig, exp, sticky = _normalize_u64(mag, exp_t, st)

    out_zero = u64.is_zero(mag) & (st == 0)
    sign = jnp.where(out_zero, u32(0), sign)

    # zero operands: the other passes through untouched (exactly)
    sign = jnp.where(a.is_zero, b.sign, jnp.where(b.is_zero, a.sign, sign))
    exp = jnp.where(a.is_zero, b.exp, jnp.where(b.is_zero, a.exp, exp))
    sig = jnp.where(a.is_zero, b.sig, jnp.where(b.is_zero, a.sig, sig))
    sticky = jnp.where(a.is_zero | b.is_zero, u32(0), sticky)
    # boolean select as logic: Mosaic cannot lower a select of i1 vectors
    is_zero = (a.is_zero & b.is_zero) | (~a.is_zero & ~b.is_zero & out_zero)
    is_nar = a.is_nar | b.is_nar
    return PIR(sign, exp, sig, is_zero, is_nar), sticky


def vpsub(a: PIR, b: PIR, cfg: PositConfig):
    return vpadd(a, negate(b), cfg)


def vpmul(a: PIR, b: PIR, cfg: PositConfig):
    """Vector posit multiply on PIRs -> (PIR, sticky)."""
    del cfg
    sign = a.sign ^ b.sign
    exp = a.exp + b.exp
    prod = u64.mul_32x32(a.sig, b.sig)        # Q2.62, value in [1, 4)
    hi_set = (prod.hi >> u32(31)) != 0        # bit 63 -> value >= 2
    sig_hi = prod.hi                          # bits 63..32
    st_hi = jnp.where(prod.lo != 0, u32(1), u32(0))
    sig_lo = (prod.hi << u32(1)) | (prod.lo >> u32(31))
    st_lo = jnp.where((prod.lo & u32(0x7FFFFFFF)) != 0, u32(1), u32(0))
    sig = jnp.where(hi_set, sig_hi, sig_lo)
    sticky = jnp.where(hi_set, st_hi, st_lo)
    exp = exp + jnp.where(hi_set, i32(1), i32(0))

    is_zero = a.is_zero | b.is_zero
    is_nar = a.is_nar | b.is_nar
    sign = jnp.where(is_zero | is_nar, u32(0), sign)
    sig = jnp.where(is_zero, u32(0), sig)
    sticky = jnp.where(is_zero, u32(0), sticky)
    return PIR(sign, exp, sig, is_zero, is_nar), sticky


# ---------------------------------------------------------------------------
# Division
# ---------------------------------------------------------------------------

# Newton-Raphson seed x0 = 48/17 - 32/17 * c for c in [0.5, 1), in Q1.31.
_K1_Q31 = int(round(48 / 17 * (1 << 31)))   # needs 33 bits -> kept as u64
_K2_Q31 = int(round(32 / 17 * (1 << 31)))   # fits 32 bits


def _nr_reciprocal(sig_b, iters: int = 3):
    """Approximate 2^63 / sig_b (i.e. 1/c for c = sig_b * 2^-32 in (0.5, 1)).

    Returns x in Q1.31 (value = x * 2^-31 in (1, 2)).  Truncating fixed
    point throughout — this is the hardware-faithful path whose residual
    error gives the paper its 95.84 % division accuracy.
    """
    term = u64.mul_32x32(u32(_K2_Q31), sig_b).hi      # (K2 * c) in Q1.31
    k1 = u64.make(jnp.full_like(sig_b, _K1_Q31 >> 32),
                  jnp.full_like(sig_b, _K1_Q31 & 0xFFFFFFFF))
    x = u64.sub(k1, u64.from32(term)).lo              # x0 in Q1.31 (< 2^32)
    for _ in range(iters):
        t = u64.mul_32x32(sig_b, x)                   # c*x in Q2.62-ish
        tm = u64.neg(t)                               # (2 - c*x) at 2^63 scale
        hi = u64.mul_64x32_hi64(tm, x)                # (x*tm) >> 32
        x = (hi.hi << u32(1)) | (hi.lo >> u32(31))    # >> 63 overall -> Q1.31
    return x


def _div_exact_sig(sig_a, sig_b):
    """Exactly-rounded significand quotient via restoring long division.

    Computes q = sig_a / sig_b in (0.5, 2) with 33 quotient bits + exact
    remainder -> (sig Q1.31 normalized, exp_adjust, sticky).
    """
    # Pre-step establishes the invariant rem < den (ratio's integer bit),
    # then 33 shift-subtract steps develop q = floor(sig_a * 2^33 / sig_b).
    den = u64.from32(sig_b)
    ge0 = sig_a >= sig_b
    q = u64.from32(jnp.where(ge0, u32(1), u32(0)))
    rem = u64.from32(jnp.where(ge0, sig_a - sig_b, sig_a))

    def body(_, carry):
        q, rem = carry
        rem = u64.shl(rem, i32(1))
        geq = u64.ge(rem, den)
        rem = u64.select(geq, u64.sub(rem, den), rem)
        q = u64.add(u64.shl(q, i32(1)),
                    u64.from32(jnp.where(geq, u32(1), u32(0))))
        return q, rem

    q, rem = jax.lax.fori_loop(0, 33, body, (q, rem))
    sticky = jnp.where(u64.is_zero(rem), u32(0), u32(1))
    # q in (2^32, 2^34); value = q * 2^-33.
    # ratio >= 1 <=> bit 33 set: sig = q >> 2; else sig = q >> 1, exp -1.
    bit33 = (q.hi >> u32(1)) & u32(1)
    sig_hi, st_hi = u64.shr_sticky(q, i32(2))
    sig_lo, st_lo = u64.shr_sticky(q, i32(1))
    sig = jnp.where(bit33 == 1, sig_hi.lo, sig_lo.lo)
    sticky = sticky | jnp.where(bit33 == 1, st_hi, st_lo)
    exp_adj = jnp.where(bit33 == 1, i32(0), i32(-1))
    return sig, exp_adj, sticky


def vpdiv(a: PIR, b: PIR, cfg: PositConfig, mode: str = "nr3"):
    """Vector posit divide -> (PIR, sticky).

    mode='nr3'   paper-faithful Newton-Raphson, 3 iterations (§IV-D).
    mode='exact' beyond-paper exactly-rounded restoring division.
    """
    del cfg
    sign = a.sign ^ b.sign
    exp = a.exp - b.exp

    if mode == "exact":
        sig, exp_adj, sticky = _div_exact_sig(a.sig, b.sig)
        exp = exp + exp_adj
    elif mode == "nr3":
        x = _nr_reciprocal(b.sig, iters=3)
        prod = u64.mul_32x32(a.sig, x)        # value ~= 2*a/b in Q2.62
        # NR truncation can land the product marginally below 1.0, so use
        # the general renormalizer (handles MSB at 63, 62, or below).
        sig, exp, sticky = _normalize_u64(prod, exp, u32(jnp.zeros_like(x)))
        exp = exp - 1                          # fold the factor-of-2
        # exact shortcut when dividing by a power of two (sig_b == 1.0);
        # also guarantees q == a for b == 1 like the hardware fast path.
        pow2 = b.sig == u32(0x80000000)
        sig = jnp.where(pow2, a.sig, sig)
        sticky = jnp.where(pow2, u32(0), sticky)
        exp = jnp.where(pow2, a.exp - b.exp, exp)
    else:
        raise ValueError(f"unknown div mode {mode!r}")

    is_nar = a.is_nar | b.is_nar | b.is_zero  # x/0 = NaR (posit standard)
    is_zero = a.is_zero & ~b.is_zero
    sign = jnp.where(is_zero | is_nar, u32(0), sign)
    sig = jnp.where(is_zero, u32(0), sig)
    sticky = jnp.where(is_zero, u32(0), sticky)
    return PIR(sign, exp, sig, is_zero, is_nar), sticky
