"""Pallas TPU kernel: the faithful PVU vpdot datapath (§IV-E), K-tiled.

One pass of the paper's pipeline per (row, K) tile, entirely in VMEM:
decode -> elementwise significand multiply (16-bit limb partial products)
-> align to the tile max exponent -> 128-bit two's-complement column
accumulation -> and, *across* K tiles, the streaming quire-lite state
(limb columns + alignment exponent + sticky + NaR) carried in VMEM
scratch via ``core.dot.quire_combine``.  The single normalize + RNE
encode happens once, on the last K step — so reductions of any length
round exactly once, and a reduction that fits one tile is bit-identical
to the original monolithic kernel.

This is the numerics-audit kernel (bit-exact posit dot products for
verification tables); the throughput path for large GEMMs is
``posit_gemm`` (dequant + MXU), and the bit-exact posit-in -> posit-out
matmul built on the same streaming quire is ``posit_qgemm``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dot as dot_mod
from repro.core.pir import decode, encode_pir
from repro.core.types import PositConfig

from ._compat import resolve_interpret

# rows x MAX_DOT_LENGTH keeps the quire working set (about 20 u32 planes
# per element) inside the TPU's default 16 MiB of scoped VMEM
DEFAULT_ROWS = 32
DEFAULT_BLOCK_K = dot_mod.MAX_DOT_LENGTH


def _read_state(acc_ref, mexp_ref, sticky_ref, nar_ref):
    return dot_mod.QuireState(acc=acc_ref[...],
                              m_exp=mexp_ref[...][:, 0],
                              sticky=sticky_ref[...][:, 0],
                              nar=nar_ref[...][:, 0] != 0)


def _write_state(st, acc_ref, mexp_ref, sticky_ref, nar_ref):
    acc_ref[...] = st.acc
    mexp_ref[...] = st.m_exp[:, None]
    sticky_ref[...] = st.sticky[:, None]
    nar_ref[...] = st.nar.astype(jnp.uint32)[:, None]


def quire_scratch(rows: int):
    """VMEM scratch carrying ``rows`` streamed quire states across K."""
    return [
        pltpu.VMEM((rows, dot_mod._NLIMB), jnp.uint32),   # quire limbs
        pltpu.VMEM((rows, 1), jnp.int32),                 # m_exp
        pltpu.VMEM((rows, 1), jnp.uint32),                # sticky
        pltpu.VMEM((rows, 1), jnp.uint32),                # NaR flag
    ]


def vpdot_tile(a, b, o_ref, acc_ref, mexp_ref, sticky_ref, nar_ref, *,
               cfg: PositConfig, k, nk: int):
    """One K step of row-wise quire dot products, shared by ``vpdot_rows``
    and ``posit_qgemm``: ``a``/``b`` (rows, bk) uint32 patterns; ``k`` is
    the step's position on the sequential K grid axis; the last step
    rounds once into ``o_ref`` (rows, 1)."""
    tile = dot_mod.quire_partial(decode(a, cfg), decode(b, cfg), axis=-1)

    @pl.when(k == 0)
    def _init():
        _write_state(tile, acc_ref, mexp_ref, sticky_ref, nar_ref)

    @pl.when(k > 0)
    def _accumulate():
        carried = _read_state(acc_ref, mexp_ref, sticky_ref, nar_ref)
        merged = dot_mod.quire_combine(carried, tile)
        _write_state(merged, acc_ref, mexp_ref, sticky_ref, nar_ref)

    @pl.when(k == nk - 1)
    def _finalize():
        state = _read_state(acc_ref, mexp_ref, sticky_ref, nar_ref)
        pir, sticky = dot_mod.quire_finalize(state)
        out = encode_pir(pir, cfg, sticky).astype(o_ref.dtype)
        o_ref[...] = out[:, None]


def _vpdot_kernel(a_ref, b_ref, o_ref, *scratch, cfg: PositConfig, nk: int):
    vpdot_tile(a_ref[...].astype(jnp.uint32), b_ref[...].astype(jnp.uint32),
               o_ref, *scratch, cfg=cfg, k=pl.program_id(1), nk=nk)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "block_rows", "block_k",
                                    "interpret"))
def vpdot_rows(a_patterns, b_patterns, cfg: PositConfig,
               block_rows: int = DEFAULT_ROWS, block_k: int | None = None,
               interpret=None):
    """Row-wise posit dot product: (R, L) x (R, L) -> (R,) patterns.

    L is unbounded: the reduction runs as a sequential K grid dimension
    of ``block_k`` (default MAX_DOT_LENGTH) tiles whose quire states
    accumulate in VMEM scratch.  L <= block_k is a single tile — the
    exact monolithic §IV-E pipeline.
    """
    r, length = a_patterns.shape
    if a_patterns.shape != b_patterns.shape:
        raise ValueError(
            f"vpdot_rows operand shapes differ: {a_patterns.shape} vs "
            f"{b_patterns.shape}")
    if r == 0 or length == 0:
        # empty quire -> posit zero (pattern 0); nothing to launch
        return jnp.zeros((r,), cfg.storage_dtype)
    bk = min(block_k or DEFAULT_BLOCK_K, length)
    if bk > dot_mod.MAX_DOT_LENGTH:
        raise ValueError(
            f"vpdot_rows block_k {bk} exceeds MAX_DOT_LENGTH="
            f"{dot_mod.MAX_DOT_LENGTH} (uint32 half-limb column-sum bound)")
    pad = (-length) % bk
    if pad:  # zero patterns decode to posit zero: excluded from the quire
        a_patterns = jnp.pad(a_patterns, ((0, 0), (0, pad)))
        b_patterns = jnp.pad(b_patterns, ((0, 0), (0, pad)))
    nk = (length + pad) // bk
    bm = min(block_rows, r)
    grid = (pl.cdiv(r, bm), nk)
    out = pl.pallas_call(
        functools.partial(_vpdot_kernel, cfg=cfg, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), cfg.storage_dtype),
        scratch_shapes=quire_scratch(bm),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(a_patterns, b_patterns)
    return out[:, 0]
