"""Error-feedback posit gradient compression (cross-pod DP sync).

Scheme (EF-SGD / EF21 style):
    buf    <- g + e                  (accumulate residual)
    q      <- posit_quantize(buf)    (what crosses the wire)
    e'     <- buf - dequantize(q)    (residual stays local)

With error feedback the quantization noise is *recycled*, so SGD/Adam
convergence is preserved (the bias telescopes).  ``tests/test_compression``
verifies convergence on a quadratic and exactness bounds.

The wire format is the paper's posit16/posit8; in the multi-pod train
step the quantized patterns (uint16/uint8) are what the 'pod'-axis
all-gather moves — see runtime/train_loop.py.

Wire-format (posit-domain) reductions — ``combine_compressed``,
``mean_compressed``, ``scale_compressed`` — run on the fused Pallas
elementwise kernels (``repro.kernels.ops``): the patterns never round-trip
through f32, so a hierarchical cross-pod reduction can re-transmit its
intermediate sums in wire format with one rounding per op instead of two.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.convert import f32_to_posit, posit_to_f32
from repro.core import softposit_ref
from repro.core.types import POSIT8, POSIT16, PositConfig
from repro.kernels import ops as kops

_CFGS = {"posit16": POSIT16, "posit8": POSIT8}


def pcfg_of(name: str) -> PositConfig:
    return _CFGS[name]


def init_error_state(params):
    return jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compress_with_feedback(grads, error, name: str):
    """Returns (patterns tree, new error tree)."""
    cfg = pcfg_of(name)

    def one(g, e):
        buf = g.astype(jnp.float32) + e
        q = f32_to_posit(buf, cfg)
        e_new = buf - posit_to_f32(q, cfg)
        return q, e_new

    out = jax.tree.map(one, grads, error)
    flat, td = jax.tree.flatten(out, is_leaf=lambda x: isinstance(x, tuple))
    qs = jax.tree.unflatten(td, [t[0] for t in flat])
    es = jax.tree.unflatten(td, [t[1] for t in flat])
    return qs, es


def decompress(patterns, name: str):
    cfg = pcfg_of(name)
    return jax.tree.map(lambda q: posit_to_f32(q, cfg), patterns)


# ---------------------------------------------------------------------------
# Posit-domain wire-format reductions (fused elementwise kernels)
# ---------------------------------------------------------------------------

def scalar_pattern(value: float, cfg: PositConfig):
    """Encode a python scalar as a 0-d posit pattern (exact RNE)."""
    return jnp.asarray(softposit_ref.from_float(float(value), cfg),
                       cfg.storage_dtype)


def combine_compressed(qa, qb, name: str, interpret: Optional[bool] = None):
    """Elementwise posit add of two wire-format gradient trees.

    Single rounding per element (fused decode->add->encode); the
    dequantize->f32 add->requantize composition this replaces rounds
    twice and costs two codec passes plus an f32 temporary.
    """
    cfg = pcfg_of(name)
    return jax.tree.map(
        lambda a, b: kops.vadd(a, b, cfg, interpret=interpret), qa, qb)


def scale_compressed(q, scale: float, name: str,
                     interpret: Optional[bool] = None):
    """Scale a wire-format tree by a scalar, staying in the posit domain."""
    cfg = pcfg_of(name)
    s = scalar_pattern(scale, cfg)
    return jax.tree.map(
        lambda p: kops.vmul(p, s, cfg, interpret=interpret), q)


def mean_compressed(q_tiled, name: str, interpret: Optional[bool] = None):
    """Mean over the leading (pod) axis, entirely in wire format.

    Pairwise vadd tree-reduction then one exact divide by the pod count
    (``mode='exact'`` — for power-of-two pod counts the divide is a pure
    exponent shift, so it never rounds).  The result is a pattern tree
    ready to re-transmit; ``decompress`` crosses back to f32.
    """
    cfg = pcfg_of(name)

    def one(q):
        parts = [q[i] for i in range(q.shape[0])]
        while len(parts) > 1:  # balanced tree keeps intermediate error low
            nxt = [kops.vadd(parts[i], parts[i + 1], cfg,
                             interpret=interpret)
                   for i in range(0, len(parts) - 1, 2)]
            if len(parts) % 2:
                nxt.append(parts[-1])
            parts = nxt
        count = scalar_pattern(float(q.shape[0]), cfg)
        return kops.vdiv(parts[0], count, cfg, mode="exact",
                         interpret=interpret)

    return jax.tree.map(one, q_tiled)
