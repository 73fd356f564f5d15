"""Plain float32 reference forward, from helpers that every lane shares.

Straight ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no cache,
no batching, no posit codec.  One sequence at a time, causal attention
over the whole sequence, layer by layer (each layer's bf16 weights are
upcast to f32 inside its own call, so no f32 copy of the model exists).
It imports nothing from the program; it reads the config file and the
weights that ``weights.py`` made.  Each lane (``lanes/<lane>.py``) writes
its block's equations with these helpers; :func:`logits` runs the lane
the config file names.

Shared equations (``x`` the residual stream, ``rms(v, w) = v /
sqrt(mean(v^2) + eps) * w``, RoPE on the half-split pairs ``(v[:k/2],
v[k/2:])`` at theta from the config, as Hugging Face's ``rotate_half``
does): the logits are ``rms(x, final_norm) @ lm_head`` after the
layers; a SwiGLU MLP is ``(silu(h@wg) * (h@wi)) @ wo``.

``precision="fp8"`` is the control: every dense matmul takes both its
operands rounded to float8 e4m3 (per-tensor scale for the weight,
per-row for the activation), the nearest precision below the bf16 the
configs state.  Attention scores and softmax stay f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

import lane

HI = lax.Precision.HIGHEST
_E4M3_MAX = 448.0
Q_BLOCK = 256


def _fp8(x, axis):
    """Round to float8 e4m3 with an absmax scale over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.matmul(x, w, precision=HI)


def rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, pos, theta):
    """x (T, heads, k) f32, pos (T,)."""
    k = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, k, 2, dtype=jnp.float32) / k)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(q, k, v, scale):
    """Causal attention. q (T,H,dq), k (T,H,dq), v (T,H,dv) -> (T,H*dv);
    queries in blocks so the score matrix stays small."""
    t, h, _ = q.shape
    nq = t // Q_BLOCK
    kpos = jnp.arange(t)

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[None, :, None] >= kpos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = lax.map(block, jnp.arange(nq))               # (nq, QB, H, dv)
    return out.reshape(t, h * v.shape[-1])


def swiglu(h, mw, fp8: bool):
    """A SwiGLU MLP ``{"wi", "wg", "wo"}`` on the normed stream ``h``."""
    f = jax.nn.silu(mm(h, mw["wg"]["w"], fp8)) * mm(h, mw["wi"]["w"], fp8)
    return mm(f, mw["wo"]["w"], fp8)


@functools.partial(jax.jit, static_argnames=("layer", "m_items", "fp8"))
def layer_at(x, layers, i, pos, *, layer, m_items, fp8):
    """``layer(m, fp8, x, lw, pos)`` on layer ``i`` of the stacked tree."""
    lw = jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, i, 0, False),
                      layers)
    return layer(dict(m_items), fp8, x, lw, pos)


@jax.jit
def embed(tok_embed, tokens):
    return tok_embed[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def head(x, final_norm, lm_head, *, eps, fp8):
    return mm(rms(x, final_norm["scale"], eps), lm_head["w"], fp8)


def bucket(n: int) -> int:
    """Padded sequence length: a power of two, at least ``Q_BLOCK``."""
    t = Q_BLOCK
    while t < n:
        t *= 2
    return t


def stacked(m: dict, w, tokens, precision: str, layer):
    """Logits of a model whose ``m["n_layers"]`` layers share one tree,
    each ``x = layer(m, fp8, x, lw, pos)``; see :func:`logits`."""
    fp8 = {"f32": False, "fp8": True}[precision]
    n = len(tokens)
    t = bucket(n)
    toks = jnp.zeros((t,), jnp.int32).at[:n].set(jnp.asarray(tokens, jnp.int32))
    pos = jnp.arange(t, dtype=jnp.int32)
    m_items = tuple(sorted(m.items()))
    x = embed(w["tok_embed"], toks)
    for i in range(m["n_layers"]):
        x = layer_at(x, w["layers"], jnp.int32(i), pos, layer=layer,
                     m_items=m_items, fp8=fp8)
    return head(x, w["final_norm"], w["lm_head"], eps=m["eps"],
                fp8=fp8)[:n]


def logits(c: dict, w, tokens, precision: str = "f32"):
    """(T, vocab) f32 logits of one causal sequence ``tokens`` (T,),
    padded on the right to :func:`bucket` (causality keeps padding out of
    the real positions), returned unpadded: the forward of the lane the
    config file names."""
    return lane.load(c).logits(c, w, tokens, precision)
