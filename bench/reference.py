"""Plain float32 reference forward for the two attention lanes.

Straight ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no cache,
no batching, no posit codec.  One sequence at a time, causal attention
over the whole sequence, layer by layer (each layer's bf16 weights are
upcast to f32 inside its own call, so no f32 copy of the model exists).
It imports nothing from the program; it reads the config file and the
weights that ``weights.py`` made.

Equations (``x`` the residual stream, ``rms(v, w) = v / sqrt(mean(v^2) +
eps) * w``, RoPE on the half-split pairs ``(v[:k/2], v[k/2:])`` at theta
from the config, as Hugging Face's ``rotate_half`` does):

* dense GQA (Phi-3): ``q, k, v = h@wq, h@wk, h@wv``; RoPE on ``q, k``;
  query head ``i`` reads KV head ``i // (heads / kv_heads)``; softmax of
  ``q.k / sqrt(head_dim)``; ``@ wo``.
* MLA (MiniCPM3): ``q = rms(h@wdq, q_norm) @ wuq`` split into
  ``(nope, rope)`` per head; ``[c, r] = h@wdkv``, ``c = rms(c, kv_norm)``,
  ``k = [c@wuk per head, rope(r) shared by all heads]``, ``v = c@wuv``;
  softmax of ``q.k / sqrt(nope + rope)``; ``@ wo``.  (The program decodes
  with the absorbed form; this is the expanded one.)
* each layer ``x += attn(rms(x, ln1)); x += (silu(h@wg) * (h@wi)) @ wo``
  with ``h = rms(x, ln2)``; logits ``rms(x, final_norm) @ lm_head``.

Departures from the published models, shared with the program: MiniCPM3
without its muP scalings (``scale_emb``, ``scale_depth``,
``dim_model_base``) and with plain RoPE in place of LongRoPE; Phi-3
without its ``sliding_window`` (full attention, as HF eager runs it).
The config files list these under ``reduced``.

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

from weights import dims

HI = lax.Precision.HIGHEST
_E4M3_MAX = 448.0
Q_BLOCK = 256


def _fp8(x, axis):
    """Round to float8 e4m3 with an absmax scale over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (T, heads, k) f32, pos (T,)."""
    k = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, k, 2, dtype=jnp.float32) / k)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, scale):
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


def _layer(m, fp8, x, lw, pos):
    eps, t = m["eps"], x.shape[0]
    h = _rms(x, lw["ln1"]["scale"], eps)
    a = lw["attn"]
    if m["mla"]:
        nh, nope, rope = m["h"], m["nope"], m["rope"]
        q = _mm(_rms(_mm(h, a["wdq"]["w"], fp8), a["q_norm"]["scale"], eps),
                a["wuq"]["w"], fp8).reshape(t, nh, nope + rope)
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], pos, m["theta"])], -1)
        dkv = _mm(h, a["wdkv"]["w"], fp8)
        c = _rms(dkv[:, :m["kv_rank"]], a["kv_norm"]["scale"], eps)
        r = _rope(dkv[:, None, m["kv_rank"]:], pos, m["theta"])
        k = jnp.concatenate(
            [_mm(c, a["wuk"]["w"], fp8).reshape(t, nh, nope),
             jnp.broadcast_to(r, (t, nh, rope))], -1)
        v = _mm(c, a["wuv"]["w"], fp8).reshape(t, nh, m["vd"])
        o = _attend(q, k, v, (nope + rope) ** -0.5)
    else:
        nh, g, hd = m["h"], m["g"], m["hd"]
        q = _rope(_mm(h, a["wq"]["w"], fp8).reshape(t, nh, hd), pos,
                  m["theta"])
        k = _rope(_mm(h, a["wk"]["w"], fp8).reshape(t, g, hd), pos,
                  m["theta"])
        v = _mm(h, a["wv"]["w"], fp8).reshape(t, g, hd)
        k = jnp.repeat(k, nh // g, axis=1)
        v = jnp.repeat(v, nh // g, axis=1)
        o = _attend(q, k, v, hd ** -0.5)
    x = x + _mm(o, a["wo"]["w"], fp8)
    h = _rms(x, lw["ln2"]["scale"], eps)
    mw = lw["mlp"]
    f = jax.nn.silu(_mm(h, mw["wg"]["w"], fp8)) * _mm(h, mw["wi"]["w"], fp8)
    return x + _mm(f, mw["wo"]["w"], fp8)


@functools.partial(jax.jit, static_argnames=("m_items", "fp8"))
def _layer_at(x, layers, i, pos, *, m_items, fp8):
    lw = jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, i, 0, False),
                      layers)
    return _layer(dict(m_items), fp8, x, lw, pos)


@jax.jit
def _embed(tok_embed, tokens):
    return tok_embed[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, final_norm, lm_head, *, eps, fp8):
    return _mm(_rms(x, final_norm["scale"], eps), lm_head["w"], fp8)


def bucket(n: int) -> int:
    """Padded sequence length: a power of two, at least ``Q_BLOCK``."""
    t = Q_BLOCK
    while t < n:
        t *= 2
    return t


def logits(c: dict, w, tokens, precision: str = "f32"):
    """(T, vocab) f32 logits of one causal sequence ``tokens`` (T,),
    padded on the right to :func:`bucket` (causality keeps padding out of
    the real positions), returned unpadded."""
    m = dims(c)
    fp8 = {"f32": False, "fp8": True}[precision]
    n = len(tokens)
    t = bucket(n)
    toks = jnp.zeros((t,), jnp.int32).at[:n].set(jnp.asarray(tokens, jnp.int32))
    pos = jnp.arange(t, dtype=jnp.int32)
    m_items = tuple(sorted(m.items()))
    x = _embed(w["tok_embed"], toks)
    for i in range(m["n_layers"]):
        x = _layer_at(x, w["layers"], jnp.int32(i), pos, m_items=m_items,
                      fp8=fp8)
    return _head(x, w["final_norm"], w["lm_head"], eps=m["eps"],
                 fp8=fp8)[:n]
