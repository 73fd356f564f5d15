"""The paged decode step in its per-layer ``xs``/``ys`` formulation.

Each layer's arena slice rides the layer scan as ``xs`` and comes back
as ``ys``, written by ``paged_cache_update`` on that one slice and read
by ``paged_gather`` off it.  The model's step carries the stacked arenas
instead and updates them in place; the tests hold it to this reference
bit for bit (``test_paged.py``) and measure, on a described chip, the
copies and memory this formulation costs (``test_tpu_compile.py``).
"""
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models import transformer as T


def decode_step_xs_ys(params, cache, token, cfg, active):
    """Same signature and result as ``transformer._decode_step_paged``."""
    b = token.shape[0]
    lens = jnp.asarray(cache["lens"], jnp.int32)
    tables = cache["block_tables"]
    adv = jnp.ones((b,), jnp.int32) if active is None \
        else jnp.asarray(active).astype(jnp.int32)
    ok = (adv > 0) & (lens < jnp.asarray(cache["max_len"], jnp.int32))
    x = params["tok_embed"][token][:, None, :].astype(L.cdtype(cfg))
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    keys = ("c_kv", "k_rope") if cfg.mla else ("k", "v")
    attn = T._decode_attn_mla_paged if cfg.mla else \
        T._decode_attn_dense_paged

    def body(h, layer):
        lp, a_arena, b_arena = layer
        # layer=None: the helpers act on this one layer's arena slices
        a, a_arena, b_arena = attn(
            lp["attn"], L.rms_norm(lp["ln1"], h, cfg), a_arena, b_arena,
            None, tables, lens, ok, cfg)
        h = h + a
        hh = L.rms_norm(lp["ln2"], h, cfg)
        f = L.moe(lp["moe"], hh, cfg) if cfg.is_moe else \
            L.mlp(lp["mlp"], hh, cfg)
        return h + f, (a_arena, b_arena)

    x, arenas = lax.scan(
        body, x, (params["layers"], cache[keys[0]], cache[keys[1]]))
    new_cache = dict(cache, lens=lens + adv)
    new_cache.update(zip(keys, arenas))
    x = L.rms_norm(params["final_norm"], x, cfg)
    logits = x[:, 0, :] @ T._unembed_weight(params, cfg).astype(x.dtype)
    return logits.astype(jnp.float32), new_cache
