"""Lane ``gqa``: dense grouped-query attention and a dense SwiGLU MLP in
every layer (Phi-3).  The interface is ``lane.py``'s.

Reference equations (``reference.py``'s notation): ``q, k, v = h@wq,
h@wk, h@wv``; RoPE on ``q, k``; query head ``i`` reads KV head ``i //
(heads / kv_heads)``; softmax of ``q.k / sqrt(head_dim)``; ``@ wo``.
Each layer ``x += attn(rms(x, ln1)); x += swiglu(rms(x, ln2))``.

Departure from the published Phi-3, shared with the program: no
``sliding_window`` (full attention, as Hugging Face's eager attention
runs it).
"""
import jax.numpy as jnp

import hbm
from reference import attend, mm, rms, rope, stacked, swiglu
from weights import base_dims, dense, mlp, model, norm


def dims(c: dict) -> dict:
    return dict(base_dims(c), hd=c.get("head_dim", c["hidden_size"]
                                       // c["num_attention_heads"]))


def layout(c: dict) -> dict:
    m = dims(c)
    d, n, hd = m["d"], m["n_layers"], m["hd"]
    attn = {"wq": dense(n, d, m["h"] * hd), "wk": dense(n, d, m["g"] * hd),
            "wv": dense(n, d, m["g"] * hd), "wo": dense(n, m["h"] * hd, d)}
    return model(m, {"ln1": norm(n, d), "attn": attn, "ln2": norm(n, d),
                     "mlp": mlp(n, d, m["f"])})


def _layer(m, fp8, x, lw, pos):
    eps, t = m["eps"], x.shape[0]
    h = rms(x, lw["ln1"]["scale"], eps)
    a = lw["attn"]
    nh, g, hd = m["h"], m["g"], m["hd"]
    q = rope(mm(h, a["wq"]["w"], fp8).reshape(t, nh, hd), pos, m["theta"])
    k = rope(mm(h, a["wk"]["w"], fp8).reshape(t, g, hd), pos, m["theta"])
    v = mm(h, a["wv"]["w"], fp8).reshape(t, g, hd)
    k = jnp.repeat(k, nh // g, axis=1)
    v = jnp.repeat(v, nh // g, axis=1)
    x = x + mm(attend(q, k, v, hd ** -0.5), a["wo"]["w"], fp8)
    return x + swiglu(rms(x, lw["ln2"]["scale"], eps), lw["mlp"], fp8)


def logits(c: dict, w, tokens, precision: str = "f32"):
    return stacked(dims(c), w, tokens, precision, _layer)


def fields(c: dict) -> dict:
    return {"head_dim": dims(c)["hd"]}


def check(cfg, c: dict) -> None:
    if cfg.mla or cfg.sliding_window or cfg.is_moe \
            or cfg.act != c["hidden_act"] or cfg.scale_embed \
            or cfg.norm_plus_one:
        raise ValueError(f"{c['name']}: the program's {c['program_arch']} "
                         "differs from the config file beyond the mapped "
                         "fields")


def per_position(c: dict) -> dict:
    m = dims(c)
    d, f, h, n, hd = m["d"], m["f"], m["h"], m["n_layers"], m["hd"]
    proj = d * h * hd + 2 * d * m["g"] * hd + h * hd * d
    return dict(dense=2 * n * (proj + 3 * d * f),
                attn_per_ctx=2 * n * h * (hd + hd),
                head=2 * d * m["vocab"])


def weight_bytes(c: dict) -> int:
    return hbm.held_bytes(layout(c), c["torch_dtype"])


def kv_bytes_per_position(c: dict) -> int:
    """K and V of every KV head, every layer."""
    m = dims(c)
    return 2 * m["g"] * m["hd"] * m["n_layers"] \
        * hbm.KV_BYTES[c["kv_cache_dtype"]]


def round_bytes(c: dict, counters: dict) -> int:
    return hbm.dense_round_bytes(counters, weight_bytes(c),
                                 kv_bytes_per_position(c))
