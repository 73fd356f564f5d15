"""Lane ``mla``: multi-head latent attention with a q-LoRA and a dense
SwiGLU MLP in every layer (MiniCPM3).  The interface is ``lane.py``'s.

Reference equations (``reference.py``'s notation): ``q = rms(h@wdq,
q_norm) @ wuq`` split into ``(nope, rope)`` per head; ``[c, r] =
h@wdkv``, ``c = rms(c, kv_norm)``, ``k = [c@wuk per head, rope(r)
shared by all heads]``, ``v = c@wuv``; softmax of ``q.k / sqrt(nope +
rope)``; ``@ wo``.  (The program decodes with the absorbed form; this
is the expanded one.)  Each layer ``x += attn(rms(x, ln1)); x +=
swiglu(rms(x, ln2))``.

Departures from the published MiniCPM3, shared with the program: no
muP scalings (``scale_emb``, ``scale_depth``, ``dim_model_base``) and
plain RoPE in place of LongRoPE.  The config file lists these under
``reduced``.
"""
import jax.numpy as jnp

import hbm
from reference import attend, mm, rms, rope, stacked, swiglu
from weights import base_dims, dense, mlp, model, norm


def dims(c: dict) -> dict:
    return dict(base_dims(c), q_rank=c["q_lora_rank"],
                kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                rope=c["qk_rope_head_dim"], vd=c["v_head_dim"])


def layout(c: dict) -> dict:
    m = dims(c)
    d, n, h, qh = m["d"], m["n_layers"], m["h"], m["nope"] + m["rope"]
    attn = {"wdq": dense(n, d, m["q_rank"]), "q_norm": norm(n, m["q_rank"]),
            "wuq": dense(n, m["q_rank"], h * qh),
            "wdkv": dense(n, d, m["kv_rank"] + m["rope"]),
            "kv_norm": norm(n, m["kv_rank"]),
            "wuk": dense(n, m["kv_rank"], h * m["nope"]),
            "wuv": dense(n, m["kv_rank"], h * m["vd"]),
            "wo": dense(n, h * m["vd"], d)}
    return model(m, {"ln1": norm(n, d), "attn": attn, "ln2": norm(n, d),
                     "mlp": mlp(n, d, m["f"])})


def _layer(m, fp8, x, lw, pos):
    eps, t = m["eps"], x.shape[0]
    h = rms(x, lw["ln1"]["scale"], eps)
    a = lw["attn"]
    nh, nope, rp = m["h"], m["nope"], m["rope"]
    q = mm(rms(mm(h, a["wdq"]["w"], fp8), a["q_norm"]["scale"], eps),
           a["wuq"]["w"], fp8).reshape(t, nh, nope + rp)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, m["theta"])],
                        -1)
    dkv = mm(h, a["wdkv"]["w"], fp8)
    c = rms(dkv[:, :m["kv_rank"]], a["kv_norm"]["scale"], eps)
    r = rope(dkv[:, None, m["kv_rank"]:], pos, m["theta"])
    k = jnp.concatenate([mm(c, a["wuk"]["w"], fp8).reshape(t, nh, nope),
                         jnp.broadcast_to(r, (t, nh, rp))], -1)
    v = mm(c, a["wuv"]["w"], fp8).reshape(t, nh, m["vd"])
    x = x + mm(attend(q, k, v, (nope + rp) ** -0.5), a["wo"]["w"], fp8)
    return x + swiglu(rms(x, lw["ln2"]["scale"], eps), lw["mlp"], fp8)


def logits(c: dict, w, tokens, precision: str = "f32"):
    return stacked(dims(c), w, tokens, precision, _layer)


def fields(c: dict) -> dict:
    return {"q_lora_rank": c["q_lora_rank"], "kv_lora_rank": c["kv_lora_rank"],
            "qk_nope_dim": c["qk_nope_head_dim"],
            "qk_rope_dim": c["qk_rope_head_dim"],
            "v_head_dim": c["v_head_dim"],
            "head_dim": c["qk_nope_head_dim"] + c["qk_rope_head_dim"]}


def check(cfg, c: dict) -> None:
    if not cfg.mla or cfg.sliding_window or cfg.is_moe \
            or cfg.act != c["hidden_act"] or cfg.scale_embed \
            or cfg.norm_plus_one:
        raise ValueError(f"{c['name']}: the program's {c['program_arch']} "
                         "differs from the config file beyond the mapped "
                         "fields")


def per_position(c: dict) -> dict:
    m = dims(c)
    d, f, h, n = m["d"], m["f"], m["h"], m["n_layers"]
    qk, vd = m["nope"] + m["rope"], m["vd"]
    proj = (d * m["q_rank"] + m["q_rank"] * h * qk
            + d * (m["kv_rank"] + m["rope"])
            + m["kv_rank"] * h * (m["nope"] + vd) + h * vd * d)
    return dict(dense=2 * n * (proj + 3 * d * f),
                attn_per_ctx=2 * n * h * (qk + vd),
                head=2 * d * m["vocab"])


def weight_bytes(c: dict) -> int:
    return hbm.held_bytes(layout(c), c["torch_dtype"])


def kv_bytes_per_position(c: dict) -> int:
    """The latent and its RoPE part, every layer."""
    m = dims(c)
    return (m["kv_rank"] + m["rope"]) * m["n_layers"] \
        * hbm.KV_BYTES[c["kv_cache_dtype"]]


def round_bytes(c: dict, counters: dict) -> int:
    return hbm.dense_round_bytes(counters, weight_bytes(c),
                                 kv_bytes_per_position(c))
