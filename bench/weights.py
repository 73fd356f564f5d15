"""Random weights from the seed, made by the benchmark, not the program.

The tree is the program's parameter layout (its checkpoint format):
``tok_embed``, ``layers`` stacked over depth, ``final_norm``,
``lm_head``; each dense leaf is ``{"w": (d_in, d_out)}`` and each norm
``{"scale": (d,)}``.  The same tree feeds the program under test and
``reference.py``, so the reference never reads weights the program made.

Scales follow the usual fan-in rule (``d_in ** -0.5``), embeddings
``0.02``, norms ``1``: logits then spread about one unit, so a greedy
token is a clear winner at most positions.  Everything is made on the
device, in the served dtype, in one jitted call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from traffic import seed_sequence


def dims(c: dict) -> dict:
    """The sizes the reference and the layout need, from a config file."""
    d = dict(d=c["hidden_size"], f=c["intermediate_size"],
             n_layers=c["num_hidden_layers"], h=c["num_attention_heads"],
             g=c["num_key_value_heads"], vocab=c["vocab_size"],
             eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
             mla="kv_lora_rank" in c)
    if d["mla"]:
        d.update(q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
                 nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
                 vd=c["v_head_dim"])
    else:
        d["hd"] = c.get("head_dim", c["hidden_size"] // d["h"])
    return d


def layout(c: dict) -> dict:
    """Leaf -> (shape, init) with init ``("normal", scale)`` or ``("ones",)``;
    layer leaves carry the depth as their first axis."""
    m = dims(c)
    d, f, n = m["d"], m["f"], m["n_layers"]

    def dense(i, o):
        return {"w": ((n, i, o), ("normal", i ** -0.5))}

    def norm(k):
        return {"scale": ((n, k), ("ones",))}

    if m["mla"]:
        h, qh = m["h"], m["nope"] + m["rope"]
        attn = {"wdq": dense(d, m["q_rank"]), "q_norm": norm(m["q_rank"]),
                "wuq": dense(m["q_rank"], h * qh),
                "wdkv": dense(d, m["kv_rank"] + m["rope"]),
                "kv_norm": norm(m["kv_rank"]),
                "wuk": dense(m["kv_rank"], h * m["nope"]),
                "wuv": dense(m["kv_rank"], h * m["vd"]),
                "wo": dense(h * m["vd"], d)}
    else:
        hd = m["hd"]
        attn = {"wq": dense(d, m["h"] * hd), "wk": dense(d, m["g"] * hd),
                "wv": dense(d, m["g"] * hd), "wo": dense(m["h"] * hd, d)}
    return {
        "tok_embed": ((m["vocab"], d), ("normal", 0.02)),
        "layers": {"ln1": norm(d), "attn": attn, "ln2": norm(d),
                   "mlp": {"wi": dense(d, f), "wg": dense(d, f),
                           "wo": dense(f, d)}},
        "final_norm": {"scale": ((d,), ("ones",))},
        "lm_head": {"w": ((d, m["vocab"]), ("normal", d ** -0.5))},
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], tuple)


def make(c: dict, seed: int, dtype=jnp.bfloat16):
    """All weights for config ``c`` from ``seed``, on the default device."""
    specs = layout(c)
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    state = seed_sequence(seed, 11).generate_state(2, np.uint32)

    def build(key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        out = []
        for i, (shape, init) in enumerate(leaves):
            if init[0] == "ones":
                out.append(jnp.ones(shape, dtype))
            else:
                x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
                out.append((x * init[1]).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build)(jnp.asarray(state))
