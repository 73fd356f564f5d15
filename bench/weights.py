"""Random weights from the seed, made by the benchmark, not the program.

The tree is the program's parameter layout (its checkpoint format),
as the config's lane lays it out (``lane.py``): ``tok_embed``,
``layers`` stacked over depth, ``final_norm``, ``lm_head``; each dense
leaf is ``{"w": (d_in, d_out)}`` and each norm ``{"scale": (d,)}``.
The same tree feeds the program under test and ``reference.py``, so
the reference never reads weights the program made.

Scales follow the usual fan-in rule (``d_in ** -0.5``), embeddings
``0.02``, norms ``1``: logits then spread about one unit, so a greedy
token is a clear winner at most positions.  Everything is made on the
device, in the served dtype, in one jitted call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import lane
from traffic import seed_sequence


def layout(c: dict) -> dict:
    """Leaf -> (shape, init) with init ``("normal", scale)`` or ``("ones",)``;
    layer leaves carry the depth as their first axis (its lane's
    ``layout``)."""
    return lane.load(c).layout(c)


def base_dims(c: dict) -> dict:
    """The sizes every lane shares."""
    return dict(d=c["hidden_size"], f=c["intermediate_size"],
                n_layers=c["num_hidden_layers"],
                h=c["num_attention_heads"], g=c["num_key_value_heads"],
                vocab=c["vocab_size"], eps=float(c["rms_norm_eps"]),
                theta=float(c["rope_theta"]))


def dense(n: int, i: int, o: int) -> dict:
    """A projection ``(d_in, d_out)`` stacked over ``n`` layers."""
    return {"w": ((n, i, o), ("normal", i ** -0.5))}


def norm(n: int, k: int) -> dict:
    return {"scale": ((n, k), ("ones",))}


def mlp(n: int, d: int, f: int) -> dict:
    """A SwiGLU MLP of width ``f`` stacked over ``n`` layers."""
    return {"wi": dense(n, d, f), "wg": dense(n, d, f), "wo": dense(n, f, d)}


def model(m: dict, layers: dict) -> dict:
    """The whole tree around a lane's ``layers``: embedding, final norm
    and LM head."""
    d = m["d"]
    return {
        "tok_embed": ((m["vocab"], d), ("normal", 0.02)),
        "layers": layers,
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
