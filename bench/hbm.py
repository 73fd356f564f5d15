"""The least HBM traffic of a serving round, counted from the config file.

What the model's equations have to move, whatever implements them: a
decode step reads every held layer's weights and the LM head once,
reads the cached K and V (or latent) of each position it attends, and
writes one position per decoding row; a round with a prefilling row
reads the weights once more.  The embedding is read a row per token and
is left out.  What the program reads beyond that (every row's whole
``max_len``, the KV of decode steps past a row's last token) does not
count, so ``hbm_pct`` reads how much of the chip's bandwidth goes to
the model, the same for any program that does the same work.
"""
from __future__ import annotations

import math

from weights import dims, layout

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
KV_BYTES = {"posit16": 2, "posit8": 1}


def _sizes(tree):
    if isinstance(tree, dict):
        return sum(_sizes(v) for v in tree.values())
    shape, _ = tree
    return math.prod(shape)


def weight_bytes(c: dict) -> int:
    """Bytes of the layers, the final norm and the LM head as served."""
    spec = layout(c)
    n = sum(_sizes(spec[k]) for k in ("layers", "final_norm", "lm_head"))
    return n * DTYPE_BYTES[c["torch_dtype"]]


def kv_bytes_per_position(c: dict) -> int:
    """Cached bytes of one position over all layers: K and V of every KV
    head, or the latent and its RoPE part."""
    m = dims(c)
    width = m["kv_rank"] + m["rope"] if m["mla"] else 2 * m["g"] * m["hd"]
    return width * m["n_layers"] * KV_BYTES[c["kv_cache_dtype"]]


def round_bytes(c: dict, counters: dict) -> int:
    """One round from its ``sched.round`` counters: ``decode_steps``
    steps attending ``kv_live_positions`` positions in all, each writing
    one position for each of ``decode_rows`` rows, and the weights once
    more where ``prefill_rows``."""
    steps = counters["decode_steps"]
    weight_reads = steps + (1 if counters["prefill_rows"] else 0)
    positions = counters["kv_live_positions"] + steps * counters["decode_rows"]
    return (weight_reads * weight_bytes(c)
            + positions * kv_bytes_per_position(c))
