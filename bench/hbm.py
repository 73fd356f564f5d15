"""The least HBM traffic of a serving round, counted from the config file.

What the model's equations have to move, whatever implements them: a
decode step reads every held layer's weights and the LM head once,
reads the cached K and V (or latent) of each position it attends, and
writes one position per decoding row; a round with a prefilling row
reads the weights once more.  The embedding is read a row per token and
is left out.  What the program reads beyond that (every row's whole
``max_len``, the KV of decode steps past a row's last token) does not
count, so ``hbm_pct`` reads how much of the chip's bandwidth goes to
the model, the same for any program that does the same work.  Each
lane (``lane.py``) counts its own block; a lane whose decode step reads
only part of its weights reads that part from its round's counters.
"""
from __future__ import annotations

import math

import lane

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
KV_BYTES = {"posit16": 2, "posit8": 1}


def weight_bytes(c: dict) -> int:
    """Bytes of the layers, the final norm and the LM head as served."""
    return lane.load(c).weight_bytes(c)


def kv_bytes_per_position(c: dict) -> int:
    """Cached bytes of one position over all layers."""
    return lane.load(c).kv_bytes_per_position(c)


def round_bytes(c: dict, counters: dict) -> int:
    """The least bytes of one round from its ``sched.round`` counters."""
    return lane.load(c).round_bytes(c, counters)


def _sizes(tree):
    if isinstance(tree, dict):
        return sum(_sizes(v) for v in tree.values())
    shape, _ = tree
    return math.prod(shape)


def held_bytes(spec: dict, dtype: str) -> int:
    """Bytes of a layout's layers, final norm and LM head in ``dtype``:
    what a decode step reads, the embedding being read a row per token."""
    n = sum(_sizes(spec[k]) for k in ("layers", "final_norm", "lm_head"))
    return n * DTYPE_BYTES[dtype]


def dense_round_bytes(counters: dict, weights: int, kv: int) -> int:
    """One round of a model whose decode step reads all ``weights`` bytes
    and ``kv`` bytes per position attended: ``decode_steps`` steps
    attending ``kv_live_positions`` positions in all, each writing one
    position for each of ``decode_rows`` rows, and the weights once more
    where ``prefill_rows``."""
    steps = counters["decode_steps"]
    weight_reads = steps + (1 if counters["prefill_rows"] else 0)
    positions = counters["kv_live_positions"] + steps * counters["decode_rows"]
    return weight_reads * weights + positions * kv
