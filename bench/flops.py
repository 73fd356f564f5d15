"""Model FLOPs from shapes, and the chip's peaks.

Model FLOPs count what the model's equations need, once per position:
the dense projections (2 per multiply-add), causal attention over the
position's own context (scores and the weighted sum), and the LM head
only where a token is sampled.  The program's own extra work (decode
steps of rows that already finished, re-expanding MLA latents over the
whole ``max_len`` each prefill chunk) does not count, so ``mfu`` reads
how much of the chip's peak goes to the model.
"""
from __future__ import annotations

import json
from pathlib import Path

import lane

PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a device not in the
    table is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add a row with its source")
    return table[device_kind]


def per_position(c: dict) -> dict:
    """FLOPs of one position: ``dense`` (all layers' projections),
    ``attn_per_ctx`` (attention per key attended, all layers) and
    ``head`` (the LM head), as the config's lane counts them."""
    return lane.load(c).per_position(c)


def span(c: dict, lo: int, hi: int, sampled: int) -> float:
    """FLOPs of cache positions ``lo .. hi-1`` of one sequence (position
    ``p`` attends to ``p + 1`` keys) plus ``sampled`` LM-head rows."""
    if hi <= lo:
        return float(sampled * per_position(c)["head"])
    pp = per_position(c)
    keys = (hi * (hi + 1) - lo * (lo + 1)) // 2     # sum of p + 1
    return float((hi - lo) * pp["dense"] + keys * pp["attn_per_ctx"]
                 + sampled * pp["head"])
