"""Helpers for the benchmark's own tests (run by hand, on the CPU):

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

# lane -> its toy-width config (``tiny/<lane>.json``): a new lane brings
# its own file
TINY = {p.stem: json.loads(p.read_text())
        for p in sorted((BENCH / "tests" / "tiny").glob("*.json"))}

TINY_CHAT = {"arrival": "open_loop", "size_seed": 5,
             "prompt_tokens": {"median": 20, "sigma": 0.8, "min": 4,
                               "max": 60},
             "output_tokens": {"median": 12, "sigma": 0.5, "min": 4,
                               "max": 30}}


def tiny_cell(lane: str, **check) -> dict:
    """A whole cell at toy sizes: what ``run.load_cell`` returns."""
    limits = {"sample": 3, "max_logit_gap": 0.5, "min_tokens_compared": 8}
    limits.update(check)
    return dict(
        name=f"tiny-{lane}.chat", chips=1, config=TINY[lane], mix=TINY_CHAT,
        params={"n_slots": 4, "max_len": 128, "block_size": 8,
                "chunk_size": 8, "rate_per_s": 4.0,
                "trace": {"last_s": 2}, "check": limits},
        end_to_end=[{"name": n, "unit": u} for n, u in
                    (("ttft_p95_ms", "ms"), ("tpot_p95_ms", "ms"),
                     ("output_tok_s", "tokens/s"), ("setup_s", "s"))],
        per_layer=[{"name": n, "unit": u} for n, u in
                   (("queue_wait_p95_ms.chat", "ms"),
                    ("decode_slot_use.batch", "%"))])
