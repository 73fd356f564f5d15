"""The comparison that decides ``correct``.

After the window, a sample of the requests the program finished, drawn
from the seed and always holding the longest, is run once through the
float32 reference over ``prompt + served tokens``.  At each served
position the reference's best logit is compared with its logit for the
token the program served; the number compared is the widest such gap.
A greedy program that computes the model correctly serves the
reference's best token, or one within rounding of it, so the gap stays
small; a lost KV write, a wrong position or an altered token serves a
token the reference ranks far down.

The first served token comes from the chunked prefill's logits and the
rest from decode steps that read the posit16 arena, so the comparison
covers both, and the LM head.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import reference
from traffic import seed_sequence


def sample(finished: list, n: int, seed: int) -> list:
    """``n`` finished requests: the longest, then others drawn from the
    seed.  ``finished`` holds ``(prompt, tokens)`` pairs."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    rest = order[1:]
    rng = np.random.default_rng(seed_sequence(seed, 13))
    picked = [order[0]] + [rest[i] for i in rng.permutation(len(rest))]
    return [finished[i] for i in picked[:n]]


def served_gaps(c: dict, w, prompt, served, precision: str = "f32"):
    """Per served token: the f32 reference's best logit minus its logit
    for the token served.  With ``precision="fp8"`` the served tokens are
    ignored and the tokens are the ones the fp8 control ranks first on
    the same prefixes (the control's reading)."""
    served = np.asarray(served, np.int64)
    toks = list(prompt) + [int(t) for t in served[:-1]]
    p = len(prompt)
    ref = reference.logits(c, w, toks)[p - 1:]
    if precision == "f32":
        pick = jnp.asarray(served, jnp.int32)
    else:
        pick = jnp.argmax(reference.logits(c, w, toks, precision)[p - 1:], -1)
    at = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return np.asarray(ref.max(-1) - at, np.float64)


def compare(c: dict, w, finished: list, seed: int, n_sample: int,
            precision: str = "f32") -> dict:
    """Readings over the sample: the widest gap, and what was compared."""
    picked = sample(finished, n_sample, seed)
    gaps = [served_gaps(c, w, p, s, precision) for p, s in picked]
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    return dict(max_logit_gap=float(flat.max()) if flat.size else None,
                tokens_compared=int(flat.size),
                requests_compared=len(picked),
                longest_compared=max((len(p) + len(s) for p, s in picked),
                                     default=0))


def verdict(readings: dict, limits: dict) -> tuple:
    """``(correct, checks)``: each compared number beside its limit."""
    checks = {
        "max_logit_gap": {"value": readings["max_logit_gap"],
                          "limit": limits["max_logit_gap"], "rule": "<="},
        "tokens_compared": {"value": readings["tokens_compared"],
                            "limit": limits["min_tokens_compared"],
                            "rule": ">="},
    }
    ok = readings["max_logit_gap"] is not None \
        and readings["max_logit_gap"] <= limits["max_logit_gap"] \
        and readings["tokens_compared"] >= limits["min_tokens_compared"]
    return bool(ok), checks
