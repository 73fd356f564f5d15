"""One general traffic generator; each mix is a data file in ``traffic/``.

Every seed gets the same work: the prompt lengths, answer lengths and
arrival times are drawn once from the mix's own ``size_seed``, in one
order, and the run's ``--seed`` draws only the token ids (and, in
``run.py``, the weights).  Permuting the schedule per seed was tried
first: with about 30 requests in the window, which long prompts arrive
late moved the p95 time to first token by 18 % (quartile spread over
six seeds) where two runs of one seed agreed within 1 %.

Two arrival kinds:

* ``open_loop``: ``round(rate * seconds)`` requests due inside the
  window.  The gaps are exponential (a Poisson process conditioned on
  its count), scaled so that the requests fall in ``[0, seconds)``; a
  request's latency is taken from when it was due, not from when the
  harness got round to submitting it.
* ``backlog``: ``backlog_per_slot * n_slots`` requests, all queued
  before the window opens, so the slot pool stays full.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def seed_sequence(seed: int, *salt: int) -> np.random.SeedSequence:
    """A ``SeedSequence`` for any whole number, negative or wider than
    64 bits, with ``salt`` separating streams drawn from one seed."""
    words = []
    s = abs(int(seed))
    while True:
        words.append(s & 0xFFFFFFFF)
        s >>= 32
        if not s:
            break
    words.append(1 if int(seed) < 0 else 0)
    return np.random.SeedSequence(words + [int(x) for x in salt])


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float          # offset from the window's start
    prompt: tuple         # token ids
    max_new_tokens: int


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def request_count(mix: dict, cell: dict, seconds: float) -> int:
    if mix["arrival"] == "open_loop":
        return max(1, int(round(cell["rate_per_s"] * seconds)))
    if mix["arrival"] == "backlog":
        return int(mix["backlog_per_slot"]) * int(cell["n_slots"])
    raise ValueError(f"unknown arrival kind {mix['arrival']!r}")


def generate(mix: dict, cell: dict, seed: int, seconds: float,
             vocab: int) -> list:
    """The run's requests, sorted by due time."""
    n = request_count(mix, cell, seconds)
    sizes = np.random.default_rng(seed_sequence(mix["size_seed"], n))
    plen = _lengths(sizes, mix["prompt_tokens"], n)
    olen = _lengths(sizes, mix["output_tokens"], n)
    if mix["arrival"] == "open_loop":
        gaps = sizes.exponential(1.0, size=n + 1)
    else:
        gaps = np.zeros(n + 1)

    rng = np.random.default_rng(seed_sequence(seed, 7))
    due = np.cumsum(gaps)[:n] / max(gaps.sum(), 1e-30) * float(seconds)
    return [Request(due_s=float(due[i]),
                    prompt=tuple(int(t) for t in
                                 rng.integers(1, vocab, int(plen[i]))),
                    max_new_tokens=int(olen[i]))
            for i in range(n)]
