"""Arithmetic shared by the metric readers in ``metrics/``.

A reader gets the run record that ``run.py`` builds and returns a
number, or ``None`` where it finds nothing to read (the harness then
leaves the metric out of the result).  Record times are seconds from
the window's start; the window is ``[0, seconds)``.

Per request: ``due``, ``admit`` (start of the round that admitted it, or
``None``), ``first`` (end of the round that delivered its first token,
or ``None``) and ``deliveries`` (``[time, tokens so far]`` at the end of
each round that delivered any).  Per round: ``start``, ``end``,
``steps`` (decode steps run), ``emitted`` and ``first_tokens`` (tokens
delivered, and how many of them were first tokens), ``spans``
(``[lo, hi, sampled]``: cache positions written and LM-head rows used,
per request) and ``traced``.
"""
from __future__ import annotations

import numpy as np


def p95(values):
    return float(np.percentile(values, 95)) if len(values) else None


def waits(run, key: str) -> list:
    """Seconds from due to ``key`` (``admit`` or ``first``) for every
    request due in the window; one still waiting at the close counts
    with its wait so far."""
    w = run["seconds"]
    out = []
    for r in run["requests"]:
        if r["due"] >= w:
            continue
        t = r[key]
        out.append((t if t is not None and t <= w else w) - r["due"])
    return out


def tpots(run) -> list:
    """Per request with two tokens or more by the close: seconds per
    token after the first, (last delivery - first delivery) / (n - 1)."""
    w = run["seconds"]
    out = []
    for r in run["requests"]:
        got = [(t, n) for t, n in r["deliveries"] if t <= w]
        if got and got[-1][1] >= 2:
            out.append((got[-1][0] - got[0][0]) / (got[-1][1] - 1))
    return out


def tokens_in_window(run) -> int:
    w = run["seconds"]
    total = 0
    for r in run["requests"]:
        got = [n for t, n in r["deliveries"] if t <= w]
        total += got[-1] if got else 0
    return total


def rounds_in_window(run) -> list:
    return [r for r in run["rounds"] if r["end"] <= run["seconds"]]


def traced_rounds(run) -> list:
    return [r for r in run["rounds"] if r["traced"]]


def module_ms(run, module: str):
    """Mean device milliseconds of one execution of ``module`` in the
    traced window (the trace names a module ``<name>(<fingerprint>)``)."""
    tr = run.get("trace")
    d = [x for name, xs in (tr or {}).get("modules", {}).items()
         if name.split("(")[0] == module for x in xs]
    return 1e3 * sum(d) / len(d) if d else None


def idle_pct(run):
    tr = run.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
