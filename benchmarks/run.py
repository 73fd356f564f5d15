"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (harness contract).
  python -m benchmarks.run              # all
  python -m benchmarks.run accuracy     # one suite
  python -m benchmarks.run serve --json # also write BENCH_serve.json

``--json`` additionally writes one ``benchmarks/BENCH_<suite>.json``
per suite run (next to this file, regardless of the invoking CWD): the
same rows with the ``derived`` ``key=value`` pairs parsed into a dict
(numbers as numbers), so the perf trajectory — serving tok/s, goodput,
peak cache bytes — is machine-comparable across PRs.

``benchmarks/baselines/BENCH_<suite>.json`` holds the committed
baseline for a suite (seeded from the PR-6 run).  When one exists, each
fresh row is compared against its committed counterpart and a
``# delta vs baseline`` line is printed per matching row — refresh the
baseline by copying the new ``BENCH_<suite>.json`` over it whenever a
PR intentionally moves the numbers.
"""
from __future__ import annotations

import json
import os
import sys


def _parse_derived(derived: str) -> dict:
    out = {}
    for part in str(derived).split():
        if "=" not in part:
            out.setdefault("notes", []).append(part)
            continue
        k, v = part.split("=", 1)
        num = v[:-1] if v.endswith("x") else v
        try:
            out[k] = int(num)
        except ValueError:
            try:
                out[k] = float(num)
            except ValueError:
                out[k] = v
    return out


def _write_json(suite: str, rows) -> str:
    # artifacts land next to this file, never in the invoking CWD (a
    # repo-root BENCH_*.json was an easy stray to commit); committed
    # baselines live one level deeper in benchmarks/baselines/
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_{suite}.json")
    payload = [
        {"name": name, "us_per_call": float(us),
         "derived": _parse_derived(derived)}
        for name, us, derived in rows
    ]
    with open(path, "w") as f:
        json.dump({"suite": suite, "rows": payload}, f, indent=2)
        f.write("\n")
    return path


def _print_deltas(suite: str, rows, baselines_dir: str = None) -> None:
    """Compare fresh rows against ``benchmarks/baselines/BENCH_<suite>.json``
    (committed baseline) and print a ``# delta vs baseline`` line per
    matching row name.  A suite with no committed baseline says so
    explicitly (it used to skip silently, which read as "no change"
    when it meant "nothing to compare against"); corrupt baselines
    warn and skip."""
    if baselines_dir is None:
        baselines_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "baselines")
    path = os.path.join(baselines_dir, f"BENCH_{suite}.json")
    if not os.path.exists(path):
        print(f"# {suite}: no committed baseline "
              f"(benchmarks/baselines/BENCH_{suite}.json missing; run "
              f"'python -m benchmarks.run {suite} --json' and copy "
              f"benchmarks/BENCH_{suite}.json there to start tracking "
              "deltas)", file=sys.stderr, flush=True)
        return
    try:
        with open(path) as f:
            base = {r["name"]: r for r in json.load(f)["rows"]}
    except (OSError, ValueError, KeyError) as e:  # corrupt baseline: warn
        print(f"# baseline {path} unreadable: {e}", file=sys.stderr)
        return
    for name, us, derived in rows:
        ref = base.get(str(name))
        if ref is None:
            print(f"# {name}: new row (no baseline)", file=sys.stderr)
            continue
        parts = []
        b_us = float(ref.get("us_per_call", 0.0))
        if b_us > 0:
            parts.append(f"us_per_call {(float(us) - b_us) / b_us:+.1%}")
        fresh = _parse_derived(derived)
        for k, bv in ref.get("derived", {}).items():
            fv = fresh.get(k)
            if not isinstance(bv, (int, float)) or isinstance(bv, bool):
                continue
            if not isinstance(fv, (int, float)) or isinstance(fv, bool):
                continue
            if fv == bv:
                continue
            if bv != 0:
                parts.append(f"{k} {bv:g}->{fv:g} ({(fv - bv) / bv:+.1%})")
            else:
                parts.append(f"{k} {bv:g}->{fv:g}")
        if parts:
            print(f"# {name} delta vs baseline: " + " ".join(parts),
                  file=sys.stderr, flush=True)


def main() -> None:
    from benchmarks import (bench_accuracy, bench_compression, bench_cost,
                            bench_dnn_accuracy, bench_dot, bench_elementwise,
                            bench_serve, roofline)
    from repro.launch import compile_cache
    compile_cache.enable()
    suites = {
        "accuracy": bench_accuracy.run,        # paper §VI table
        "dnn": bench_dnn_accuracy.run,         # paper Figs 5/6
        "cost": bench_cost.run,                # paper Table IV analogue
        "compression": bench_compression.run,  # beyond-paper systems wins
        "elementwise": bench_elementwise.run,  # fused PVU ops vs round-trip
        "dot": bench_dot.run,                  # §IV-E tiled quire sweep
        "serve": bench_serve.run,              # engine tok/s + paged cache
        "roofline": roofline.run,              # §Roofline summary
    }
    args = sys.argv[1:]
    as_json = "--json" in args
    wanted = [a for a in args if a != "--json"] or list(suites)
    print("name,us_per_call,derived")
    for name in wanted:
        rows = list(suites[name]())
        for row in rows:
            print(",".join(str(x) for x in row), flush=True)
        _print_deltas(name, rows)
        if as_json:
            path = _write_json(name, rows)
            print(f"# wrote {path}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
