"""Find the open-loop knee of a chat cell once, by a sweep on the chip.

    python bench/sweep.py --workload <cell> --seconds <s> --rates 0.3,0.5,0.7

Runs the cell at each offered rate in one process (compiled programs
shared) and prints, per rate, the end-to-end metrics and how far the
queue grew: requests due, admitted, with a first token, and still
queued at the close.  The knee is the highest rate at which the
queue does not grow through the window; the cell's ``rate_per_s`` is
set at about four fifths of it, as a number in the cell file.  Runs of
the benchmark never sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    cell = run.load_cell(args.workload)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for rate in (float(r) for r in args.rates.split(",")):
        c = dict(cell, params=dict(cell["params"], rate_per_s=rate))
        try:
            result, _, _, rec = run.run_cell(c, args.seed, args.seconds,
                                             False)
        except run.NoChip as e:
            print(f"sweep: {e}", file=sys.stderr)
            return 3
        w = rec["seconds"]
        reqs = rec["requests"]
        ttft = [(r["first"] if r["first"] is not None and r["first"] <= w
                 else w) - r["due"] for r in reqs]
        print(json.dumps(dict(
            rate=rate, correct=result["correct"],
            metrics={k: v["value"] for k, v in result["metrics"].items()},
            due=len(reqs),
            admitted=sum(r["admit"] is not None and r["admit"] <= w
                         for r in reqs),
            first_token=sum(r["first"] is not None and r["first"] <= w
                            for r in reqs),
            queued_at_close=sum(r["admit"] is None or r["admit"] > w
                                for r in reqs),
            ttft_p50_s=float(np.median(ttft)),
            rounds=len(rec["rounds"]))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
