"""Readings that a cell's correctness limit is set from, in one process.

    python bench/limits.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed: a whole run of the cell (weights, warm-up, the measured
window at the cell's own load), then the comparison of ``check.py`` on
the program's served tokens (the lower reading) and on the fp8
control's tokens for the same sample (the upper reading).  One JSON
line per seed; compiled programs are shared across the seeds.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    cell = run.load_cell(args.workload)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            result, log, _, _ = run.run_cell(cell, seed, args.seconds, False,
                                          control=True)
        except run.NoChip as e:
            print(f"limits: {e}", file=sys.stderr)
            return 3
        print("\n".join(log), file=sys.stderr, flush=True)
        print(json.dumps(dict(seed=seed, correct=result["correct"],
                              metrics=result["metrics"],
                              readings=result["readings"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
