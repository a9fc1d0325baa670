#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 20 --control-seeds 3

For each seed, one whole run of the cell (``run.execute``, a short
window at the cell's own load) gives the program's reading of every
number compared; for the first ``--control-seeds`` seeds the control
(the reference with every matmul operand in float8, put in the
program's place) goes through the same comparison
(``check.compare(..., control=True)``) on the same sampled prompts and
tokens, and its ``correct`` has to come out false.  One
JSON line per seed; the limits in ``limits/<cell>.json`` are set from
these (PERF.md gives the readings and the limits).  The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run
import check


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        got = {}

        class A:
            workload, trace, seconds = args.workload, 0, args.seconds
        a = A()
        a.seed = seed
        t = time.perf_counter()
        res = run.execute(a, t_start=t, hooks={
            "after_check": lambda c, s, n, w, lim: got.update(
                cfg=c, sample=s, numbers=n, window=w, limits=lim)})
        line = {"seed": seed, "correct": res["correct"],
                "program": {k: v["value"] for k, v in got["numbers"].items()},
                "tokens": sum(len(r.token_ids) for r in got["sample"]),
                "seconds": time.perf_counter() - t}
        if i < args.control_seeds:
            t = time.perf_counter()
            numbers = check.compare(got["cfg"], seed, got["sample"],
                                    got["window"], got["limits"],
                                    control=True)
            line["control"] = {k: v["value"] for k, v in numbers.items()}
            line["control_correct"] = check.is_correct(numbers)
            line["control_seconds"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
