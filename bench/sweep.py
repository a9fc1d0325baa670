#!/usr/bin/env python3
"""Find the highest arrival rate a cell's system sustains (its knee).

    python3 bench/sweep.py --workload <cell> --rates 0.4,0.6,0.8 \
        --seconds 40 --seed 7

One process, one system (weights, warm-up) for all rates; each rate
serves the cell's open-loop mix at that rate for its warm-up and a
window, then drains.  One JSON line per rate: requests due and finished,
time to first token (median, p90), time per output token (p90), output
tokens per second and the admission wait.  The knee is the highest rate
at which every request due finished within the drain and the tails
stay near the low-rate ones; the cell's traffic file then offers about
four fifths of it.  Kept to show how each cell's rate was set.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import arrivals as A
import drive
import run
import system as S


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    bench = run.load_bench(run.ROOT)
    cell = run.cell_spec(bench, args.workload)
    cfg = S.load_config(S.config_path(run.ROOT, bench, cell["config"]))
    base = A.load_named(run.HERE, cell["traffic"])
    run.use_persistent_cache()
    devices = run.find_devices(cell["chips"])
    clock = run.CompileClock()
    system = S.System(cfg, args.seed, run.make_mesh(cfg, devices))
    wd = drive.Driver(system, args.seed)
    drive.warm_shapes(wd, cfg, base)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(base, rate_per_s=rate)
        d = drive.Driver(system, args.seed)
        plan = drive.Plan(mix, cfg, args.seed, args.seconds)
        w0 = plan.start(d)
        w1 = w0 + args.seconds
        plan.window(d, w1)
        plan.drain(d, w0, w1, time.perf_counter() + run.DRAIN_S)
        while system.engine.active_count():
            d.boundary([], lambda: [])
        rec = drive.due_between(d.records, w0, w1)
        ok = [r for r in rec if r.status == "ok"]
        ttft = [(r.first - r.due) * 1e3 for r in ok]
        tpot = [(r.done - r.first) / (r.tokens - 1) * 1e3 for r in ok
                if r.tokens > 1]
        wait = [(r.admitted - r.due) * 1e3 for r in ok]
        got = sum(b.tokens for b in d.boundaries if w0 <= b.end < w1)
        print(json.dumps({
            "rate_per_s": rate, "due": len(rec), "finished": len(ok),
            "ttft_p50_ms": drive.percentile(ttft, 50) if ttft else None,
            "ttft_p90_ms": drive.percentile(ttft, 90) if ttft else None,
            "tpot_p90_ms": drive.percentile(tpot, 90) if tpot else None,
            "admit_wait_p90_ms": drive.percentile(wait, 90) if wait
            else None,
            "output_tok_s": got / args.seconds,
            "compiles_in_window": clock.names_between(w0, w1),
            "growth": system.engine.growth_stats()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
