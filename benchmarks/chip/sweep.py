#!/usr/bin/env python3
"""Rate sweep of a serving cell, on the chip, in one process.

    python3 benchmarks/chip/sweep.py --workload <name> --rates 1,2,3 \
        --seconds <s> --seed <n> --schedules 1,2

Runs the cell's mix at each offered rate, on each schedule (the mix's
``schedule_seed``), and prints the tails, the completed tokens per second
and the mean wait of the first and of the last quarter of the requests.  A
rate the system sustains completes the tokens it is offered on every
schedule; above it the tokens fall short and the wait grows through the
window.  The quarters alone mislead at lower rates, where they move either
way with the sizes of the requests that fall in them.  Nothing is compared
with the reference.  A cell's rate
is fixed in its traffic file from such a sweep; the benchmark never
searches for one.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--schedules", default="1")
    args = ap.parse_args(argv)
    for sched in (int(s) for s in args.schedules.split(",")):
        for rate in (float(r) for r in args.rates.split(",")):
            line = bench.run(pathlib.Path.cwd(), args.workload, args.seed,
                             args.seconds, trace=False, keep_cell=True,
                             check=False, overrides={"traffic": {
                                 "rate_per_s": rate,
                                 "schedule_seed": sched}})
            first, last = line.pop("cell").wait_trend()
            print(json.dumps({
                "rate_per_s": rate, "schedule_seed": sched,
                "metrics": {k: v["value"]
                            for k, v in line["metrics"].items()},
                "wait_first_quarter_s": first,
                "wait_last_quarter_s": last}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
