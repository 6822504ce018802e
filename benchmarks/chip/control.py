#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip, in one process.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3 \
        --seconds <s> [--control] [--fault <name>] [--override <json>]

Runs the cell once per seed, as ``bench.py`` does, and prints each run's
compared numbers.  With ``--control`` the plain reference in the next
precision below the configuration's takes the program's place (the serving
cell reads the program and the control on the same served tokens); it has
to come out as not correct.  ``--fault`` breaks the timed path as a
binding names it (``unchanged_ssd_state``, ``skipped_chunk``, ...), and
``--override`` replaces keys of the configuration or the mix
(``{"config": {...}, "traffic": {...}}``).  The benchmark's own runs never
run it.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault")
    ap.add_argument("--override", type=json.loads)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        line = bench.run(pathlib.Path.cwd(), args.workload, seed,
                         args.seconds, trace=False, control=args.control,
                         fault=args.fault, overrides=args.override)
        print(json.dumps({"seed": seed, "control": args.control,
                          "fault": args.fault, "override": args.override,
                          "correct": line["correct"],
                          "metrics": line["metrics"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
