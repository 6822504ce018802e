#!/usr/bin/env python3
"""Where a cell's idle chip time goes, by the program's own spans: one
traced window on the chip, in one process.

    python3 benchmarks/chip/program_gaps.py --workload <name> --seed <n> \
        --seconds <s>

Runs the cell as ``bench.py --trace 1`` does (the mix's traced window, the
checks, the per-layer readers) and, before the run deletes its trace,
reduces that trace a second time against the runtime's ``repro.runtime.*``
spans (``yardstick/spans.py``).  Prints the run's result line with one key
more, ``program``: the idle seconds put down to the program span open over
each gap (``program_idle_gaps``), and in % of the traced window the idle
with no ``repro.runtime.chunk`` open on any thread
(``idle_outside_chunk_share``) and the idle while some worker of the pool
was outside ``repro.runtime.park`` (``idle_with_work_share``).  A program
that writes no such spans leaves the shares null and all its idle under
"no program span open".
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import bench
from yardstick import spans, trace


def run(root: pathlib.Path, workload: str, seed: int, seconds: float) -> dict:
    """One traced run of ``workload``; -> its result line with
    ``program`` added."""
    split: dict = {}
    load = trace.load

    def load_and_split(trace_dir: str, window_s: float | None = None):
        from jax.profiler import ProfileData
        out = load(trace_dir, window_s)
        profile = ProfileData.from_file(trace.find_xplane(trace_dir))
        split.update(spans.split(profile, out.window_ns))
        return out

    trace.load = load_and_split
    try:
        line = bench.run(root, workload, seed, seconds, trace=True)
    finally:
        trace.load = load
    line["program"] = split
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        line = run(pathlib.Path.cwd(), args.workload, args.seed,
                   args.seconds)
    except bench.NoChip as e:
        print(f"program_gaps: {e}; nothing run", file=sys.stderr)
        return 3
    print(json.dumps(bench._finite(line)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
