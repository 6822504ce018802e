#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip it is started on.

    python3 benchmarks/chip/bench.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``.  The harness is
driven by data: a cell of ``BENCHMARK.json`` names a configuration and a
traffic mix, and the harness finds

* the configuration's sizes in the file its entry names, and the code that
  binds it to the program (and its plain reference) in
  ``configs/<binding>.py``, where ``binding`` is a key of that file;
* the traffic mix in ``traffic/<traffic>.json``;
* each per-layer metric's reader in ``metrics/<metric>.py``.

A new cell is new files and new entries; no file here changes.

The run refuses to start without a TPU (exit 3, no result).  It makes its
weights and inputs from ``--seed``, warms every shape the cell uses (set-up,
timed from process start to the window's first arrival), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line last on standard output.  ``--trace 1``
runs the mix's shorter traced window under the profiler and reports the
per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    """Everything a configuration's binding is given for one run."""

    root: pathlib.Path          # checkout root (holds BENCHMARK.json)
    bench_dir: pathlib.Path     # the benchmark's directory
    cell: dict                  # the BENCHMARK.json workload entry
    config: dict                # the configuration file's contents
    traffic: dict               # the traffic mix's contents
    seed: int
    seconds: float
    trace: bool
    fault: str | None = None    # tests only: break the timed path this way
    control: bool = False       # lower-precision control in the program's
    #                             place (benchmarks/chip/control.py)

    def log(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path):
    """Import a file by path; names may hold dots and hyphens."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: pathlib.Path) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def resolve(root: pathlib.Path, bench: dict, workload: str):
    """-> (bench_dir, cell, config, traffic, binding module)."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    bench_dir = root / bench["paths"][0]
    with open(root / entry["file"]) as f:
        config = json.load(f)
    with open(bench_dir / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    binding = load_module(bench_dir / "configs" / f"{config['binding']}.py")
    return bench_dir, cell, config, traffic, binding


def _listed(metric: dict, cell: dict, bench: dict) -> bool:
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:                       # an end-to-end metric
        return True
    e2e = {m["name"]: m for m in bench["end_to_end"]}[moves]
    return _listed(e2e, cell, bench)


def cell_metrics(bench: dict, cell: dict, kind: str) -> list:
    return [m for m in bench[kind] if _listed(m, cell, bench)]


def chips(n: int):
    """The first ``n`` TPU chips, or :class:`NoChip`."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:n]


def enable_cache(bench_dir: pathlib.Path) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else a fixed directory inside the checkout.  Every
    program is cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        bench_dir / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def run(root: pathlib.Path, workload: str, seed: int, seconds: float,
        trace: bool, require_tpu: bool = True, use_cache: bool = True,
        fault: str | None = None, control: bool = False,
        overrides: dict | None = None, keep_cell: bool = False,
        check: bool = True) -> dict:
    """One run; returns the result line as a dict.  Tests pass
    ``require_tpu=False`` to drive the rest of a run on the CPU, and
    ``overrides`` (``{"config": {...}, "traffic": {...}}``) to shrink it.
    ``check=False`` (the rate sweep) compares nothing: ``correct`` is
    then ``None``."""
    bench = load_benchmark(root)
    bench_dir, cell, config, traffic, binding = resolve(root, bench, workload)
    if overrides:
        config = {**config, **overrides.get("config", {})}
        traffic = {**traffic, **overrides.get("traffic", {})}
    for p in (str(bench_dir), str(root / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from yardstick.clock import CompileClock
    from yardstick.peaks import device_peaks

    if require_tpu:
        devices = chips(cell["chips"])
        peaks = device_peaks(devices[0].device_kind)
    else:
        devices = jax.devices()[:cell["chips"]]
        peaks = None
    dev = devices[0]
    tag = f"[{dev.platform} {dev.device_kind} x{len(devices)}]"
    cache = enable_cache(bench_dir) if use_cache else "off"
    clock = CompileClock().install()
    ctx = Context(root=root, bench_dir=bench_dir, cell=cell, config=config,
                  traffic=traffic, seed=seed, seconds=seconds, trace=trace,
                  fault=fault, control=control)
    ctx.log(f"{tag} cell {workload} seed {seed} seconds {seconds} trace "
            f"{int(trace)} compile cache {cache}")

    c = binding.Cell(ctx)
    c.setup()
    setup_s = time.perf_counter() - T_START
    ctx.log(f"{tag} setup_s {setup_s:.3f}: compile {clock.secs:.3f} s, "
            f"{clock.lowered} programs lowered, {clock.compiled} compiled, "
            f"{clock.cache_hits} persistent-cache hits")

    lowered0, compiled0 = clock.lowered, clock.compiled
    window = float(traffic.get("trace_seconds", seconds)) if trace else \
        float(seconds)
    trace_dir = bench_dir / ".trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    t0 = time.perf_counter()
    c.measure(window)
    traced_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    in_window = (clock.lowered - lowered0, clock.compiled - compiled0)
    ctx.log(f"{tag} window {window:.3f} s: {in_window[0]} programs lowered "
            f"and {in_window[1]} compiled inside it (should be 0 and 0)")

    peak = _peak_bytes(devices)
    result = c.result(window)           # end-to-end values and counts
    c.release()
    checks = c.checks() if check else []
    correct = all(ch.ok for ch in checks) if check else None

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line: dict = {"correct": correct, "attempted": result["attempted"],
                  "failed": result["failed"]}
    if trace:
        from yardstick.trace import load
        dtrace = load(str(trace_dir), window_s=traced_s)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=dtrace.busy_s, window_s=dtrace.window_s)
        reader_run = ReaderRun(cell=c, trace=dtrace, peaks=peaks,
                               config=config, traffic=traffic)
        metrics = {}
        for m in cell_metrics(bench, cell, "per_layer"):
            reader = load_module(bench_dir / "metrics" / f"{m['name']}.py")
            value = reader.read(reader_run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
    else:
        values = dict(result["metrics"], setup_s=setup_s)
        line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                       "unit": m["unit"]}
                           for m in cell_metrics(bench, cell, "end_to_end")}
    line["device"] = device
    if trace:
        line["breakdown"] = dtrace.breakdown()
    for name, v in line["metrics"].items():
        ctx.log(f"{tag} {name} = {v['value']!r} {v['unit']}")
    ctx.log(f"{tag} memory_peak_bytes = {peak}; attempted "
            f"{result['attempted']}, failed {result['failed']}")
    if keep_cell:
        line["cell"] = c
    line["checks"] = {ch.name: {"value": ch.value, "limit": ch.limit}
                      for ch in checks}
    for ch in checks:
        ctx.log(f"check {ch.name}: {ch.value!r} (limit {ch.limit!r}) "
                f"{'ok' if ch.ok else 'FAIL'}")
    return line


@dataclasses.dataclass
class ReaderRun:
    """What a per-layer metric's reader is given: the cell after its run
    (its host-side records), the reduced device trace, the chip's peaks and
    the cell's configuration and traffic."""

    cell: object
    trace: object
    peaks: object
    config: dict
    traffic: dict


def _finite(obj):
    """JSON has no inf or nan: write them as strings."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return repr(obj)
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_finite(v) for v in obj]
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = pathlib.Path.cwd()
    try:
        line = run(root, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 3
    print(json.dumps(_finite(line)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
