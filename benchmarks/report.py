"""Generate the EXPERIMENTS.md §Dry-run and §Roofline tables from the
dry-run JSON artifacts, and (with ``--benches``) aggregate every committed
``benchmarks/BENCH_*.json`` into one perf-trajectory table.

Usage::

    PYTHONPATH=src python -m benchmarks.report [--dir experiments/dryrun]
    PYTHONPATH=src python -m benchmarks.report --benches
    PYTHONPATH=src python -m benchmarks.report --benches --filter speedup
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re

# the default trajectory view: headline outcomes, not every micro number —
# pass --filter '' (empty regex matches everything) for the full dump
BENCH_HIGHLIGHTS = (r"speedup|taos_per_s|attainment|p99|makespan|conserved"
                    r"|violations|exchanges|completed")

SHAPE_TOKENS = {          # tokens processed per step (global)
    "train_4k": 4096 * 256,
    "prefill_32k": 32768 * 32,
    "decode_32k": 1 * 128,
    "long_500k": 1 * 1,
}


def model_flops_global(arch: str, shape: str) -> float:
    """MODEL_FLOPS: 6·N_active·D (train) or 2·N_active·D (fwd-only), with
    N_active = matmul-active params (embedding-table lookups excluded, LM
    head included).  Attention score FLOPs are intentionally excluded (the
    classic 6ND convention) — the ratio column absorbs them."""
    import numpy as np
    from repro.configs import get_config
    from repro.models import get_model

    cfg = get_config(arch)
    model = get_model(cfg)
    n_active = model.active_param_count()
    # matmul-active params: subtract the embedding table (pure lookup);
    # tied models still do the head matmul, so add it back.
    defs = model.param_defs()
    if "embed.w" in defs:
        n_active -= int(np.prod(defs["embed.w"].shape))
    if cfg.tie_embeddings:
        n_active += cfg.padded_vocab * cfg.d_model
    tokens = SHAPE_TOKENS[shape]
    mult = 6 if shape == "train_4k" else 2
    return float(mult * n_active * tokens)


def load_cells(d: pathlib.Path) -> list[dict]:
    out = []
    for p in sorted(d.glob("*.json")):
        out.append(json.loads(p.read_text()))
    return out


def roofline_row(rec: dict) -> dict:
    from repro.launch.device_peaks import device_peaks
    from repro.launch.mesh import PRODUCTION_DEVICE_KIND

    peaks = device_peaks(PRODUCTION_DEVICE_KIND)
    chips = rec["chips"]
    flops_dev = rec["flops"]
    bytes_dev = rec["bytes_accessed"]
    coll = rec.get("collectives", {})
    coll_dev = sum(v for k, v in coll.items() if k != "count")
    t_comp = flops_dev / peaks.flops_bf16
    t_mem = bytes_dev / peaks.hbm_bw
    t_coll = coll_dev / peaks.ici_link_bw
    dominant = max((t_comp, "compute"), (t_mem, "memory"),
                   (t_coll, "collective"))[1]
    mf = model_flops_global(rec["arch"], rec["shape"])
    hlo_global = flops_dev * chips
    mem_gib = (rec["memory"]["argument_size_bytes"] +
               rec["memory"]["temp_size_bytes"]) / 2**30
    return {
        "arch": rec["arch"], "shape": rec["shape"],
        "t_comp": t_comp, "t_mem": t_mem, "t_coll": t_coll,
        "dominant": dominant,
        "model_flops": mf, "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "mem_gib": mem_gib,
        "roofline_fraction": t_comp / max(t_comp, t_mem, t_coll),
        "compile_s": rec.get("compile_s", 0.0),
    }


def flatten_leaves(obj, prefix: str = "") -> list[tuple[str, object]]:
    """Depth-first flatten of a JSON tree to ``(dotted.path, scalar)`` pairs.

    Only numeric/bool leaves are kept — strings (platform tags, notes)
    are metadata, not trajectory metrics."""
    out: list[tuple[str, object]] = []
    if isinstance(obj, dict):
        for k in sorted(obj):
            out.extend(flatten_leaves(obj[k], f"{prefix}.{k}" if prefix else k))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            out.extend(flatten_leaves(v, f"{prefix}[{i}]"))
    elif isinstance(obj, bool) or isinstance(obj, (int, float)):
        out.append((prefix, obj))
    return out


def bench_table(bench_dir: pathlib.Path, pattern: str) -> None:
    """One trajectory table over every ``BENCH_*.json`` in ``bench_dir``."""
    rx = re.compile(pattern, re.IGNORECASE)
    files = sorted(bench_dir.glob("BENCH_*.json"))
    if not files:
        print(f"no BENCH_*.json under {bench_dir}")
        return
    print(f"### Bench trajectory — {len(files)} suites "
          f"(filter: `{pattern or 'all'}`)")
    print()
    print("| suite | metric | value |")
    print("|---|---|---|")
    for p in files:
        suite = p.stem.replace("BENCH_", "")
        try:
            data = json.loads(p.read_text())
        except (OSError, json.JSONDecodeError) as e:
            print(f"| {suite} | UNREADABLE | {e} |")
            continue
        rows = [(k, v) for k, v in flatten_leaves(data) if rx.search(k)]
        for k, v in rows:
            if isinstance(v, bool):
                val = str(v).lower()
            elif isinstance(v, float):
                val = f"{v:.4g}"
            else:
                val = str(v)
            print(f"| {suite} | {k} | {val} |")
        if not rows:
            print(f"| {suite} | (no metric matches filter) | – |")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--mesh", default="single_pod")
    ap.add_argument("--benches", action="store_true",
                    help="aggregate benchmarks/BENCH_*.json into one table")
    ap.add_argument("--filter", default=BENCH_HIGHLIGHTS,
                    help="regex over dotted metric paths ('' = everything)")
    args = ap.parse_args()
    if args.benches:
        bench_table(pathlib.Path(__file__).resolve().parent, args.filter)
        return
    d = pathlib.Path(args.dir) / args.mesh
    cells = load_cells(d)

    print("### Dry-run summary —", args.mesh)
    print()
    print("| arch | shape | status | mem/dev GiB | compile s | "
          "HLO GFLOPs/dev | coll MB/dev |")
    print("|---|---|---|---|---|---|---|")
    for rec in cells:
        if rec["status"] != "ok":
            print(f"| {rec['arch']} | {rec['shape']} | {rec['status'].upper()}"
                  f" — {rec.get('reason', rec.get('error', ''))[:60]} "
                  f"| – | – | – | – |")
            continue
        coll = sum(v for k, v in rec.get("collectives", {}).items()
                   if k != "count")
        mem = (rec["memory"]["argument_size_bytes"] +
               rec["memory"]["temp_size_bytes"]) / 2**30
        print(f"| {rec['arch']} | {rec['shape']} | ok | {mem:.2f} | "
              f"{rec['compile_s']:.0f} | {rec['flops'] / 1e9:.1f} | "
              f"{coll / 1e6:.1f} |")
    print()

    oks = [roofline_row(r) for r in cells if r["status"] == "ok"]
    if not oks:
        return
    from repro.launch.device_peaks import device_peaks
    from repro.launch.mesh import PRODUCTION_DEVICE_KIND

    peaks = device_peaks(PRODUCTION_DEVICE_KIND)
    print("### Roofline —", args.mesh,
          f"(terms in seconds/step/device; peaks of {PRODUCTION_DEVICE_KIND} "
          f"per {peaks.source}: {peaks.flops_bf16 / 1e12:g} TF bf16, "
          f"{peaks.hbm_bw / 1e9:g} GB/s HBM, "
          f"{peaks.ici_link_bw / 1e9:g} GB/s ICI per link)")
    print()
    print("| arch | shape | compute s | memory s | collective s | bound | "
          "MODEL_FLOPS | useful ratio | roofline frac |")
    print("|---|---|---|---|---|---|---|---|---|")
    for r in sorted(oks, key=lambda r: r["roofline_fraction"]):
        print(f"| {r['arch']} | {r['shape']} | {r['t_comp']:.4f} | "
              f"{r['t_mem']:.4f} | {r['t_coll']:.4f} | {r['dominant']} | "
              f"{r['model_flops']:.2e} | {r['useful_ratio']:.2f} | "
              f"{r['roofline_fraction']:.2%} |")


if __name__ == "__main__":
    main()
