"""The harness, driven on the CPU at a tiny size with its look for a chip
skipped: cells are found by name, a new mix and cell need no edit of an
existing file, a run without a TPU prints no result, and a run whose timed
path is broken underneath comes out as not correct."""
from chip_bench_setup import BENCH_DIR, ROOT  # first: puts the benchmark on sys.path
import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import bench


SEED = 2**31 + 4321          # larger than 32 signed bits hold

TINY_DAG = {
    "config": {"n_tasks": 60},
    "traffic": {"payloads": {
        "matmul": {"shape": [128, 128], "dtype": "float32"},
        "sort": {"shape": [8, 256], "dtype": "float32"},
        "copy": {"shape": [256, 128], "dtype": "float32"}},
        "warmup_tasks": 20},
}
TINY_SERVE = {
    # wide enough that logits spread as at full width, two layers deep.  A
    # sound run reads a gap of 0.002-0.008 here and a broken one 1.17-2.32,
    # so this size has a limit of its own: the cell's is set from readings
    # at 48 layers
    "config": {"d_model": 512, "n_layer": 2, "vocab_size": 503,
               "ssm_cfg": {"layer": "Mamba2", "d_state": 32, "d_conv": 4,
                           "expand": 2, "headdim": 64, "ngroups": 1,
                           "chunk_size": 16},
               "limits": {"served_logit_gap_max": 0.25}},
    "traffic": {"prompt_lengths": [16, 32], "prompt_weights": [0.5, 0.5],
                "output_min": 8, "output_max": 16, "rate_per_s": 20,
                "check_requests": 4},
}


def tiny(workload):
    return TINY_DAG if workload.startswith("paper-dag") else TINY_SERVE


def run_tiny(workload, root=ROOT, **kw):
    return bench.run(root, workload, SEED, 0.5, trace=False,
                     require_tpu=False, use_cache=False,
                     overrides=tiny(workload), **kw)


def _tree_hashes(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_cells_are_found_by_name():
    spec = bench.load_benchmark(ROOT)
    for cell in spec["workloads"]:
        _, got, config, traffic, binding = bench.resolve(ROOT, spec,
                                                        cell["name"])
        assert got == cell and hasattr(binding, "Cell")
        assert bench.cell_metrics(spec, cell, "end_to_end")
        assert bench.cell_metrics(spec, cell, "per_layer")
        for m in bench.cell_metrics(spec, cell, "per_layer"):
            assert (BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()


def test_new_mix_and_cell_need_no_edit(tmp_path):
    """A new configuration, mix and cell are new files and new entries of
    BENCHMARK.json; every file already there stays as it was."""
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    spec = bench.load_benchmark(ROOT)
    before = _tree_hashes(tmp_path)
    old = json.loads(json.dumps(spec))

    chip = tmp_path / "benchmarks" / "chip"
    cfg = json.loads((chip / "configs" / "paper-dag.json").read_text())
    cfg["n_tasks"] = 45
    (chip / "configs" / "paper-dag-45.json").write_text(json.dumps(cfg))
    mix = json.loads((chip / "traffic" / "fine.json").read_text())
    mix.update(TINY_DAG["traffic"], warmup_tasks=12)
    (chip / "traffic" / "tiny.json").write_text(json.dumps(mix))
    spec["configs"].append(dict(spec["configs"][0], name="paper-dag-45",
                                file="benchmarks/chip/configs/"
                                     "paper-dag-45.json"))
    spec["workloads"].append({"name": "paper-dag-45.tiny",
                              "config": "paper-dag-45", "traffic": "tiny",
                              "chips": 1, "why": "a test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "paper-dag.fine" in m.get("workloads", ()):
            m["workloads"].append("paper-dag-45.tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    line = bench.run(tmp_path, "paper-dag-45.tiny", SEED, 0.5, trace=False,
                     require_tpu=False, use_cache=False)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"setup_s", "taos_per_s"}
    assert line["metrics"]["taos_per_s"]["value"] > 0
    after = _tree_hashes(tmp_path)
    assert {k: after[k] for k in before} == before
    for key in ("configs", "workloads"):       # entries added, none changed
        assert spec[key][:len(old[key])] == old[key]


@pytest.mark.parametrize("workload", ["paper-dag.fine", "paper-dag.coarse",
                                      "serve-mamba2-780m.code-steady"])
def test_sound_run_is_correct(workload):
    line = run_tiny(workload)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["failed"] == 0 and line["attempted"] > 0
    for v in line["metrics"].values():
        assert v["value"] > 0


@pytest.mark.parametrize("workload,fault", [
    ("paper-dag.fine", "alter_answer"),        # an answer altered
    ("paper-dag.fine", "unchanged_state"),     # a sort returns its input
    ("paper-dag.fine", "skipped_chunk"),       # a quarter of chunks not run
    ("serve-mamba2-780m.code-steady", "alter_token"),
    ("serve-mamba2-780m.code-steady", "unchanged_state"),
    ("serve-mamba2-780m.code-steady", "unchanged_ssd_state"),
    ("serve-mamba2-780m.code-steady", "dropped_ssd_state"),
])
def test_broken_timed_path_is_not_correct(workload, fault):
    line = run_tiny(workload, fault=fault)
    assert not line["correct"], line["checks"]


def test_no_chip_prints_no_result(capsys):
    rc = bench.main(["--workload", "paper-dag.fine", "--seed", str(SEED),
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc != 0 and out == ""


def test_benchmark_alone_prints_no_result(tmp_path):
    """A directory with BENCHMARK.json and the benchmark's files but not
    the program gives no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload",
         "paper-dag.fine", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
