"""Paths for the benchmark's own tests, which run on the CPU: the
benchmark's directory and the program's ``src`` go on ``sys.path``.

Imported first by each test module here.  Not a ``conftest.py``: the
repository's ``tests/conftest.py`` is imported by name, and a second
module of that name would shadow it."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
for p in (str(BENCH_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
