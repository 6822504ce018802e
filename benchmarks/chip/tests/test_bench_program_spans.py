"""The program's own spans against the chip's idle time (``spans.py``,
``program_gaps.py``) and the readers of the runtime's records, on
synthetic planes and records."""
import chip_bench_setup  # first: puts the benchmark on sys.path
import dataclasses
import math
import types

import jax
import pytest

import bench
import program_gaps
from yardstick import spans, trace


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


@dataclasses.dataclass
class Profile:
    planes: list


def _profile():
    """Ops leave the chip idle over (10, 50), (60, 100) and (110, 120).
    Two workers (A, B) overlap chunk calls, then park in turn; the admitter
    (C) admits while both are parked or in a chunk; the caller (D) holds
    the run, the spawn and the join."""
    def runtime(*evs):
        return [Ev("repro.runtime." + n, s, e - s) for n, s, e in evs]
    ops = [Ev("%fusion.1 = f32[8] fusion(...)", s, 10)
           for s in (0, 50, 100, 120)]
    a = runtime(("chunk", 5, 28), ("commit", 31, 40), ("admit", 32, 38),
                ("park", 41, 95), ("park", 96, 125))
    b = runtime(("chunk", 20, 45), ("park", 45, 70), ("chunk", 70, 78),
                ("park", 78, 105), ("park", 106, 125))
    c = runtime(("admit_dag", 75, 85), ("admit", 76, 84))
    d = runtime(("run", 0, 130), ("spawn", 0, 2), ("join", 125, 128))
    return Profile([
        Plane("/host:CPU", [Line("python", a), Line("python", b),
                            Line("python", c), Line("python", d),
                            Line("python", [Ev("bench.tao.copy", 0, 5)])]),
        Plane("/device:TPU:0", [Line("XLA Modules", []),
                                Line("XLA Ops", ops)]),
    ])


def _reader(name):
    return bench.load_module(chip_bench_setup.BENCH_DIR / "metrics"
                             / f"{name}.py")


def test_interval_arithmetic():
    assert spans.merge([(5, 8), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 8)]
    assert spans.intersect([(0, 10), (20, 30)], [(5, 25)]) == [
        (5, 10), (20, 25)]
    assert spans.subtract([(0, 10), (20, 30)], [(2, 3), (8, 22)]) == [
        (0, 2), (3, 8), (22, 30)]
    assert spans.total([(0, 10), (5, 15)]) == 15


def test_program_spans_are_kept_with_their_thread():
    """The idle intervals are those the harness's reduction finds; the
    benchmark's own ``bench.`` span is not a program span."""
    idle, program = spans.from_profile(_profile())
    assert idle == [(10, 50), (60, 100), (110, 120)]
    t = trace.reduce_profile(_profile())
    assert spans.total(idle) == t.window_ns - sum(t.busy_ns)
    assert len(program) == 15
    assert len({line for _, _, _, line in program}) == 4


def test_program_idle_gaps():
    """Mid 30: only B's chunk is open; mid 80: the admitter's admit (inside
    its admit_dag) outranks two parks and the run; mid 115: every worker
    is parked."""
    idle, program = spans.from_profile(_profile())
    assert spans.label_idle(idle, program) == {
        "repro.runtime.chunk": 40, "repro.runtime.admit": 40,
        "repro.runtime.park": 10}
    assert spans.split(_profile(), 130)["program_idle_gaps"] == [
        ["repro.runtime.chunk", 40e-9], ["repro.runtime.admit", 40e-9],
        ["repro.runtime.park", 10e-9]]
    assert spans.label_idle([(200, 210)], program) == {spans.NO_SPAN: 10}


def test_idle_outside_chunk_share():
    """Chunks cover (5, 45) and (70, 78): idle outside them is (45, 50),
    (60, 70), (78, 100) and (110, 120)."""
    idle, program = spans.from_profile(_profile())
    assert spans.idle_outside(idle, program, "repro.runtime.chunk") == 47
    assert spans.split(_profile(), 130)["idle_outside_chunk_share"] == \
        pytest.approx(100 * 47 / 130)


def test_idle_with_work_share():
    """The pool is up over (0, 125); both workers are parked over (45, 70),
    (78, 95), (96, 105) and (106, 125).  Idle with a worker awake: (10, 45)
    (B in a chunk, then A in its commit), (70, 78) (B alone) and (95, 96)
    (A between parks)."""
    _, program = spans.from_profile(_profile())
    assert spans.awake(program) == [(0, 45), (70, 78), (95, 96), (105, 106)]
    assert spans.split(_profile(), 130)["idle_with_work_share"] == \
        pytest.approx(100 * 44 / 130)


def test_split_of_a_program_without_spans():
    """A program that writes no ``repro.`` span (one from before the
    runtime's tracing) leaves both shares out and all idle unlabelled."""
    bare = Profile([p for p in _profile().planes
                    if not p.name.startswith("/host:")])
    assert spans.split(bare, 130) == {
        "program_idle_gaps": [[spans.NO_SPAN, 90e-9]],
        "idle_outside_chunk_share": None, "idle_with_work_share": None}


def test_program_gaps_splits_the_trace_the_run_loads(monkeypatch):
    """The tool splits the very trace the run reduces, adds it to the run's
    line, and leaves the harness's loader as it found it."""
    loaded = []

    def load(trace_dir, window_s=None):
        loaded.append(trace_dir)
        t = trace.reduce_profile(_profile())
        t.window_ns = window_s * 1e9
        return t

    def run(root, workload, seed, seconds, **kw):
        assert kw == {"trace": True}
        t = trace.load("dir", window_s=260e-9)
        return {"correct": True, "device": {"window_s": t.window_s}}

    monkeypatch.setattr(trace, "load", load)
    monkeypatch.setattr(trace, "find_xplane", lambda d: d + "/x.xplane.pb")
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        lambda path: _profile())
    monkeypatch.setattr(bench, "run", run)
    line = program_gaps.run(chip_bench_setup.ROOT, "cell", 7, 50.0)
    assert loaded == ["dir"] and trace.load is load
    assert line["program"]["idle_outside_chunk_share"] == pytest.approx(
        100 * 47 / 260)
    assert line["program"]["program_idle_gaps"][0] == [
        "repro.runtime.chunk", 40e-9]


def _records(*pairs):
    return [types.SimpleNamespace(start=s, ready=r) for s, r in pairs]


def test_ready_wait_and_sched_readers():
    res = types.SimpleNamespace(
        trace=_records((1.0, 0.999), (2.0, 1.997), (3.0, 2.998)),
        host_ns={"admit": 3000, "place": 6000, "commit": 9000},
        counts={"commits": 3})
    dag = types.SimpleNamespace(runs=[(0.0, res, None)])
    run = types.SimpleNamespace(cell=dag, trace=None)
    assert _reader("ready_wait_ms.dag").read(run) == pytest.approx(2.0)
    assert _reader("sched_us_per_tao.dag").read(run) == pytest.approx(6.0)
    serve = types.SimpleNamespace(stats=types.SimpleNamespace(result=res))
    run = types.SimpleNamespace(cell=serve, trace=None)
    assert _reader("ready_wait_ms.serve").read(run) == pytest.approx(2.0)


@pytest.mark.parametrize("name", [
    "ready_wait_ms.dag", "sched_us_per_tao.dag", "ready_wait_ms.serve"])
def test_new_readers_read_nothing_where_nothing_was_recorded(name):
    """Records without the ready stamp, host time or counts (a program that
    keeps none, or a run with the profiler off), and a cell with no
    records: each reader returns None."""
    read = _reader(name).read
    untraced = types.SimpleNamespace(trace=_records((1.0, math.nan)),
                                     host_ns={}, counts={})
    before = types.SimpleNamespace(trace=[types.SimpleNamespace(start=1.0)])
    for res in (untraced, before):
        cells = [types.SimpleNamespace(runs=[(0.0, res, None)]),
                 types.SimpleNamespace(stats=types.SimpleNamespace(
                     result=res))]
        for cell in cells + [types.SimpleNamespace()]:
            for t in (None, trace.reduce_profile(_profile())):
                assert read(types.SimpleNamespace(cell=cell, trace=t)) \
                    is None
