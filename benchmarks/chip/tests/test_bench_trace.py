"""Trace reduction: union of busy intervals, idle gaps named by the host
span open over them, program time by stable name; on synthetic planes and
on a small trace recorded on a TPU v5e."""
import chip_bench_setup  # noqa: F401  (puts the benchmark on sys.path)
import dataclasses
import pathlib

import pytest

from yardstick import trace

DATA = pathlib.Path(__file__).parent / "data"


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
    ops = [Ev("%fusion.1 = f32[8] fusion(...)", 0, 10),
           Ev("%fusion.1 = f32[8] fusion(...)", 5, 10),     # overlaps
           Ev("%matmul.1 = bf16[8] custom-call(...)", 40, 20),
           Ev("%copy.2 = f32[8] custom-call(...)", 100, 10)]
    mods = [Ev("jit_tao_matmul(123)", 40, 20),
            Ev("jit_tao_matmul(456)", 100, 10),
            Ev("jit_step(9)", 0, 15)]
    host = [Ev("bench.tao.matmul", 20, 30),
            Ev("bench.tao.copy", 61, 10),
            Ev("unrelated", 61, 100)]
    return Profile([
        Plane("/host:CPU", [Line("python3", host)]),
        Plane("/device:TPU:0", [Line("XLA Modules", mods),
                                Line("XLA Ops", ops)]),
        Plane("/device:CUSTOM:Megascale Trace", [Line("XLA Ops", ops)]),
    ])


def test_union_and_gaps():
    assert trace.union_ns([(0, 10), (5, 15), (20, 25)]) == 20
    assert trace.union_ns([]) == 0
    assert trace.gaps([(0, 10), (5, 15), (20, 25)], 0, 30) == [
        (15, 20), (25, 30)]


def test_names():
    assert trace.module_key("jit_mamba2_prefill(4182935518241950169)") == \
        "mamba2_prefill"
    assert trace.module_key("jit__argmax(1)") == "_argmax"
    assert trace.op_key("%fusion.33 = bf16[6448] fusion(x)") == "fusion.33"


def test_reduce_synthetic():
    t = trace.reduce_profile(_profile())
    assert t.n_devices == 1                      # only /device:TPU planes
    assert t.window_ns == 110                    # first op start, last end
    assert t.busy_ns == [15 + 20 + 10]
    assert t.module("tao_matmul") == (30e-9, 2)
    assert t.module("absent") == (0.0, 0)
    assert t.op_ns == {"fusion.1": 20, "matmul.1": 20, "copy.2": 10}
    # gaps: (15, 40) mid 27.5 under the matmul span; (60, 100) mid 80,
    # where only the copy span (61..71) has ended, so the later-starting
    # open span that is not ours is ignored and none of ours is open
    assert t.idle_by_host == {"bench.tao.matmul": 25,
                              trace.NO_SPAN: 40}
    assert t.idle_share == pytest.approx(1 - 45 / 110)
    bd = t.breakdown()
    assert bd["device_ops"][0][0] in ("fusion.1", "matmul.1")
    assert bd["idle_gaps"][0] == [trace.NO_SPAN, 40e-9]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_profile(Profile([Plane("/host:CPU", [])]))


def test_recorded_v5e_trace():
    """Three calls each of a 256^2 f32 matmul, a 32x1024 sort, a 2048x256
    copy and a 4096^2 bf16 matmul, traced on one TPU v5e."""
    from jax.profiler import ProfileData
    t = trace.reduce_profile(ProfileData.from_file(
        str(DATA / "kernels_v5e.xplane.pb")))
    assert t.n_devices == 1
    assert t.module_count == {"matmul": 6, "sort_rows": 3, "copy": 3}
    # a 4096^3 bf16 matmul is 137 GFLOP: at least 0.70 ms at 197 TFLOP/s
    secs, n = t.module("matmul")
    assert secs > 3 * 137.4e9 / 197e12
    assert 0 < t.busy_s < t.window_s
    assert sum(t.op_ns.values()) == pytest.approx(
        sum(t.module_ns.values()), rel=1e-3)
