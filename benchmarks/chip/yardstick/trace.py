"""Reduction of a JAX profiler trace to device metrics.

A TPU trace (``<dir>/plugins/profile/<time>/<host>.xplane.pb``) holds one
plane per chip, ``/device:TPU:<i>``, with the line ``XLA Modules`` (one
event per run of a compiled program, named ``jit_<function>(<id>)``) and
the line ``XLA Ops`` (one event per operation).  Host threads are lines of
``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans appear there by
name.  Events carry a start and a duration in nanoseconds on one clock.

Busy time is the union of the operation intervals of a chip; idle share is
1 minus busy over the traced window.  Program time is keyed by the stable
function name (the ``jit_`` prefix and the id stripped), so a reader finds
``mamba2_prefill`` however often it was recompiled.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_MODULE_NAME = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
_OP_NAME = re.compile(r"^%?([^ =]+)")


def module_key(name: str) -> str:
    """``jit_mamba2_prefill(4182935518241950169)`` -> ``mamba2_prefill``."""
    return _MODULE_NAME.match(name).group(1)


def op_key(name: str) -> str:
    """``%fusion.33 = bf16[6448] fusion(...)`` -> ``fusion.33``."""
    m = _OP_NAME.match(name)
    return m.group(1) if m else name


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float):
    """Idle ``(start, end)`` gaps between the union of ``intervals``, inside
    ``[lo, hi]``."""
    out = []
    t = lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class DeviceTrace:
    """What one traced window showed, per chip and summed."""

    n_devices: int
    window_ns: float
    busy_ns: list            # per chip: union of operation intervals
    module_ns: dict          # function name -> summed device ns (all chips)
    module_count: dict       # function name -> runs
    op_ns: dict              # operation name -> summed device ns
    idle_by_host: dict       # host span name -> idle ns while it was open

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        return sum(self.busy_ns) / max(1, len(self.busy_ns)) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module(self, name: str) -> tuple[float, int]:
        """-> (summed device seconds, runs) of function ``name``."""
        return self.module_ns.get(name, 0.0) / 1e9, self.module_count.get(
            name, 0)

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:n]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v / 1e9] for k, v in ops],
                "idle_gaps": [[k, v / 1e9] for k, v in idle]}


NO_SPAN = "no benchmark span open"


def _host_spans(profile, prefix: str):
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return sorted(spans)


def _label_gaps(idle, spans) -> dict:
    """Idle ns by the innermost host span open at each gap's middle."""
    out: dict = collections.defaultdict(float)
    starts = [s for s, _, _ in spans]
    for s, e in idle:
        mid = 0.5 * (s + e)
        label = NO_SPAN
        i = bisect.bisect_right(starts, mid)
        best = None
        for j in range(i - 1, -1, -1):
            ss, se, name = spans[j]
            if se >= mid and (best is None or ss >= best[0]):
                best = (ss, name)
            if mid - ss > 60e9:      # no span is open for a minute
                break
        if best is not None:
            label = best[1]
        out[label] += e - s
    return dict(out)


def reduce_profile(profile, span_prefix: str = "bench.") -> DeviceTrace:
    """Reduce a ``jax.profiler.ProfileData`` (or an object with the same
    ``planes``/``lines``/``events`` shape) to a :class:`DeviceTrace`.  The
    window runs from the first to the last event of any device plane
    unless the caller overrides ``window_ns``."""
    busy, ops_all = [], []
    module_ns: dict = collections.defaultdict(float)
    module_count: dict = collections.defaultdict(int)
    op_ns: dict = collections.defaultdict(float)
    lo, hi = float("inf"), float("-inf")
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ivs = []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    k = module_key(ev.name)
                    module_ns[k] += ev.duration_ns
                    module_count[k] += 1
            elif line.name == OPS_LINE:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    ivs.append((s, e))
                    op_ns[op_key(ev.name)] += ev.duration_ns
                    lo, hi = min(lo, s), max(hi, e)
        busy.append(union_ns(ivs))
        ops_all.extend(ivs)
    if not busy:
        raise ValueError("the trace holds no /device:TPU plane")
    idle = gaps(ops_all, lo, hi) if ops_all else []
    return DeviceTrace(n_devices=len(busy), window_ns=max(0.0, hi - lo),
                       busy_ns=busy, module_ns=dict(module_ns),
                       module_count=dict(module_count), op_ns=dict(op_ns),
                       idle_by_host=_label_gaps(idle, _host_spans(
                           profile, span_prefix)))


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir: str, window_s: float | None = None) -> DeviceTrace:
    """Read the newest trace under ``trace_dir``.  ``window_s``, where
    given, is the traced window on the host's clock and replaces the span
    of the device events (which leaves out idle time at either end)."""
    from jax.profiler import ProfileData
    out = reduce_profile(ProfileData.from_file(find_xplane(trace_dir)))
    if window_s is not None:
        out.window_ns = window_s * 1e9
    return out
