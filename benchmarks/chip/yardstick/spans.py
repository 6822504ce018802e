"""The program's own host spans against the chip's idle time, and the
ready stamps of its trace records.

The threaded runtime writes ``repro.runtime.<site>`` spans while the
profiler records (``core/runtime.py``): ``run``, ``spawn`` and ``join`` on
the caller's thread, ``admit_dag`` on the admitter's, and ``admit``,
``place``, ``chunk``, ``commit`` and ``park`` on the workers'.  A span here
is ``(start_ns, end_ns, name, line)``, ``line`` naming the host thread it
was recorded on; spans of one line nest.  Intervals are ``(start, end)``
pairs in ns on the trace's clock.
"""
from __future__ import annotations

import collections
import math

from . import trace

PREFIX = "repro."
RUNTIME = "repro.runtime."
NO_SPAN = "no program span open"
# which open span a gap is put down to when several threads have one open:
# the work nearest the chip first, a parked worker and the bare run last
RANK = tuple(RUNTIME + s for s in (
    "chunk", "commit", "place", "admit", "admit_dag", "spawn", "join",
    "park", "run"))
WORKER_SITES = frozenset(RUNTIME + s for s in (
    "chunk", "commit", "place", "park"))


def merge(intervals) -> list:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def intersect(a, b) -> list:
    """The intersection of two unions of intervals."""
    a, b, out = merge(a), merge(b), []
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list:
    """``a`` less ``b``, both unions of intervals."""
    out, b = [], merge(b)
    j = 0
    for s, e in merge(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def named(spans, name: str) -> list:
    return [(s, e) for s, e, n, _ in spans if n == name]


def idle_outside(idle, spans, name: str) -> float:
    """ns of ``idle`` in which no span ``name`` was open on any thread."""
    return total(subtract(idle, named(spans, name)))


def _pools(spans) -> list:
    """``(spawn start, join start)`` of each run's worker pool: the join
    that follows each spawn on the caller's thread."""
    by_line = collections.defaultdict(lambda: ([], []))
    for s, _, n, line in spans:
        if n == RUNTIME + "spawn":
            by_line[line][0].append(s)
        elif n == RUNTIME + "join":
            by_line[line][1].append(s)
    out = []
    for spawns, joins in by_line.values():
        joins.sort()
        for s in sorted(spawns):
            later = [j for j in joins if j >= s]
            if later:
                out.append((s, later[0]))
    return out


def awake(spans) -> list:
    """Intervals in which a worker pool was up and at least one of its
    workers was outside ``repro.runtime.park``: work in the runtime, as
    opposed to a pool in which every worker waits for work."""
    worker_spans = collections.defaultdict(list)
    for s, e, n, line in spans:
        if n in WORKER_SITES:
            worker_spans[line].append((s, e, n))
    out = []
    for lo, hi in _pools(spans):
        parked = [(lo, hi)]
        n_workers = 0
        for sps in worker_spans.values():
            if not any(s < hi and e > lo for s, e, _ in sps):
                continue
            n_workers += 1
            parked = intersect(parked, [(s, e) for s, e, n in sps
                                        if n == RUNTIME + "park"])
        if n_workers:
            out.extend(subtract([(lo, hi)], parked))
    return out


def label_idle(idle, spans) -> dict:
    """Idle ns by the program span each gap is put down to, judged at the
    gap's middle: on each thread its innermost open span (the latest
    started), then across threads the one that ranks first in ``RANK``
    (names not in it rank before ``park``); ``NO_SPAN`` where none is
    open."""
    rank = {n: i for i, n in enumerate(RANK)}
    other = rank[RUNTIME + "park"] - 0.5
    out: dict = collections.defaultdict(float)
    starts = sorted(spans, key=lambda sp: sp[0])
    ends = sorted(spans, key=lambda sp: sp[1])
    open_on: dict = {}                  # line -> its open spans, by start
    i = j = 0
    for mid, length in sorted((0.5 * (s + e), e - s) for s, e in idle):
        while i < len(starts) and starts[i][0] <= mid:
            open_on.setdefault(starts[i][3], []).append(starts[i])
            i += 1
        while j < len(ends) and ends[j][1] < mid:
            line = ends[j][3]
            open_on[line].remove(ends[j])
            if not open_on[line]:
                del open_on[line]
            j += 1
        label, best = NO_SPAN, math.inf
        for sps in open_on.values():
            name = sps[-1][2]
            r = rank.get(name, other)
            if r < best:
                label, best = name, r
        out[label] += length
    return dict(out)


def from_profile(profile) -> tuple[list, list]:
    """-> (idle, spans) of a ``jax.profiler.ProfileData`` (or an object of
    the same shape): the chips' idle intervals between their first and last
    operation, as ``trace.reduce_profile`` finds them, and every host span
    named under ``PREFIX``, its ``line`` being (plane, line) by position."""
    ops, spans = [], []
    for p, plane in enumerate(profile.planes):
        if trace.DEVICE_PLANE.match(plane.name):
            ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                       for line in plane.lines if line.name == trace.OPS_LINE
                       for ev in line.events)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name, (p, i))
                             for ev in line.events
                             if ev.name.startswith(PREFIX))
    idle = trace.gaps(ops, min(s for s, _ in ops),
                      max(e for _, e in ops)) if ops else []
    return idle, spans


def split(profile, window_ns: float) -> dict:
    """Where the chip's idle time in ``profile`` went, by the program's
    spans: idle s by the span each gap is put down to
    (``program_idle_gaps``, largest first), and in % of ``window_ns`` the
    idle with no ``repro.runtime.chunk`` open on any thread
    (``idle_outside_chunk_share``) and the idle while a worker pool was up
    and some worker outside ``repro.runtime.park``
    (``idle_with_work_share``).  A share is None where the program wrote
    no span of its site."""
    idle, spans = from_profile(profile)
    gaps = sorted(label_idle(idle, spans).items(), key=lambda kv: -kv[1])
    out = {"program_idle_gaps": [[k, v / 1e9] for k, v in gaps],
           "idle_outside_chunk_share": None, "idle_with_work_share": None}
    if named(spans, RUNTIME + "chunk"):
        out["idle_outside_chunk_share"] = 100.0 * idle_outside(
            idle, spans, RUNTIME + "chunk") / window_ns
    if named(spans, RUNTIME + "park"):
        out["idle_with_work_share"] = 100.0 * total(
            intersect(idle, awake(spans))) / window_ns
    return out


def ready_waits(records) -> list:
    """Seconds from entering a ready deque to being placed, per record
    that carries the runtime's ready stamp."""
    out = []
    for rec in records:
        ready = getattr(rec, "ready", math.nan)
        if not math.isnan(ready):
            out.append(rec.start - ready)
    return out
