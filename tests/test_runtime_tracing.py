"""Tracing inside the threaded runtime: ``repro.runtime.*`` spans on the
JAX profiler's clock, the ready stamp of ``TraceRecord`` and the per-site
host time and counts of ``WorkloadResult`` — all present while the
profiler records and absent, at no cost but a bool test, when it does
not."""
import collections
import glob
import math
import os
import time

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import (ChunkedWork, Simulator, ThreadedRuntime, Workload,
                        hikey960, make_policy, random_dag)
from repro.core.identity import trace_signature
from repro.core.simulator import TraceRecord

N_CHUNKS = 3


def _workload(n_dags=2, n_tasks=30):
    wl = Workload()
    for s in range(n_dags):
        dag = random_dag(n_tasks, target_degree=2.5, seed=s)
        for node in dag.nodes:
            node.work = ChunkedWork(lambda i: time.sleep(1e-4), N_CHUNKS)
        wl.add(dag, at=0.002 * s)
    return wl


def _spans(trace_dir):
    """-> [(name, start_ns, end_ns, line, {stat: value})] of the
    ``repro.`` host spans of the newest trace under ``trace_dir``."""
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, (p, i),
                                {k: v for k, v in ev.stats}))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One small workload run under the profiler: (workload, result,
    spans)."""
    d = str(tmp_path_factory.mktemp("trace"))
    wl = _workload()
    rt = ThreadedRuntime(hikey960(), make_policy("molding:weight"), seed=0)
    with jax.profiler.trace(d):
        res = rt.run_workload(wl, timeout_s=60.0)
    return wl, res, _spans(d)


def _by_name(spans):
    out = collections.defaultdict(list)
    for sp in spans:
        out[sp[0]].append(sp)
    return out


def test_one_chunk_span_per_chunk_call_and_one_place_per_dispatch(traced):
    wl, res, spans = traced
    named = _by_name(spans)
    chunks = collections.Counter(
        (st["dag_id"], st["tao_id"], st["chunk"])
        for _, _, _, _, st in named["repro.runtime.chunk"])
    want = {(r.dag_id, r.tao_id, c) for r in res.trace
            for c in range(N_CHUNKS)}
    assert set(chunks) == want and set(chunks.values()) == {1}
    places = collections.Counter((st["dag_id"], st["tao_id"])
                                 for _, _, _, _, st in
                                 named["repro.runtime.place"])
    assert len(res.trace) == wl.total_taos()
    assert places == collections.Counter((r.dag_id, r.tao_id)
                                         for r in res.trace)
    for name in ("run", "spawn", "join", "admit_dag", "admit", "commit",
                 "park"):
        assert named[f"repro.runtime.{name}"], name
    assert len(named["repro.runtime.run"]) == 1
    assert len(named["repro.runtime.admit_dag"]) == len(wl)
    assert len(named["repro.runtime.admit"]) == wl.total_taos()
    assert len(named["repro.runtime.commit"]) == wl.total_taos()


def test_ready_precedes_start_on_every_record(traced):
    _, res, _ = traced
    for r in res.trace:
        assert not math.isnan(r.ready)
        assert 0.0 <= r.ready <= r.start <= r.end


def test_record_times_map_onto_the_run_span(traced):
    """The run span's start plus a record's start lands inside that TAO's
    place span, where the start is stamped, within 1 ms."""
    _, res, spans = traced
    named = _by_name(spans)
    (_, run_start, _, _, _), = named["repro.runtime.run"]
    place = {}
    for _, s, e, _, st in named["repro.runtime.place"]:
        place.setdefault((st["dag_id"], st["tao_id"]), (s, e))
    for r in res.trace:
        s, e = place[(r.dag_id, r.tao_id)]
        at = run_start + r.start * 1e9
        assert s - 1e6 <= at <= e + 1e6


def test_host_time_and_counts(traced):
    wl, res, spans = traced
    n = wl.total_taos()
    c = res.counts
    assert c["admits"] == c["places"] == c["commits"] == n
    assert c["chunks"] == n * N_CHUNKS
    assert c["steals"] <= c["steal_attempts"]
    assert c["park_timeouts"] <= c["parks"]
    assert c["parks"] == len(_by_name(spans)["repro.runtime.park"])
    assert set(res.host_ns) == {"admit", "place", "commit"}
    assert all(v > 0 for v in res.host_ns.values())
    # self time: the commit spans less the admit spans nested in them, so
    # the three sites never count more than the spans that hold them
    named = _by_name(spans)
    spanned = sum(e - s for k in ("admit", "place", "commit")
                  for _, s, e, _, _ in named[f"repro.runtime.{k}"])
    assert sum(res.host_ns.values()) <= 1.5 * spanned


def test_profiler_off_constructs_no_span(monkeypatch):
    class NoSpan:
        def __init__(self, *a, **kw):
            raise AssertionError("a span was made with the profiler off")

        @staticmethod
        def is_enabled():
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", NoSpan)
    wl = _workload(n_dags=1, n_tasks=20)
    rt = ThreadedRuntime(hikey960(), make_policy("molding:weight"), seed=0)
    res = rt.run_workload(wl, timeout_s=60.0)
    assert res.completed == wl.total_taos()
    assert res.host_ns == {} and res.counts == {}
    assert all(math.isnan(r.ready) for r in res.trace)
    out = rt.run(random_dag(20, target_degree=2.5, seed=3), timeout_s=60.0)
    assert out["completed"] == 20


def test_offline_run_is_spanned(tmp_path):
    rt = ThreadedRuntime(hikey960(), make_policy("molding:weight"), seed=0)
    dag = random_dag(15, target_degree=2.0, seed=1)
    for node in dag.nodes:
        node.work = ChunkedWork(lambda i: None, 2)
    with jax.profiler.trace(str(tmp_path)):
        rt.run(dag, timeout_s=60.0)
    named = _by_name(_spans(str(tmp_path)))
    assert len(named["repro.runtime.run"]) == 1
    assert len(named["repro.runtime.chunk"]) == 30
    assert len(named["repro.runtime.commit"]) == 15


def test_ready_is_outside_the_trace_signature():
    res = Simulator(hikey960(), make_policy("molding:weight"),
                    seed=0).run(random_dag(40, target_degree=2.5, seed=2))
    stamped = [TraceRecord(r.tao_id, r.type, r.leader, r.width, r.start,
                           r.end, r.participants, dag_id=r.dag_id,
                           preempted=r.preempted, impl=r.impl, ready=0.5)
               for r in res.trace]
    assert all(math.isnan(r.ready) for r in res.trace)
    assert stamped == res.trace
    assert trace_signature(stamped) == trace_signature(res.trace)
