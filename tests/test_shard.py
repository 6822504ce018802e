"""Sharded-scheduler invariants (repro.core.shard).

Four guarantee families, mirroring the module contract:

* **routing stability** — the ShardMap is a pure function of ``dag_id``:
  admission order, retries, or query interleaving can never change a DAG's
  home shard (hypothesis property, repo ``importorskip`` convention);
* **byte-identity at n_shards=1** — every pinned trace signature
  reproduces through the sharded code path;
* **exchange conservation** — no TAO is lost or duplicated crossing a
  shard boundary, on both execution vehicles;
* **leg identity** — ``reset_learning`` restores a fresh-scheduler state
  (the PR 7 A/B contract), including the exchange/imbalance counters.

Plus unit tests for the simulator's word-array ``_BitSet`` (the ready-set
structure whose ``choice`` must match the seed path's
``rng.choice(sorted(...))`` draw exactly).
"""
import random

import pytest

from repro.core import (ChunkedWork, ShardedScheduler, ShardMap, Simulator,
                        ThreadedRuntime, fleet, make_policy,
                        partition_workers, random_workload, trace_signature)
from repro.core.identity import check_pins

# ----------------------------------------------------------- shard routing --


def test_shard_map_routes_in_range_and_pure():
    m = ShardMap([3, 5, 8, 4])
    routes = {d: m.shard_of(d) for d in range(500)}
    assert all(0 <= s < 4 for s in routes.values())
    # pure: re-query in reverse order, and from a freshly-built equal map
    m2 = ShardMap([3, 5, 8, 4])
    for d in reversed(range(500)):
        assert m.shard_of(d) == routes[d] == m2.shard_of(d)


def test_shard_map_capacity_weighting():
    # a 10x-larger shard should receive roughly 10x the DAGs
    m = ShardMap([100, 10])
    n = 2000
    big = sum(1 for d in range(n) if m.shard_of(d) == 0)
    assert big / n > 0.8


def test_shard_map_rejects_bad_capacities():
    with pytest.raises(ValueError):
        ShardMap([])
    with pytest.raises(ValueError):
        ShardMap([4, 0, 2])


def test_shard_map_stable_under_admission_order():
    pytest.importorskip("hypothesis")  # dev-only dep: skip, not error
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=50, deadline=None)
    @given(caps=st.lists(st.integers(1, 64), min_size=1, max_size=12),
           dag_ids=st.lists(st.integers(0, 2**31), min_size=1, max_size=40),
           order_seed=st.integers(0, 2**16))
    def prop(caps, dag_ids, order_seed):
        m = ShardMap(caps)
        baseline = [m.shard_of(d) for d in dag_ids]
        assert all(0 <= s < len(caps) for s in baseline)
        shuffled = list(enumerate(dag_ids))
        random.Random(order_seed).shuffle(shuffled)
        # admit in any other order: every DAG still lands on the same shard
        for i, d in shuffled:
            assert m.shard_of(d) == baseline[i]

    prop()


def test_partition_workers_disjoint_covering_nonempty():
    pytest.importorskip("hypothesis")  # dev-only dep: skip, not error
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=50, deadline=None)
    @given(n_big=st.integers(1, 40), n_little=st.integers(0, 40),
           n_shards=st.integers(1, 8))
    def prop(n_big, n_little, n_shards):
        spec = fleet(n_big, n_little)
        if n_shards > spec.n_workers:
            return
        parts = partition_workers(spec, n_shards)
        assert len(parts) == n_shards
        flat = [w for p in parts for w in p]
        assert sorted(flat) == list(range(spec.n_workers))  # disjoint+cover
        assert all(len(p) >= 1 for p in parts)              # non-empty
        assert all(list(p) == sorted(p) for p in parts)     # ascending ids

    prop()


# ------------------------------------------------- byte-identity at n=1 --


def test_one_shard_reproduces_all_pinned_signatures():
    """The tentpole correctness bar: the full sharded code path with a
    single shard is byte-identical to the plain SchedulerCore on every
    pinned configuration (DAG, workload and serving pins)."""
    assert check_pins(n_shards=1) == []


# --------------------------------------------------- exchange conservation --

# stream sized so the 4-shard simulator actually crosses the imbalance
# threshold (verified: dozens of exchanges fire at this size; tiny
# well-balanced streams fire none and would test nothing)
_CONS_SPEC = lambda: fleet(192, 64)
_CONS_WL = lambda: random_workload(n_dags=8, rate=50.0, n_tasks=80, seed=0)


def test_sim_exchange_conservation():
    wl = _CONS_WL()
    sim = Simulator(_CONS_SPEC(), make_policy("molding:adaptive"), seed=1,
                    n_shards=4)
    res = sim.run_workload(wl)
    assert res.completed == wl.total_taos()
    ex = res.exchanges
    assert ex is not None and ex["total"] > 0          # exchanges DID fire
    assert sum(ex["in"]) == ex["total"] == sum(ex["out"])
    assert sim.core.exchange_conserved()


def test_sim_unsharded_has_no_exchange_stats():
    wl = random_workload(n_dags=2, rate=8.0, n_tasks=20, seed=0)
    res = Simulator(fleet(6, 2), make_policy("molding:adaptive"),
                    seed=1).run_workload(wl)
    assert res.exchanges is None


def test_threaded_exchange_conservation():
    """Same guarantee on real worker threads — assertions are timing-free
    (completion count + counter balance), never wall-clock."""
    import time as _time

    wl = random_workload(n_dags=6, rate=30.0, n_tasks=24, seed=5)
    for arr in wl.arrivals():
        for node in arr.dag.nodes:
            node.work = ChunkedWork(lambda i: _time.sleep(0.0002), 2)
    rt = ThreadedRuntime(fleet(8, 4), make_policy("molding:adaptive"),
                         seed=3, n_shards=4)
    res = rt.run_workload(wl, timeout_s=120.0)
    assert res.completed == wl.total_taos()
    ex = res.exchanges
    assert ex is not None
    assert sum(ex["in"]) == ex["total"] == sum(ex["out"])
    assert rt.core.exchange_conserved()


# ------------------------------------------------------------ leg identity --


def test_sharded_reset_learning_makes_legs_byte_identical():
    """PR 7's A/B contract extended to shards: leg B after
    ``reset_learning()`` reproduces a fresh ShardedScheduler's leg B byte
    for byte, and the exchange/imbalance counters restart from zero."""
    spec, pol = fleet(48, 16), "molding:adaptive"
    wl = lambda s: random_workload(n_dags=6, rate=20.0, n_tasks=40, seed=s)
    sim = Simulator(spec, make_policy(pol), seed=4, n_shards=4)
    sim.run_workload(wl(1))                            # leg A (learns, exchanges)
    sim.reset_learning()
    assert sim.core.exchange_stats()["total"] == 0     # counters cleared
    assert sim.core.exchange_stats()["imbalance_peak"] == 0
    reused = trace_signature(sim.run_workload(wl(2)).trace)
    fresh = Simulator(spec, make_policy(pol), seed=4, n_shards=4)
    assert trace_signature(fresh.run_workload(wl(2)).trace) == reused


def test_sharded_reset_counters_clears_exchange_state():
    wl = _CONS_WL()
    sim = Simulator(_CONS_SPEC(), make_policy("molding:adaptive"), seed=1,
                    n_shards=4)
    sim.run_workload(wl)
    assert sim.core.exchange_stats()["total"] > 0
    sim.core.reset_counters()
    st = sim.core.exchange_stats()
    assert st["total"] == 0 and st["imbalance_peak"] == 0
    assert st["in"] == [0] * 4 and st["out"] == [0] * 4


# ------------------------------------------------------------ _BitSet unit --


def test_bitset_full_equals_elementwise_adds():
    from repro.core.simulator import _BitSet

    for n in (0, 1, 63, 64, 65, 130, 1000):
        full = _BitSet.full(n)
        built = _BitSet(range(n))
        assert len(full) == len(built) == n
        assert all(v in full and v in built for v in range(n))
        assert n not in full and n + 7 not in full


def test_bitset_add_discard_contains():
    from repro.core.simulator import _BitSet

    bs = _BitSet()
    ref: set = set()
    rng = random.Random(11)
    for _ in range(3000):
        v = rng.randrange(400)
        if rng.random() < 0.5:
            bs.add(v)
            ref.add(v)
        else:
            bs.discard(v)
            ref.discard(v)
        assert len(bs) == len(ref)
    assert {v for v in range(400) if v in bs} == ref
    bs.discard(10_000)                 # out of range: no-op, no growth
    assert len(bs) == len(ref)


def test_bitset_choice_matches_kth_smallest_draw():
    """``choice`` must consume exactly one ``randrange(count)`` and return
    the k-th *smallest* member — the very element the seed path's
    ``rng.choice(sorted(members))`` would pick for the same RNG state."""
    from repro.core.simulator import _BitSet

    members = sorted(random.Random(5).sample(range(5000), 321))
    bs = _BitSet(members)
    for seed in range(40):
        a, b = random.Random(seed), random.Random(seed)
        assert bs.choice(a) == members[b.randrange(len(members))]
        assert a.random() == b.random()    # identical stream consumption
