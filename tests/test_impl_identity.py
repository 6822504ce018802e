"""Byte-identity gate: single-variant TAOs must schedule EXACTLY as the
pre-variant stack did.

The joint (impl, width, leader) refactor threads an impl dimension through
the DAG, PTT, policies, scheduler core and both vehicles.  Every policy
branches onto the legacy code path when a TAO carries one variant — same
comparisons, same RNG draws — so these pinned fingerprints (captured on the
PR-6 tree) must reproduce bit for bit.  A mismatch here is a refactor bug,
never timing noise: every pinned config runs on the virtual-time simulator.
"""
import pytest

from repro.core import DEFAULT_IMPL, trace_signature
from repro.core.identity import (DAG_PIN_POLICIES, PINNED_SIGNATURES,
                                 check_pins, dag_pin_trace, serve_pin_trace,
                                 workload_pin_trace)


@pytest.mark.parametrize("policy", DAG_PIN_POLICIES)
def test_dag_pin(policy):
    assert trace_signature(dag_pin_trace(policy)) == \
        PINNED_SIGNATURES[f"dag.{policy}"]


def test_workload_pin():
    assert trace_signature(workload_pin_trace()) == \
        PINNED_SIGNATURES["workload.molding:adaptive"]


def test_serve_pin():
    assert trace_signature(serve_pin_trace()) == \
        PINNED_SIGNATURES["serve.molding:weight"]


def test_check_pins_empty():
    # the aggregate checker test_shard.py also calls at n_shards=1
    assert check_pins() == []


def test_single_variant_records_default_impl():
    # the trace's impl column exists but is pure DEFAULT_IMPL on legacy runs
    trace = dag_pin_trace("molding:weight")
    assert trace and all(t.impl == DEFAULT_IMPL for t in trace)


def test_signature_ignores_impl_column():
    # the fingerprint must hash only pre-variant fields, or the pins could
    # never have been carried over from the PR-6 tree
    t = dag_pin_trace("adaptive")
    mutated = [type(r)(**{**r.__dict__, "impl": "zzz"}) for r in t]
    assert trace_signature(mutated) == trace_signature(t)
