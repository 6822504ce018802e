"""Median over the window's prefill and decode TAOs of (start - ready)
from the runtime's trace records, in ms: how long a ready TAO waited in a
ready deque until a worker placed it.  ``TraceRecord.ready`` is stamped in
``ThreadedRuntime._admit_ready`` only while the profiler records.  Layer:
scheduler and vehicle.  Moves ``sojourn_p90_s``."""
from yardstick.spans import ready_waits
from yardstick.stats import median


def read(run):
    stats = getattr(run.cell, "stats", None)
    if stats is None:
        return None
    waits = ready_waits(stats.result.trace)
    return median(waits) * 1e3 if waits else None
