"""Median over the window's TAOs of (start - ready) from the runtime's
trace records, in ms: how long a ready TAO waited in a ready deque until a
worker placed it.  ``TraceRecord.ready`` is stamped in
``ThreadedRuntime._admit_ready`` only while the profiler records.  Layer:
scheduler and vehicle.  Moves ``taos_per_s``."""
from yardstick.spans import ready_waits
from yardstick.stats import median


def read(run):
    waits = ready_waits(rec for _, res, _ in getattr(run.cell, "runs", ())
                        for rec in res.trace)
    return median(waits) * 1e3 if waits else None
