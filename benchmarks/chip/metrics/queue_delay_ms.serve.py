"""Median wait of a request from when it was due to its first TAO's start
(the runtime's ``DagStats.queue_delay``), in ms.  Layer: workload and
admission.  Moves ``ttft_p90_s``."""
from yardstick.stats import median


def read(run):
    delays = getattr(run.cell, "queue_delays", None)
    return median(delays) * 1e3 if delays else None
