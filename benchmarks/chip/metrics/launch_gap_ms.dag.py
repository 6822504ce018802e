"""Median over TAOs of (start - the end of its last parent), from the
runtime's trace records, in ms: the scheduler's and the runtime's time to
launch a TAO once it is ready.  Layer: scheduler and vehicle.  Moves
``taos_per_s``."""
from yardstick.stats import median


def read(run):
    gaps = run.cell.launch_gaps()
    return median(gaps) * 1e3 if gaps else None
