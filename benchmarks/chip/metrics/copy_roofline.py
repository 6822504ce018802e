"""Share of its roofline that the copy TAO payload reached, in %: bytes
read and written over HBM bandwidth, times its runs, over the device time
of its program ``tao_copy`` in the trace.  Layer: kernels.  Moves
``taos_per_s``."""
from yardstick.work import least_time


def read(run):
    if run.trace is None:
        return None
    secs, n = run.trace.module("tao_copy")
    if n == 0 or secs <= 0:
        return None
    least, _bound = least_time(run.cell.payload_work("copy"), run.peaks)
    return 100.0 * n * least / secs
