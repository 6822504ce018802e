"""Share of HBM bandwidth that a batch-1 decode step reached, in %: the
bytes a step must move (every parameter as stored, the request's state
read and written) times the steps, over the device time of
``mamba2_decode`` in the trace, over the chip's HBM bandwidth.  Layer:
model step.  Moves ``sojourn_p90_s``."""


def read(run):
    if run.trace is None:
        return None
    secs, n = run.trace.module("mamba2_decode")
    if n == 0 or secs <= 0:
        return None
    return 100.0 * n * run.cell.decode_work().bytes / secs / run.peaks.hbm_bw
