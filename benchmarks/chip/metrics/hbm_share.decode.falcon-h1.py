"""Share of HBM bandwidth that Falcon-H1's batch-1 decode steps reached, in
%: the bytes a step must move (every parameter in bf16, the head slice, the
KV cache at the step's own length, the conv and SSD state read and written;
``yardstick/falcon_h1_work.py``), averaged over the window's steps, times
the runs of ``falcon_h1_decode`` in the trace, over their device time, over
the chip's HBM bandwidth.  Layer: model step.  Moves ``sojourn_p90_s``."""


def read(run):
    if run.trace is None or not hasattr(run.cell, "decode_bytes"):
        return None
    secs, n = run.trace.module("falcon_h1_decode")
    steps = run.cell.decode_bytes()
    if n == 0 or secs <= 0 or not steps:
        return None
    per_step = sum(steps) / len(steps)
    return 100.0 * n * per_step / secs / run.peaks.hbm_bw
