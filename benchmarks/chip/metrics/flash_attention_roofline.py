"""Share of its roofline that the Pallas flash-attention kernel reached, in
%: the least time of its declared work (per call, the larger of causal
FLOPs over the bf16 peak and q/k/v/o bytes over HBM bandwidth;
``yardstick/falcon_h1_work.attention``), summed over the window's calls,
over the device time of the kernel's ops (``flash_attention``, ``.N``) in
the trace.  Layer: kernels.  Moves ``ttft_p90_s``."""
import re

from yardstick.work import least_time

KERNEL = re.compile(r"^flash_attention(\.\d+)?$")


def read(run):
    if run.trace is None or not hasattr(run.cell, "attention_calls"):
        return None
    secs = sum(ns for op, ns in run.trace.op_ns.items()
               if KERNEL.match(op)) / 1e9
    calls = run.cell.attention_calls()
    if secs <= 0 or not calls:
        return None
    return 100.0 * sum(least_time(w, run.peaks)[0] for w in calls) / secs
