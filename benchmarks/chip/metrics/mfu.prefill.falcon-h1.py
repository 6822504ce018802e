"""Model FLOP utilization of Falcon-H1's prefill program, in %: the
prefills' declared operations (2 x matmul parameters x prompt tokens, causal
attention 2 x S^2 x query width in every layer, the head once;
``yardstick/falcon_h1_work.py``) over the device time of
``falcon_h1_prefill`` in the trace, over the chip's bf16 peak.  Layer:
model step.  Moves ``ttft_p90_s``."""


def read(run):
    if run.trace is None or not hasattr(run.cell, "prefill_work"):
        return None
    secs, n = run.trace.module("falcon_h1_prefill")
    flops = run.cell.prefill_work().flops
    if n == 0 or secs <= 0 or flops <= 0:
        return None
    return 100.0 * flops / secs / run.peaks.flops_bf16
