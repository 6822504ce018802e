"""Model FLOP utilization of the prefill program, in %: the prefills'
declared operations (2 x matmul parameters x prompt tokens, plus the head
once) over the device time of ``mamba2_prefill`` in the trace, over the
chip's bf16 peak.  Layer: model step.  Moves ``ttft_p90_s``."""


def read(run):
    if run.trace is None:
        return None
    secs, n = run.trace.module("mamba2_prefill")
    flops = run.cell.prefill_work().flops
    if n == 0 or secs <= 0 or flops <= 0:
        return None
    return 100.0 * flops / secs / run.peaks.flops_bf16
