"""Operations and bytes that a call must do, from its declared shapes.

The counts come from shapes alone, never from an implementation, so a
later kernel is read against the same work.  ``least_time`` is the
roofline: the larger of operations over peak FLOP/s and bytes over peak
HBM bandwidth, with the name of the bound that applies.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float


def matmul(m: int, k: int, n: int, in_bytes: int, out_bytes: int) -> Work:
    """``(m, k) @ (k, n)``: 2mkn operations; each operand read once and the
    product written once."""
    return Work(flops=2.0 * m * k * n,
                bytes=float((m * k + k * n) * in_bytes + m * n * out_bytes))


def copy(shape, itemsize: int) -> Work:
    """A copy reads and writes every element once."""
    return Work(flops=0.0, bytes=2.0 * math.prod(shape) * itemsize)


def sort_rows(rows: int, n: int, itemsize: int) -> Work:
    """A row sort reads and writes every element at least once; the
    operations are the compare-exchanges of a bitonic network,
    ``n/2 * log2(n) * (log2(n) + 1) / 2`` per row, counted as one each."""
    lg = int(math.log2(n))
    return Work(flops=float(rows * (n // 2) * lg * (lg + 1) // 2),
                bytes=2.0 * rows * n * itemsize)


def least_time(work: Work, peaks) -> tuple[float, str]:
    """-> (seconds, "compute" or "memory"): the bound that applies."""
    t_c = work.flops / peaks.flops_bf16
    t_m = work.bytes / peaks.hbm_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# -- mamba2 -------------------------------------------------------------------
def mamba2_dims(cfg: dict) -> dict:
    """Widths of a Mamba-2 language model from its published config keys."""
    ssm = cfg["ssm_cfg"]
    m = cfg["d_model"]
    di = ssm["expand"] * m
    heads = di // ssm["headdim"]
    groups = ssm["ngroups"]
    n = ssm["d_state"]
    mult = cfg["pad_vocab_size_multiple"]
    vocab = -(-cfg["vocab_size"] // mult) * mult
    return dict(layers=cfg["n_layer"], d_model=m, d_inner=di, heads=heads,
                head_dim=ssm["headdim"], d_state=n, groups=groups,
                d_conv=ssm["d_conv"], conv_dim=di + 2 * groups * n,
                d_in_proj=2 * di + 2 * groups * n + heads, vocab=vocab)


def mamba2_matmul_params(cfg: dict) -> tuple[int, int]:
    """-> (matmul parameters of all layers, of the output head)."""
    d = mamba2_dims(cfg)
    per_layer = d["d_model"] * d["d_in_proj"] + d["d_inner"] * d["d_model"]
    return d["layers"] * per_layer, d["d_model"] * d["vocab"]


def mamba2_prefill(cfg: dict, tokens: int) -> Work:
    """A prefill runs every layer over every prompt token and the head over
    the last one (serving needs only the next token's logits).  Operations
    follow the 2 * N * tokens convention over matmul parameters; the SSD
    scan's own operations and the convolution are left out."""
    layers, head = mamba2_matmul_params(cfg)
    return Work(flops=2.0 * (layers * tokens + head), bytes=0.0)


def mamba2_param_bytes(cfg: dict, param_bytes: int) -> float:
    """Bytes of every parameter a decode step reads: all layers, the norms,
    the head, and one row of the embedding."""
    d = mamba2_dims(cfg)
    per_layer = (d["d_model"] * d["d_in_proj"] + d["d_inner"] * d["d_model"]
                 + d["d_conv"] * d["conv_dim"] + d["conv_dim"]
                 + 3 * d["heads"] + d["d_model"] + d["d_inner"])
    total = (d["layers"] * per_layer + d["d_model"] * d["vocab"]
             + d["d_model"] + d["d_model"])
    return float(total * param_bytes)


def mamba2_state_bytes(cfg: dict, conv_bytes: int = 2,
                       ssd_bytes: int = 4) -> float:
    """Bytes of one request's recurrent state: the convolution's last
    ``d_conv - 1`` inputs and the SSD state of every layer."""
    d = mamba2_dims(cfg)
    conv = d["layers"] * (d["d_conv"] - 1) * d["conv_dim"] * conv_bytes
    ssd = (d["layers"] * d["heads"] * d["d_state"] * d["head_dim"]
           * ssd_bytes)
    return float(conv + ssd)


def mamba2_decode(cfg: dict, param_bytes: int = 4) -> Work:
    """A batch-1 decode step reads every parameter as stored and reads and
    writes the request's state; its operations are 2 per matmul
    parameter."""
    layers, head = mamba2_matmul_params(cfg)
    return Work(flops=2.0 * (layers + head),
                bytes=mamba2_param_bytes(cfg, param_bytes)
                + 2.0 * mamba2_state_bytes(cfg))
