"""Operations and bytes of Falcon-H1's serving programs, from the published
config keys alone (``serve-falcon-h1-34b.json``), never from the program.

Conventions as in ``work.py``: a matmul parameter costs 2 operations per
token; causal attention over S positions costs 2 * S^2 * (query heads x head
dim) per layer (QK^T and PV, half of each masked away); the SSD scan, the
convolution, norms and RoPE are left out of the operations.
"""
from __future__ import annotations

from yardstick.work import Work


def dims(cfg: dict) -> dict:
    m, d = cfg["hidden_size"], cfg["head_dim"]
    ssm_heads, gn = cfg["mamba_n_heads"], cfg["mamba_n_groups"] * cfg[
        "mamba_d_state"]
    d_ssm = cfg["mamba_d_ssm"]
    conv_dim = d_ssm + 2 * gn
    return dict(layers=cfg["num_hidden_layers"], d_model=m, head_dim=d,
                q_width=cfg["num_attention_heads"] * d,
                kv_width=cfg["num_key_value_heads"] * d,
                d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                d_ssm=d_ssm, ssm_heads=ssm_heads,
                ssm_head_dim=cfg["mamba_d_head"], d_state=cfg["mamba_d_state"],
                d_conv=cfg["mamba_d_conv"], conv_dim=conv_dim,
                d_in_proj=d_ssm + conv_dim + ssm_heads)


def matmul_params(cfg: dict) -> tuple[int, int]:
    """-> (matmul parameters of all layers, of the output head)."""
    d = dims(cfg)
    m = d["d_model"]
    attn = m * (d["q_width"] + 2 * d["kv_width"]) + d["q_width"] * m
    ssm = m * d["d_in_proj"] + d["d_ssm"] * m
    mlp = 3 * m * d["d_ff"]
    return d["layers"] * (attn + ssm + mlp), m * d["vocab"]


def attention(cfg: dict, tokens: int) -> Work:
    """One layer's causal attention over ``tokens`` positions: operations
    2 * S^2 * q_width; bytes of q, k, v and the output once each in bf16."""
    d = dims(cfg)
    return Work(flops=2.0 * tokens * tokens * d["q_width"],
                bytes=2.0 * tokens * (2 * d["q_width"] + 2 * d["kv_width"]))


def prefill(cfg: dict, tokens: int) -> Work:
    """Every layer over every prompt token, causal attention in each layer,
    the head over the last token only."""
    layers, head = matmul_params(cfg)
    attn = dims(cfg)["layers"] * attention(cfg, tokens).flops
    return Work(flops=2.0 * (layers * tokens + head) + attn, bytes=0.0)


def param_bytes(cfg: dict) -> float:
    """Every parameter a decode step reads, in bf16: the layers' matrices,
    convolution and norms, the SSM's per-head scalars in float32, the head
    slice, the final norm and one embedding row."""
    d = dims(cfg)
    layers, head = matmul_params(cfg)
    m = d["d_model"]
    per_layer_bf16 = (d["d_conv"] * d["conv_dim"] + d["conv_dim"]
                      + 2 * m + d["d_ssm"])
    per_layer_f32 = 3 * d["ssm_heads"]
    small = d["layers"] * (2 * per_layer_bf16 + 4 * per_layer_f32)
    return 2.0 * (layers + head + 2 * m) + small


def state_bytes(cfg: dict) -> float:
    """One request's fixed-size state: the convolution's last ``d_conv - 1``
    inputs (bf16) and the SSD state (float32) of every layer."""
    d = dims(cfg)
    conv = (d["d_conv"] - 1) * d["conv_dim"] * 2
    ssd = d["ssm_heads"] * d["d_state"] * d["ssm_head_dim"] * 4
    return float(d["layers"] * (conv + ssd))


def kv_bytes(cfg: dict, positions: int) -> float:
    """K and V of ``positions`` tokens in every layer, bf16."""
    d = dims(cfg)
    return float(d["layers"] * 2 * d["kv_width"] * 2 * positions)


def decode(cfg: dict, position: int) -> Work:
    """A batch-1 decode step at ``position`` (the new token's): reads every
    parameter, reads the KV cache up to and including the new token and
    writes the new token's K and V, reads and writes the fixed state."""
    layers, head = matmul_params(cfg)
    return Work(flops=2.0 * (layers + head),
                bytes=param_bytes(cfg) + kv_bytes(cfg, position + 2)
                + 2.0 * state_bytes(cfg))
