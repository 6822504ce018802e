"""falcon-h1-34b [falcon_h1] — Mamba-2 and GQA attention side by side in
every layer, muP multipliers.  [tiiuae/Falcon-H1-34B-Instruct config.json]

72L d_model=5120, attention 20H (GQA kv=4, head_dim 128, RoPE theta 1e11),
Mamba-2 mixer d_ssm=4096 (32 heads of 128, d_state 256, 2 B/C groups, conv
4, chunk 128), SwiGLU d_ff=21504, vocab 261120 untied.  The published
``mamba_d_ssm`` 4096 overrides ``mamba_expand`` 2: ``ssm_expand`` 0.8 gives
it.  Every layer attends over the whole context, so long_500k is skipped.
"""
from ..models import ModelConfig

# the published muP multipliers (config.json)
MULTIPLIERS = dict(
    embedding_multiplier=5.656854249492381,
    attention_in_multiplier=1.0,
    attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804,
    ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    lm_head_multiplier=0.0078125,
)


def config() -> ModelConfig:
    return ModelConfig(
        name="falcon-h1-34b", family="falcon_h1",
        n_layers=72, d_model=5120, n_heads=20, n_kv_heads=4, head_dim=128,
        d_ff=21504, vocab_size=261120, rope_theta=1e11,
        ssm_state=256, ssm_expand=0.8, ssm_head_dim=128, ssm_conv=4,
        ssm_chunk=128, ssm_groups=2, **MULTIPLIERS,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="falcon-h1-smoke", family="falcon_h1",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=503, rope_theta=1e11,
        ssm_state=8, ssm_expand=1, ssm_head_dim=16, ssm_conv=4,
        ssm_chunk=16, ssm_groups=2, vocab_pad_multiple=16, **MULTIPLIERS,
    )
