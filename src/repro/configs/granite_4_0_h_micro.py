"""granite-4.0-h-micro [hybrid] — 40L d_model=2048: 4 periods of 9 Mamba-2
layers and 1 GQA attention layer (``mmmmmammmm``), every layer followed by
a SwiGLU MLP of width 8192; Mamba-2 with 64 heads of 64 (d_inner 4096),
d_state 128, one group, conv 4; attention 32 query / 8 KV heads of 64 with
no positional encoding (NoPE); tied embedding, vocab 100352; the muP
scalars embedding x12, residual x0.22, attention scale 1/64, logits / 8.
[https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json]

Hybrid with O(1) recurrent state in 36 of 40 layers -> long_500k RUNS.
"""

from repro.models.config import LayerSpec, ModelConfig

_MU_P = dict(embedding_multiplier=12.0, residual_multiplier=0.22,
             logits_scaling=8.0, attn_scale=0.015625)

FULL = ModelConfig(
    name="granite-4.0-h-micro",
    d_model=2048,
    vocab_size=100352,
    block_pattern=(LayerSpec("ssm"),) * 5 + (LayerSpec("attn"),)
    + (LayerSpec("ssm"),) * 4,
    block_repeat=4,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    rope="none",
    d_ff=8192,
    d_inner=4096,
    d_state=128,
    n_ssd_heads=64,
    d_conv=4,
    n_ssm_groups=1,
    tie_embeddings=True,
    **_MU_P,
)

# 2 periods of (2 SSM, 1 attention), one group, the scalars as published
REDUCED = ModelConfig(
    name="granite-reduced",
    d_model=64,
    vocab_size=512,
    block_pattern=(LayerSpec("ssm"),) * 2 + (LayerSpec("attn"),),
    block_repeat=2,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    rope="none",
    d_ff=128,
    d_inner=128,
    d_state=16,
    n_ssd_heads=8,
    d_conv=4,
    n_ssm_groups=1,
    tie_embeddings=True,
    **_MU_P,
)
