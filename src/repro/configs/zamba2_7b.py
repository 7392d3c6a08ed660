"""zamba2-7b [hybrid] — 81L d_model=3584, Mamba2 backbone (ssm_state=64)
with a SHARED GQA attention block (32H kv=32, d_ff=14336) applied once per
repeat.  [arXiv:2411.15242; unverified tier]

We model the 81 layers as 6 Mamba2 layers x 13 repeats (78) + 13
applications of ONE shared attention+MLP block (weights tied across
repeats — the Zamba2 signature).  Cell-level DP is disabled for the shared
block: replicating it would break the weight tying.  Hybrid -> long_500k
RUNS.
"""

from repro.models.config import LayerSpec, ModelConfig

FULL = ModelConfig(
    name="zamba2-7b",
    d_model=3584,
    vocab_size=32000,
    block_pattern=(LayerSpec("ssm"),) * 6,
    block_repeat=13,
    d_inner=7168,
    d_state=64,
    n_ssd_heads=64,            # head_dim 112
    d_conv=4,
    ffn_kind="none",
    n_heads=32,
    n_kv_heads=32,
    head_dim=112,
    shared_attn=True,
    shared_d_ff=14336,
    d_ff=14336,
)

REDUCED = ModelConfig(
    name="zamba2-reduced",
    d_model=64,
    vocab_size=512,
    block_pattern=(LayerSpec("ssm"),) * 2,
    block_repeat=2,
    d_inner=128,
    d_state=16,
    n_ssd_heads=4,
    d_conv=4,
    ffn_kind="none",
    n_heads=4,
    n_kv_heads=4,
    head_dim=16,
    shared_attn=True,
    shared_d_ff=128,
    d_ff=128,
)
