"""Pure-JAX neural net layers used by the model zoo.

All layers are pure functions over parameter pytrees (dicts of jnp arrays);
no framework (flax/haiku) dependency.  Shapes follow (batch, seq, dim)
unless stated.  These layers run plain XLA on every backend; the Pallas
candidates in repro.kernels are called by no layer.
"""

from .norms import layer_norm, rms_norm
from .rope import apply_mrope, apply_rope, rope_angles
from .attention import (gqa_attention, gqa_decode_step, init_attention,
                        init_mla, mla_attention, mla_decode_step)
from .mlp import init_mlp, mlp_forward
from .moe import init_moe, moe_forward
from .ssm import init_mamba2, mamba2_decode_step, mamba2_forward

__all__ = [
    "apply_mrope", "apply_rope", "gqa_attention", "gqa_decode_step",
    "init_attention", "init_mamba2", "init_mla", "init_moe", "init_mlp",
    "layer_norm", "mamba2_decode_step", "mamba2_forward", "mla_attention",
    "mla_decode_step", "mlp_forward", "moe_forward", "rms_norm",
    "rope_angles",
]
