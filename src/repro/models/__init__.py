"""Model zoo: unified decoder LM + encoder-decoder, configured per arch."""

from .config import EncoderConfig, LayerSpec, ModelConfig
from .transformer import (decode_step, forward, init_cache, init_params,
                          param_count)
from .encdec import (encdec_decode_step, encdec_forward, encdec_prefill,
                     encode, init_encdec_params)

__all__ = [
    "EncoderConfig", "LayerSpec", "ModelConfig", "decode_step",
    "encdec_decode_step", "encdec_forward", "encdec_prefill", "encode",
    "forward", "init_cache", "init_encdec_params", "init_params",
    "param_count",
]
