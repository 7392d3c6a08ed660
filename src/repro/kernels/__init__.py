"""Pallas TPU kernels for the serving hot spots.

Each kernel package ships three files:
  * ``<name>.py`` — the pl.pallas_call kernel with explicit BlockSpec VMEM
    tiling (TPU is the TARGET; validated with interpret=True on CPU,
    compiled for a described v5e by tests/test_tpu_compile.py and run
    against ``ref.py`` on the chip by chip_smoke.py),
  * ``ops.py``    — the jit'd public wrapper that dispatches kernel vs.
    pure-jnp fallback,
  * ``ref.py``    — the pure-jnp oracle the tests assert_allclose against.

Kernels: flash_attention (prefill), decode_attention (one token vs KV
cache, flash-decoding tiling), ssd_scan (Mamba2 chunked SSD), rmsnorm.
"""

from .flash_attention.ops import flash_attention
from .decode_attention.ops import decode_attention
from .ssd_scan.ops import ssd_scan
from .rmsnorm.ops import fused_rms_norm

__all__ = ["decode_attention", "flash_attention", "fused_rms_norm",
           "ssd_scan"]
