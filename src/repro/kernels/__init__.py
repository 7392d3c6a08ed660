"""Pallas TPU kernel candidates for the serving hot spots.

No served path calls these kernels: the layers in ``repro.layers`` run
plain XLA.  Each kernel is compiled for a described v5e by
tests/test_tpu_compile.py, checked against its oracle in interpret mode by
tests/test_kernels.py and on the chip by chip_smoke.py.  A kernel that
wins a cell is called from its layer, chosen by what the code can observe
(the platform); one that wins none is deleted.

Each kernel package holds two files, imported directly:
  * ``<name>.py`` — the pl.pallas_call kernel with explicit BlockSpec VMEM
    tiling,
  * ``ref.py``    — the pure-jnp oracle the tests assert_allclose against.

Kernels: flash_attention (prefill), decode_attention (one token vs KV
cache, flash-decoding tiling; it transposes and pads the whole unstacked
cache per call), ssd_scan (Mamba2 chunked SSD), rmsnorm.
"""
