"""Compile for a described TPU v5e chip (no chip attached).

The TPU compiler is installed with jax, and compiles for a topology that is
described rather than present.  That refuses what interpret mode accepts:
block shapes off the (8, 128) tiling, rank-1 SMEM/VMEM blocks, programs
that do not fit the chip's 16 GB.  Here every Pallas kernel compiles with
``interpret=False`` at a real width (internlm2-1.8b's attention and norm,
mamba2-2.7b's scan), and internlm2-1.8b's FULL ``decode_step`` compiles at
batch 8, max_len 2048 within the chip's memory.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
Run the file in one worker (``--dist loadfile`` under xdist).  It skips
only where the ``libtpu`` package is absent; any other failure to describe
the chip fails the tests.
The persistent compilation cache is off around these compiles (a program
compiled for a described chip cannot be read back without one).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs as C
from repro.core.cluster import TPU_V5E
from repro.kernels.decode_attention.decode_attention import \
    decode_attention_pallas
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.rmsnorm.rmsnorm import rms_norm_pallas
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas
from repro.models import transformer as T

LM = C.get_config("internlm2_1_8b")
SSM = C.get_config("mamba2_2_7b")
BATCH, MAX_LEN = 8, 2048


@pytest.fixture(scope="module")
def one_chip():
    pytest.importorskip("libtpu", reason="the TPU compiler is not installed")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, sh):
    hq, hkv, hd = LM.n_heads, LM.n_kv_heads, LM.resolved_head_dim
    H = SSM.n_ssd_heads
    P, N, S = SSM.d_inner // H, SSM.d_state, 1024
    f32 = jnp.float32
    return {
        "rms_norm": (lambda x, w: rms_norm_pallas(x, w, interpret=False),
                     [_spec(sh, (BATCH * 32, LM.d_model)),
                      _spec(sh, (LM.d_model,), f32)]),
        "decode_attention": (
            lambda q, k, v, n: decode_attention_pallas(q, k, v, n,
                                                       interpret=False),
            [_spec(sh, (BATCH, hq, hd)),
             _spec(sh, (BATCH, MAX_LEN, hkv, hd)),
             _spec(sh, (BATCH, MAX_LEN, hkv, hd)),
             _spec(sh, (BATCH,), jnp.int32)]),
        "flash_attention": (
            lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
            [_spec(sh, (1, MAX_LEN, hq, hd)),
             _spec(sh, (1, MAX_LEN, hkv, hd)),
             _spec(sh, (1, MAX_LEN, hkv, hd))]),
        "ssd_scan": (
            lambda x, dt, a, b, c: ssd_scan_pallas(x, dt, a, b, c,
                                                   interpret=False),
            [_spec(sh, (1, S, H, P)), _spec(sh, (1, S, H), f32),
             _spec(sh, (H,), f32), _spec(sh, (1, S, N)),
             _spec(sh, (1, S, N))]),
    }[name]


@pytest.mark.parametrize("name", ["rms_norm", "decode_attention",
                                  "flash_attention", "ssd_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_internlm2_full_decode_step_fits_one_v5e(one_chip):
    params = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), LM))
    cache = jax.eval_shape(lambda: T.init_cache(LM, BATCH, MAX_LEN))
    place = lambda tree: jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype), tree)
    compiled = jax.jit(lambda p, t, c: T.decode_step(p, LM, t, c)).lower(
        place(params), _spec(one_chip, (BATCH, 1), jnp.int32),
        place(cache)).compile()
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 3.5e9      # bf16 weights + cache
    assert held < TPU_V5E.hbm_bytes, held
