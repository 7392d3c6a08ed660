"""Compile for a described TPU v5e chip (no chip attached).

The TPU compiler is installed with jax, and compiles for a topology that is
described rather than present.  That refuses what interpret mode accepts:
block shapes off the (8, 128) tiling, rank-1 SMEM/VMEM blocks, programs
that do not fit the chip's 16 GB.  Here every Pallas kernel compiles with
``interpret=False`` at a real width (internlm2-1.8b's attention and norm,
mamba2-2.7b's scan), and the serving engine's step (``make_decode_step``)
for internlm2-1.8b FULL compiles at batch 8, max_len 2048 within the
chip's memory, and at batch 16 updates its donated cache in place: the
output aliases the whole cache, and no layer of it is copied out.  The
benchmark's deepseek-v2-lite-stage step at 32 x 2048 fits the chip and
reads each MoE layer's expert stacks where they lie: none is copied out.
Its granite-4.0-h-micro step at 32 x 2048 fits the chip and writes each
Mamba layer's float32 state into the donated cache in place.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
Run the file in one worker (``--dist loadfile`` under xdist).  It skips
only where the ``libtpu`` package is absent; any other failure to describe
the chip fails the tests.
The persistent compilation cache is off around these compiles (a program
compiled for a described chip cannot be read back without one).
"""

import importlib.util
import json
import pathlib
import re

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
from repro.serving.engine import make_decode_step

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
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


def _engine_step(sharding, batch, cfg=LM, make_params=None):
    """The engine's donated step for ``cfg`` (internlm2-1.8b FULL) at
    ``batch`` slots of ``MAX_LEN``, compiled for the described chip; and
    its cache.  ``make_params`` builds the parameters (``init_params``)."""
    params = jax.eval_shape(make_params or (
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    cache = jax.eval_shape(lambda: T.init_cache(cfg, batch, MAX_LEN))
    place = lambda tree: jax.tree.map(
        lambda x: _spec(sharding, x.shape, x.dtype), tree)
    compiled = make_decode_step(cfg).lower(
        place(params), _spec(sharding, (batch, 1), jnp.int32),
        place(cache)).compile()
    return compiled, cache


def test_internlm2_full_decode_step_fits_one_v5e(one_chip):
    compiled, _ = _engine_step(one_chip, BATCH)
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 3.5e9      # bf16 weights + cache
    assert held < TPU_V5E.hbm_bytes, held


def _top_level_outputs(hlo: str):
    """(computation, output type) of every instruction outside a fusion's
    body: the values the compiled program holds in memory."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", hlo))
    comp, out = None, []
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        inst = re.match(r"\s+(?:ROOT )?%\S+ = (\w+\[[\d,]*\])", line)
        if inst and comp not in fused:
            out.append((comp, inst.group(1)))
    return out


def test_engine_step_updates_the_donated_cache_in_place(one_chip):
    """At the benchmark's batch (16 slots of 2048): the step's output
    aliases the whole cache, its temporaries stay under one layer's K leaf,
    and no instruction outside a fusion yields the squeezed (16, 2048, 8,
    128) layer that a scan over the cache as input slices out and then
    restacks.  (The attention's read of a layer may still be staged whole
    into the chip's fast memory, as a (1, 16, 2048, 8, 128) slice.)"""
    batch = 16
    compiled, cache = _engine_step(one_chip, batch)
    mem = compiled.memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    k = cache["blocks"]["l0"]["k"]
    layer_bytes = k.size // k.shape[0] * k.dtype.itemsize
    assert cache_bytes > 3.2e9 and layer_bytes == 64 * 2 ** 20
    assert mem.alias_size_in_bytes >= cache_bytes, mem
    assert mem.temp_size_in_bytes < layer_bytes, mem
    shape = lambda dims: "bf16[%s]" % ",".join(map(str, dims))
    outputs = _top_level_outputs(compiled.as_text())
    assert shape(k.shape) in {t for _, t in outputs}   # the parse sees it
    assert not [o for o in outputs if o[1] == shape(k.shape[1:])]


def _bench_config(name):
    """A configuration of the chip benchmark: its program config and a
    maker of its weights (``configs/<name>.py``)."""
    path = CHIP / "configs" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace("-", "_")
                                                  .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    raw = json.loads(path.with_suffix(".json").read_text())
    return mod.program_config(raw), lambda: mod.make_weights(0, raw)


def test_dsv2_step_reads_the_expert_stacks_in_place(one_chip):
    """The DeepSeek stage's step at its cell's batch (32 slots of 2048)
    fits the chip, and no instruction outside a fusion yields one layer's
    (64, 2048, 1408) or (64, 1408, 2048) expert stack: the expert scan
    reads each expert's block from the layer-stacked leaves where it lies,
    instead of copying the layer's stacks out first."""
    cfg, make_params = _bench_config("deepseek-v2-lite-stage")
    compiled, _ = _engine_step(one_chip, 32, cfg, make_params)
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 8e9        # bf16 weights + cache
    assert held < TPU_V5E.hbm_bytes, held
    E, d, f = cfg.n_routed, cfg.d_model, cfg.d_ff_expert
    assert (E, d, f) == (64, 2048, 1408)
    shape = lambda dims: "bf16[%s]" % ",".join(map(str, dims))
    outputs = _top_level_outputs(compiled.as_text())
    R = cfg.block_repeat - cfg.first_k_dense
    assert shape((R, E, d, f)) in {t for _, t in outputs}  # the parse sees it
    stacks = {shape((E, d, f)), shape((E, f, d))}
    assert not [o for o in outputs if o[1] in stacks]


def test_granite_step_writes_its_state_in_place(one_chip):
    """Granite-4.0-H-Micro's step at its cell's batch (32 slots of 2048)
    fits the chip; the donated cache, 2.42 GB of it float32 SSM state and
    conv windows, aliases the step's outputs whole, and no instruction
    outside a fusion yields one layer's (32, 64, 64, 128) state: each Mamba
    layer writes its slots' state into the layer-stacked leaf in place.
    The Mamba decode's ops carry the ``ssm.decode`` scope."""
    cfg, make_params = _bench_config("granite-4.0-h-micro")
    compiled, cache = _engine_step(one_chip, 32, cfg, make_params)
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 9e9     # bf16 weights + cache
    assert held < TPU_V5E.hbm_bytes, held
    nbytes = lambda xs: sum(x.size * x.dtype.itemsize for x in xs)
    state = [x for path, x in jax.tree_util.tree_leaves_with_path(cache)
             if path[-1].key in ("ssm", "conv_x", "conv_bc")]
    assert nbytes(state) > 2.4e9
    assert mem.alias_size_in_bytes >= nbytes(jax.tree.leaves(cache)), mem
    ssm = cache["blocks"]["l0"]["ssm"]
    assert ssm.shape == (cfg.block_repeat, 32, 64, 64, 128)
    shape = lambda dims: "f32[%s]" % ",".join(map(str, dims))
    hlo = compiled.as_text()
    outputs = _top_level_outputs(hlo)
    assert shape(ssm.shape) in {t for _, t in outputs}   # the parse sees it
    assert not [o for o in outputs if o[1] == shape(ssm.shape[1:])]
    assert "/ssm.decode/" in hlo
