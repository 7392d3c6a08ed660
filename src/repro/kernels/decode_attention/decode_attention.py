"""Decode attention (one new token vs. a long KV cache) — Pallas TPU kernel.

The serving decode hot spot is MEMORY-bound: the kernel's job is to stream
the KV cache through VMEM exactly once at full HBM bandwidth.  Tiling:

  * grid = (batch, kv_heads, kv_blocks) — kv_blocks minor, so the online
    softmax state for one (batch, kv_head) persists in VMEM scratch across
    KV tiles (flash-decoding's split-K, laid out for the TPU's sequential
    grid instead of CUDA thread blocks).
  * All ``group`` query heads of a kv head are processed TOGETHER as a
    (group, D) panel: GQA turns the q·K product into a small (group x D)
    x (D x block_kv) matmul — enough arithmetic intensity to keep the MXU
    from starving while staying bandwidth-limited (this is the TPU
    adaptation; a CUDA kernel would instead parallelize across warps).
  * per-sequence valid length masks ring/linear caches uniformly; the
    length vector sits whole in SMEM.
  * the wrapper lays K/V out as (B, Hkv, S, D) so every VMEM block ends in
    (rows, D), the shape the TPU's (8, 128) tiling accepts.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   block_kv: int, group: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    n_valid = len_ref[pl.program_id(0)]
    k_lo = ki * block_kv

    @pl.when(k_lo < n_valid)
    def _compute():
        q = q_ref[...].astype(jnp.float32)               # (group, D)
        k = k_ref[...].astype(jnp.float32)               # (bk, D)
        v = v_ref[...].astype(jnp.float32)               # (bk, Dv)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (group, bk)
        slot = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, block_kv), 1)
        s = jnp.where(slot < n_valid, s, NEG_INF)
        m_prev = m_scr[...]                               # (group, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = (acc_scr[...] * corr
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_kv", "interpret"))
def decode_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            lengths: jnp.ndarray, *, block_kv: int = 512,
                            interpret: bool = True) -> jnp.ndarray:
    """q: (B, Hq, D) one new token per sequence; k/v: (B, Smax, Hkv, D)
    caches; lengths: (B,) valid entries per sequence.
    Returns (B, Hq, Dv)."""
    B, Hq, D = q.shape
    _, Smax, Hkv, Dv = v.shape
    group = Hq // Hkv
    bk = min(block_kv, Smax)
    S_p = -(-Smax // bk) * bk
    # (B, Hkv, S, D): each K/V block is a (bk, D) panel
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    if S_p != Smax:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, S_p - Smax), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, S_p - Smax), (0, 0)))
    # group query heads by kv head: (B, Hkv, group, D)
    qg = q.reshape(B, Hkv, group, D)

    grid = (B, Hkv, S_p // bk)
    kernel = functools.partial(_decode_kernel, block_kv=bk, group=group)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, group, D),
                         lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((None, None, bk, D),
                         lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((None, None, bk, Dv),
                         lambda b, h, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, group, Dv),
                               lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k, v)
    return out.reshape(B, Hq, Dv)
