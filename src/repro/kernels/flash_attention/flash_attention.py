"""Flash attention (prefill) as a Pallas TPU kernel.

TPU-native tiling, not a CUDA port:
  * grid = (batch, q_heads, q_blocks, kv_blocks) — the kv axis is the
    MINOR grid dimension, so on TPU its iterations run sequentially per
    (b, h, qi) and the online-softmax running state (m, l, acc) lives in
    VMEM scratch that persists across kv steps.
  * BlockSpecs pull one (block_q, head_dim) Q tile and one
    (block_kv, head_dim) K/V tile into VMEM per step; block sizes default
    to 128 — MXU-aligned.
  * GQA is handled in the K/V index_map (q head h reads kv head
    h // group) — no repeated K/V materialization in HBM.
  * the wrapper lays Q/K/V out as (B, H, S, D), so every block ends in
    (rows, D) as the TPU's (8, 128) tiling requires; the online-softmax
    statistics are (block_q, 1) columns.
  * causal + sliding-window masking is applied per tile; fully-masked
    tiles are skipped with pl.when.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, block_q: int, block_kv: int,
                 seq_kv: int, causal: bool, window, q_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute positions: (bq, 1) query, (1, bk) key (2-D iota for TPU)
    q_pos = (q_offset + qi * block_q
             + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
    k_pos = (ki * block_kv
             + jax.lax.broadcasted_iota(jnp.int32, (1, block_kv), 1))

    # tile-level skip: many tiles are fully masked under causal/window
    q_hi = q_offset + qi * block_q + block_q - 1
    q_lo = q_offset + qi * block_q
    k_lo = ki * block_kv
    k_hi = k_lo + block_kv - 1
    live = k_lo < seq_kv
    if causal:
        live = jnp.logical_and(live, k_lo <= q_hi)
    if window is not None:
        live = jnp.logical_and(live, k_hi > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)              # (bq, D)
        k = k_ref[...].astype(jnp.float32)              # (bk, D)
        v = v_ref[...].astype(jnp.float32)              # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        mask = k_pos < seq_kv
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                             # (bq, 1)
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


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv",
                     "q_offset", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True, window=None,
                           block_q: int = 128, block_kv: int = 128,
                           q_offset: int = 0,
                           interpret: bool = True) -> jnp.ndarray:
    """q: (B, Sq, Hq, D); k/v: (B, Skv, Hkv, D).  Returns (B, Sq, Hq, Dv).

    ``interpret=True`` executes the kernel body in Python on CPU (this
    container has no TPU); on a real TPU pass interpret=False.
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, Dv = v.shape
    if Hq % Hkv:
        raise ValueError("Hq must be a multiple of Hkv")
    group = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    bq = min(block_q, Sq)
    bk = min(block_kv, Skv)
    Sq_p = -(-Sq // bq) * bq
    Skv_p = -(-Skv // bk) * bk
    # (B, H, S, D): each block is a (rows, D) panel of one head
    q = jnp.pad(q.transpose(0, 2, 1, 3),
                ((0, 0), (0, 0), (0, Sq_p - Sq), (0, 0)))
    k = jnp.pad(k.transpose(0, 2, 1, 3),
                ((0, 0), (0, 0), (0, Skv_p - Skv), (0, 0)))
    v = jnp.pad(v.transpose(0, 2, 1, 3),
                ((0, 0), (0, 0), (0, Skv_p - Skv), (0, 0)))

    grid = (B, Hq, Sq_p // bq, Skv_p // bk)
    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=bq, block_kv=bk,
        seq_kv=Skv, causal=causal, window=window, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, bk, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((None, None, bk, Dv),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, Dv),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)
