"""Attention layers: GQA (w/ sliding window, QKV bias, RoPE/M-RoPE) and
DeepSeek-style MLA (multi-head latent attention).

Two execution paths:
  * ``*_attention``   — full-sequence (training / prefill).  Uses a
    blockwise online-softmax implementation (`blockwise_attention`) so the
    S x S score matrix is never materialized — mandatory for the 32k-prefill
    dry-run shapes.  The Pallas candidate repro/kernels/flash_attention
    tiles the same way in VMEM; no layer calls it.
  * ``*_decode_step`` — one new token against a KV cache (serving).  A
    step takes one layer's cache, or the stacked caches of the decoder's
    layer scan and the layer to use: it writes one entry per slot into the
    stacked leaf and reads the layer where it lies, so a carried, donated
    cache is updated in place and never copied.

Parameters are plain dicts of jnp arrays; init fns take explicit dims.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .norms import rms_norm
from .rope import apply_mrope, apply_rope, yarn_mscale

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(rng, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, qkv_bias: bool = False,
                   dtype=jnp.bfloat16) -> dict:
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    s = 1.0 / math.sqrt(d_model)
    p = {
        "wq": (jax.random.normal(k1, (d_model, n_heads * head_dim)) * s
               ).astype(dtype),
        "wk": (jax.random.normal(k2, (d_model, n_kv_heads * head_dim)) * s
               ).astype(dtype),
        "wv": (jax.random.normal(k3, (d_model, n_kv_heads * head_dim)) * s
               ).astype(dtype),
        "wo": (jax.random.normal(k4, (n_heads * head_dim, d_model))
               * (1.0 / math.sqrt(n_heads * head_dim))).astype(dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


def init_mla(rng, d_model: int, n_heads: int, kv_lora_rank: int,
             qk_nope_head_dim: int = 128, qk_rope_head_dim: int = 64,
             v_head_dim: int = 128, dtype=jnp.bfloat16) -> dict:
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    qk_head = qk_nope_head_dim + qk_rope_head_dim
    s = 1.0 / math.sqrt(d_model)
    return {
        "wq": (jax.random.normal(k1, (d_model, n_heads * qk_head)) * s
               ).astype(dtype),
        "wdkv": (jax.random.normal(
            k2, (d_model, kv_lora_rank + qk_rope_head_dim)) * s
        ).astype(dtype),
        "kv_norm": jnp.ones((kv_lora_rank,), dtype),
        "wukv": (jax.random.normal(
            k3, (kv_lora_rank, n_heads * (qk_nope_head_dim + v_head_dim)))
            * (1.0 / math.sqrt(kv_lora_rank))).astype(dtype),
        "wo": (jax.random.normal(k4, (n_heads * v_head_dim, d_model))
               * (1.0 / math.sqrt(n_heads * v_head_dim))).astype(dtype),
    }


# ---------------------------------------------------------------------------
# blockwise (flash-pattern) attention — the scalable jnp path
# ---------------------------------------------------------------------------

def _tile_mask(q_pos, k_pos, causal: bool, window, Skv: int):
    """(qb, kb) mask for one tile; q_pos (qb,), k_pos (kb,)."""
    mask = (k_pos < Skv)[None, :]
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    if window is not None:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    return mask


def _blockwise_fwd_impl(q, k, v, causal, window, q_block, kv_block,
                        q_offset, skv_true, scale):
    """Returns (out (B,Sq_p,Hq,Dv), lse (B,Hq,Sq_p)) on PADDED lengths."""
    B, Sq_p, Hq, D = q.shape
    _, Skv_p, Hkv, Dv = v.shape
    rep = Hq // Hkv
    qb, kb = q_block, kv_block
    nq, nk = Sq_p // qb, Skv_p // kb

    qs = q.reshape(B, nq, qb, Hq, D).transpose(1, 0, 2, 3, 4)
    ks = k.reshape(B, nk, kb, Hkv, D).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kb, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    q_pos_base = jnp.arange(qb)
    k_pos_base = jnp.arange(kb)
    Skv_true = skv_true

    def q_block_body(args):
        qi, q_blk = args
        q_pos = q_offset + qi * qb + q_pos_base

        def kv_step(carry, inputs):
            m, l, acc = carry
            ki, k_blk, v_blk = inputs
            k_pos = ki * kb + k_pos_base
            kr = jnp.repeat(k_blk, rep, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, kr,
                           preferred_element_type=jnp.float32) * scale
            mask = _tile_mask(q_pos, k_pos, causal, window, Skv_true)
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            vr = jnp.repeat(v_blk, rep, axis=2)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vr.dtype), vr)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hq, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hq, qb), jnp.float32)
        a0 = jnp.zeros((B, Hq, qb, Dv), v.dtype)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (jnp.arange(nk), ks, vs))
        l_safe = jnp.maximum(l, 1e-30)
        out = acc / l_safe[..., None].astype(acc.dtype)
        lse = m + jnp.log(l_safe)                         # (B,Hq,qb)
        return out.transpose(0, 2, 1, 3), lse

    outs, lses = jax.lax.map(q_block_body, (jnp.arange(nq), qs))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq_p, Hq, Dv)
    lse = lses.transpose(1, 2, 0, 3).reshape(B, Hq, Sq_p)
    return out, lse


import functools as _ft


@_ft.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _blockwise_attention(q, k, v, causal, window, q_block, kv_block,
                         q_offset, skv_true, scale):
    out, _ = _blockwise_fwd_impl(q, k, v, causal, window, q_block,
                                 kv_block, q_offset, skv_true, scale)
    return out


def _bw_fwd(q, k, v, causal, window, q_block, kv_block, q_offset,
            skv_true, scale):
    out, lse = _blockwise_fwd_impl(q, k, v, causal, window, q_block,
                                   kv_block, q_offset, skv_true, scale)
    return out, (q, k, v, out, lse)


def _bw_bwd(causal, window, q_block, kv_block, q_offset, skv_true, scale,
            res, dout):
    """Flash backward: recompute p per tile from the saved LSE — O(S)
    memory instead of autodiff-through-scan's O(S^2 / block) residuals."""
    q, k, v, out, lse = res
    B, Sq_p, Hq, D = q.shape
    _, Skv_p, Hkv, Dv = v.shape
    rep = Hq // Hkv
    qb, kb = q_block, kv_block
    nq, nk = Sq_p // qb, Skv_p // kb
    Skv_true = skv_true

    # D_i = rowsum(dout * out): (B, Hq, Sq)
    Dsum = jnp.einsum("bqhd,bqhd->bhq", dout.astype(jnp.float32),
                      out.astype(jnp.float32))

    qs = q.reshape(B, nq, qb, Hq, D).transpose(1, 0, 2, 3, 4)
    dos = dout.reshape(B, nq, qb, Hq, Dv).transpose(1, 0, 2, 3, 4)
    lses = lse.reshape(B, Hq, nq, qb).transpose(2, 0, 1, 3)
    Dsums = Dsum.reshape(B, Hq, nq, qb).transpose(2, 0, 1, 3)
    ks = k.reshape(B, nk, kb, Hkv, D).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kb, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    q_pos_base = jnp.arange(qb)
    k_pos_base = jnp.arange(kb)

    def kv_block_body(dq_acc, kv_in):
        ki, k_blk, v_blk = kv_in
        k_pos = ki * kb + k_pos_base
        kr = jnp.repeat(k_blk, rep, axis=2)               # (B,kb,Hq,D)
        vr = jnp.repeat(v_blk, rep, axis=2)

        def q_step(carry, q_in):
            dk_r, dv_r = carry
            qi, q_blk, do_blk, lse_blk, D_blk = q_in
            q_pos = q_offset + qi * qb + q_pos_base
            s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, kr,
                           preferred_element_type=jnp.float32) * scale
            mask = _tile_mask(q_pos, k_pos, causal, window, Skv_true)
            s = jnp.where(mask[None, None], s, NEG_INF)
            p = jnp.exp(s - lse_blk[..., None])           # (B,Hq,qb,kb)
            dv_r = dv_r + jnp.einsum("bhqk,bqhd->bkhd", p,
                                     do_blk.astype(jnp.float32))
            dp = jnp.einsum("bqhd,bkhd->bhqk",
                            do_blk.astype(jnp.float32),
                            vr.astype(jnp.float32))
            ds = p * (dp - D_blk[..., None]) * scale
            dq_blk = jnp.einsum("bhqk,bkhd->bqhd", ds,
                                kr.astype(jnp.float32))
            dk_r = dk_r + jnp.einsum("bhqk,bqhd->bkhd", ds,
                                     q_blk.astype(jnp.float32))
            return (dk_r, dv_r), dq_blk

        zero_k = jnp.zeros((B, kb, Hq, D), jnp.float32)
        zero_v = jnp.zeros((B, kb, Hq, Dv), jnp.float32)
        (dk_r, dv_r), dq_blocks = jax.lax.scan(
            q_step, (zero_k, zero_v),
            (jnp.arange(nq), qs, dos, lses, Dsums))
        # fold GQA reps back onto the kv heads
        dk_blk = dk_r.reshape(B, kb, Hkv, rep, D).sum(axis=3)
        dv_blk = dv_r.reshape(B, kb, Hkv, rep, Dv).sum(axis=3)
        dq_acc = dq_acc + dq_blocks
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((nq, B, qb, Hq, D), jnp.float32)
    dq_acc, (dk_blocks, dv_blocks) = jax.lax.scan(
        kv_block_body, dq0, (jnp.arange(nk), ks, vs))
    dq = dq_acc.transpose(1, 0, 2, 3, 4).reshape(B, Sq_p, Hq, D)
    dk = dk_blocks.transpose(1, 0, 2, 3, 4).reshape(B, Skv_p, Hkv, D)
    dv = dv_blocks.transpose(1, 0, 2, 3, 4).reshape(B, Skv_p, Hkv, Dv)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_blockwise_attention.defvjp(_bw_fwd, _bw_bwd)


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True,
                        window: Optional[int] = None,
                        q_block: int = 512, kv_block: int = 1024,
                        q_offset: int = 0,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """Online-softmax attention without materializing S_q x S_kv scores.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) with Hq % Hkv == 0 (GQA).
    ``q_offset``: absolute position of q[:, 0] relative to k[:, 0]
    (prefill: Skv - Sq when a prefix cache exists; 0 otherwise).
    ``scale``: the scores' scale before the softmax (None: 1/sqrt(D)).
    Returns (B, Sq, Hq, Dv).

    Differentiable via a flash-style custom VJP (recompute-from-LSE), so
    training memory is O(S) — plain autodiff through the online-softmax
    scan would retain every (qb x kb) tile.
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, Dv = v.shape
    qb = min(q_block, max(Sq, 1))
    kb = min(kv_block, max(Skv, 1))
    Sq_p = -(-Sq // qb) * qb
    Skv_p = -(-Skv // kb) * kb
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    if Skv_p != Skv:
        k = jnp.pad(k, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    out = _blockwise_attention(q, k, v, causal, window, qb, kb, q_offset,
                               Skv, scale)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def _project_qkv(params: dict, x: jnp.ndarray, n_heads: int,
                 n_kv_heads: int, head_dim: int):
    B, S, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    return (q.reshape(B, S, n_heads, head_dim),
            k.reshape(B, S, n_kv_heads, head_dim),
            v.reshape(B, S, n_kv_heads, head_dim))


def gqa_attention(params: dict, x: jnp.ndarray, positions: jnp.ndarray,
                  *, n_heads: int, n_kv_heads: int, head_dim: int,
                  window: Optional[int] = None, rope: str = "rope",
                  rope_theta: float = 10000.0,
                  scale: Optional[float] = None) -> jnp.ndarray:
    """Full-sequence GQA (training / prefill).  x: (B, S, d_model);
    ``scale``: the softmax scale (None: 1/sqrt(head_dim))."""
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    if rope == "rope":
        q, k = apply_rope(q, k, positions, rope_theta)
    elif rope == "mrope":
        q, k = apply_mrope(q, k, positions, theta=rope_theta)
    out = blockwise_attention(q, k, v, causal=True, window=window,
                              scale=scale)
    B, S = x.shape[:2]
    return out.reshape(B, S, n_heads * head_dim) @ params["wo"]


def cache_layer(leaf: jnp.ndarray, layer) -> jnp.ndarray:
    """Layer ``layer`` of a stacked (R, ...) cache leaf, indexed where it
    lies; an unstacked leaf (``layer`` None) as it is."""
    if layer is None:
        return leaf
    return jax.lax.dynamic_index_in_dim(leaf, layer, keepdims=False)


def write_entries(cache: jnp.ndarray, new: jnp.ndarray, idx: jnp.ndarray,
                  layer=None) -> jnp.ndarray:
    """Write each slot's entry ``new[b]`` (B, 1, ...) at position ``idx[b]``
    of ``cache`` (B, Smax, ...), or of layer ``layer`` of a stacked cache
    (R, B, Smax, ...): one scatter of B entries, in place when the cache is
    carried or donated.  A position past the end clamps to the last, as
    ``dynamic_update_slice`` does."""
    b = jnp.arange(new.shape[0])
    at = (b, idx) if layer is None else (layer, b, idx)
    return cache.at[at].set(new[:, 0].astype(cache.dtype), mode="clip")


def gqa_decode_step(params: dict, x: jnp.ndarray, cache_k: jnp.ndarray,
                    cache_v: jnp.ndarray, cache_len: jnp.ndarray,
                    *, n_heads: int, n_kv_heads: int, head_dim: int,
                    window: Optional[int] = None, rope: str = "rope",
                    rope_theta: float = 10000.0, layer=None,
                    scale: Optional[float] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step.  x: (B, 1, d_model); cache_k/v: (B, Smax, Hkv, D),
    or with ``layer`` (a scalar index) the stacked (R, B, Smax, Hkv, D)
    caches of a layer scan, whose layer ``layer`` the step writes and
    reads in place; cache_len: (B,) ABSOLUTE sequence lengths so far;
    ``scale``: the softmax scale (None: 1/sqrt(head_dim)).

    Sliding-window layers use a RING cache: allocate Smax == window + 1 and
    the ring then holds exactly the last `window`+1 tokens — K entries are
    RoPE-rotated at their absolute positions when written, attention scores
    need no position bookkeeping, and no further window mask is required.
    Full-attention layers use Smax == max_len (linear writes).
    Returns (y, new_k, new_v), the caches in the layout they came in."""
    B = x.shape[0]
    Smax = cache_k.shape[-3]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    pos = cache_len[:, None]                               # (B, 1) absolute
    if rope == "rope":
        q, k = apply_rope(q, k, pos, rope_theta)
    elif rope == "mrope":
        pos3 = jnp.broadcast_to(pos[..., None], (B, 1, 3))
        q, k = apply_mrope(q, k, pos3, theta=rope_theta)
    # ring caches are allocated at window+1 rounded up to a shardable
    # multiple (models/transformer.ring_size); anything <= window+16 slots
    # is a ring. The ring retains the last Smax-1 >= window tokens.
    ring = window is not None and Smax <= window + 16
    idx = cache_len % Smax if ring else cache_len          # (B,) write slot
    cache_k = write_entries(cache_k, k, idx, layer)
    cache_v = write_entries(cache_v, v, idx, layer)

    # Grouped by KV head: query head h attends with KV head h // g, so q is
    # viewed as (B, 1, Hkv, g, D) and each cached K/V entry is read once —
    # no (B, Smax, Hq, D) repeat is built.  The cache is read in the
    # compute dtype: a no-op for init_cache's cache, kept in the model's
    # dtype; a cache of another dtype is upcast on read.  The shard hints
    # pin the cache and the scores to the cache's SEQUENCE sharding, making
    # the softmax+readout a flash-decoding combine (psum of small (B,H)
    # stats + (B,H,D) partials) instead of a per-layer KV all-gather
    # (no-ops off-mesh).
    from .hints import data_axis_names, shard_hint
    daxes = data_axis_names() or None
    g = n_heads // n_kv_heads
    if scale is None:
        scale = 1.0 / math.sqrt(head_dim)
    qg = q.reshape(B, 1, n_kv_heads, g, head_dim)
    kc = shard_hint(cache_layer(cache_k, layer).astype(q.dtype), daxes,
                    "model", None, None)
    vc = shard_hint(cache_layer(cache_v, layer).astype(q.dtype), daxes,
                    "model", None, None)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kc,
                   preferred_element_type=jnp.float32) * scale
    s = shard_hint(s, daxes, None, None, None, "model")
    k_slot = jnp.arange(Smax)[None, :]                     # (1, Smax)
    n_valid = jnp.minimum(cache_len + 1, Smax)             # (B,)
    valid = k_slot < n_valid[:, None]
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(vc.dtype)
    p = shard_hint(p, daxes, None, None, None, "model")
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vc)
    y = out.reshape(B, 1, n_heads * head_dim) @ params["wo"]
    return y, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------

def _mla_expand(params: dict, c_kv: jnp.ndarray, n_heads: int,
                qk_nope: int, v_dim: int):
    """Expand latent cache -> per-head K_nope and V.  c_kv: (B, S, r)."""
    B, S, _ = c_kv.shape
    u = c_kv @ params["wukv"]                              # (B,S,H*(dn+dv))
    u = u.reshape(B, S, n_heads, qk_nope + v_dim)
    return u[..., :qk_nope], u[..., qk_nope:]


def _mla_latent(params: dict, x: jnp.ndarray, kv_lora_rank: int):
    """The normalised latent c_kv (B, S, r) and the un-rotated rope key
    k_pe (B, S, dr): ``kv_a_layernorm`` applies to the latent only."""
    dkv = x @ params["wdkv"]                               # (B,S,r+dr)
    return (rms_norm(dkv[..., :kv_lora_rank], params["kv_norm"]),
            dkv[..., kv_lora_rank:])


def _yarn_softmax_factor(rope_scaling) -> float:
    """What YaRN multiplies MLA's softmax scale qk_head^-0.5 by:
    mscale(factor, mscale_all_dim)^2 (1 without YaRN)."""
    if rope_scaling is None:
        return 1.0
    return yarn_mscale(rope_scaling.factor,
                       rope_scaling.mscale_all_dim) ** 2


def mla_attention(params: dict, x: jnp.ndarray, positions: jnp.ndarray,
                  *, n_heads: int, kv_lora_rank: int,
                  qk_nope_head_dim: int = 128, qk_rope_head_dim: int = 64,
                  v_head_dim: int = 128, rope_theta: float = 10000.0,
                  rope_scaling=None) -> jnp.ndarray:
    """Full-sequence MLA.  The latent c_kv is shared across heads; the RoPE
    key part k_pe is computed once and broadcast (DeepSeek-V2 §2.1)."""
    B, S, _ = x.shape
    qk_head = qk_nope_head_dim + qk_rope_head_dim
    q = (x @ params["wq"]).reshape(B, S, n_heads, qk_head)
    q_nope, q_pe = q[..., :qk_nope_head_dim], q[..., qk_nope_head_dim:]
    c_kv, k_pe = _mla_latent(params, x, kv_lora_rank)
    k_pe = k_pe[:, :, None, :]                             # (B,S,1,dr)
    q_pe, k_pe = apply_rope(q_pe, k_pe, positions, rope_theta, rope_scaling)
    k_nope, v = _mla_expand(params, c_kv, n_heads, qk_nope_head_dim,
                            v_head_dim)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:3]
                                  + (qk_rope_head_dim,))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
    # blockwise_attention scales by qk_head^-0.5; q carries YaRN's factor
    factor = _yarn_softmax_factor(rope_scaling)
    if factor != 1.0:
        q_full = (q_full.astype(jnp.float32) * factor).astype(q_full.dtype)
    out = blockwise_attention(q_full, k_full, v, causal=True, window=None)
    return out.reshape(B, S, n_heads * v_head_dim) @ params["wo"]


@jax.named_scope("mla.decode")
def mla_decode_step(params: dict, x: jnp.ndarray, cache_c: jnp.ndarray,
                    cache_kpe: jnp.ndarray, cache_len: jnp.ndarray,
                    *, n_heads: int, kv_lora_rank: int,
                    qk_nope_head_dim: int = 128, qk_rope_head_dim: int = 64,
                    v_head_dim: int = 128, rope_theta: float = 10000.0,
                    rope_scaling=None, layer=None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step with the COMPRESSED cache (the MLA memory win):
    cache_c: (B, Smax, r) normalised latents; cache_kpe: (B, Smax, dr)
    rotated rope keys; with ``layer``, the stacked (R, B, Smax, *) caches
    of a layer scan, written and read in place as in ``gqa_decode_step``.
    Its device ops carry the scope ``mla.decode``."""
    B = x.shape[0]
    qk_head = qk_nope_head_dim + qk_rope_head_dim
    q = (x @ params["wq"]).reshape(B, 1, n_heads, qk_head)
    q_nope, q_pe = q[..., :qk_nope_head_dim], q[..., qk_nope_head_dim:]
    c_new, kpe_new = _mla_latent(params, x, kv_lora_rank)
    pos = cache_len[:, None]
    q_pe, kpe_rot = apply_rope(q_pe, kpe_new[:, :, None, :], pos, rope_theta,
                               rope_scaling)
    cache_c = write_entries(cache_c, c_new, cache_len, layer)
    cache_kpe = write_entries(cache_kpe, kpe_rot[:, :, 0, :], cache_len,
                              layer)

    # expand every cached latent to per-head K_nope and V (the simple form;
    # the absorbed form scores q against the latent directly), read in the
    # compute dtype as in gqa_decode_step
    k_nope, v = _mla_expand(params,
                            cache_layer(cache_c, layer).astype(x.dtype),
                            n_heads, qk_nope_head_dim,
                            v_head_dim)                    # (B,Smax,H,*)
    scale = _yarn_softmax_factor(rope_scaling) / math.sqrt(qk_head)
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhd,bkd->bhqk", q_pe,
                      cache_layer(cache_kpe, layer).astype(x.dtype),
                      preferred_element_type=jnp.float32)) * scale
    Smax = cache_c.shape[-2]
    valid = jnp.arange(Smax)[None, :] <= cache_len[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    y = out.reshape(B, 1, n_heads * v_head_dim) @ params["wo"]
    return y, cache_c, cache_kpe
