"""Expert parallelism: capacity-based all-to-all MoE dispatch (shard_map).

The model's default MoE path (layers/moe.py) is a dense-dispatch einsum —
exact but computing every expert on every token (E/top_k x FLOP waste;
visible in the roofline MODEL_FLOPS/HLO_FLOPs ratio).  This module is the
optimized path the APEX planner's "ep" template maps to:

  * tokens are sharded over the "model" axis (sequence-split), experts are
    sharded over the same axis (E_local = E / tp per device),
  * each device routes its T/tp tokens and buckets them per expert with a
    fixed CAPACITY (cap_factor * T_local * top_k / E), dropping overflow
    (GShard/DeepSpeed-MoE semantics — drops are counted and returned,
    never silent),
  * one all-to-all sends buckets to expert owners, experts run dense GEMMs
    once per bucket, a second all-to-all returns outputs, combine weights
    rescale them.

Exact top-k FLOPs (no dense-dispatch waste) and the paper's EP
communication pattern (2 all-to-alls vs TP's all-reduce).  No served path
calls it yet.  Correctness is asserted against the dense oracle in
tests/test_parallel.py (with capacity high enough that nothing drops).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map

from repro.layers.moe import top_k_gates


def _bucket_by_expert(x, idx, n_exp: int, cap: int):
    """Bucket token-assignments into (n_exp, cap, d) buffers, dropping
    overflow.  x: (T, d); idx: (T, k) expert ids.
    Returns (buffers, (tok_of_assign, e_idx, s_idx, kept), n_dropped)."""
    T, d = x.shape
    k = idx.shape[1]
    flat_e = idx.reshape(-1)                          # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # rank within each equal-expert run of the sorted list
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_in_run = jnp.arange(T * k) - first
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
    slot = pos_in_run[inv]                            # (T*k,)
    kept = slot < cap
    drops = jnp.sum(~kept)
    tok_of_assign = jnp.repeat(jnp.arange(T), k)
    e_idx = jnp.where(kept, flat_e, 0)
    s_idx = jnp.where(kept, slot, cap - 1)
    buffers = jnp.zeros((n_exp, cap, d), x.dtype).at[e_idx, s_idx].add(
        jnp.where(kept[:, None], x[tok_of_assign], 0))
    return buffers, (tok_of_assign, e_idx, s_idx, kept), drops


def moe_ep_forward(params: dict, x: jnp.ndarray, top_k: int, mesh: Mesh,
                   axis: str = "model", cap_factor: float = 1.25, *,
                   norm_topk_prob: bool = True
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """EP MoE over ``axis``.  x: (B, S, d); S must divide mesh[axis].
    Gates follow ``layers.moe.top_k_gates``, as ``moe_forward``'s do.
    Returns (y (B,S,d), dropped_fraction scalar)."""
    n_exp = params["w_up"].shape[0]
    tp = mesh.shape[axis]
    if n_exp % tp:
        raise ValueError(f"{n_exp} experts not divisible by axis {tp}")
    e_local = n_exp // tp
    B, S, d = x.shape
    if S % tp:
        raise ValueError(f"seq {S} not divisible by EP axis {tp}")
    gated = "w_gate" in params

    def local(x_l, router, w_up, w_gate, w_down):
        # x_l: (B_l, S/tp, d) — this device's token slice
        Bl, Sl, _ = x_l.shape
        T = Bl * Sl
        xt = x_l.reshape(T, d)
        logits = xt.astype(jnp.float32) @ router           # router replicated
        gates, top_idx = top_k_gates(logits, top_k, norm_topk_prob)
        cap = max(1, int(cap_factor * T * top_k / n_exp))
        buffers, (tok_a, e_idx, s_idx, kept), drops = _bucket_by_expert(
            xt, top_idx, n_exp, cap)
        # dispatch: (tp, e_local, cap, d) -> expert owners
        bufs = buffers.reshape(tp, e_local, cap, d)
        recv = jax.lax.all_to_all(bufs, axis, split_axis=0, concat_axis=0,
                                  tiled=False)             # (tp, e_l, cap, d)
        h = recv.reshape(tp, e_local, cap, d)
        eids = jnp.arange(e_local)
        up = jnp.einsum("secd,edf->secf", h, w_up[eids])
        if gated:
            gt = jnp.einsum("secd,edf->secf", h, w_gate[eids])
            up = jax.nn.silu(gt) * up
        else:
            up = jax.nn.gelu(up)
        yv = jnp.einsum("secf,efd->secd", up, w_down[eids])
        # combine: return buckets to their source devices
        back = jax.lax.all_to_all(yv, axis, split_axis=0, concat_axis=0,
                                  tiled=False)             # (tp, e_l, cap, d)
        yb = back.reshape(n_exp, cap, d)                   # expert-major
        vals = yb[e_idx, s_idx]                            # (T*k, d)
        gflat = gates.reshape(-1)
        vals = vals * (gflat * kept).astype(vals.dtype)[:, None]
        y = jnp.zeros((T, d), vals.dtype).at[tok_a].add(vals)
        drop_frac = drops.astype(jnp.float32) / (T * top_k)
        drop_frac = jax.lax.pmean(drop_frac, axis)
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                drop_frac = jax.lax.pmean(drop_frac, ax)
        return y.reshape(Bl, Sl, d).astype(x_l.dtype), drop_frac

    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dspec = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(dspec, axis, None), P(), P(axis), P(axis) if gated
                  else P(), P(axis)),
        out_specs=(P(dspec, axis, None), P()),
        check_rep=False)
    y, drop = fn(x, params["router"], params["w_up"],
                 params.get("w_gate", jnp.zeros((), x.dtype)),
                 params["w_down"])
    if "shared" in params:
        from repro.layers.mlp import mlp_forward
        y = y + mlp_forward(params["shared"], x)
    return y, drop
