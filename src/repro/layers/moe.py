"""Mixture-of-Experts FFN: top-k routing + optional shared experts.

Dense-dispatch formulation: every expert processes every token, masked by
the routing weights.  O(E/topk) more FLOPs than a gathered implementation,
but it is fully shardable with a single einsum (experts on the "model" mesh
axis = expert parallelism under pjit) and exactly matches the gathered
result — the right trade for smoke tests, training at modest expert counts,
and the dry-run (where only the sharded HLO matters; XLA's SPMD partitioner
turns the expert einsum + masked routing into the standard EP all-to-all
pattern).  This is the layer the serving engine runs.  A token-dropping
capacity-based gathered dispatch is in repro/parallel/ep.py; only the
multi-device tests run it.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def init_moe(rng, d_model: int, d_ff_expert: int, n_routed: int,
             top_k: int, n_shared: int = 0, gated: bool = True,
             dtype=jnp.bfloat16) -> dict:
    kr, ke1, ke2, ke3, ks = jax.random.split(rng, 5)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff_expert)
    p = {
        "router": (jax.random.normal(kr, (d_model, n_routed)) * s_in
                   ).astype(jnp.float32),
        "w_up": (jax.random.normal(ke1, (n_routed, d_model, d_ff_expert))
                 * s_in).astype(dtype),
        "w_down": (jax.random.normal(ke2, (n_routed, d_ff_expert, d_model))
                   * s_out).astype(dtype),
    }
    if gated:
        p["w_gate"] = (jax.random.normal(ke3, (n_routed, d_model, d_ff_expert))
                       * s_in).astype(dtype)
    if n_shared:
        from .mlp import init_mlp
        p["shared"] = init_mlp(ks, d_model, d_ff_expert * n_shared,
                               gated=gated, dtype=dtype)
    return p


def top_k_gates(logits: jnp.ndarray, top_k: int, norm_topk_prob: bool
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(gates, expert ids), each (..., top_k), from f32 router logits."""
    if norm_topk_prob:
        top_vals, top_idx = jax.lax.top_k(logits, top_k)
        return jax.nn.softmax(top_vals, axis=-1), top_idx   # renormalized
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)


def _route(params: dict, x: jnp.ndarray, top_k: int,
           router_noise: Optional[jnp.ndarray],
           norm_topk_prob: bool) -> jnp.ndarray:
    """(B, S, E) combine weights: nonzero at each token's top-k experts."""
    B, S, _ = x.shape
    n_routed = params["router"].shape[1]
    logits = (x.astype(jnp.float32) @ params["router"])     # (B,S,E)
    if router_noise is not None:
        logits = logits + router_noise
    gates, top_idx = top_k_gates(logits, top_k, norm_topk_prob)
    # dense dispatch mask: (B,S,E) combine weights
    combine = jnp.zeros((B, S, n_routed), jnp.float32)
    return jax.vmap(jax.vmap(
        lambda c, i, g: c.at[i].add(g)))(combine, top_idx, gates)


def moe_forward(params: dict, x: jnp.ndarray, top_k: int,
                router_noise: Optional[jnp.ndarray] = None, *,
                norm_topk_prob: bool = True) -> jnp.ndarray:
    """x: (B, S, d_model) -> (B, S, d_model).

    Gates: with ``norm_topk_prob`` a softmax over the top-k logits
    (Mixtral); without it a softmax over all experts in f32, whose top-k
    probabilities are kept as they are (DeepSeek-V2, ``norm_topk_prob:
    false``, ``routed_scaling_factor`` 1).  Device ops carry the scopes
    ``moe.route`` (router and gates), ``moe.experts`` (routed experts) and
    ``moe.shared`` (shared experts).

    Two dense-dispatch layouts (both exact; gathered EP dispatch lives in
    parallel/ep.py):
      * expert-sharded einsum when n_routed divides the "model" mesh axis
        (DeepSeek's 64 experts / 16): one big (E,B,S,f) einsum, E sharded;
      * scan-over-experts otherwise (Mixtral's 8 experts can't shard over
        16): one expert's (B,S,f) intermediate live at a time — the einsum
        layout would put the FULL (E,B,S,f) tensor on every device.
    """
    with jax.named_scope("moe.route"):
        combine = _route(params, x, top_k, router_noise, norm_topk_prob)
    with jax.named_scope("moe.experts"):
        out = _experts(params, x, combine)
    if "shared" in params:
        from .mlp import mlp_forward
        with jax.named_scope("moe.shared"):
            out = out + mlp_forward(params["shared"], x)
    return out


def _experts(params: dict, x: jnp.ndarray,
             combine: jnp.ndarray) -> jnp.ndarray:
    """Every routed expert on every token, weighted by ``combine``."""
    from .hints import mesh_axis_size
    n_routed = params["router"].shape[1]
    m = mesh_axis_size("model")
    gated = "w_gate" in params
    if m > 1 and n_routed % m == 0:
        # expert-sharded einsum: (E,B,S,f) with E over "model"
        up = jnp.einsum("bsd,edf->ebsf", x, params["w_up"])
        if gated:
            gate = jnp.einsum("bsd,edf->ebsf", x, params["w_gate"])
            h = jax.nn.silu(gate) * up
        else:
            h = jax.nn.gelu(up)
        y = jnp.einsum("ebsf,efd->ebsd", h, params["w_down"])
        out = jnp.einsum("ebsd,bse->bsd", y, combine.astype(y.dtype))
    else:
        # scan-over-experts: one expert's WHOLE-TENSOR intermediates at a
        # time (with the d_ff dim TP-sharded these are ~tokens x d_ff/16 —
        # small), accumulated into a full-tensor carry.  Keeping the expert
        # body a straight-line matmul chain (no inner token-chunk loop)
        # lets XLA defer the per-expert partial reductions to one
        # all-reduce per layer in forward-only programs.
        comb_t = combine.transpose(2, 0, 1).astype(x.dtype)  # (E,B,S)

        def expert_step(y, inp):
            if gated:
                wu, wg, wd, ce = inp
            else:
                wu, wd, ce = inp
            up = x @ wu
            h = jax.nn.silu(x @ wg) * up if gated else jax.nn.gelu(up)
            return y + (h @ wd) * ce[..., None], None

        xs = ((params["w_up"], params["w_gate"], params["w_down"], comb_t)
              if gated else (params["w_up"], params["w_down"], comb_t))
        out, _ = jax.lax.scan(expert_step, jnp.zeros_like(x), xs)
    return out
