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

The routed-expert leaves (``w_gate``, ``w_up``, ``w_down``) are one
layer's (E, ...) stacks, or, with a ``layer`` index, the decode step's
layer-stacked (R, E, ...) leaves, read where they lie: one expert's block
at a time, never a layer's stack copied out first.
"""

from __future__ import annotations

import functools
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
                norm_topk_prob: bool = True, layer=None) -> jnp.ndarray:
    """x: (B, S, d_model) -> (B, S, d_model).

    Gates: with ``norm_topk_prob`` a softmax over the top-k logits
    (Mixtral); without it a softmax over all experts in f32, whose top-k
    probabilities are kept as they are (DeepSeek-V2, ``norm_topk_prob:
    false``, ``routed_scaling_factor`` 1).  Device ops carry the scopes
    ``moe.route`` (router and gates), ``moe.experts`` (routed experts) and
    ``moe.shared`` (shared experts).

    ``params`` holds one layer's router and shared experts.  Its routed
    expert leaves are that layer's (E, ...) stacks, or with ``layer`` the
    layer-stacked (R, E, ...) leaves, of which layer ``layer`` is read
    where it lies (``transformer.decode_step``).

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
        out = _experts(params, x, combine, layer)
    if "shared" in params:
        from .mlp import mlp_forward
        with jax.named_scope("moe.shared"):
            out = out + mlp_forward(params["shared"], x)
    return out


def _expert_weights(w: jnp.ndarray, layer, e=None) -> jnp.ndarray:
    """Expert ``e``'s block of a routed-expert leaf ``w``, or with ``e``
    None every expert's: from the layer's (E, ...) stack, or with ``layer``
    from layer ``layer`` of the (R, E, ...) stack, indexed where it lies.
    The one slice reads the block straight from the stacked leaf, so it
    fuses into the matmul that consumes it (slicing the layer first, then
    the expert, would copy the layer's stack out)."""
    if e is None:
        return w if layer is None else jax.lax.dynamic_index_in_dim(
            w, layer, keepdims=False)
    lead = () if layer is None else (layer,)
    d, f = w.shape[-2:]
    block = jax.lax.dynamic_slice(w, lead + (e, 0, 0),
                                  (1,) * (len(lead) + 1) + (d, f))
    return block.reshape(d, f)


def _experts(params: dict, x: jnp.ndarray, combine: jnp.ndarray,
             layer=None) -> jnp.ndarray:
    """Every routed expert on every token, weighted by ``combine``.  The
    expert leaves are one layer's stacks, or with ``layer`` the
    layer-stacked leaves; each expert's blocks are read where they lie
    (``_expert_weights``)."""
    from .hints import mesh_axis_size
    n_routed = params["router"].shape[1]
    m = mesh_axis_size("model")
    gated = "w_gate" in params
    w = functools.partial(_expert_weights, layer=layer)
    if m > 1 and n_routed % m == 0:
        # expert-sharded einsum: (E,B,S,f) with E over "model"
        up = jnp.einsum("bsd,edf->ebsf", x, w(params["w_up"]))
        if gated:
            gate = jnp.einsum("bsd,edf->ebsf", x, w(params["w_gate"]))
            h = jax.nn.silu(gate) * up
        else:
            h = jax.nn.gelu(up)
        y = jnp.einsum("ebsf,efd->ebsd", h, w(params["w_down"]))
        out = jnp.einsum("ebsd,bse->bsd", y, combine.astype(y.dtype))
    else:
        # scan-over-experts: one expert's WHOLE-TENSOR intermediates at a
        # time (with the d_ff dim TP-sharded these are ~tokens x d_ff/16 —
        # small), accumulated into a full-tensor carry.  Keeping the expert
        # body a straight-line matmul chain (no inner token-chunk loop)
        # lets XLA defer the per-expert partial reductions to one
        # all-reduce per layer in forward-only programs.  The scan runs
        # over expert ids, not over the weights: a weight slice that is a
        # loop's operand would be copied out whole before the loop.
        comb_t = combine.transpose(2, 0, 1).astype(x.dtype)  # (E,B,S)

        def expert_step(y, inp):
            e, ce = inp
            up = x @ w(params["w_up"], e=e)
            h = (jax.nn.silu(x @ w(params["w_gate"], e=e)) * up if gated
                 else jax.nn.gelu(up))
            return y + (h @ w(params["w_down"], e=e)) * ce[..., None], None

        out, _ = jax.lax.scan(expert_step, jnp.zeros_like(x),
                              (jnp.arange(n_routed), comb_t))
    return out
