"""Operations and bytes that one engine step of a DeepSeek-V2 decoder needs
(MLA with a latent cache, leading dense SwiGLU layers, then MoE layers of
routed and shared experts, untied head), from the configuration's shapes
and the actual context length of each token.

``ctxs`` lists, for every token the step computes usefully, how many cache
entries it attends to, itself included (as in ``costs/internlm2.py``).

The least work the model needs, so the counts are of the absorbed MLA form
whatever the program computes: q_nope is taken into the latent space
through W_uk (per head 128 -> 512), scored against each cached latent and
rope key (512 + 64 a head), the softmax-weighted latents (512 a head) go
out through W_uv (512 -> 128); no per-head K/V is built from the cache.

Counted: every weight read once (norms, projections, the dense layers'
FFN, the router, the shared experts, the head) plus the embedding rows of
the tokens; of the routed experts, those a step's ``n`` tokens are expected
to hit, ``E (1 - (1 - k/E)^n)`` of them; the latent cache (576 values a
token a layer) read at each token's actual length, ``ctx - 1`` entries, and
the new entry written; two operations per multiply-add of every matmul,
with each token through its top-k and the shared experts.  Activations,
softmax, norms and the gates' arithmetic are left out.

``mla_decode_cost``, ``moe_route_cost``, ``moe_experts_cost`` and
``moe_shared_cost`` split the step by the program's scopes (``mla.decode``,
``moe.route``, ``moe.experts``, ``moe.shared``); ``step_cost`` is their
sum plus the dense layers' FFN, the norms, the embedding rows and the head.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def shapes(cfg: dict) -> dict:
    d, H, r = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    L, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    fe = cfg["moe_intermediate_size"]
    return dict(
        d=d, H=H, r=r, dn=dn, dr=dr, dv=dv, L=L, dense=dense, moe=L - dense,
        V=cfg["vocab_size"], E=cfg["n_routed_experts"],
        k=cfg["num_experts_per_tok"],
        # per layer: W_q, W_kva, W_kvb (W_uk and W_uv), W_o
        attn_mm=d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
        + H * dv * d,
        dense_mm=3 * d * cfg["intermediate_size"],
        expert_mm=3 * d * fe,
        shared_mm=3 * d * fe * cfg["n_shared_experts"],
        eb=BYTES[cfg["torch_dtype"]])


def experts_hit(cfg: dict, n: int) -> float:
    """Expected distinct routed experts that ``n`` tokens hit, each token
    picking k of E at random."""
    s = shapes(cfg)
    return s["E"] * (1.0 - (1.0 - s["k"] / s["E"]) ** n)


def mla_decode_cost(cfg: dict, ctxs) -> tuple:
    """(flops, bytes) of MLA over all layers, absorbed form: projections,
    latent scores and readout at each token's length, the latent cache
    read and the new entries written, and the kv norm weights."""
    s = shapes(cfg)
    n, total_ctx = len(ctxs), sum(ctxs)
    H, r, dn, dr, dv = s["H"], s["r"], s["dn"], s["dr"], s["dv"]
    # per token: projections with W_uk / W_uv applied to one vector a head
    proj = (s["d"] * H * (dn + dr) + s["d"] * (r + dr) + H * dn * r
            + H * r * dv + H * dv * s["d"])
    flops = s["L"] * (2 * proj * n + 2 * H * (r + dr + r) * total_ctx)
    weights = s["L"] * (s["attn_mm"] + r) * s["eb"]
    cache = s["L"] * (r + dr) * s["eb"] * total_ctx
    return flops, weights + cache


def moe_route_cost(cfg: dict, ctxs) -> tuple:
    s = shapes(cfg)
    return (2 * s["moe"] * s["d"] * s["E"] * len(ctxs),
            s["moe"] * s["d"] * s["E"] * s["eb"])


def moe_experts_cost(cfg: dict, ctxs) -> tuple:
    """Each token through its top-k routed experts; the weights of the
    experts the step's tokens are expected to hit, read once."""
    s = shapes(cfg)
    n = len(ctxs)
    return (2 * s["moe"] * s["k"] * s["expert_mm"] * n,
            s["moe"] * experts_hit(cfg, n) * s["expert_mm"] * s["eb"])


def moe_shared_cost(cfg: dict, ctxs) -> tuple:
    s = shapes(cfg)
    return (2 * s["moe"] * s["shared_mm"] * len(ctxs),
            s["moe"] * s["shared_mm"] * s["eb"])


def step_cost(cfg: dict, ctxs) -> tuple:
    """(flops, bytes) the step needs for tokens with context lengths
    ``ctxs``."""
    s = shapes(cfg)
    n = len(ctxs)
    rest_mm = s["dense"] * s["dense_mm"] + s["d"] * s["V"]
    flops = 2 * rest_mm * n
    nbytes = (rest_mm + (2 * s["L"] + 1) * s["d"] + n * s["d"]) * s["eb"]
    for part in (mla_decode_cost, moe_route_cost, moe_experts_cost,
                 moe_shared_cost):
        f, b = part(cfg, ctxs)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes
