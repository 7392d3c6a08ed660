"""Operations and bytes that one engine step of a dense GQA decoder needs
(InternLM2 layout: q/k/v/o projections, SwiGLU, untied head), from the
configuration's shapes and the actual context length of each token.

``ctxs`` lists, for every token the step computes usefully, how many cache
entries it attends to, itself included.  A decode step over ``B`` active
slots has ``B`` such tokens; a prompt-replay step has one.  Padding and
inactive slots are work the step need not do, so they are not counted.

Counted: every weight read once (layers, norms, head) plus the embedding
rows of the tokens; the cache entries each token reads (``ctx - 1``, its own
K/V comes from the projection) and the one it writes; two operations per
multiply-add of every matmul and of attention's QK^T and PV.  Activations,
softmax and norms are left out: they are small beside these at every
context length the cells use.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def shapes(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim", d // H)
    K, f, L, V = (cfg["num_key_value_heads"], cfg["intermediate_size"],
                  cfg["num_hidden_layers"], cfg["vocab_size"])
    layer_mm = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * f
    return dict(d=d, H=H, hd=hd, K=K, L=L, V=V, layer_mm=layer_mm,
                eb=BYTES[cfg["torch_dtype"]])


def step_cost(cfg: dict, ctxs) -> tuple:
    """(flops, bytes) the step needs for tokens with context lengths
    ``ctxs``."""
    s = shapes(cfg)
    n, total_ctx = len(ctxs), sum(ctxs)
    mm_params = s["L"] * s["layer_mm"] + s["d"] * s["V"]
    flops = 2 * mm_params * n + 4 * s["L"] * s["H"] * s["hd"] * total_ctx
    weights = (mm_params + (2 * s["L"] + 1) * s["d"]) * s["eb"]
    embed_rows = n * s["d"] * s["eb"]
    kv_token = s["L"] * 2 * s["K"] * s["hd"] * s["eb"]
    # each token reads ctx - 1 past entries and writes its own one
    return flops, weights + embed_rows + total_ctx * kv_token
