"""Operations and bytes that one engine step of a Granite-4.0-H hybrid
decoder needs (Mamba-2 and NoPE GQA layers by ``layer_types``, each
followed by a SwiGLU MLP; tied head), from the configuration's shapes and
the actual context length of each token.  Routed experts
(``num_local_experts``) are not counted: the configurations served have
none.

``ctxs`` lists, for every token the step computes usefully, how many cache
entries it attends to, itself included (as in ``costs/internlm2.py``): a
decode step over ``B`` active slots has ``B`` such tokens, a prompt-replay
step one.

Counted: every weight read once (projections, convs, the per-head vectors,
norms, the MLPs, the head) plus the embedding rows of the tokens; for each
token, in each Mamba layer, its slot's float32 SSM state (heads x head dim
x state) read and written and its conv windows (``mamba_d_conv - 1``
positions of every conv channel) read and written; in each attention
layer, the cache entries it reads (``ctx - 1``) and the one it writes; two
operations per multiply-add of every matmul, of attention's QK^T and PV,
and of the state update (one multiply-add per state element, the input's
outer product added to the decayed state) and the readout.  Activations,
the conv, softmax and norms are left out.

``ssm_decode_cost`` is the part under the program's ``ssm.decode`` scope
(the mixers, their state and windows); ``step_cost`` adds the attention
layers, the MLPs, the norms, the embedding rows and the head.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
STATE_BYTES = 4            # the program keeps the SSM state in float32


def shapes(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // H
    Kv = cfg["num_key_value_heads"]
    di = cfg["mamba_expand"] * d
    G, N, Hm = cfg["mamba_n_groups"], cfg["mamba_d_state"], \
        cfg["mamba_n_heads"]
    conv = di + 2 * G * N
    kinds = cfg["layer_types"]
    return dict(
        d=d, H=H, hd=hd, Kv=Kv, V=cfg["vocab_size"], L=len(kinds),
        Lm=sum(k == "mamba" for k in kinds),
        La=sum(k == "attention" for k in kinds),
        # per Mamba layer: in_proj (z, x, B, C, dt) and out_proj
        mamba_mm=d * (di + conv + Hm) + di * d,
        # conv weight and bias, A_log, dt_bias, D, the gated norm
        mamba_vec=(cfg["mamba_d_conv"] + 1) * conv + 3 * Hm + di,
        state=Hm * cfg["mamba_d_head"] * N,
        window=(cfg["mamba_d_conv"] - 1) * conv,
        attn_mm=2 * d * H * hd + 2 * d * Kv * hd,
        mlp_mm=3 * d * cfg["shared_intermediate_size"],
        eb=BYTES[cfg["torch_dtype"]])


def ssm_decode_cost(cfg: dict, ctxs) -> tuple:
    """(flops, bytes) of the Mamba mixers over all their layers: the
    projections, the state update and readout, the weights read once, and
    each token's state and conv windows read and written."""
    s = shapes(cfg)
    n = len(ctxs)
    flops = s["Lm"] * n * (2 * s["mamba_mm"] + 4 * s["state"])
    weights = s["Lm"] * (s["mamba_mm"] + s["mamba_vec"]) * s["eb"]
    state = s["Lm"] * n * 2 * (s["state"] * STATE_BYTES
                               + s["window"] * s["eb"])
    return flops, weights + state


def step_cost(cfg: dict, ctxs) -> tuple:
    """(flops, bytes) the step needs for tokens with context lengths
    ``ctxs``."""
    s = shapes(cfg)
    n, total_ctx = len(ctxs), sum(ctxs)
    rest_mm = s["La"] * s["attn_mm"] + s["L"] * s["mlp_mm"] + s["d"] * s["V"]
    flops = 2 * rest_mm * n + 4 * s["La"] * s["H"] * s["hd"] * total_ctx
    kv_token = s["La"] * 2 * s["Kv"] * s["hd"] * s["eb"]
    nbytes = ((rest_mm + (2 * s["L"] + 1) * s["d"] + n * s["d"]) * s["eb"]
              + total_ctx * kv_token)
    f, b = ssm_decode_cost(cfg, ctxs)
    return flops + f, nbytes + b
