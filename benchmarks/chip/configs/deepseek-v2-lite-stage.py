"""deepseek-v2-lite-stage: the program's ModelConfig, the weights the
benchmark serves, and the plain float32 reference of the same model.

The benchmark, not the program, draws the weights from the run's seed.
``make_weights`` builds the program's pytree on the device in one jitted
call (the MoE layers' leaves in one ``lax.map`` over layers, so no layer
is stacked after the fact); ``Reference`` draws the same numbers again, one
layer at a time, and runs a straightforward ``jax.numpy`` forward pass in
float32 at ``Precision.HIGHEST``.  The reference imports nothing of the
program.

Layer equations (DeepSeek-V2, arXiv:2405.04434; HF
``modeling_deepseek.py``), pre-norm RMSNorm (eps ``rms_norm_eps``) around
each sublayer, no biases:

* MLA, no q-LoRA: q = h W_q, split per head into q_nope (128) and q_pe
  (64); [c, k_pe] = h W_kva; c = RMSNorm(c) (``kv_a_layernorm``); per head
  [k_nope, v] = c W_kvb; q_pe and the one shared k_pe are rotated with
  YaRN-scaled RoPE (rotate-half); scores [q_nope, q_pe] . [k_nope, k_pe]
  times 192^-0.5 * mscale(40, mscale_all_dim)^2, causal softmax, o = p v,
  out = o W_o.
* YaRN (HF ``DeepseekV2YarnRotaryEmbedding``): frequency slot i of the 32
  keeps theta^(-i/32) below the correction range, is divided by ``factor``
  above it, and is blended on a linear ramp inside it; the range is
  floor/ceil of 64 ln(L0 / (beta 2 pi)) / (2 ln theta) for beta_fast and
  beta_slow; cos and sin are scaled by mscale(factor, mscale) /
  mscale(factor, mscale_all_dim), mscale(s, m) = 0.1 m ln s + 1.
* FFN: the first ``first_k_dense_replace`` layers are SwiGLU of width
  ``intermediate_size``; the rest are MoE: gate probabilities = softmax of
  h W_gate over all 64 experts in float32, the top 6 kept as they are
  (``norm_topk_prob`` false) times ``routed_scaling_factor``; each routed
  expert is a SwiGLU of width ``moe_intermediate_size`` computed on the
  tokens routed to it only, weighted by its gate; plus the shared experts
  (one SwiGLU of width ``moe_intermediate_size * n_shared_experts``) on
  every token.
* Final RMSNorm, untied output head.

Weights: the embedding has unit variance; every projection is
N(0, 1/fan_in) (the router included); norm weights are 1 + 0.1 N(0, 1).
All are drawn in float32 and stored in ``torch_dtype``, the type they are
served in.

``quant=True`` is the correctness control: the same reference with every
weight and activation matmul input rounded to float8 e4m3 (per-row and
per-column absmax scales), the precision step below the configuration's
bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
HEAD_ROWS = 1024        # rows of hidden state per output-head block
EXPERT_ROWS = 256       # routed (token, expert) pairs per expert block


def sizes(cfg: dict) -> dict:
    rs = cfg["rope_scaling"]
    return dict(d=cfg["hidden_size"], L=cfg["num_hidden_layers"],
                H=cfg["num_attention_heads"], r=cfg["kv_lora_rank"],
                dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
                dv=cfg["v_head_dim"], f=cfg["intermediate_size"],
                fe=cfg["moe_intermediate_size"], E=cfg["n_routed_experts"],
                k=cfg["num_experts_per_tok"],
                fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
                dense=cfg["first_k_dense_replace"], V=cfg["vocab_size"],
                eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
                scale=float(cfg["routed_scaling_factor"]),
                yarn=dict(factor=float(rs["factor"]),
                          L0=int(rs["original_max_position_embeddings"]),
                          beta_fast=float(rs["beta_fast"]),
                          beta_slow=float(rs["beta_slow"]),
                          mscale=float(rs["mscale"]),
                          mscale_all_dim=float(rs["mscale_all_dim"])),
                dtype=jnp.dtype(cfg["torch_dtype"]))


def program_config(cfg: dict):
    """The program's ModelConfig for this configuration, run as it
    states.  The program has no routed-expert scale: it refuses any but
    the published 1."""
    from repro.models.config import LayerSpec, ModelConfig, RopeScaling
    s, rs = sizes(cfg), cfg["rope_scaling"]
    if s["scale"] != 1.0:
        raise ValueError(f"routed_scaling_factor {s['scale']}: the program "
                         "serves only 1")
    return ModelConfig(
        name="deepseek-v2-lite-stage", d_model=s["d"], vocab_size=s["V"],
        block_pattern=(LayerSpec("attn"),), block_repeat=s["L"],
        n_heads=s["H"], n_kv_heads=s["H"], attn_kind="mla",
        kv_lora_rank=s["r"], qk_nope_head_dim=s["dn"],
        qk_rope_head_dim=s["dr"], v_head_dim=s["dv"], rope_theta=s["theta"],
        rope_scaling=RopeScaling(
            factor=rs["factor"],
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        ffn_kind="moe", n_routed=s["E"], top_k=s["k"],
        n_shared=cfg["n_shared_experts"], d_ff_expert=s["fe"],
        norm_topk_prob=cfg["norm_topk_prob"], first_k_dense=s["dense"],
        d_ff_dense_first=s["f"], d_ff=s["fe"], dtype=cfg["torch_dtype"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def seed_words(seed: int) -> np.ndarray:
    """Any whole number (negative or past 64 bits included) as the two
    uint32 words of a threefry key."""
    seed %= 2 ** 64
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _root(words):
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def _layer_leaves(s: dict, moe: bool):
    d, H, r, dr = s["d"], s["H"], s["r"], s["dr"]
    attn = (("input_layernorm", (d,), None),
            ("q_proj", (d, H * (s["dn"] + dr)), d),
            ("kv_a_proj_with_mqa", (d, r + dr), d),
            ("kv_a_layernorm", (r,), None),
            ("kv_b_proj", (r, H * (s["dn"] + s["dv"])), r),
            ("o_proj", (H * s["dv"], d), H * s["dv"]),
            ("post_attention_layernorm", (d,), None))
    if not moe:
        f = s["f"]
        return attn + (("gate_proj", (d, f), d), ("up_proj", (d, f), d),
                       ("down_proj", (f, d), f))
    E, fe, fs = s["E"], s["fe"], s["fs"]
    return attn + (("gate", (d, E), d),
                   ("experts.gate_proj", (E, d, fe), d),
                   ("experts.up_proj", (E, d, fe), d),
                   ("experts.down_proj", (E, fe, d), fe),
                   ("shared.gate_proj", (d, fs), d),
                   ("shared.up_proj", (d, fs), d),
                   ("shared.down_proj", (fs, d), fs))


def _leaf(key, shape, fan_in, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    w = 1.0 + 0.1 * z if fan_in is None else z * (1.0 / math.sqrt(fan_in))
    return w.astype(dtype)


def _layer_weights(words, layer, s: dict, moe: bool) -> dict:
    key = jax.random.fold_in(jax.random.fold_in(_root(words), 1), layer)
    return {name: _leaf(jax.random.fold_in(key, i), shape, fan, s["dtype"])
            for i, (name, shape, fan) in enumerate(_layer_leaves(s, moe))}


def _global_weights(words, s: dict) -> dict:
    root = _root(words)
    embed = jax.random.normal(jax.random.fold_in(root, 2), (s["V"], s["d"]),
                              jnp.float32).astype(s["dtype"])
    return {"embed_tokens": embed,
            "norm": _leaf(jax.random.fold_in(root, 3), (s["d"],), None,
                          s["dtype"]),
            "lm_head": _leaf(jax.random.fold_in(root, 4), (s["d"], s["V"]),
                             s["d"], s["dtype"])}


def _program_layer(w: dict, moe: bool) -> dict:
    attn = {"wq": w["q_proj"], "wdkv": w["kv_a_proj_with_mqa"],
            "kv_norm": w["kv_a_layernorm"], "wukv": w["kv_b_proj"],
            "wo": w["o_proj"]}
    if moe:
        ffn = {"router": w["gate"], "w_gate": w["experts.gate_proj"],
               "w_up": w["experts.up_proj"],
               "w_down": w["experts.down_proj"],
               "shared": {"w_gate": w["shared.gate_proj"],
                          "w_up": w["shared.up_proj"],
                          "w_down": w["shared.down_proj"]}}
    else:
        ffn = {"w_gate": w["gate_proj"], "w_up": w["up_proj"],
               "w_down": w["down_proj"]}
    return {"l0": {"norm1": w["input_layernorm"], "attn": attn,
                   "norm2": w["post_attention_layernorm"], "ffn": ffn}}


def make_weights(seed: int, cfg: dict) -> dict:
    """The program's parameter pytree, in ``torch_dtype``, made on the
    device in one jitted call from the seed.  The program's ModelConfig is
    built first, so a program that cannot run this configuration fails
    before any weight is drawn."""
    program_config(cfg)
    s = sizes(cfg)

    @jax.jit
    def make(words):
        moe = jax.lax.map(lambda l: _layer_weights(words, l, s, True),
                          jnp.arange(s["dense"], s["L"]))
        g = _global_weights(words, s)
        return {"embed": g["embed_tokens"], "final_norm": g["norm"],
                "head": g["lm_head"],
                "prefix": [_program_layer(_layer_weights(words, l, s, False),
                                          False)
                           for l in range(s["dense"])],
                "blocks": _program_layer(moe, True)}

    return make(seed_words(seed))


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32), scale


def _mm(x, w, quant: bool):
    if not quant:
        return jnp.matmul(x, w, precision=HIGHEST)
    xq, sx = _fp8(x, -1)
    wq, sw = _fp8(w, 0)
    return jnp.matmul(xq, wq, precision=HIGHEST) * sx * sw


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu(x, gate, up, down, quant: bool):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn_inv_freq(dim: int, theta: float, y: dict):
    half = dim // 2
    extra = theta ** (-np.arange(half, dtype=np.float64) / half)

    def slot(beta):
        return dim * math.log(y["L0"] / (beta * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(slot(y["beta_fast"])), 0)
    high = min(math.ceil(slot(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    inv = extra / y["factor"] * ramp + extra * (1 - ramp)
    return jnp.asarray(inv, jnp.float32)


def _rope(x, s: dict):
    """x: (n, S, heads, dr), positions 0..S-1, rotate-half pairing, YaRN
    frequencies and cos/sin scale."""
    S, dr = x.shape[1], x.shape[-1]
    half = dr // 2
    y = s["yarn"]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * _yarn_inv_freq(dr, s["theta"], y)                 # (S, half)
    m = _mscale(y["factor"], y["mscale"]) \
        / _mscale(y["factor"], y["mscale_all_dim"])
    cos = (jnp.cos(ang) * m)[:, None, :]
    sin = (jnp.sin(ang) * m)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, w, s: dict, quant: bool):
    """x + MLA(RMSNorm(x)) over the whole sequence, causal."""
    n, S, _ = x.shape
    H, r, dn, dr, dv = s["H"], s["r"], s["dn"], s["dr"], s["dv"]
    h = _rms(x, w["input_layernorm"], s["eps"])
    q = _mm(h, w["q_proj"], quant).reshape(n, S, H, dn + dr)
    kv_a = _mm(h, w["kv_a_proj_with_mqa"], quant)
    c = _rms(kv_a[..., :r], w["kv_a_layernorm"], s["eps"])
    kv = _mm(c, w["kv_b_proj"], quant).reshape(n, S, H, dn + dv)
    q_pe = _rope(q[..., dn:], s)
    k_pe = _rope(kv_a[..., None, r:], s)                    # (n, S, 1, dr)
    scores = (jnp.einsum("nqhd,nkhd->nhqk", q[..., :dn], kv[..., :dn],
                         precision=HIGHEST)
              + jnp.einsum("nqhd,nkd->nhqk", q_pe, k_pe[:, :, 0],
                           precision=HIGHEST))
    y = s["yarn"]
    scores = scores * (dn + dr) ** -0.5 \
        * _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", p, kv[..., dn:], precision=HIGHEST)
    return x + _mm(o.reshape(n, S, H * dv), w["o_proj"], quant)


def _dense_block(x, w, s: dict, quant: bool):
    x = _attention(x, w, s, quant)
    h = _rms(x, w["post_attention_layernorm"], s["eps"])
    return x + _swiglu(h, w["gate_proj"], w["up_proj"], w["down_proj"],
                       quant)


def _routed(h, idx, gates, w, s: dict, quant: bool):
    """Each routed expert on the tokens routed to it.  h: (N, d); idx,
    gates: (N, k).  The (token, expert) pairs are sorted by expert and each
    expert's run is padded to whole blocks of EXPERT_ROWS, so every block
    belongs to one expert; a block computes its expert's SwiGLU on its
    tokens and adds them, gate-weighted, into the output."""
    N, k = idx.shape
    E, b = s["E"], EXPERT_ROWS
    expert = idx.reshape(-1)
    order = jnp.argsort(expert, stable=True)
    expert, token = expert[order], (jnp.arange(N * k) // k)[order]
    gate = gates.reshape(-1)[order]
    count = jnp.bincount(expert, length=E)
    padded = -(-count // b) * b
    first = jnp.cumsum(count) - count                      # in the sorted run
    pfirst = jnp.cumsum(padded) - padded                   # in the blocks
    dest = pfirst[expert] + jnp.arange(N * k) - first[expert]
    n_blocks = -(-(N * k + E * (b - 1)) // b)
    tok = jnp.zeros(n_blocks * b, jnp.int32).at[dest].set(token)
    gat = jnp.zeros(n_blocks * b, jnp.float32).at[dest].set(gate)
    owner = jnp.minimum(jnp.searchsorted(jnp.cumsum(padded),
                                         jnp.arange(n_blocks) * b,
                                         side="right"), E - 1)

    def block(out, inp):
        e, t, g = inp
        y = _swiglu(h[t], w["experts.gate_proj"][e], w["experts.up_proj"][e],
                    w["experts.down_proj"][e], quant)
        return out.at[t].add(y * g[:, None]), None

    out, _ = jax.lax.scan(block, jnp.zeros_like(h),
                          (owner, tok.reshape(n_blocks, b),
                           gat.reshape(n_blocks, b)))
    return out


def _moe_block(x, w, s: dict, quant: bool):
    x = _attention(x, w, s, quant)
    n, S, d = x.shape
    h = _rms(x, w["post_attention_layernorm"], s["eps"]).reshape(n * S, d)
    probs = jax.nn.softmax(_mm(h, w["gate"], quant), axis=-1)
    gates, idx = jax.lax.top_k(probs, s["k"])
    routed = _routed(h, idx, gates * s["scale"], w, s, quant)
    shared = _swiglu(h, w["shared.gate_proj"], w["shared.up_proj"],
                     w["shared.down_proj"], quant)
    return x + (routed + shared).reshape(n, S, d)


def _upcast(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


class Reference:
    """float32 forward of this configuration on the weights of ``seed``,
    one layer's weights on the device at a time."""

    def __init__(self, cfg: dict, seed: int):
        s = self.s = sizes(cfg)
        self.words = seed_words(seed)
        self._layer_w = {
            moe: jax.jit(lambda w, l, moe=moe: _upcast(
                _layer_weights(w, l, s, moe)))
            for moe in (False, True)}
        self._global_w = jax.jit(lambda w: _upcast(_global_weights(w, s)))
        self._blocks = {(moe, q): jax.jit(functools.partial(
            _moe_block if moe else _dense_block, s=s, quant=q))
            for moe in (False, True) for q in (False, True)}
        self._final = jax.jit(lambda x, w: _rms(x, w, s["eps"]))
        self._head = {False: jax.jit(self._head_stats),
                      True: jax.jit(self._head_stats_ctl)}

    def hidden(self, tokens: np.ndarray, quant: bool = False):
        """Final-norm hidden states (n, S, d) float32 of token rows (n, S);
        causal, so right padding never reaches an earlier position."""
        g = self._global_w(self.words)
        x = g["embed_tokens"][jnp.asarray(tokens)]
        del g["embed_tokens"]
        for layer in range(self.s["L"]):
            moe = layer >= self.s["dense"]
            x = self._blocks[moe, quant](
                x, self._layer_w[moe](self.words, layer))
        return self._final(x, g["norm"])

    def logits(self, tokens: np.ndarray, quant: bool = False):
        """Logits (n, S, vocab) float32 of token rows (n, S)."""
        head = self._global_w(self.words)["lm_head"]
        return jnp.matmul(self.hidden(tokens, quant), head,
                          precision=HIGHEST)

    @staticmethod
    def _head_stats(h_ref, h_ctl, head, targets):
        del h_ctl
        ref = jnp.matmul(h_ref, head, precision=HIGHEST)
        at_target = jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
        return ref.max(-1), at_target

    @staticmethod
    def _head_stats_ctl(h_ref, h_ctl, head, targets):
        ref = jnp.matmul(h_ref, head, precision=HIGHEST)
        at_target = jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
        pick = jnp.argmax(_mm(h_ctl, head, True), -1)
        at_pick = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        return ref.max(-1), at_target, at_pick

    def logit_stats(self, h_ref, targets, h_ctl=None):
        """Per row of ``h_ref`` (R, d): the reference's best logit and its
        logit at ``targets`` (R,); where ``h_ctl`` (the control's hidden
        states) is given, also the reference's logit at the token the
        control puts first.  Rows go through the head in blocks of
        HEAD_ROWS."""
        head = self._global_w(self.words)["lm_head"]
        R = h_ref.shape[0]
        pad = -R % HEAD_ROWS
        control = h_ctl is not None
        h_ref = jnp.pad(h_ref, ((0, pad), (0, 0)))
        h_ctl = jnp.pad(h_ctl, ((0, pad), (0, 0))) if control else h_ref
        targets = jnp.pad(jnp.asarray(targets, jnp.int32), (0, pad))
        out = [self._head[control](h_ref[i:i + HEAD_ROWS],
                                   h_ctl[i:i + HEAD_ROWS], head,
                                   targets[i:i + HEAD_ROWS])
               for i in range(0, R + pad, HEAD_ROWS)]
        return [np.concatenate([np.asarray(o[j]) for o in out])[:R]
                for j in range(len(out[0]))]
