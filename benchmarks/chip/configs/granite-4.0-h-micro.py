"""granite-4.0-h-micro: the program's ModelConfig, the weights the benchmark
serves, and the plain float32 reference of the same model.

The benchmark, not the program, draws the weights from the run's seed.
``make_weights`` builds the program's pytree on the device in one jitted
call (each position of the layer period in one ``lax.map`` over the
period's repeats, so no layer is stacked after the fact); ``Reference``
draws the same numbers again, one layer at a time, and runs a
straightforward ``jax.numpy`` forward pass in float32 at
``Precision.HIGHEST``.  The reference imports nothing of the program.

Layer equations (HF ``modeling_granitemoehybrid.py``; Mamba-2,
arXiv:2405.21060).  Every RMSNorm has eps ``rms_norm_eps``; there are no
biases but the conv's:

* Embedding: x = E[token] * ``embedding_multiplier``.
* Each of the 40 layers, by ``layer_types``:
  x = x + m * mixer(RMSNorm(x)), then x = x + m * MLP(RMSNorm(x)), with
  m = ``residual_multiplier``; the MLP is SwiGLU of width
  ``shared_intermediate_size``: [g, u] = h W_in, out = (silu(g) * u) W_out.
* Mamba-2 mixer (one group): [z, xBC, dt] = h W_in_proj, widths d_inner =
  ``mamba_expand`` * hidden, d_inner + 2 N, H; xBC = silu(causal depthwise
  conv of width ``mamba_d_conv`` over positions, with bias); [x, B, C] =
  xBC; dt = softplus(dt + dt_bias); A = -exp(A_log); per head h and
  position t, with x_t split into H heads of P = ``mamba_d_head``:
  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T (P x N, S_0 = 0),
  y_t = S_t C_t + D x_t; then the gated RMSNorm over all d_inner channels,
  y = RMSNorm(y * silu(z)) * w, and out = y W_out_proj.  The recurrence is
  a plain scan over positions.
* Attention (``position_embedding_type`` "nope": no positional encoding):
  q, k, v = h W_q, h W_k, h W_v; query head i reads KV head
  i // (heads / kv_heads); scores q.k times ``attention_multiplier``,
  causal softmax, o = p v, out = o W_o.
* Final RMSNorm; logits = x E^T / ``logits_scaling`` (tied head).

Weights: the embedding N(0, s^2) with s = 1 / (10 x
``embedding_multiplier``) (scaled, the embedding has std 0.1, so the tied
head does not merely repeat the input token); projections N(0, 1/fan_in);
the depthwise conv N(0, 1/d_conv); norms 1 + 0.1 N(0, 1); Mamba-2's
defaults for A_log, dt_bias and D.  All are drawn in float32 and stored in
``torch_dtype``, the type they are served in (A_log, dt_bias and D are
served as float32 of those values).

``quant=True`` is the correctness control: the same reference with every
weight and activation matmul input rounded to float8 e4m3 (per-row and
per-column absmax scales), the precision step below the configuration's
bfloat16.  The recurrence and the attention scores stay float32.
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
# Mamba-2's initialisation defaults (the config gives none)
A_RANGE = (1.0, 16.0)
TIME_STEP = (0.001, 0.1)
TIME_STEP_FLOOR = 1e-4
EMBED_STD = 0.1         # of the scaled embedding


def sizes(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    di = cfg["mamba_expand"] * d
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return dict(d=d, L=cfg["num_hidden_layers"], V=cfg["vocab_size"], H=H,
                Kv=cfg["num_key_value_heads"], hd=d // H,
                f=cfg["shared_intermediate_size"], di=di,
                Hm=cfg["mamba_n_heads"], P=cfg["mamba_d_head"], G=G, N=N,
                K=cfg["mamba_d_conv"], conv=di + 2 * G * N,
                kinds=tuple(cfg["layer_types"]),
                eps=float(cfg["rms_norm_eps"]),
                emb_mult=float(cfg["embedding_multiplier"]),
                res_mult=float(cfg["residual_multiplier"]),
                logit_div=float(cfg["logits_scaling"]),
                attn_scale=float(cfg["attention_multiplier"]),
                dtype=jnp.dtype(cfg["torch_dtype"]))


def period(kinds) -> int:
    """The shortest run of layer types that ``kinds`` repeats whole."""
    L = len(kinds)
    return next(p for p in range(1, L + 1)
                if L % p == 0 and tuple(kinds) == tuple(kinds[:p]) * (L // p))


def program_config(cfg: dict):
    """The program's ModelConfig for this configuration, run as it states.
    The program serves one SSM group, no routed experts, no positional
    encoding here, and only the biases the config has."""
    from repro.models.config import LayerSpec, ModelConfig
    s = sizes(cfg)
    wanted = {"position_embedding_type": "nope", "num_local_experts": 0,
              "mamba_n_groups": 1, "attention_bias": False,
              "mamba_proj_bias": False, "mamba_conv_bias": True,
              "hidden_act": "silu", "tie_word_embeddings": True}
    for key, value in wanted.items():
        if cfg[key] != value:
            raise ValueError(f"{key} {cfg[key]!r}: the program serves only "
                             f"{value!r} here")
    if s["Hm"] * s["P"] != s["di"]:
        raise ValueError("mamba_n_heads * mamba_d_head != d_inner")
    p = period(s["kinds"])
    kind = {"mamba": "ssm", "attention": "attn"}
    return ModelConfig(
        name="granite-4.0-h-micro", d_model=s["d"], vocab_size=s["V"],
        block_pattern=tuple(LayerSpec(kind[k]) for k in s["kinds"][:p]),
        block_repeat=s["L"] // p, n_heads=s["H"], n_kv_heads=s["Kv"],
        head_dim=s["hd"], rope="none", d_ff=s["f"], d_inner=s["di"],
        d_state=s["N"], n_ssd_heads=s["Hm"], d_conv=s["K"],
        n_ssm_groups=s["G"], tie_embeddings=True,
        embedding_multiplier=s["emb_mult"],
        residual_multiplier=s["res_mult"], logits_scaling=s["logit_div"],
        attn_scale=s["attn_scale"], dtype=cfg["torch_dtype"])


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


def _layer_leaves(s: dict, kind: str):
    """(name, shape, init) of one layer's weights, HF's names with each
    matrix as (in, out); init is a fan-in or the name of a rule."""
    d, f = s["d"], s["f"]
    mlp = (("post_attention_layernorm", (d,), "norm"),
           ("shared_mlp.input_linear", (d, 2 * f), d),
           ("shared_mlp.output_linear", (f, d), f))
    if kind == "attention":
        q, kv = s["H"] * s["hd"], s["Kv"] * s["hd"]
        return (("input_layernorm", (d,), "norm"),
                ("self_attn.q_proj", (d, q), d),
                ("self_attn.k_proj", (d, kv), d),
                ("self_attn.v_proj", (d, kv), d),
                ("self_attn.o_proj", (q, d), q)) + mlp
    di, Hm, C, K = s["di"], s["Hm"], s["conv"], s["K"]
    return (("input_layernorm", (d,), "norm"),
            ("mamba.in_proj", (d, di + C + Hm), d),
            ("mamba.conv1d.weight", (C, K), K),
            ("mamba.conv1d.bias", (C,), K),
            ("mamba.dt_bias", (Hm,), "dt_bias"),
            ("mamba.A_log", (Hm,), "A_log"),
            ("mamba.D", (Hm,), "D"),
            ("mamba.norm.weight", (di,), "norm"),
            ("mamba.out_proj", (di, d), di)) + mlp


def _leaf(key, shape, init, dtype):
    if init == "norm":
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif init == "A_log":
        w = jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))
    elif init == "dt_bias":
        lo, hi = math.log(TIME_STEP[0]), math.log(TIME_STEP[1])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        dt = jnp.maximum(dt, TIME_STEP_FLOOR)
        w = dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)
    elif init == "D":
        w = jnp.ones(shape, jnp.float32)
    else:
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(init)
    return w.astype(dtype)


def _layer_weights(words, layer, s: dict, kind: str) -> dict:
    key = jax.random.fold_in(jax.random.fold_in(_root(words), 1), layer)
    return {name: _leaf(jax.random.fold_in(key, i), shape, init, s["dtype"])
            for i, (name, shape, init) in enumerate(_layer_leaves(s, kind))}


def _global_weights(words, s: dict) -> dict:
    root = _root(words)
    std = EMBED_STD / s["emb_mult"]
    embed = jax.random.normal(jax.random.fold_in(root, 2), (s["V"], s["d"]),
                              jnp.float32) * std
    return {"embed_tokens": embed.astype(s["dtype"]),
            "norm": _leaf(jax.random.fold_in(root, 3), (s["d"],), "norm",
                          s["dtype"])}


def _program_layer(w: dict, s: dict, kind: str) -> dict:
    f = s["f"]
    gate_up = w["shared_mlp.input_linear"]
    layer = {"norm1": w["input_layernorm"],
             "norm2": w["post_attention_layernorm"],
             "ffn": {"w_gate": gate_up[:, :f], "w_up": gate_up[:, f:],
                     "w_down": w["shared_mlp.output_linear"]}}
    if kind == "attention":
        layer["attn"] = {"wq": w["self_attn.q_proj"],
                         "wk": w["self_attn.k_proj"],
                         "wv": w["self_attn.v_proj"],
                         "wo": w["self_attn.o_proj"]}
        return layer
    di = s["di"]
    in_proj = w["mamba.in_proj"]
    conv, bias = w["mamba.conv1d.weight"], w["mamba.conv1d.bias"]
    f32 = lambda a: a.astype(jnp.float32)
    layer["mixer"] = {
        "w_z": in_proj[:, :di], "w_x": in_proj[:, di:2 * di],
        "w_bcdt": in_proj[:, 2 * di:],                  # [B, C, dt]
        "conv_x": conv[:di].T, "conv_x_b": bias[:di],
        "conv_bc": conv[di:].T, "conv_bc_b": bias[di:],
        "a_log": f32(w["mamba.A_log"]), "dt_bias": f32(w["mamba.dt_bias"]),
        "d_skip": f32(w["mamba.D"]), "norm_w": w["mamba.norm.weight"],
        "w_out": w["mamba.out_proj"]}
    return layer


def make_weights(seed: int, cfg: dict) -> dict:
    """The program's parameter pytree, in ``torch_dtype``, made on the
    device in one jitted call from the seed.  The program's ModelConfig is
    built first, so a program that cannot run this configuration fails
    before any weight is drawn."""
    program_config(cfg)
    s = sizes(cfg)
    p = period(s["kinds"])
    repeats = jnp.arange(s["L"] // p)

    @jax.jit
    def make(words):
        g = _global_weights(words, s)
        blocks = {}
        for i, kind in enumerate(s["kinds"][:p]):
            blocks[f"l{i}"] = jax.lax.map(
                lambda r, i=i, kind=kind: _program_layer(
                    _layer_weights(words, r * p + i, s, kind), s, kind),
                repeats)
        return {"embed": g["embed_tokens"], "final_norm": g["norm"],
                "blocks": blocks}

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


def _mlp(x, w, s: dict, quant: bool):
    """x + m * SwiGLU(RMSNorm(x))."""
    f = s["f"]
    h = _rms(x, w["post_attention_layernorm"], s["eps"])
    gu = _mm(h, w["shared_mlp.input_linear"], quant)
    out = _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:],
              w["shared_mlp.output_linear"], quant)
    return x + s["res_mult"] * out


def _causal_conv(u, w, b):
    """Depthwise conv over positions, causal, with bias: u (n, S, C), w
    (C, K); position t sees u[t - K + 1 .. t], zeros before the start."""
    S, K = u.shape[1], w.shape[1]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(up[:, k:k + S, :] * w[:, k] for k in range(K))


def _recurrence(x, dt, A, B, C, D):
    """The Mamba-2 recurrence, one position at a time.  x (n, S, H, P);
    dt (n, S, H); A, D (H,); B, C (n, S, N).  Returns y (n, S, H, P)."""
    n, S, H, P = x.shape

    def step(state, t):
        x_t, dt_t, B_t, C_t = t
        decay = jnp.exp(dt_t * A)                              # (n, H)
        state = (state * decay[:, :, None, None]
                 + (dt_t[:, :, None] * x_t)[..., None]
                 * B_t[:, None, None, :])                      # (n, H, P, N)
        y = jnp.einsum("nhpk,nk->nhp", state, C_t, precision=HIGHEST)
        return state, y + D[:, None] * x_t

    seq = lambda a: jnp.moveaxis(a, 1, 0)
    state0 = jnp.zeros((n, H, P, B.shape[-1]), jnp.float32)
    _, ys = jax.lax.scan(step, state0, (seq(x), seq(dt), seq(B), seq(C)))
    return jnp.moveaxis(ys, 0, 1)


def _mamba_block(x, w, s: dict, quant: bool):
    """x + m * Mamba2(RMSNorm(x)), then the MLP; one SSM group."""
    n, S, _ = x.shape
    di, N, Hm, C = s["di"], s["N"], s["Hm"], s["conv"]
    h = _rms(x, w["input_layernorm"], s["eps"])
    zxbcdt = _mm(h, w["mamba.in_proj"], quant)
    z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:di + C], \
        zxbcdt[..., di + C:]
    xbc = jax.nn.silu(_causal_conv(xbc, w["mamba.conv1d.weight"],
                                   w["mamba.conv1d.bias"]))
    xs, B, Cm = xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + w["mamba.dt_bias"])
    y = _recurrence(xs.reshape(n, S, Hm, s["P"]), dt,
                    -jnp.exp(w["mamba.A_log"]), B, Cm, w["mamba.D"])
    y = _rms(y.reshape(n, S, di) * jax.nn.silu(z), w["mamba.norm.weight"],
             s["eps"])
    x = x + s["res_mult"] * _mm(y, w["mamba.out_proj"], quant)
    return _mlp(x, w, s, quant)


def _attention_block(x, w, s: dict, quant: bool):
    """x + m * NoPE causal GQA(RMSNorm(x)), then the MLP."""
    n, S, _ = x.shape
    H, Kv, hd = s["H"], s["Kv"], s["hd"]
    h = _rms(x, w["input_layernorm"], s["eps"])
    q = _mm(h, w["self_attn.q_proj"], quant).reshape(n, S, Kv, H // Kv, hd)
    k = _mm(h, w["self_attn.k_proj"], quant).reshape(n, S, Kv, hd)
    v = _mm(h, w["self_attn.v_proj"], quant).reshape(n, S, Kv, hd)
    scores = jnp.einsum("nqkgd,nskd->nkgqs", q, k,
                        precision=HIGHEST) * s["attn_scale"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("nkgqs,nskd->nqkgd", p, v, precision=HIGHEST)
    x = x + s["res_mult"] * _mm(o.reshape(n, S, H * hd),
                                w["self_attn.o_proj"], quant)
    return _mlp(x, w, s, quant)


def _upcast(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


class Reference:
    """float32 forward of this configuration on the weights of ``seed``,
    one layer's weights on the device at a time."""

    KINDS = ("mamba", "attention")

    def __init__(self, cfg: dict, seed: int):
        s = self.s = sizes(cfg)
        if s["G"] != 1:
            raise ValueError("the reference computes one SSM group")
        self.words = seed_words(seed)
        self._layer_w = {
            kind: jax.jit(lambda w, l, kind=kind: _upcast(
                _layer_weights(w, l, s, kind)))
            for kind in self.KINDS}
        self._global_w = jax.jit(lambda w: _upcast(_global_weights(w, s)))
        self._blocks = {(kind, q): jax.jit(functools.partial(
            _mamba_block if kind == "mamba" else _attention_block, s=s,
            quant=q)) for kind in self.KINDS for q in (False, True)}
        self._final = jax.jit(lambda x, w: _rms(x, w, s["eps"]))
        self._head = {False: jax.jit(self._head_stats),
                      True: jax.jit(self._head_stats_ctl)}

    def hidden(self, tokens: np.ndarray, quant: bool = False):
        """Final-norm hidden states (n, S, d) float32 of token rows (n, S);
        causal, so right padding never reaches an earlier position."""
        g = self._global_w(self.words)
        x = g["embed_tokens"][jnp.asarray(tokens)] * self.s["emb_mult"]
        del g["embed_tokens"]
        for layer, kind in enumerate(self.s["kinds"]):
            x = self._blocks[kind, quant](
                x, self._layer_w[kind](self.words, layer))
        return self._final(x, g["norm"])

    def _logits(self, h, embed):
        return jnp.einsum("rd,vd->rv", h, embed,
                          precision=HIGHEST) / self.s["logit_div"]

    def logits(self, tokens: np.ndarray, quant: bool = False):
        """Logits (n, S, vocab) float32 of token rows (n, S)."""
        h = self.hidden(tokens, quant)
        embed = self._global_w(self.words)["embed_tokens"]
        return self._logits(h.reshape(-1, h.shape[-1]), embed).reshape(
            h.shape[:2] + (-1,))

    def _head_stats(self, h_ref, h_ctl, embed, targets):
        del h_ctl
        ref = self._logits(h_ref, embed)
        at_target = jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
        return ref.max(-1), at_target

    def _head_stats_ctl(self, h_ref, h_ctl, embed, targets):
        ref = self._logits(h_ref, embed)
        at_target = jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
        pick = jnp.argmax(_mm(h_ctl, embed.T, True), -1)
        at_pick = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        return ref.max(-1), at_target, at_pick

    def logit_stats(self, h_ref, targets, h_ctl=None):
        """Per row of ``h_ref`` (R, d): the reference's best logit and its
        logit at ``targets`` (R,); where ``h_ctl`` (the control's hidden
        states) is given, also the reference's logit at the token the
        control puts first.  Rows go through the head in blocks of
        HEAD_ROWS."""
        embed = self._global_w(self.words)["embed_tokens"]
        R = h_ref.shape[0]
        pad = -R % HEAD_ROWS
        control = h_ctl is not None
        h_ref = jnp.pad(h_ref, ((0, pad), (0, 0)))
        h_ctl = jnp.pad(h_ctl, ((0, pad), (0, 0))) if control else h_ref
        targets = jnp.pad(jnp.asarray(targets, jnp.int32), (0, pad))
        out = [self._head[control](h_ref[i:i + HEAD_ROWS],
                                   h_ctl[i:i + HEAD_ROWS], embed,
                                   targets[i:i + HEAD_ROWS])
               for i in range(0, R + pad, HEAD_ROWS)]
        return [np.concatenate([np.asarray(o[j]) for o in out])[:R]
                for j in range(len(out[0]))]
