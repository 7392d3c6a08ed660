"""internlm2-1.8b: the program's ModelConfig, the weights the benchmark
serves, and the plain float32 reference of the same model.

The benchmark, not the program, draws the weights from the run's seed.
``make_weights`` builds the program's stacked pytree on the device in one
jitted call; ``Reference`` draws the same numbers again, one layer at a
time, and runs a straightforward ``jax.numpy`` forward pass in float32 at
``Precision.HIGHEST``.  The reference imports nothing of the program.

Layer equations (InternLM2, arXiv:2403.17297; HF ``modeling_internlm2.py``):
pre-norm RMSNorm (eps 1e-5), GQA with RoPE (rotate-half, base
``rope_theta``), no biases, SwiGLU ``w2(silu(w1 x) * w3 x)``, final RMSNorm,
untied output head.  The HF checkpoint packs q/k/v into one ``wqkv``; with
weights drawn here that packing is a relabelling of columns and is not
modelled.

Weights: the embedding has unit variance (so the hidden state's mean square
is about 1 and RMSNorm's eps is immaterial); every projection is
N(0, 1/fan_in); norm weights are 1 + 0.1 N(0, 1).  All are drawn in float32
and stored in bfloat16, the type they are served in.

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


def sizes(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, f=cfg["intermediate_size"], L=cfg["num_hidden_layers"],
                H=H, K=cfg["num_key_value_heads"], hd=cfg.get("head_dim", d // H),
                V=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]))


def program_config(cfg: dict):
    """The program's ModelConfig for this configuration, run as it states
    (``rope_theta`` included)."""
    from repro.models.config import LayerSpec, ModelConfig
    s = sizes(cfg)
    return ModelConfig(name="internlm2-1.8b", d_model=s["d"],
                       vocab_size=s["V"], block_pattern=(LayerSpec("attn"),),
                       block_repeat=s["L"], n_heads=s["H"], n_kv_heads=s["K"],
                       head_dim=s["hd"], d_ff=s["f"], rope_theta=s["theta"],
                       dtype="bfloat16")


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


def _layer_leaves(s: dict):
    d, f, H, K, hd = s["d"], s["f"], s["H"], s["K"], s["hd"]
    return (("attention_norm", (d,), None), ("wq", (d, H * hd), d),
            ("wk", (d, K * hd), d), ("wv", (d, K * hd), d),
            ("wo", (H * hd, d), H * hd), ("ffn_norm", (d,), None),
            ("w1", (d, f), d), ("w3", (d, f), d), ("w2", (f, d), f))


def _leaf(key, shape, fan_in):
    z = jax.random.normal(key, shape, jnp.float32)
    w = 1.0 + 0.1 * z if fan_in is None else z * (1.0 / math.sqrt(fan_in))
    return w.astype(jnp.bfloat16)


def _layer_weights(words, layer, s: dict) -> dict:
    key = jax.random.fold_in(jax.random.fold_in(_root(words), 1), layer)
    return {name: _leaf(jax.random.fold_in(key, i), shape, fan)
            for i, (name, shape, fan) in enumerate(_layer_leaves(s))}


def _global_weights(words, s: dict) -> dict:
    root = _root(words)
    embed = jax.random.normal(jax.random.fold_in(root, 2), (s["V"], s["d"]),
                              jnp.float32).astype(jnp.bfloat16)
    return {"tok_embeddings": embed,
            "norm": _leaf(jax.random.fold_in(root, 3), (s["d"],), None),
            "output": _leaf(jax.random.fold_in(root, 4), (s["d"], s["V"]),
                            s["d"])}


def make_weights(seed: int, cfg: dict) -> dict:
    """The program's parameter pytree, bf16, made on the device in one
    jitted call from the seed."""
    s = sizes(cfg)

    @jax.jit
    def make(words):
        w = jax.vmap(lambda l: _layer_weights(words, l, s))(
            jnp.arange(s["L"]))
        g = _global_weights(words, s)
        return {"embed": g["tok_embeddings"], "final_norm": g["norm"],
                "head": g["output"],
                "blocks": {"l0": {
                    "norm1": w["attention_norm"],
                    "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                             "wo": w["wo"]},
                    "norm2": w["ffn_norm"],
                    "ffn": {"w_gate": w["w1"], "w_up": w["w3"],
                            "w_down": w["w2"]}}}}

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


def _rope(x, theta):
    """x: (n, S, heads, hd), positions 0..S-1, rotate-half pairing."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv     # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, w, s: dict, quant: bool):
    n, S, d = x.shape
    H, K, hd = s["H"], s["K"], s["hd"]
    h = _rms(x, w["attention_norm"], s["eps"])
    q = _rope(_mm(h, w["wq"], quant).reshape(n, S, H, hd), s["theta"])
    k = _rope(_mm(h, w["wk"], quant).reshape(n, S, K, hd), s["theta"])
    v = _mm(h, w["wv"], quant).reshape(n, S, K, hd)
    k = jnp.repeat(k, H // K, axis=2)          # query head j reads kv j // (H/K)
    v = jnp.repeat(v, H // K, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HIGHEST)
    scores = scores / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(n, S, H * hd), w["wo"], quant)
    h = _rms(x, w["ffn_norm"], s["eps"])
    g = jax.nn.silu(_mm(h, w["w1"], quant)) * _mm(h, w["w3"], quant)
    return x + _mm(g, w["w2"], quant)


def _upcast(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


class Reference:
    """float32 forward of this configuration on the weights of ``seed``,
    one layer's weights on the device at a time."""

    def __init__(self, cfg: dict, seed: int):
        s = self.s = sizes(cfg)
        self.words = seed_words(seed)
        self._layer_w = jax.jit(lambda w, l: _upcast(_layer_weights(w, l, s)))
        self._global_w = jax.jit(lambda w: _upcast(_global_weights(w, s)))
        self._blocks = {q: jax.jit(functools.partial(_block, s=s, quant=q))
                        for q in (False, True)}
        self._final = jax.jit(lambda x, w: _rms(x, w, s["eps"]))
        self._head = {False: jax.jit(self._head_stats),
                      True: jax.jit(self._head_stats_ctl)}

    def hidden(self, tokens: np.ndarray, quant: bool = False):
        """Final-norm hidden states (n, S, d) float32 of token rows (n, S);
        causal, so right padding never reaches an earlier position."""
        g = self._global_w(self.words)
        x = g["tok_embeddings"][jnp.asarray(tokens)]
        del g["tok_embeddings"]
        for layer in range(self.s["L"]):
            x = self._blocks[quant](x, self._layer_w(self.words, layer))
        return self._final(x, g["norm"])

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
        head = self._global_w(self.words)["output"]
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
