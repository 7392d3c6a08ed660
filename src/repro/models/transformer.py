"""Unified decoder model covering the assigned LM-family architectures.

Design notes
------------
* **Layer-stacked scan**: parameters for the repeating block are stacked on
  a leading ``block_repeat`` axis and iterated with ``jax.lax.scan``.  The
  lowered HLO is O(1) in depth — a 48-layer Gemma3 and a synthetic
  trillion-parameter model compile in the same time (the XLA-level mirror
  of APEX's Transformer-IR block extrapolation).
* **Pure functions over dict pytrees** — no framework.  ``init_params``,
  ``forward`` (full sequence: training and the dry-run), ``init_cache``
  and ``decode_step`` (one token vs. cache) are the entire public surface,
  shared by the trainer, the serving engine, and the multi-pod dry-run.
  The serving engine fills a slot's cache by replaying its prompt through
  ``decode_step`` (``serving.engine.ServingEngine._prefill_slot``).
* **The decode step carries the cache**: the layer scan iterates over the
  stacked parameters and the layer index, and carries the stacked cache;
  each layer writes its new entries (one per slot) or its new SSM state
  into the stacked leaves in place and reads its layer where it lies.  No
  layer's cache is sliced out or restacked, so a donated cache is updated
  in place.  The MoE layers' routed-expert stacks are not among the
  scan's inputs either: the expert scan reads each expert's blocks where
  they lie in the stacked leaves.
* **Heterogeneous blocks**: the block pattern interleaves attention and SSD
  layers (Gemma3 local:global, Zamba2 and Granite-4.0-H hybrids); every
  layer is followed by the FFN unless ``ffn_kind`` is "none"; Zamba2's
  shared attention block has ONE weight set applied once per repeat
  (weights live outside the scanned pytree).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.layers import (gqa_attention, gqa_decode_step, init_attention,
                          init_mamba2, init_mla, init_mlp, init_moe,
                          mamba2_decode_step, mamba2_forward, mla_attention,
                          mla_decode_step, mlp_forward, moe_forward,
                          rms_norm)
from repro.layers.attention import blockwise_attention, cache_layer
from .config import LayerSpec, ModelConfig


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def ring_size(window: int) -> int:
    """Sliding-window ring-cache size: window+1 rounded up to a multiple of
    16 for sharding."""
    return -(-(window + 1) // 16) * 16


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(rng, cfg: ModelConfig, spec: LayerSpec,
                dense_ffn: bool = False) -> dict:
    dt = _dtype(cfg)
    k1, k2, k3 = jax.random.split(rng, 3)
    p = {"norm1": jnp.ones((cfg.d_model,), dt)}
    if spec.kind == "ssm":
        p["mixer"] = init_mamba2(k1, cfg.d_model, cfg.d_inner, cfg.d_state,
                                 cfg.n_ssd_heads, cfg.d_conv,
                                 cfg.n_ssm_groups, dtype=dt)
    elif cfg.attn_kind == "mla":
        p["attn"] = init_mla(k1, cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
                             cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                             cfg.v_head_dim, dtype=dt)
    else:
        p["attn"] = init_attention(k1, cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.resolved_head_dim,
                                   cfg.qkv_bias, dtype=dt)
    if cfg.cross_attn and spec.kind != "ssm":
        p["xattn"] = init_attention(k2, cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, cfg.resolved_head_dim,
                                    dtype=dt)
        p["norm_x"] = jnp.ones((cfg.d_model,), dt)
    if cfg.ffn_kind != "none":
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        if cfg.ffn_kind == "moe" and not dense_ffn:
            p["ffn"] = init_moe(k3, cfg.d_model, cfg.d_ff_expert,
                                cfg.n_routed, cfg.top_k, cfg.n_shared,
                                cfg.ffn_gated, dtype=dt)
        else:
            d_ff = cfg.d_ff_dense_first if dense_ffn and \
                cfg.d_ff_dense_first else cfg.d_ff
            p["ffn"] = init_mlp(k3, cfg.d_model, d_ff, cfg.ffn_gated,
                                dtype=dt)
    return p


def init_params(rng, cfg: ModelConfig) -> dict:
    """Build the full parameter pytree.  Block params are stacked on a
    leading ``block_repeat`` axis for lax.scan."""
    cfg.validate()
    dt = _dtype(cfg)
    k_emb, k_blocks, k_shared, k_head, k_pre = jax.random.split(rng, 5)
    params: dict = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model))
                  * (1.0 / math.sqrt(cfg.d_model))).astype(dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(
            k_head, (cfg.d_model, cfg.vocab_size))
            * (1.0 / math.sqrt(cfg.d_model))).astype(dt)

    def init_block(rng_b, dense_ffn=False):
        keys = jax.random.split(rng_b, len(cfg.block_pattern))
        return {f"l{i}": _init_layer(keys[i], cfg, spec, dense_ffn)
                for i, spec in enumerate(cfg.block_pattern)}

    # prefix blocks (DeepSeek first-k-dense) are NOT scanned
    n_prefix = cfg.first_k_dense
    if n_prefix:
        pk = jax.random.split(k_pre, n_prefix)
        params["prefix"] = [init_block(pk[i], dense_ffn=True)
                            for i in range(n_prefix)]

    n_scan = cfg.block_repeat - n_prefix
    if n_scan <= 0:
        raise ValueError("first_k_dense must be < block_repeat")
    bkeys = jax.random.split(k_blocks, n_scan)
    blocks = [init_block(bkeys[i]) for i in range(n_scan)]
    params["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)

    if cfg.shared_attn:
        s1, s2 = jax.random.split(k_shared)
        params["shared"] = {
            "norm1": jnp.ones((cfg.d_model,), dt),
            "attn": init_attention(s1, cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.resolved_head_dim,
                                   dtype=dt),
            "norm2": jnp.ones((cfg.d_model,), dt),
            "mlp": init_mlp(s2, cfg.d_model, cfg.shared_d_ff or cfg.d_ff,
                            cfg.ffn_gated, dtype=dt),
        }
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# forward (training / prefill math)
# ---------------------------------------------------------------------------

def _ffn_apply(cfg: ModelConfig, p: dict, x: jnp.ndarray,
               layer=None) -> jnp.ndarray:
    """The layer's FFN.  With ``layer``, an MoE FFN's routed-expert leaves
    are the layer-stacked ones (``decode_step``)."""
    if "router" in p:          # MoE params
        return moe_forward(p, x, cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
                           layer=layer)
    return mlp_forward(p, x)


def _residual(cfg: ModelConfig, x: jnp.ndarray, y: jnp.ndarray
              ) -> jnp.ndarray:
    """The residual stream ``x`` plus a sublayer's output ``y``, scaled by
    the residual multiplier where it is not 1."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return x + y


def _ffn_sublayer(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                  layer=None) -> jnp.ndarray:
    """``x`` through the layer's FFN sublayer (pre-norm ``norm2``, then the
    residual), which follows every layer, SSM or attention, unless
    ``ffn_kind`` is "none"."""
    if cfg.ffn_kind == "none":
        return x
    return _residual(cfg, x, _ffn_apply(cfg, p["ffn"],
                                        rms_norm(x, p["norm2"]), layer))


def _embed(params: dict, cfg: ModelConfig, tokens, embeds) -> jnp.ndarray:
    """Token embeddings (or the given ``embeds``), times the embedding
    multiplier where it is not 1."""
    x = params["embed"][tokens] if embeds is None \
        else embeds.astype(_dtype(cfg))
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _final_hidden(params: dict, cfg: ModelConfig,
                  x: jnp.ndarray) -> jnp.ndarray:
    """The final norm, divided by ``logits_scaling`` where it is not 1: its
    product with the head is the logits."""
    x = rms_norm(x, params["final_norm"])
    if cfg.logits_scaling != 1.0:
        x = x / cfg.logits_scaling
    return x


def _layer_apply(cfg: ModelConfig, spec: LayerSpec, p: dict, x: jnp.ndarray,
                 positions: jnp.ndarray,
                 enc_memory: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    if spec.kind == "ssm":
        x = _residual(cfg, x, mamba2_forward(
            p["mixer"], rms_norm(x, p["norm1"]), d_inner=cfg.d_inner,
            d_state=cfg.d_state, n_heads=cfg.n_ssd_heads,
            n_groups=cfg.n_ssm_groups))
        return _ffn_sublayer(cfg, p, x)
    h = rms_norm(x, p["norm1"])
    # Archs whose head count doesn't divide the TP axis (qwen2-0.5b: 14,
    # qwen1.5-32b: 40, qwen2-vl: 28) keep attention projections replicated;
    # distribute the attention compute by resharding the BATCH over
    # ("data","model") instead (no-op off-mesh / when indivisible).
    from repro.layers.hints import data_axis_names, mesh_axis_size, \
        shard_hint
    m_sz = mesh_axis_size("model")
    reshard = m_sz > 1 and cfg.n_heads % m_sz != 0
    if reshard:
        daxes = data_axis_names()
        h = shard_hint(h, daxes + ("model",), None, None)
    if cfg.attn_kind == "mla":
        attn = mla_attention(p["attn"], h, positions,
                             n_heads=cfg.n_heads,
                             kv_lora_rank=cfg.kv_lora_rank,
                             qk_nope_head_dim=cfg.qk_nope_head_dim,
                             qk_rope_head_dim=cfg.qk_rope_head_dim,
                             v_head_dim=cfg.v_head_dim,
                             rope_theta=cfg.rope_theta,
                             rope_scaling=cfg.rope_scaling)
    else:
        attn = gqa_attention(p["attn"], h, positions,
                             n_heads=cfg.n_heads,
                             n_kv_heads=cfg.n_kv_heads,
                             head_dim=cfg.resolved_head_dim,
                             window=spec.window, rope=cfg.rope,
                             rope_theta=cfg.rope_theta,
                             scale=cfg.attn_scale)
    if reshard:
        attn = shard_hint(attn, data_axis_names() or None, None, None)
    x = _residual(cfg, x, attn)
    if cfg.cross_attn and enc_memory is not None:
        hx = rms_norm(x, p["norm_x"])
        B, S, _ = hx.shape
        hd = cfg.resolved_head_dim
        q = (hx @ p["xattn"]["wq"]).reshape(B, S, cfg.n_heads, hd)
        Se = enc_memory.shape[1]
        k = (enc_memory @ p["xattn"]["wk"]).reshape(B, Se, cfg.n_kv_heads, hd)
        v = (enc_memory @ p["xattn"]["wv"]).reshape(B, Se, cfg.n_kv_heads, hd)
        out = blockwise_attention(q, k, v, causal=False)
        x = x + out.reshape(B, S, cfg.n_heads * hd) @ p["xattn"]["wo"]
    return _ffn_sublayer(cfg, p, x)


def _shared_apply(cfg: ModelConfig, shared: dict,
                  x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    h = rms_norm(x, shared["norm1"])
    x = x + gqa_attention(shared["attn"], h, positions,
                          n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                          head_dim=cfg.resolved_head_dim, rope=cfg.rope,
                          rope_theta=cfg.rope_theta)
    return x + mlp_forward(shared["mlp"], rms_norm(x, shared["norm2"]))


def forward(params: dict, cfg: ModelConfig,
            tokens: Optional[jnp.ndarray] = None,
            embeds: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None,
            enc_memory: Optional[jnp.ndarray] = None,
            remat: bool = False,
            return_hidden: bool = False) -> jnp.ndarray:
    """Full-sequence forward -> logits (B, S, vocab).

    ``tokens``: (B, S) int32 — or ``embeds``: (B, S, d_model) for stubbed
    modality frontends (VLM patches / audio frames).
    ``positions``: (B, S) or (B, S, 3) for M-RoPE; defaults to arange.
    ``remat``: activation-checkpoint each block (training memory policy).
    ``return_hidden``: return final-norm hidden states (divided by
    ``logits_scaling``) instead of logits: their product with the head is
    the logits (lets the trainer chunk the LM-head matmul + loss over the
    sequence).
    """
    x = _embed(params, cfg, tokens, embeds)
    B, S = x.shape[:2]
    if positions is None:
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        if cfg.rope == "mrope":
            pos = jnp.broadcast_to(pos[..., None], (B, S, 3))
        positions = pos

    for blk in params.get("prefix", []):
        for i, spec in enumerate(cfg.block_pattern):
            x = _layer_apply(cfg, spec, blk[f"l{i}"], x, positions,
                             enc_memory)

    shared = params.get("shared")

    # nested per-layer checkpoints only pay off for multi-layer blocks
    # (gemma3's 6-deep pattern): with a single-layer block they re-remat
    # the identical region, re-running every TP collective a third time.
    nest_remat = remat and len(cfg.block_pattern) > 1

    def block_body(x, blk):
        for i, spec in enumerate(cfg.block_pattern):
            if nest_remat:
                layer_fn = jax.checkpoint(
                    functools.partial(_layer_apply, cfg, spec))
                x = layer_fn(blk[f"l{i}"], x, positions, enc_memory)
            else:
                x = _layer_apply(cfg, spec, blk[f"l{i}"], x, positions,
                                 enc_memory)
        if shared is not None:
            x = _shared_apply(cfg, shared, x, positions)
        return x, None

    body = jax.checkpoint(block_body) if remat else block_body
    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = _final_hidden(params, cfg, x)
    if return_hidden:
        return x
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    return x @ head


# ---------------------------------------------------------------------------
# KV / state cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               source_len: int = 0) -> dict:
    """All-zero cache pytree in the model's dtype.  Layout per scanned
    repeat (leading R axis): attention -> k/v (R, B, Smax, Hkv, D); MLA ->
    latent + rope-key; SSM -> fp32 state + conv window.  ``len``: (B,)
    valid lengths.

    A cache that holds SSM state also carries ``advance``: (B,) bool, the
    slots a decode step advances (all of them here).  A step changes a
    slot's ``ssm``, ``conv_x`` and ``conv_bc`` only where ``advance`` is
    set, and takes a slot's state so far as zero where its ``len`` is 0,
    so a reused slot starts its replay from zero state.  The serving
    engine uploads ``advance`` beside ``len``: the replaying slot during a
    prompt replay, the active slots otherwise.  Caches with no SSM layer
    carry no ``advance``."""
    dt = _dtype(cfg)
    R = cfg.block_repeat - cfg.first_k_dense
    hd = cfg.resolved_head_dim

    def layer_cache(spec: LayerSpec, lead=(R,)) -> dict:
        if spec.kind == "ssm":
            P = cfg.d_inner // cfg.n_ssd_heads
            gn = cfg.n_ssm_groups * cfg.d_state
            return {
                "ssm": jnp.zeros(lead + (batch, cfg.n_ssd_heads, P,
                                         cfg.d_state), jnp.float32),
                "conv_x": jnp.zeros(lead + (batch, cfg.d_conv - 1,
                                            cfg.d_inner), dt),
                "conv_bc": jnp.zeros(lead + (batch, cfg.d_conv - 1, 2 * gn),
                                     dt),
            }
        if cfg.attn_kind == "mla":
            c = {
                "c_kv": jnp.zeros(lead + (batch, max_len, cfg.kv_lora_rank),
                                  dt),
                "k_pe": jnp.zeros(lead + (batch, max_len,
                                          cfg.qk_rope_head_dim), dt),
            }
        else:
            # ring caches are rounded up to a multiple of 16 so the
            # sequence dim shards cleanly over the model axis (a 4097-slot
            # ring would replicate). The ring then retains up to
            # ring-1 >= window past tokens — a window enlarged by < 16
            # tokens.
            kv_len = max_len if spec.window is None \
                else min(max_len, ring_size(spec.window))
            c = {
                "k": jnp.zeros(lead + (batch, kv_len, cfg.n_kv_heads, hd),
                               dt),
                "v": jnp.zeros(lead + (batch, kv_len, cfg.n_kv_heads, hd),
                               dt),
            }
        if cfg.cross_attn:
            c["xk"] = jnp.zeros(lead + (batch, source_len, cfg.n_kv_heads,
                                        hd), dt)
            c["xv"] = jnp.zeros(lead + (batch, source_len, cfg.n_kv_heads,
                                        hd), dt)
        return c

    cache = {
        "blocks": {f"l{i}": layer_cache(spec)
                   for i, spec in enumerate(cfg.block_pattern)},
        "len": jnp.zeros((batch,), jnp.int32),
    }
    if cfg.has_ssm:
        cache["advance"] = jnp.ones((batch,), bool)
    if cfg.first_k_dense:
        cache["prefix"] = [
            {f"l{i}": layer_cache(spec, lead=())
             for i, spec in enumerate(cfg.block_pattern)}
            for _ in range(cfg.first_k_dense)]
    if cfg.shared_attn:
        cache["shared"] = {
            "k": jnp.zeros((cfg.block_repeat, batch, max_len,
                            cfg.n_kv_heads, hd), dt),
            "v": jnp.zeros((cfg.block_repeat, batch, max_len,
                            cfg.n_kv_heads, hd), dt),
        }
    return cache


# ---------------------------------------------------------------------------
# decode step (serving)
# ---------------------------------------------------------------------------

_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _split_experts(blocks: dict) -> Tuple[dict, dict]:
    """The stacked block params without the MoE layers' routed-expert
    leaves, and those leaves by layer name.  The layer scan slices the
    first per layer; the second stay stacked for the expert scan to read
    in place (a slice of them that is its operand would be copied out)."""
    scanned, experts = {}, {}
    for name, p in blocks.items():
        ffn = p.get("ffn", {})
        if "router" in ffn:
            experts[name] = {k: ffn[k] for k in _EXPERT_LEAVES if k in ffn}
            p = {**p, "ffn": {k: v for k, v in ffn.items()
                              if k not in _EXPERT_LEAVES}}
        scanned[name] = p
    return scanned, experts


def _put_layer(leaf: jnp.ndarray, new: jnp.ndarray, layer) -> jnp.ndarray:
    """``new`` as layer ``layer`` of the stacked leaf (in place), or as the
    unstacked leaf itself (``layer`` None), in the leaf's dtype."""
    new = new.astype(leaf.dtype)
    if layer is None:
        return new
    return jax.lax.dynamic_update_index_in_dim(leaf, new, layer, 0)


def _layer_decode(cfg: ModelConfig, spec: LayerSpec, p: dict, x: jnp.ndarray,
                  lc: dict, cache_len: jnp.ndarray, layer=None,
                  advance=None) -> Tuple[jnp.ndarray, dict]:
    """One layer of the decode step.  ``lc`` holds the layer's cache leaves,
    or with ``layer`` the layer scan's stacked leaves, of which layer
    ``layer`` is read and written in place: attention K/V and MLA's latent
    and rope key take one entry per slot, SSM leaves the layer's whole new
    state; cross-attention K/V are read only.  With ``layer``, an MoE
    FFN's routed-expert leaves in ``p`` are stacked too and read in place;
    the rest of ``p`` is the layer's own.

    An SSM layer advances the state of the slots in ``advance`` (the
    cache's (B,) mask, which an SSM layer needs) and leaves every other
    slot's as it was; a slot whose ``cache_len`` is 0 starts from zero
    state.  Every layer's FFN sublayer follows its mixer or attention."""
    new_lc = dict(lc)
    at = functools.partial(cache_layer, layer=layer)
    if spec.kind == "ssm":
        h = rms_norm(x, p["norm1"])
        y, st, cv = mamba2_decode_step(
            p["mixer"], h, at(lc["ssm"]),
            {"x": at(lc["conv_x"]), "bc": at(lc["conv_bc"])},
            d_inner=cfg.d_inner, d_state=cfg.d_state,
            n_heads=cfg.n_ssd_heads, n_groups=cfg.n_ssm_groups,
            advance=advance, reset=cache_len == 0)
        new_lc["ssm"] = _put_layer(lc["ssm"], st, layer)
        new_lc["conv_x"] = _put_layer(lc["conv_x"], cv["x"], layer)
        new_lc["conv_bc"] = _put_layer(lc["conv_bc"], cv["bc"], layer)
        return _ffn_sublayer(cfg, p, _residual(cfg, x, y), layer), new_lc
    h = rms_norm(x, p["norm1"])
    if cfg.attn_kind == "mla":
        y, cc, ck = mla_decode_step(p["attn"], h, lc["c_kv"], lc["k_pe"],
                                    cache_len, n_heads=cfg.n_heads,
                                    kv_lora_rank=cfg.kv_lora_rank,
                                    qk_nope_head_dim=cfg.qk_nope_head_dim,
                                    qk_rope_head_dim=cfg.qk_rope_head_dim,
                                    v_head_dim=cfg.v_head_dim,
                                    rope_theta=cfg.rope_theta,
                                    rope_scaling=cfg.rope_scaling,
                                    layer=layer)
        new_lc["c_kv"], new_lc["k_pe"] = cc, ck
    else:
        # sliding-window caches are ring buffers (see gqa_decode_step)
        y, ck, cv = gqa_decode_step(
            p["attn"], h, lc["k"], lc["v"], cache_len,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, window=spec.window,
            rope=cfg.rope, rope_theta=cfg.rope_theta, layer=layer,
            scale=cfg.attn_scale)
        new_lc["k"], new_lc["v"] = ck, cv
    x = _residual(cfg, x, y)
    if cfg.cross_attn and "xk" in lc:
        hx = rms_norm(x, p["norm_x"])
        B = hx.shape[0]
        hd = cfg.resolved_head_dim
        rep = cfg.n_heads // cfg.n_kv_heads
        q = (hx @ p["xattn"]["wq"]).reshape(B, 1, cfg.n_heads, hd)
        kr = jnp.repeat(at(lc["xk"]), rep, axis=2)
        vr = jnp.repeat(at(lc["xv"]), rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                       preferred_element_type=jnp.float32) \
            / math.sqrt(hd)
        pattn = jax.nn.softmax(s, axis=-1).astype(vr.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", pattn, vr)
        x = x + out.reshape(B, 1, cfg.n_heads * hd) @ p["xattn"]["wo"]
    return _ffn_sublayer(cfg, p, x, layer), new_lc


def decode_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: dict,
                embeds: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, dict]:
    """One serving step: (B, 1) token ids (or embeds) + cache -> logits
    (B, vocab), updated cache.

    The layer scan carries the stacked cache and writes each layer's new
    entries into it in place (one per slot and leaf; an SSM layer's new
    state); no layer's cache is copied out or restacked.  An SSM layer
    advances only the slots in the cache's ``advance`` mask, which the
    step passes on unchanged, and starts a slot whose ``len`` is 0 from
    zero state (``init_cache``).  The MoE layers' routed-expert leaves
    stay out of the scan's inputs: they reach the expert scan stacked,
    with the layer index, and each expert's blocks are read where they
    lie.  The serving engine donates the cache to the jitted step
    (``serving.engine.make_decode_step``), so the step updates the one
    cache buffer it is given and the caller's cache is consumed."""
    x = _embed(params, cfg, tokens, embeds)
    cache_len = cache["len"]
    new_cache = {"len": cache_len + 1}
    advance = cache.get("advance")
    if advance is not None:
        new_cache["advance"] = advance

    if "prefix" in cache:
        new_cache["prefix"] = []
        for blk, pc in zip(params["prefix"], cache["prefix"]):
            npc = {}
            for i, spec in enumerate(cfg.block_pattern):
                x, npc[f"l{i}"] = _layer_decode(cfg, spec, blk[f"l{i}"], x,
                                                pc[f"l{i}"], cache_len,
                                                advance=advance)
            new_cache["prefix"].append(npc)

    shared = params.get("shared")
    blocks, experts = _split_experts(params["blocks"])

    def block_body(carry, inp):
        x, cblk, sc = carry
        blk, layer = inp
        cblk = dict(cblk)
        for i, spec in enumerate(cfg.block_pattern):
            name = f"l{i}"
            p = blk[name]
            if name in experts:
                p = {**p, "ffn": {**p["ffn"], **experts[name]}}
            x, cblk[name] = _layer_decode(cfg, spec, p, x, cblk[name],
                                          cache_len, layer, advance)
        if shared is not None:
            h = rms_norm(x, shared["norm1"])
            y, nk, nv = gqa_decode_step(
                shared["attn"], h, sc["k"], sc["v"], cache_len,
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.resolved_head_dim, rope=cfg.rope,
                rope_theta=cfg.rope_theta, layer=layer)
            sc = {"k": nk, "v": nv}
            x = x + y
            x = x + mlp_forward(shared["mlp"], rms_norm(x, shared["norm2"]))
        return (x, cblk, sc), None

    n_scan = jax.tree.leaves(params["blocks"])[0].shape[0]
    (x, new_cache["blocks"], shared_cache), _ = jax.lax.scan(
        block_body, (x, cache["blocks"], cache.get("shared")),
        (blocks, jnp.arange(n_scan)))
    if shared_cache is not None:
        new_cache["shared"] = shared_cache

    x = _final_hidden(params, cfg, x)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    return (x @ head)[:, 0, :], new_cache
