"""Attention-head padding: deploy-time TP alignment transform.

Several assigned archs have head counts that do not divide the 16-wide
"model" mesh axis (qwen1.5-32b: 40 q/kv heads; qwen2-vl: 28; qwen2-0.5b:
14) — their attention projections fall back to replication (see
parallel/sharding.py), costing replicated weights AND 16x-redundant
attention compute.  Padding the head count up to the next multiple of the
axis (40 -> 48) with ZERO output rows is mathematically exact:

    out = concat(head_0..head_39, pad_heads) @ [wo_real; 0] == original

(the padded heads' attention outputs are annihilated by the zero rows of
wo; q/k/v pad weights are zero so padded heads attend uniformly — finite,
no NaN).  The price is n_pad/n_heads extra attention FLOPs and KV bytes —
20% for qwen1.5 versus 1500% redundant compute without it.  Same trick
Megatron applies to vocab padding.

benchmarks/perf_iterations.py lowers a ``padded_config`` deployment;
``pad_attention_heads`` pads a parameter tree to match it.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


def _pad_dim(x: jnp.ndarray, dim: int, new: int) -> jnp.ndarray:
    pad = [(0, 0)] * x.ndim
    pad[dim] = (0, new - x.shape[dim])
    return jnp.pad(x, pad)


def pad_attention_heads(params: dict, cfg: ModelConfig, multiple: int = 16
                        ) -> Tuple[dict, ModelConfig]:
    """Zero-pad attention heads to the next multiple of ``multiple``.

    Returns (padded params, padded cfg).  No-op when already aligned.
    """
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    hq_p = -(-hq // multiple) * multiple
    hkv_p = -(-hkv // multiple) * multiple
    if hq_p == hq and hkv_p == hkv:
        return params, cfg
    if cfg.attn_kind == "mla":
        raise NotImplementedError("MLA archs are already head-aligned")

    def pad_leaf(path: str, x):
        leaf = path.rsplit("/", 1)[-1]
        stacked = path.startswith("blocks") or "/layers/" in f"/{path}/"
        off = 1 if stacked else 0
        if leaf in ("wq",):
            return _pad_dim(x, off + 1, hq_p * hd)
        if leaf in ("wk", "wv"):
            return _pad_dim(x, off + 1, hkv_p * hd)
        if leaf == "wo":
            return _pad_dim(x, off + 0, hq_p * hd)   # zero rows: exactness
        if leaf in ("bq",):
            return _pad_dim(x, off, hq_p * hd)
        if leaf in ("bk", "bv"):
            return _pad_dim(x, off, hkv_p * hd)
        return x

    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        # only pad attention-module leaves (mixer/mlp share leaf names? no)
        if "/attn/" in f"/{pstr}/" or "/xattn/" in f"/{pstr}/":
            out.append(pad_leaf(pstr, leaf))
        else:
            out.append(leaf)
    new_params = jax.tree_util.tree_unflatten(tdef, out)
    new_cfg = dataclasses.replace(cfg, n_heads=hq_p, n_kv_heads=hkv_p,
                                  head_dim=hd)
    return new_params, new_cfg


def padded_config(cfg: ModelConfig, multiple: int = 16) -> ModelConfig:
    """Config-only variant (for ShapeDtypeStruct dry-runs)."""
    hd = cfg.resolved_head_dim
    hq_p = -(-cfg.n_heads // multiple) * multiple
    hkv_p = -(-cfg.n_kv_heads // multiple) * multiple
    if hq_p == cfg.n_heads and hkv_p == cfg.n_kv_heads:
        return cfg
    return dataclasses.replace(cfg, n_heads=hq_p, n_kv_heads=hkv_p,
                               head_dim=hd)
