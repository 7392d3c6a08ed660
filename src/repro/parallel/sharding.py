"""Sharding rules: model parameter / activation / cache PartitionSpecs.

The default production layout (what the heuristic baseline plan and the
dry-run use):

  * batch        -> ("pod", "data")   (model-level DP; the pod axis is DP)
  * TP           -> "model": attention heads / MLP d_ff columns / expert
                    axis (EP-style) or expert-ff (TP-style) for MoE / SSD
                    heads; vocab for embedding + LM head.
  * FSDP (train) -> "data" additionally shards every parameter's largest
                    replicated dim; optimizer state follows parameters.
  * KV caches    -> batch over "data", SEQUENCE over "model".  Sequence-
                    sharding (not head-sharding) is deliberate: several
                    assigned archs have fewer KV heads than the 16-wide
                    model axis (gemma3 kv=8, qwen2-vl kv=4, ...), and a
                    padded head-sharding wastes up to 4x cache memory.
                    The decode step's shard hints keep attention on the
                    sequence shards, so softmax and readout combine small
                    partials instead of all-gathering each layer's cache
                    (layers/attention.gqa_decode_step).

Rules are path-based over the parameter pytree; anything unmatched is
replicated.  Divisibility is checked and falls back to replication rather
than failing — the dry-run prints fallbacks so silent inefficiency can't
hide.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def batch_pspec(mesh: Mesh) -> P:
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return P(axes if len(axes) > 1 else (axes[0] if axes else None))


def _div(dim: int, n: int) -> bool:
    return n > 0 and dim % n == 0


def param_pspecs(params, cfg: ModelConfig, mesh: Mesh,
                 fsdp: bool = False, log_fallbacks: bool = False):
    """PartitionSpec pytree matching ``params``."""
    m = _axis_size(mesh, "model")
    d = _axis_size(mesh, "data")
    ep_moe = cfg.ffn_kind == "moe" and _div(cfg.n_routed, m)
    # Head-aligned TP only: sharding a flat (H*hd) projection column dim
    # across more shards than there are heads makes GSPMD's reshape to
    # (H, hd) cut head boundaries and fall back to full replication INSIDE
    # the attention loops (measured: +10 GB/device). Non-dividing head
    # counts (qwen2-0.5b's 14 q/2 kv heads, qwen1.5's 40, ...) replicate
    # the projection instead; the layer then reshards the batch over
    # ("data","model") around attention where divisible (layers/hints.py).
    q_ok = _div(cfg.n_heads, m)
    kv_ok = _div(cfg.n_kv_heads, m)
    if cfg.attn_kind == "mla":
        kv_ok = q_ok
    ssm_ok = cfg.n_ssd_heads == 0 or _div(cfg.n_ssd_heads, m)

    def spec_for(path: str, x) -> P:
        ndim = x.ndim
        leaf = path.rsplit("/", 1)[-1]
        # params under a stacked block carry a leading repeat axis — all
        # rules run on the EFFECTIVE (unstacked) shape, then shift.
        stacked = "/blocks/" in f"/{path}/" or path.startswith("blocks/") \
            or "/layers/" in f"/{path}/" or path.startswith("layers/")
        off = 1 if stacked else 0
        shape = x.shape[off:]
        nd = ndim - off
        col = None   # effective dim to shard over "model"

        # encoder layers always have head-aligned dims (n_heads == n_kv)
        enc = path.startswith("encoder")
        q_al = True if enc else q_ok
        kv_al = True if enc else kv_ok

        if leaf == "embed":
            col = 0 if _div(shape[0], m) else None
        elif leaf == "head":
            col = 1 if _div(shape[1], m) else None
        elif leaf in ("wq", "wukv", "bq"):
            dim = 1 if nd >= 2 else 0
            col = dim if (q_al and _div(shape[dim], m)) else None
        elif leaf in ("wk", "wv", "bk", "bv"):
            dim = 1 if nd >= 2 else 0
            col = dim if (kv_al and _div(shape[dim], m)) else None
        elif leaf == "wdkv":
            col = None                           # MLA latent proj: replicated
        elif leaf == "wo":
            col = 0 if (q_al and _div(shape[0], m)) else None
        elif leaf in ("w_up", "w_gate"):
            if nd == 3:                          # MoE expert stacks (E,d,f)
                col = 0 if ep_moe else (2 if _div(shape[2], m) else None)
            else:
                col = 1 if _div(shape[1], m) else None
        elif leaf in ("w_down",):
            if nd == 3:                          # MoE (E,f,d)
                col = 0 if ep_moe else (1 if _div(shape[1], m) else None)
            else:
                col = 0 if _div(shape[0], m) else None
        elif leaf in ("w_x", "w_z"):
            col = 1 if (ssm_ok and _div(shape[1], m)) else None
        elif leaf == "w_out":
            col = 0 if (ssm_ok and _div(shape[0], m)) else None
        elif leaf == "conv_x":
            col = 1 if (ssm_ok and _div(shape[1], m)) else None
        elif leaf in ("conv_x_b", "norm_w"):
            col = 0 if (ssm_ok and _div(shape[0], m)) else None
        elif leaf in ("a_log", "dt_bias", "d_skip"):
            col = 0 if (ssm_ok and _div(shape[0], m)) else None
        elif leaf == "router":
            col = None

        spec = [None] * ndim
        if col is not None and m > 1:
            spec[col + off] = "model"
        # The embedding table stays vocab-sharded ONLY: a 2D-sharded table
        # makes GSPMD replicate the gather/scatter-add (token lookup and its
        # gradient).
        if fsdp and d > 1 and leaf != "embed":
            # shard the largest still-unsharded effective dim over "data"
            best, best_size = None, 0
            for i in range(off, ndim):
                if spec[i] is None and _div(x.shape[i], d) \
                        and x.shape[i] > best_size:
                    best, best_size = i, x.shape[i]
            if best is not None and best_size >= d:
                spec[best] = "data"
        if log_fallbacks and col is None and nd >= 2 and max(shape) >= 1024:
            print(f"  [sharding] replicated (no divisible dim): {path} "
                  f"{x.shape}")
        return P(*spec)

    return jax.tree_util.tree_map_with_path(
        lambda path, x: spec_for(_path_str(path), x), params)


def cache_pspecs(cache, cfg: ModelConfig, mesh: Mesh):
    """Cache layout: batch over data axes, sequence over "model"."""
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dax = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    d_total = 1
    for a in daxes:
        d_total *= mesh.shape[a]
    m = _axis_size(mesh, "model")

    def spec_for(path: str, x) -> P:
        leaf = path.rsplit("/", 1)[-1]
        if leaf == "len":
            return P(dax if x.shape[0] % max(d_total, 1) == 0 else None)
        stacked = not path.startswith("prefix")
        off = 1 if stacked else 0           # leading repeat axis
        ndim = x.ndim
        spec = [None] * ndim
        if ndim > off and x.shape[off] % max(d_total, 1) == 0:
            spec[off] = dax                  # batch dim (replicate if < mesh)
        if leaf in ("k", "v", "xk", "xv", "c_kv", "k_pe"):
            seq_dim = off + 1
            if _div(x.shape[seq_dim], m) and m > 1:
                spec[seq_dim] = "model"
        elif leaf == "ssm":
            # layout lead + (B, H, P, N): shard SSD heads over "model"
            h_at = off + 1
            if x.ndim > h_at and m > 1 and _div(x.shape[h_at], m):
                spec[h_at] = "model"
        elif leaf == "conv_x":
            ch = ndim - 1
            if m > 1 and _div(x.shape[ch], m):
                spec[ch] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(
        lambda path, x: spec_for(_path_str(path), x), cache)


def to_shardings(spec_tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))
