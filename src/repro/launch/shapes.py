"""Input-shape cells for the dry-run: ShapeDtypeStruct stand-ins for every
model input — weak-type-correct, shardable, NO device allocation.

Cells (applied per arch; skips per configs/<arch>.SKIP_SHAPES):
    train_4k     seq 4096  x global_batch 256   -> train_step
    prefill_32k  seq 32768 x global_batch 32    -> prefill forward
    decode_32k   seq 32768 x global_batch 128   -> serve_step (1 new token)
    long_500k    seq 524288 x global_batch 1    -> serve_step
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.config import ModelConfig

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}


def _struct_like(tree):
    return jax.tree.map(lambda x: SDS(x.shape, x.dtype), tree)


def input_specs(cfg: ModelConfig, shape: ShapeCell) -> dict:
    """ShapeDtypeStruct stand-ins for one (arch x shape) cell.

    train  -> {"tokens","labels"} (+ "frames"/"embeds" for stub frontends)
    prefill-> {"tokens"} / {"embeds"} / {"frames","tokens"}
    decode -> {"tokens": (B,1)} + "cache" structs sized to seq_len
    """
    B, S = shape.global_batch, shape.seq_len
    d = cfg.d_model
    i32, dt = jnp.int32, jnp.dtype(cfg.dtype)

    if shape.kind == "train":
        out = {"labels": SDS((B, S), i32)}
        if cfg.encoder is not None:
            # enc-dec: source frames length == seq budget, short targets
            out["frames"] = SDS((B, S, d), dt)
            out["tokens"] = SDS((B, max(256, S // 8)), i32)
            out["labels"] = SDS((B, max(256, S // 8)), i32)
        elif cfg.embeds_input:
            out["embeds"] = SDS((B, S, d), dt)
        else:
            out["tokens"] = SDS((B, S), i32)
        return out

    if shape.kind == "prefill":
        if cfg.encoder is not None:
            return {"frames": SDS((B, S, d), dt),
                    "tokens": SDS((B, 1), i32)}
        if cfg.embeds_input:
            return {"embeds": SDS((B, S, d), dt)}
        return {"tokens": SDS((B, S), i32)}

    # decode: one new token against a cache of S
    cache = jax.eval_shape(
        lambda: T.init_cache(cfg, B, S,
                             source_len=cfg.cross_source_len
                             if cfg.cross_attn else 0))
    return {"tokens": SDS((B, 1), i32), "cache": _struct_like(cache)}
