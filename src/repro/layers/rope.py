"""Rotary position embeddings: standard RoPE, YaRN-scaled RoPE
(DeepSeek-V2) and Qwen2-VL's M-RoPE.

YaRN (arXiv:2309.00071), as HF ``DeepseekV2YarnRotaryEmbedding`` computes
it: frequency slots that turn fewer than ``beta_slow`` times over the
original context are divided by ``factor``, slots that turn more than
``beta_fast`` times are kept, and a linear ramp blends the slots between;
cos and sin are scaled by ``mscale(factor, mscale) / mscale(factor,
mscale_all_dim)``.  The attention layer multiplies its softmax scale by
``mscale(factor, mscale_all_dim) ** 2``.  ``scaling`` is a
``models.config.RopeScaling``; without it the tables are plain RoPE's.

M-RoPE (arXiv:2409.12191) splits the head dimension into three sections
rotated by (temporal, height, width) position ids.  The vision frontend is
stubbed in this repo, so position ids arrive precomputed alongside the
patch embeddings; text tokens use t == h == w (which makes M-RoPE collapse
to standard RoPE — the property tests rely on this identity).
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(head_dim: int, theta: float,
                          scaling) -> Tuple[int, int]:
    """The first and last frequency slots of YaRN's ramp."""
    def slot(rotations: float) -> float:
        return head_dim * math.log(
            scaling.original_max_position_embeddings
            / (rotations * 2 * math.pi)) / (2 * math.log(theta))
    return (max(math.floor(slot(scaling.beta_fast)), 0),
            min(math.ceil(slot(scaling.beta_slow)), head_dim - 1))


def rope_angles(positions: jnp.ndarray, head_dim: int,
                theta: float = 10000.0,
                scaling=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin) tables of shape positions.shape + (head_dim // 2,)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if scaling is not None:
        low, high = yarn_correction_range(head_dim, theta, scaling)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        freqs = freqs / scaling.factor * ramp + freqs * (1.0 - ramp)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    if scaling is None:
        return jnp.cos(ang), jnp.sin(ang)
    m = yarn_mscale(scaling.factor, scaling.mscale) \
        / yarn_mscale(scaling.factor, scaling.mscale_all_dim)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def _rotate(x: jnp.ndarray, cos: jnp.ndarray,
            sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate pairs (x_even, x_odd) by the angle tables.

    x: (..., seq, heads, head_dim); cos/sin: (..., seq, head_dim//2) —
    broadcast over heads."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def apply_rope(q: jnp.ndarray, k: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0,
               scaling=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RoPE, YaRN-scaled when ``scaling`` is given.  q: (B, S, Hq, D),
    k: (B, S, Hk, D), positions: (B, S) absolute token positions."""
    cos, sin = rope_angles(positions, q.shape[-1], theta, scaling)
    return _rotate(q, cos, sin), _rotate(k, cos, sin)


def apply_mrope(q: jnp.ndarray, k: jnp.ndarray, positions: jnp.ndarray,
                sections: Sequence[int] = None,
                theta: float = 10000.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Multimodal RoPE.  positions: (B, S, 3) = (t, h, w) ids.

    ``sections`` gives the per-axis share of head_dim//2 frequency slots
    (sums to head_dim // 2).  Default follows Qwen2-VL's 1:1.5:1.5 split
    (16, 24, 24 at head_dim 128), scaled to the actual head_dim.
    """
    head_dim = q.shape[-1]
    half = head_dim // 2
    if sections is None:
        t = half // 4
        h = (half - t) // 2
        sections = (t, h, half - t - h)
    if sum(sections) != half:
        raise ValueError(f"M-RoPE sections {sections} must sum to {half}")
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # section s of the frequency slots uses position axis s
    axis_of_slot = jnp.repeat(
        jnp.arange(len(sections)), jnp.asarray(sections),
        total_repeat_length=half)
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),
        jnp.broadcast_to(axis_of_slot[None, None, :],
                         positions.shape[:2] + (half,)).astype(jnp.int32),
        axis=-1)                                   # (B, S, half)
    ang = pos * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return _rotate(q, cos, sin), _rotate(k, cos, sin)
