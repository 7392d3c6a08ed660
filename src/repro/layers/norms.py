"""Normalization layers (pure jnp).

RMSNorm is the serving hot path's glue op.  This module is the canonical
math the models run, and the oracle of the Pallas candidate in
repro/kernels/rmsnorm/, which no layer calls.
"""

from __future__ import annotations

import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray,
             eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm over the last axis; compute in fp32, cast back."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * (var + eps) ** -0.5
    return (y * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * (var + eps) ** -0.5
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)
