"""Mamba2 SSD (state-space duality) mixer — attention-free sequence layer.

Implements the chunked SSD algorithm (arXiv:2405.21060): within a chunk the
computation is an attention-like matmul against a decay-masked score matrix
(the "duality"); across chunks a small recurrent state (H, P, N) is carried
by a scan.  This file is the pure-jnp math the models run; the Pallas
candidate in repro/kernels/ssd_scan tiles the same chunk structure into
VMEM, and no layer calls it.

Parameter layout is TP-friendly: the x/z input projections and the x-conv
are separate tensors column-shardable on d_inner (= SSD-head sharding, the
APEX template for SSM cells); the small B/C/dt projections and their conv
are replicated.  The output projection w_out is row-sharded -> one
all-reduce per layer, exactly the Megatron pattern.

Recurrence (per head, discretized):
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (outer) B_t
    y_t = h_t @ C_t + D * x_t
with x_t in R^P (head dim), B_t, C_t in R^N (state dim), A < 0 scalar/head.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp


def init_mamba2(rng, d_model: int, d_inner: int, d_state: int,
                n_heads: int, d_conv: int = 4, n_groups: int = 1,
                dtype=jnp.bfloat16) -> dict:
    if d_inner % n_heads:
        raise ValueError("d_inner must divide into n_heads")
    kx, kz, kbc, kcx, kcb, ko = jax.random.split(rng, 6)
    s = 1.0 / math.sqrt(d_model)
    gn = n_groups * d_state
    return {
        "w_x": (jax.random.normal(kx, (d_model, d_inner)) * s).astype(dtype),
        "w_z": (jax.random.normal(kz, (d_model, d_inner)) * s).astype(dtype),
        "w_bcdt": (jax.random.normal(kbc, (d_model, 2 * gn + n_heads)) * s
                   ).astype(dtype),
        "conv_x": (jax.random.normal(kcx, (d_conv, d_inner)) * 0.1
                   ).astype(dtype),
        "conv_x_b": jnp.zeros((d_inner,), dtype),
        "conv_bc": (jax.random.normal(kcb, (d_conv, 2 * gn)) * 0.1
                    ).astype(dtype),
        "conv_bc_b": jnp.zeros((2 * gn,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "norm_w": jnp.ones((d_inner,), dtype),
        "w_out": (jax.random.normal(ko, (d_inner, d_model))
                  * (1.0 / math.sqrt(d_inner))).astype(dtype),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray
                 ) -> jnp.ndarray:
    """Depthwise causal conv1d + SiLU.  x: (B,S,C); w: (K,C)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(K))
    return jax.nn.silu(out + b)


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, a_log: jnp.ndarray,
                b: jnp.ndarray, c: jnp.ndarray, d_skip: jnp.ndarray,
                chunk: int = 128,
                init_state: jnp.ndarray = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan (the duality algorithm).

    x : (B, S, H, P)   head inputs
    dt: (B, S, H)      softplus-activated step sizes (> 0)
    a_log: (H,)        A = -exp(a_log) < 0
    b, c: (B, S, N)    input/output projections (n_groups = 1)
    Returns (y: (B,S,H,P), final_state: (B,H,P,N) fp32).
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, S)
    S_p = -(-S // Q) * Q
    pad = S_p - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    nC = S_p // Q
    A = -jnp.exp(a_log)                                    # (H,) < 0

    xs = x.reshape(B, nC, Q, H, P).transpose(1, 0, 2, 3, 4)
    dts = dt.reshape(B, nC, Q, H).transpose(1, 0, 2, 3)
    bs = b.reshape(B, nC, Q, N).transpose(1, 0, 2, 3)
    cs = c.reshape(B, nC, Q, N).transpose(1, 0, 2, 3)

    def chunk_step(h0, inp):
        xc, dtc, bc, cc = inp                              # (B,Q,H,P) etc.
        la = dtc * A                                       # (B,Q,H) log-decay
        cum = jnp.cumsum(la, axis=1)                       # (B,Q,H)
        # intra-chunk duality: L[i,j] = exp(cum_i - cum_j) for j <= i.
        # Mask BEFORE the exp: exp of the (masked-out) upper triangle can
        # overflow to inf, and where(mask, inf, 0) back-propagates
        # inf * 0 = NaN into dt/A gradients.
        diff = cum[:, :, None, :] - cum[:, None, :, :]     # (B,Q,Q,H)
        mask = jnp.tril(jnp.ones((Q, Q), bool))
        L = jnp.exp(jnp.where(mask[None, :, :, None], diff, -1e30))
        scores = jnp.einsum("bin,bjn->bij", cc, bc)        # (B,Q,Q)
        w = scores[..., None] * L                          # (B,Q,Q,H)
        xdt = xc * dtc[..., None]                          # (B,Q,H,P)
        y_intra = jnp.einsum("bijh,bjhp->bihp",
                             w.astype(xc.dtype), xdt.astype(xc.dtype))
        # inter-chunk: contribution of the carried state
        y_inter = jnp.einsum("bin,bhpn->bihp",
                             cc, h0.astype(cc.dtype)) \
            * jnp.exp(cum)[..., None].astype(xc.dtype)
        # new state: decayed old + chunk's own contribution
        rem = cum[:, -1:, :] - cum                         # decay i..end
        contrib = jnp.einsum(
            "bihp,bin->bhpn",
            (xdt * jnp.exp(rem)[..., None]).astype(xc.dtype), bc)
        h1 = h0 * jnp.exp(cum[:, -1, :])[:, :, None, None] + \
            contrib.astype(jnp.float32)
        return h1, y_intra + y_inter

    h_init = (jnp.zeros((B, H, P, N), jnp.float32)
              if init_state is None else init_state)
    # checkpoint the chunk body: backward keeps only the (B,H,P,N) carry
    # per chunk and recomputes the (Q,Q) duality tiles — without this the
    # scan's saved residuals are ~10x the model activations.
    h_final, ys = jax.lax.scan(jax.checkpoint(chunk_step), h_init,
                               (xs, dts, bs, cs))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S_p, H, P)[:, :S]
    y = y + x[:, :S] * d_skip[None, None, :, None].astype(x.dtype)
    return y.astype(x.dtype), h_final


def _project(params: dict, x: jnp.ndarray, d_state: int, n_groups: int,
             n_heads: int):
    """Shared input projections + convs -> (z, xi, b, c, dt)."""
    gn = n_groups * d_state
    z = x @ params["w_z"]
    xi = x @ params["w_x"]
    bcdt = x @ params["w_bcdt"]
    bc, dt = bcdt[..., :2 * gn], bcdt[..., 2 * gn:]
    return z, xi, bc, dt


def mamba2_forward(params: dict, x: jnp.ndarray, *, d_inner: int,
                   d_state: int, n_heads: int, n_groups: int = 1,
                   chunk: int = 128) -> jnp.ndarray:
    """Full-sequence Mamba2 block.  x: (B, S, d_model)."""
    from .norms import rms_norm
    B, S, _ = x.shape
    P = d_inner // n_heads
    gn = n_groups * d_state
    z, xi, bc, dt = _project(params, x, d_state, n_groups, n_heads)
    xi = _causal_conv(xi, params["conv_x"], params["conv_x_b"])
    bc = _causal_conv(bc, params["conv_bc"], params["conv_bc_b"])
    b, c = bc[..., :gn], bc[..., gn:]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"])              # (B,S,H)
    xh = xi.reshape(B, S, n_heads, P)
    y, _ = ssd_chunked(xh, dt, params["a_log"], b, c, params["d_skip"],
                       chunk=chunk)
    y = y.reshape(B, S, d_inner)
    y = rms_norm(y * jax.nn.silu(z), params["norm_w"])
    return (y @ params["w_out"]).astype(x.dtype)


@jax.named_scope("ssm.decode")
def mamba2_decode_step(params: dict, x: jnp.ndarray,
                       ssm_state: jnp.ndarray, conv_state: dict,
                       *, d_inner: int, d_state: int, n_heads: int,
                       n_groups: int = 1, advance: jnp.ndarray,
                       reset: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, dict]:
    """One decode step — O(1) in context length (the SSM serving win).

    x: (B, 1, d_model); ssm_state: (B, H, P, N) fp32;
    conv_state: {"x": (B, K-1, d_inner), "bc": (B, K-1, 2*G*N)}.

    Each slot's state is its own.  ``reset`` (B,) bool marks the slots
    whose state so far is taken as zero: a slot whose prompt replay
    starts (its cache length is 0), so a reused slot never inherits its
    last occupant's state.  ``advance`` (B,) bool marks the slots the step
    advances, those fed a real token; every other slot's state and conv
    windows come back as they were given.  The projections, the conv
    windows, the state update and readout and the gated norm run under
    the ``ssm.decode`` scope.
    """
    from .norms import rms_norm
    B = x.shape[0]
    P = d_inner // n_heads
    gn = n_groups * d_state
    K = params["conv_x"].shape[0]
    z, xi, bc, dt = _project(params, x, d_state, n_groups, n_heads)
    given = (ssm_state, conv_state)
    ssm_state = jnp.where(reset[:, None, None, None], 0.0, ssm_state)
    conv_state = {k: jnp.where(reset[:, None, None], 0, v)
                  for k, v in conv_state.items()}

    def conv_step(state, new, w, bias):
        win = jnp.concatenate([state, new], axis=1)        # (B, K, C)
        out = sum(win[:, i, :] * w[i] for i in range(K))
        return jax.nn.silu(out + bias)[:, None, :], win[:, 1:, :]

    xi, ncx = conv_step(conv_state["x"], xi, params["conv_x"],
                        params["conv_x_b"])
    bc, ncb = conv_step(conv_state["bc"], bc, params["conv_bc"],
                        params["conv_bc_b"])
    b, c = bc[..., :gn], bc[..., gn:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["a_log"])
    a = jnp.exp(dt[:, 0, :] * A)                           # (B,H)
    xh = xi.reshape(B, n_heads, P)
    upd = (dt[:, 0, :, None, None]
           * xh[..., None].astype(jnp.float32)
           * b[:, 0, None, None, :].astype(jnp.float32))   # (B,H,P,N)
    new_state = ssm_state * a[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state,
                   c[:, 0].astype(jnp.float32))            # (B,H,P)
    y = y + xh.astype(jnp.float32) * params["d_skip"][None, :, None]
    y = y.reshape(B, 1, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm_w"])
    new_state = jnp.where(advance[:, None, None, None], new_state, given[0])
    new_conv = {k: jnp.where(advance[:, None, None], v, given[1][k])
                for k, v in {"x": ncx, "bc": ncb}.items()}
    return (y @ params["w_out"]).astype(x.dtype), new_state, new_conv
