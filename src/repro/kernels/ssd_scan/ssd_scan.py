"""Mamba2 SSD chunked scan — Pallas TPU kernel.

The SSD duality (arXiv:2405.21060) splits the scan into (a) an intra-chunk
attention-like matmul and (b) a tiny cross-chunk recurrence.  TPU mapping:

  * grid = (batch, heads, chunks) — chunks minor, so the (P, N) recurrent
    state for one (batch, head) lives in VMEM scratch across chunk steps
    (the cross-chunk recurrence costs no HBM round-trips).
  * per chunk the kernel runs three small matmuls on the MXU:
    scores = C B^T (Q x Q), y_intra = (scores * decay-mask) @ (dt * x),
    state update = (decayed dt*x)^T B — all on (Q, N)/(Q, P) tiles with
    Q = 128 (MXU-aligned).
  * decays are cumulative-sum log-space scalars (Q-vectors) — VPU work
    that overlaps with the MXU matmuls.

The kernel computes one head's chunk at a time; B/C are shared across the
heads of a group (n_groups = 1 in all assigned configs), selected by the
index map — no broadcast materialization.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, h_scr, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[...].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[...].astype(jnp.float32)        # (Q, 1)
    a = a_ref[pl.program_id(1)]                 # scalar A (negative)
    b = b_ref[...].astype(jnp.float32)          # (Q, N)
    c = c_ref[...].astype(jnp.float32)          # (Q, N)

    # every value stays 2-D: cumulative log-decays as a (Q, 1) column and
    # a (1, Q) row, both from masked reductions (no 1-D vectors, no scan)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    la = dt * a                                 # (Q, 1) log-decay per step
    cum_row = jnp.sum(jnp.where(ii <= jj, la, 0.0), axis=0,
                      keepdims=True)            # (1, Q)
    cum = jnp.sum(jnp.where(ii == jj, cum_row, 0.0), axis=1,
                  keepdims=True)                # (Q, 1)
    total = jnp.sum(la, axis=0, keepdims=True)  # (1, 1)
    # intra-chunk decay mask: L[i, j] = exp(cum_i - cum_j), j <= i
    L = jnp.where(jj <= ii, jnp.exp(cum - cum_row), 0.0)
    scores = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # (Q, Q)
    w = scores * L
    xdt = x * dt                                # (Q, P)
    y = jax.lax.dot_general(w, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk: h carries (P, N); y += exp(cum) * (C @ h^T)
    h = h_scr[...]
    y = y + jnp.exp(cum) * jax.lax.dot_general(
        c, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # (Q, P)
    # state update: h' = exp(total) h + sum_i exp(total - cum_i)
    #               (dt_i x_i) outer B_i
    xw = xdt * jnp.exp(total - cum)             # (Q, P)
    h_scr[...] = (h * jnp.exp(total)
                  + jax.lax.dot_general(
                      xw, b, (((0,), (0,)), ((), ())),
                      preferred_element_type=jnp.float32))  # (P, N)
    y_ref[...] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x: jnp.ndarray, dt: jnp.ndarray, a_log: jnp.ndarray,
                    b: jnp.ndarray, c: jnp.ndarray, *, chunk: int = 128,
                    interpret: bool = True) -> jnp.ndarray:
    """x: (B, S, H, P); dt: (B, S, H) (softplus-activated); a_log: (H,);
    b, c: (B, S, N) (n_groups=1).  Returns y: (B, S, H, P) WITHOUT the
    D-skip term (ops.py adds it — keeps the kernel state-only)."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, S)
    S_p = -(-S // Q) * Q
    pad = ((0, 0), (0, 0), (0, S_p - S), (0, 0))
    # (B, H, S, P) and (B, H, S, 1): each block is a (Q, rows) panel of
    # one head, as the TPU's (8, 128) tiling requires
    x = jnp.pad(x.transpose(0, 2, 1, 3), pad)
    dt = jnp.pad(dt.transpose(0, 2, 1)[..., None], pad)
    b = jnp.pad(b, pad[1:])
    c = jnp.pad(c, pad[1:])
    A = -jnp.exp(a_log.astype(jnp.float32))     # (H,), whole in SMEM

    grid = (B, H, S_p // Q)
    kernel = functools.partial(_ssd_kernel, chunk=Q)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, Q, P),
                         lambda bi, h, ci: (bi, h, ci, 0)),
            pl.BlockSpec((None, None, Q, 1),
                         lambda bi, h, ci: (bi, h, ci, 0)),
            pl.BlockSpec((None, Q, N), lambda bi, h, ci: (bi, ci, 0)),
            pl.BlockSpec((None, Q, N), lambda bi, h, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, Q, P),
                               lambda bi, h, ci: (bi, h, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S_p, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(A, x, dt, b, c)
    return y[:, :, :S].transpose(0, 2, 1, 3)
