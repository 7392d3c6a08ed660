"""GPipe-style pipeline parallelism via shard_map + lax.ppermute.

GSPMD cannot express pipelining (it has no notion of time), so PP plans
from the APEX planner are realized here: the layer stack is sharded over a
"stage" mesh axis (each device group holds block_repeat / n_stages blocks),
microbatches stream through stages with collective-permute handoffs, and
the classic GPipe schedule (n_micro + n_stages - 1 ticks) is driven by a
lax.scan whose body runs ONE tick on every stage simultaneously.

This module implements the pattern for the dense-transformer family (the
demo + tests target); the same skeleton drives PP for the other families
by swapping the stage function.

Cross-pod use: placing the "stage" axis on the pod boundary turns the
stage handoff into the only inter-pod traffic (activations once per
microbatch) — the paper's §2.4 PP-across-slow-links guidance; combine with
training/compress.py to quantize the handoff.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.experimental.shard_map import shard_map

from repro.launch.mesh import make_mesh


def pipeline_forward(stage_fn: Callable, params_stacked, x_micro,
                     mesh: Mesh, n_stages: int,
                     stage_axis: str = "stage") -> jnp.ndarray:
    """Run microbatches through the pipeline.

    stage_fn(stage_params, x) -> x   (one stage's layers, shard-local)
    params_stacked: pytree with leading dim n_stages (sharded over stage).
    x_micro: (n_micro, mb, S, d) microbatched inputs (replicated).
    Returns (n_micro, mb, S, d) outputs.
    """
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def per_stage(params_local, xs):
        # params_local: leading dim 1 (this stage's slice); xs replicated
        stage_params = jax.tree.map(lambda a: a[0], params_local)
        idx = jax.lax.axis_index(stage_axis)
        mb_shape = xs.shape[1:]
        state = jnp.zeros(mb_shape, xs.dtype)     # in-flight microbatch
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            incoming = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), keepdims=False)
            state = jnp.where((idx == 0) & (t < n_micro), incoming, state)
            y = stage_fn(stage_params, state)
            # last stage retires microbatch t - (n_stages - 1)
            done_t = t - (n_stages - 1)
            write = (idx == n_stages - 1) & (done_t >= 0)
            outs = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(done_t, 0, n_micro - 1), 0),
                lambda o: o, outs)
            # hand off to the next stage (ring permute; stage 0 receives
            # garbage from the last stage and overwrites it on ingest)
            y_next = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (y_next, outs), None

        (_, outs), _ = jax.lax.scan(tick, (state, outs),
                                    jnp.arange(ticks))
        # every stage holds `outs`; only the last stage's is real — share it
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs

    pp = shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_rep=False)
    return pp(params_stacked, x_micro)


def make_pp_mesh(n_stages: int, tp: int = 1):
    """A (stage, model) mesh from the available devices."""
    return make_mesh((n_stages, tp), ("stage", "model"))
