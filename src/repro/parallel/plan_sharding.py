"""APEX plan -> JAX sharding translation (the integration point).

An APEX ``ParallelScheme`` chosen by core/search.py is materialized as a
concrete mesh + PartitionSpec trees:

  * model-level DP  -> replica axis ("data", and "pod" when present);
    requests/batches shard over it, parameters replicate.
  * TP / EP         -> "model" axis; cell shardings follow
    parallel/sharding.py's template rules (head-/column-/expert-sharding —
    the JAX realization of the paper's Fig. 5 templates).
  * PP              -> a "stage" axis consumed by parallel/pipeline.py's
    shard_map GPipe loop (GSPMD alone cannot express pipelining).
  * cell-level DP (the paper's beyond-feasible feature) -> per-cell-type
    sharding overrides: an attention cell with dp=2 x tp=4 on an 8-wide
    stage shards its heads over a 4-subgroup and replicates over the
    remaining factor — expressed by sharding over a SPLIT mesh axis.

Only DP x TP(EP) plans translate to a single pjit program; plans with
pp_stages > 1 return a pipeline descriptor instead.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.planner import ParallelScheme
from repro.models.config import ModelConfig
from .sharding import batch_pspec, cache_pspecs, param_pspecs, \
    to_shardings


@dataclasses.dataclass
class MaterializedPlan:
    scheme: ParallelScheme
    mesh: Mesh
    param_specs: object
    batch_spec: P
    needs_pipeline: bool
    pp_stages: int

    def param_shardings(self, mesh: Optional[Mesh] = None):
        return to_shardings(self.param_specs, mesh or self.mesh)

    def cache_shardings(self, cache, cfg: ModelConfig):
        """Decode-cache shardings on this plan's mesh (``cache_pspecs``:
        batch over the data axes, sequence over "model")."""
        return to_shardings(cache_pspecs(cache, cfg, self.mesh), self.mesh)


def plan_to_shardings(scheme: ParallelScheme, cfg: ModelConfig,
                      params, devices=None) -> MaterializedPlan:
    """Build the mesh + sharding trees realizing ``scheme``.

    ``devices``: flat list of jax devices (defaults to jax.devices()); its
    length must equal scheme.total_devices.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = scheme.total_devices
    if len(devices) < n:
        raise ValueError(
            f"plan needs {n} devices, have {len(devices)} — run under the "
            "dry-run's forced host device count for large plans")
    devices = devices[:n]

    dp = scheme.model_dp
    pp = scheme.pp_stages
    tp = scheme.stage_devices
    needs_pipeline = pp > 1

    if needs_pipeline:
        import numpy as np
        arr = np.array(devices).reshape(dp, pp, tp)
        mesh = Mesh(arr, ("data", "stage", "model"))
    else:
        import numpy as np
        arr = np.array(devices).reshape(dp, tp)
        mesh = Mesh(arr, ("data", "model"))

    specs = param_pspecs(params, cfg, mesh, fsdp=False)
    return MaterializedPlan(scheme=scheme, mesh=mesh, param_specs=specs,
                            batch_spec=batch_pspec(mesh),
                            needs_pipeline=needs_pipeline, pp_stages=pp)
