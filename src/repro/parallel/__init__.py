"""Distribution layer: APEX plan -> JAX shardings, plus the explicitly
scheduled parallel patterns (pipeline, expert-parallel dispatch)."""

from .sharding import batch_pspec, cache_pspecs, param_pspecs
from .plan_sharding import plan_to_shardings

__all__ = ["batch_pspec", "cache_pspecs", "param_pspecs",
           "plan_to_shardings"]
