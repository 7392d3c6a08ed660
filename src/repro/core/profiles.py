"""Operation-level profiling results + linear interpolation (paper §3.5).

The paper's Offline Profiler measures key transformer operations (GEMM,
attention, collectives) on the target hardware across a grid of sizes; the
Serving Simulator then looks up (and linearly interpolates) those tables.
Profiling is a one-time per-cluster cost amortized across simulations.

We reproduce the exact mechanism with swappable *backends* that stand in
for the profiler:

  * ``AnalyticBackend`` — closed-form roofline-with-efficiency-curve model
    of the target device (H100/H200/TPU v5e presets).  This is what the
    GPU-hours profiling job would have produced, up to calibration.
  * ``MeasuredBackend`` — actually executes the operation in JAX on this
    process's first device, in the op's compute dtype, and times it.  Used
    by the fidelity experiments (Fig. 6/7 reproduction): the simulator
    predicts, the real JAX serving engine runs, both on the same silicon.

Either way the simulator only ever sees a ``ProfileStore``: sparse grids of
(x, time, energy) points per (op, axes) key, linear interpolation between
grid points, linear extrapolation at the edges — faithful to §3.4's "If a
specific data point is missing, the Simulator applies linear interpolation
between the nearest profiling data points."
"""

from __future__ import annotations

import bisect
import dataclasses
import math
from typing import Callable, Dict, List, Optional, Tuple

from .cluster import Cluster
from . import collectives as _coll


# Grid of interpolation x-points the "profiler" samples. Log-spaced powers
# of two from 1 to 2^40 — covers token counts, qk products and byte sizes.
_GRID = [2 ** i for i in range(0, 41)]


def _interp(points: List[Tuple[float, float, float]], x: float
            ) -> Tuple[float, float]:
    """Piecewise-linear interpolation over sorted (x, t, e) points."""
    if x <= points[0][0]:
        # Linear through origin below the grid (cost ~ 0 at x = 0).
        x0, t0, e0 = points[0]
        return t0 * x / x0, e0 * x / x0
    if x >= points[-1][0]:
        # Linear extrapolation using the last segment's slope.
        (x0, t0, e0), (x1, t1, e1) = points[-2], points[-1]
        dt = (t1 - t0) / (x1 - x0)
        de = (e1 - e0) / (x1 - x0)
        return t1 + dt * (x - x1), e1 + de * (x - x1)
    xs = [p[0] for p in points]
    i = bisect.bisect_right(xs, x)
    (x0, t0, e0), (x1, t1, e1) = points[i - 1], points[i]
    w = (x - x0) / (x1 - x0)
    return t0 + w * (t1 - t0), e0 + w * (e1 - e0)


class ProfileBackend:
    """Produces one (time_s, energy_j) sample — the 'profiler' interface."""

    def measure(self, op: str, axes: tuple, x: float) -> Tuple[float, float]:
        raise NotImplementedError


@dataclasses.dataclass
class AnalyticBackend(ProfileBackend):
    """Roofline-style analytic device model.

    Time = max(flops / (peak * eff_c(x)), bytes / (hbm_bw * eff_m)) + launch
    overhead.  The compute-efficiency curve ``eff_c`` saturates with
    arithmetic intensity/batch (small GEMMs underutilize the MXU/tensor
    cores) — this is what makes decode memory-bound and prefill
    compute-bound in the simulation, matching §2.1.

    ``freq_ghz`` scales compute and bandwidth linearly from the device's
    base frequency (paper Table 4's 0.8 GHz rows); energy uses the
    frequency-aware power model in core/energy.py.
    """

    cluster: Cluster
    freq_ghz: Optional[float] = None
    gemm_eff_max: float = 0.85
    mem_eff: float = 0.80
    launch_overhead_s: float = 4e-6

    def __post_init__(self):
        from .energy import PowerModel  # local import to avoid cycle
        self.power = PowerModel(self.cluster.device,
                                freq_ghz=self.freq_ghz)

    def _rates(self, dtype: str) -> Tuple[float, float]:
        dev = self.cluster.device
        scale = 1.0
        if self.freq_ghz is not None:
            scale = self.freq_ghz / dev.base_freq_ghz
        return dev.flops(dtype) * scale, dev.hbm_bw * self.mem_eff * scale

    def measure(self, op: str, axes: tuple, x: float) -> Tuple[float, float]:
        flops, nbytes, dtype = _op_work(op, axes, x)
        peak, bw = self._rates(dtype)
        # MXU efficiency saturates with the x variable (token count / size).
        half = 256.0 if op == "gemm" else 4096.0
        eff = self.gemm_eff_max * (x / (x + half))
        t_compute = flops / (peak * max(eff, 1e-3))
        t_mem = nbytes / bw
        t = max(t_compute, t_mem) + self.launch_overhead_s
        util = min(1.0, (flops / peak) / t) if t > 0 else 0.0
        energy = self.power.energy(t, util)
        return t, energy


def _op_work(op: str, axes: tuple, x: float) -> Tuple[float, float, str]:
    """Recover (flops, bytes, dtype) for a profile sample point.

    Mirrors the OpCall construction in core/ir.py so that analytic samples
    land on the same work model the simulator reports MFU/MBU against.
    """
    if op == "gemm":
        n, k, dtype = axes
        m = x
        bytes_per = 2.0 if dtype in ("fp16", "bf16") else 1.0
        flops = 2.0 * m * n * k
        nbytes = (m * k + m * n + n * k) * bytes_per
        return flops, nbytes, dtype
    if op == "attn_prefill":
        heads, head_dim, dtype = axes
        qk = x
        flops = 4.0 * qk * heads * head_dim
        nbytes = 4.0 * math.sqrt(max(qk, 1.0)) * heads * head_dim * 2.0
        return flops, nbytes, dtype
    if op == "attn_decode":
        kv_heads, head_dim, dtype = axes
        kv_tokens = x
        bytes_per = 2.0 if dtype in ("fp16", "bf16") else 1.0
        flops = 4.0 * kv_tokens * kv_heads * head_dim
        nbytes = 2.0 * kv_tokens * kv_heads * head_dim * bytes_per
        return flops, nbytes, dtype
    if op == "ssd_scan":
        d_inner, d_state, dtype = axes
        t = x
        flops = 6.0 * t * d_inner * d_state
        nbytes = 2.0 * t * d_inner * 2.0
        return flops, nbytes, dtype
    if op in _coll.COLLECTIVE_FNS or op == "p2p":
        # handled by CollectiveModel, not the device backend
        raise ValueError(f"collective op {op} must go through CollectiveModel")
    raise KeyError(f"unknown profile op {op!r}")


class MeasuredBackend(ProfileBackend):
    """Times the ACTUAL operation in JAX on this process's first device
    (the fidelity experiments' profiler: the simulator predicts the engine
    running on the same silicon, closing the paper's Fig. 6/7 loop).

    ``cluster`` is the one-device cluster of that device's ``device_kind``
    (``cluster_for_device_kind``); an unknown kind raises.  Ops run in
    their compute dtype (``axes[-1]``); a dtype without a jax counterpart
    here raises rather than being timed as another.

    Pair with ``ProfileStore(x_max=...)`` so the grid stays measurable;
    beyond the grid the store extrapolates linearly — the same mechanism
    the paper uses between profiled points.
    """

    DTYPES = {"fp32": "float32", "bf16": "bfloat16", "fp16": "float16"}

    def __init__(self, repeats: int = 3):
        import jax
        import jax.numpy as jnp
        from .cluster import cluster_for_device_kind
        from .energy import PowerModel
        self._jax, self._jnp = jax, jnp
        self.cluster = cluster_for_device_kind(jax.devices()[0].device_kind)
        self.repeats = repeats
        self.power = PowerModel(self.cluster.device)

    def _dtype(self, name: str):
        if name not in self.DTYPES:
            raise KeyError(f"MeasuredBackend cannot time compute dtype "
                           f"{name!r}; known: {sorted(self.DTYPES)}")
        return self._jnp.dtype(self.DTYPES[name])

    def _build(self, op: str, axes: tuple, x: float):
        jax, jnp = self._jax, self._jnp
        key = jax.random.PRNGKey(0)
        n_x = max(1, int(x))
        dt = self._dtype(axes[-1])
        if op == "gemm":
            n, k, _ = axes
            a = jax.random.normal(key, (n_x, k), dt)
            b = jax.random.normal(key, (k, n), dt)
            return jax.jit(lambda a, b: a @ b), (a, b)
        if op == "attn_prefill":
            heads, head_dim, _ = axes
            s = max(2, int(math.sqrt(n_x)))
            q = jax.random.normal(key, (1, s, heads, head_dim), dt)
            def f(q):
                w = jnp.einsum("bqhd,bkhd->bhqk", q, q,
                               preferred_element_type=jnp.float32)
                p = jax.nn.softmax(w, axis=-1).astype(dt)
                return jnp.einsum("bhqk,bkhd->bqhd", p, q)
            return jax.jit(f), (q,)
        if op == "attn_decode":
            kv_heads, head_dim, _ = axes
            kv = jax.random.normal(key, (1, n_x, kv_heads, head_dim), dt)
            q = jax.random.normal(key, (1, 1, kv_heads, head_dim), dt)
            def f(q, kv):
                w = jnp.einsum("bqhd,bkhd->bhqk", q, kv,
                               preferred_element_type=jnp.float32)
                p = jax.nn.softmax(w, axis=-1).astype(dt)
                return jnp.einsum("bhqk,bkhd->bqhd", p, kv)
            return jax.jit(f), (q, kv)
        if op == "ssd_scan":
            d_inner, d_state, _ = axes
            h = max(1, d_inner // 64)
            from repro.kernels.ssd_scan.ref import ssd_scan_ref
            xx = jax.random.normal(key, (1, n_x, h, 64), dt)
            dtt = jnp.ones((1, n_x, h), jnp.float32)
            al = jnp.zeros((h,), jnp.float32)
            b = jax.random.normal(key, (1, n_x, d_state), dt)
            return (jax.jit(lambda x, d, a, bb:
                            ssd_scan_ref(x, d, a, bb, bb)),
                    (xx, dtt, al, b))
        raise KeyError(op)

    def measure(self, op: str, axes: tuple, x: float) -> Tuple[float, float]:
        import time as _t
        fn, args = self._build(op, axes, x)
        out = fn(*args)
        self._jax.block_until_ready(out)        # compile + warm
        best = float("inf")
        for _ in range(self.repeats):
            t0 = _t.perf_counter()
            self._jax.block_until_ready(fn(*args))
            best = min(best, _t.perf_counter() - t0)
        return best, self.power.energy(best, 0.7)


class ProfileStore:
    """Grid-sampled profiling tables with linear interpolation.

    Tables are built lazily: the first query for an (op, axes) key samples
    the backend over the x-grid (bounded to a window around the query) and
    caches the curve; subsequent queries interpolate.  ``grid_stride``
    subsamples the grid (a stride of 2 keeps every 2nd power of two) to
    emulate a sparser profiling run — used by tests to bound interpolation
    error.  ``x_max`` caps the grid (measured backends can't run 2^40-token
    GEMMs); queries beyond it extrapolate linearly.
    """

    def __init__(self, backend: ProfileBackend, grid_stride: int = 1,
                 x_max: Optional[float] = None):
        self.backend = backend
        self.grid_stride = max(1, grid_stride)
        self.x_max = x_max
        self._tables: Dict[tuple, List[Tuple[float, float, float]]] = {}
        self.lookups = 0
        self.misses = 0

    def _table(self, op: str, axes: tuple) -> List[Tuple[float, float, float]]:
        key = (op, axes)
        tbl = self._tables.get(key)
        if tbl is None:
            self.misses += 1
            grid = [g for g in _GRID[:: self.grid_stride]
                    if self.x_max is None or g <= self.x_max]
            tbl = []
            for gx in grid:
                t, e = self.backend.measure(op, axes, float(gx))
                tbl.append((float(gx), t, e))
            self._tables[key] = tbl
        return tbl

    def query(self, op: str, axes: tuple, x: float) -> Tuple[float, float]:
        """(time_s, energy_j) for one operation instance."""
        self.lookups += 1
        if x <= 0:
            return 0.0, 0.0
        return _interp(self._table(op, axes), x)

    def time(self, op: str, axes: tuple, x: float) -> float:
        return self.query(op, axes, x)[0]


class CollectiveModel:
    """Collective-communication lookup (paper profiles these separately).

    Thin adapter over core/collectives.py cost functions + the energy model;
    grouped here so search.py passes one object around.
    """

    def __init__(self, cluster: Cluster, freq_ghz: Optional[float] = None):
        from .energy import PowerModel
        self.cluster = cluster
        self.power = PowerModel(cluster.device, freq_ghz=freq_ghz)

    def query(self, kind: str, nbytes: float, group_size: int
              ) -> Tuple[float, float]:
        if kind == "p2p":
            t = _coll.p2p_time(nbytes, group_size, self.cluster)
        else:
            t = _coll.collective_time(kind, nbytes, group_size, self.cluster)
        # Communication keeps devices at low compute utilization.
        e = self.power.energy(t, utilization=0.15) * group_size
        return t, e

    def time(self, kind: str, nbytes: float, group_size: int) -> float:
        return self.query(kind, nbytes, group_size)[0]
