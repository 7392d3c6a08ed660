"""Fig. 7 reproduction — TPOT scalability trend across device counts.

The paper plots predicted vs actual TPOT for TP over 2/4/8 GPUs (two
y-axes; the TREND is the fidelity claim).  Here the simulator predicts
TPOT for TP degrees on the modeled H100 node; the 'actual' counterpart is
the REAL sharded serve_step wall-time measured on 2/4/8 forced host
devices (subprocess), normalized at the smallest degree — same
two-axis trend comparison.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from repro.core import (ApexSearch, BatchingPolicy, get_trace, h100_node)
from repro.core.planner import generate_schemes

from .common import csv_row, model_ir

_MEASURE = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(n)d"
import jax, jax.numpy as jnp
from repro import configs as C
from repro.core import generate_schemes
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.parallel.plan_sharding import plan_to_shardings

enable_compile_cache()
cfg = C.get_reduced("internlm2_1_8b")
scheme = next(s for s in generate_schemes(cfg.to_ir(), %(n)d)
              if s.model_dp == 1 and s.pp_stages == 1)
params = T.init_params(jax.random.PRNGKey(0), cfg)
cache = T.init_cache(cfg, 4, 128)
mat = plan_to_shardings(scheme, cfg, params)
ps = jax.device_put(params, mat.param_shardings())
cs = jax.device_put(cache, mat.cache_shardings(cache, cfg))
toks = jnp.ones((4, 1), jnp.int32)
step = jax.jit(lambda p, t, c: T.decode_step(p, cfg, t, c))
with jax.set_mesh(mat.mesh):
    logits, cs2 = step(ps, toks, cs)
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    N = 20
    for _ in range(N):
        logits, cs = step(ps, toks, cs)
    jax.block_until_ready(logits)
    print(json.dumps({"tpot_s": (time.perf_counter() - t0) / N}))
"""


def _measure(n: int) -> float:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", _MEASURE % {"n": n}],
                         env=env, capture_output=True, text=True,
                         timeout=420)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])["tpot_s"]


def run(quick: bool = False):
    degrees = (2, 4) if quick else (2, 4, 8)
    model = model_ir("qwen2.5-32b")   # fits TP=2 at fp16 w/ KV room
    cluster = h100_node(8)
    reqs = get_trace("chat", arrival_rate=4.0, num_requests=32)
    search = ApexSearch(model, cluster)

    predicted = {}
    for tp in degrees:
        scheme = [s for s in generate_schemes(model, tp,
                                              allow_cell_dp=False)
                  if s.model_dp == 1 and s.pp_stages == 1
                  and s.stage_devices == tp][0]
        rep = search.evaluate(scheme, reqs)
        if not rep.feasible or rep.tpot_mean <= 0:
            raise RuntimeError(f"TP={tp} plan infeasible for this model")
        predicted[tp] = rep.tpot_mean

    measured = {tp: _measure(tp) for tp in degrees}
    base = degrees[0]
    rows = []
    for tp in degrees:
        p_rel = predicted[tp] / predicted[base]
        m_rel = measured[tp] / measured[base]
        rows.append(dict(tp=tp, predicted_ms=predicted[tp] * 1e3,
                         measured_ms=measured[tp] * 1e3,
                         predicted_rel=p_rel, measured_rel=m_rel))
        csv_row(f"fig7/tp{tp}", measured[tp] * 1e6,
                f"pred_tpot={predicted[tp] * 1e3:.1f}ms "
                f"pred_rel={p_rel:.2f} meas_rel={m_rel:.2f}")
    return rows


if __name__ == "__main__":
    run()
