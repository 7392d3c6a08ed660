"""Fig. 6 reproduction — simulation fidelity: predicted vs ACTUAL speedups.

The paper compares APEX-predicted speedups against vLLM/SGLang runs on
GPUs (mean relative error 10.7%).  Here the loop closes the same way at
reduced scale on whatever device jax reports first: the simulator
(profiling tables MEASURED on that device in the model's dtype,
core/profiles.MeasuredBackend) predicts serving outcomes for configuration
variants, and the REAL JAX engine (serving/engine.py) runs them.  Variants
exercised: max-batch-size caps — the serving-dynamics knob (paper §4.6)
measurable on one device.

Reported: per-variant predicted vs actual slowdown relative to the best
variant + mean relative error.
"""

from __future__ import annotations

import jax
import numpy as np

from repro import configs as C
from repro.core import ApexSearch, BatchingPolicy, MeasuredBackend, Request
from repro.core.planner import heuristic_scheme
from repro.data.requests import make_serving_requests
from repro.models import transformer as T
from repro.serving.engine import ServingEngine

from .common import Timer, csv_row

CAPS = (1, 2, 4)


def run(arch: str = "qwen2_0_5b", n_requests: int = 6, gen_len: int = 8,
        ctx: int = 12, quick: bool = False):
    cfg = C.get_reduced(arch)
    model = cfg.to_ir()
    caps = CAPS[:2] if quick else CAPS

    # --- real engine runs ---
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = make_serving_requests("chat", 1000.0, n_requests,
                                 cfg.vocab_size, max_len=ctx)
    for r in reqs:
        r["gen_len"] = gen_len
        r["prompt"] = r["prompt"][:ctx]
    actual = {}
    for cap in caps:
        eng = ServingEngine(cfg, params, max_batch=cap, max_len=64)
        rep = eng.run([dict(r) for r in reqs], time_scale=0.0)
        actual[cap] = rep.total_time

    # --- simulator predictions with op tables measured on this device ---
    backend = MeasuredBackend()
    cluster = backend.cluster
    search = ApexSearch(model, cluster, backend=backend)
    search.store.x_max = 4096
    sim_reqs = [Request(rid=r["rid"], arrival=0.0,
                        context_len=len(r["prompt"]), gen_len=r["gen_len"])
                for r in reqs]
    scheme = heuristic_scheme(model, 1, cluster, quant="bf16")
    predicted = {}
    for cap in caps:
        rep = search.evaluate(scheme, sim_reqs,
                              policy=BatchingPolicy(max_batch_size=cap,
                                                    fast_forward=False))
        predicted[cap] = rep.e2e_latency

    # --- compare normalized slowdowns (the paper's speedup-ratio fidelity) ---
    ref = max(caps)
    errs = []
    rows = []
    for cap in caps:
        act = actual[cap] / actual[ref]
        pred = predicted[cap] / predicted[ref]
        err = abs(pred - act) / act
        errs.append(err)
        rows.append(dict(cap=cap, actual_s=actual[cap],
                         predicted_s=predicted[cap],
                         actual_ratio=act, predicted_ratio=pred,
                         rel_err=err))
        csv_row(f"fig6/{arch}/cap{cap}", actual[cap] * 1e6,
                f"pred_ratio={pred:.2f} act_ratio={act:.2f} err={err:.1%}")
    mean_err = float(np.mean(errs))
    csv_row(f"fig6/{arch}/mean_rel_err", mean_err * 1e6,
            f"mean_relative_error={mean_err:.1%} (paper: 10.7%)")
    return rows, mean_err


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
