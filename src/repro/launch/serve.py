"""Serving driver: APEX plan search + real engine execution.

The paper's workflow end-to-end: given (arch, trace, cluster) APEX finds
the optimal parallel execution plan for the FULL model on that cluster;
``serve`` then RUNS ``cfg`` (the FULL config by default, or a reduced one
on a host CPU) through the serving engine, so the fidelity loop closes.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2_1_8b \
        --cluster tpu-v5e-1 --requests 8
    PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --reduced
"""

from __future__ import annotations

import argparse
from typing import List, Optional

import jax

from repro import configs as C
from repro.core import ApexSearch, get_cluster, get_trace
from repro.data.requests import make_serving_requests
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import telemetry
from repro.serving.engine import ServingEngine


def serving_requests(cfg: ModelConfig, trace: str, n: int,
                     arrival_rate: float, max_prompt: int, max_gen: int,
                     seed: int = 0) -> List[dict]:
    """The seeded requests ``serve`` runs: ``trace``'s lengths with prompts
    cut to ``max_prompt`` tokens and generations to ``max_gen``."""
    rqs = make_serving_requests(trace, arrival_rate, n, cfg.vocab_size,
                                seed=seed, max_len=max_prompt)
    for r in rqs:
        r["gen_len"] = min(r["gen_len"], max_gen)
    return rqs


def serve(arch: str = "internlm2_1_8b", cfg: Optional[ModelConfig] = None,
          *, trace: str = "chat", requests: int = 8,
          cluster: str = "tpu-v5e-1", arrival_rate: float = 2.0,
          max_batch: int = 4, max_len: int = 256,
          max_prompt: Optional[int] = None, max_gen: Optional[int] = None,
          seed: int = 0, log=print):
    """Plan ``arch`` on ``cluster``, then serve ``requests`` seeded requests
    with ``cfg`` (default: ``arch``'s FULL config) on this process's
    device, weights drawn from ``PRNGKey(seed)``.  Prompts are cut to
    ``max_prompt`` and generations to ``max_gen`` tokens (both default to
    ``max_len // 4``), and the engine's spans are summed up in one line.
    Returns (baseline report, search result, engine report)."""
    cfg = cfg or C.get_config(arch)
    if cfg.encoder is not None or cfg.embeds_input:
        raise ValueError(f"{cfg.name}: the serving engine takes token ids "
                         "only; encoder and embedding-input models cannot "
                         "be served yet")

    # 1) APEX plan search for the FULL model on the target cluster
    model_ir = C.get_config(arch).to_ir()
    clu = get_cluster(cluster)
    reqs = get_trace(trace, arrival_rate=0.5, num_requests=64)
    search = ApexSearch(model_ir, clu)
    base = search.evaluate_baseline(reqs)
    best = search.search(reqs, feasible_only=False)
    log(f"APEX [{clu.name}]: baseline {base.plan_label} "
        f"e2e={base.e2e_latency:.1f}s")
    log(f"APEX [{clu.name}]: optimal  {best.best.plan_label} "
        f"e2e={best.best.e2e_latency:.1f}s "
        f"({base.e2e_latency / best.best.e2e_latency:.2f}x) "
        f"[{best.num_schemes} plans in {best.search_seconds:.1f}s]")

    # 2) serve cfg on this process's device
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    recorder = telemetry.EngineTrace()
    engine = ServingEngine(cfg, params, max_batch=max_batch, max_len=max_len,
                           trace=recorder)
    rqs = serving_requests(cfg, trace, requests, arrival_rate,
                           max_prompt or max_len // 4,
                           max_gen or max_len // 4, seed)
    report = engine.run(rqs, time_scale=0.0)   # all arrive at t=0
    log(f"engine [{cfg.name}]: {len(report.results)} requests, "
        f"{telemetry.summary(recorder.spans)}")
    return base, best, report


def main():
    from .compile_cache import enable_compile_cache
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2_1_8b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the arch's REDUCED config (host CPU)")
    ap.add_argument("--trace", default="chat")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--cluster", default="tpu-v5e-1")
    args = ap.parse_args()
    enable_compile_cache()
    serve(args.arch, C.get_reduced(args.arch) if args.reduced else None,
          trace=args.trace, requests=args.requests, cluster=args.cluster)


if __name__ == "__main__":
    main()
