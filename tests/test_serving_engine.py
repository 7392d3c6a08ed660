"""Real serving engine: completion, preemption, routing, fidelity hooks."""

import jax
import pytest

from repro import configs as C
from repro.data.requests import make_serving_requests
from repro.models import transformer as T
from repro.serving.engine import ServingEngine
from repro.serving.router import ReplicaRouter


@pytest.fixture(scope="module")
def small():
    cfg = C.get_reduced("qwen2_0_5b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(cfg, n, gen=6, ctx=12, rate=100.0):
    rs = make_serving_requests("chat", rate, n, cfg.vocab_size, max_len=ctx)
    for r in rs:
        r["gen_len"] = gen
        r["prompt"] = r["prompt"][:ctx]
    return rs


def test_all_requests_served(small):
    cfg, params = small
    eng = ServingEngine(cfg, params, max_batch=3, max_len=64)
    rep = eng.run(_reqs(cfg, 5), time_scale=0.0)
    assert len(rep.results) == 5
    for r in rep.results:
        assert len(r.tokens) == 6
        assert r.e2e >= r.ttft >= 0


def test_ttft_and_tpot_on_the_virtual_clock(small):
    """All requests arrive at t=0 and one slot serves them in turn: each
    first token is stamped when its own prefill ends (never at t=0, and
    after the previous request finished), and every decode gap is > 0."""
    cfg, params = small
    eng = ServingEngine(cfg, params, max_batch=1, max_len=64)
    rep = eng.run(_reqs(cfg, 3), time_scale=0.0)
    res = sorted(rep.results, key=lambda r: r.e2e)
    for r in res:
        assert 0 < r.ttft < r.e2e and r.tpot > 0
    for prev, nxt in zip(res, res[1:]):
        assert nxt.ttft > prev.e2e


def test_greedy_decode_deterministic(small):
    cfg, params = small
    e1 = ServingEngine(cfg, params, max_batch=2, max_len=64)
    e2 = ServingEngine(cfg, params, max_batch=2, max_len=64)
    r1 = e1.run(_reqs(cfg, 3), time_scale=0.0)
    r2 = e2.run(_reqs(cfg, 3), time_scale=0.0)
    t1 = {r.rid: r.tokens for r in r1.results}
    t2 = {r.rid: r.tokens for r in r2.results}
    assert t1 == t2


def test_kv_budget_preemption(small):
    cfg, params = small
    eng = ServingEngine(cfg, params, max_batch=4, max_len=64,
                        kv_token_budget=40)
    rep = eng.run(_reqs(cfg, 4, gen=8, ctx=16), time_scale=0.0)
    assert len(rep.results) == 4           # everyone completes eventually
    assert rep.preemptions >= 0


def test_router_spreads_load(small):
    cfg, params = small
    engines = [ServingEngine(cfg, params, max_batch=2, max_len=64)
               for _ in range(2)]
    router = ReplicaRouter(engines)
    buckets = router.split(_reqs(cfg, 6))
    assert len(buckets) == 2
    assert abs(len(buckets[0]) - len(buckets[1])) <= 1


def test_engine_matches_model_decode(small):
    """Engine-produced tokens == raw greedy decode_step tokens."""
    import jax.numpy as jnp
    cfg, params = small
    prompt = jnp.asarray([[5, 9, 3, 7]], jnp.int32)
    # reference: prefill + greedy decode
    from repro.models import init_cache, decode_step
    cache = init_cache(cfg, 1, 64)
    for t in range(4):
        logits, cache = decode_step(params, cfg, prompt[:, t:t + 1], cache)
    toks = [int(jnp.argmax(logits[0]))]
    for _ in range(3):
        logits, cache = decode_step(
            params, cfg, jnp.asarray([[toks[-1]]], jnp.int32), cache)
        toks.append(int(jnp.argmax(logits[0])))
    eng = ServingEngine(cfg, params, max_batch=1, max_len=64)
    rep = eng.run([dict(rid=0, arrival=0.0,
                        prompt=[5, 9, 3, 7], gen_len=4)], time_scale=0.0)
    assert rep.results[0].tokens == toks


def test_serve_reduced_config_logits_match_float32_forward():
    """``serve()`` runs the config it is given; each request's first-token
    logits as served (bf16) agree with a float32 ``forward`` of its prompt
    on the same weights — the check chip_smoke.py makes at FULL width."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import serve, serving_requests
    cfg = C.get_reduced("internlm2_1_8b")
    _, _, rep = serve("internlm2_1_8b", cfg, trace="creation", requests=3,
                      cluster="tpu-v5e-1", max_batch=2, max_len=64,
                      max_prompt=12, max_gen=5, seed=3, log=lambda m: None)
    rqs = {r["rid"]: r for r in serving_requests(cfg, "creation", 3, 2.0,
                                                 12, 5, seed=3)}
    assert sorted(r.rid for r in rep.results) == sorted(rqs)
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32),
                       T.init_params(jax.random.PRNGKey(3), cfg))
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    for r in rep.results:
        assert len(r.tokens) == rqs[r.rid]["gen_len"]
        assert all(0 <= t < cfg.vocab_size for t in r.tokens)
        prompt = jnp.asarray(rqs[r.rid]["prompt"])[None]
        ref = np.asarray(T.forward(p32, cfg32, tokens=prompt)[0, -1])
        err = np.linalg.norm(r.first_logits - ref) / np.linalg.norm(ref)
        assert err < 5e-2, (r.rid, err)


@pytest.mark.parametrize("arch", ["seamless_m4t_large_v2", "qwen2_vl_7b"])
def test_serve_refuses_what_the_engine_cannot_run(arch):
    from repro.launch.serve import serve
    with pytest.raises(ValueError, match="token ids only"):
        serve(arch, C.get_reduced(arch), log=lambda m: None)


def test_compile_cache_env_var_wins_else_fixed_repo_dir(monkeypatch):
    from repro.launch import compile_cache as cc
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert cc.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None  # jax reads env
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = cc.enable_compile_cache()
        assert path == str(cc.REPO_CACHE_DIR) == \
            jax.config.jax_compilation_cache_dir
        assert path.endswith(".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was[1])
