"""DeepSeek-V2-Lite on the served path, held to the benchmark's plain
float32 reference (``benchmarks/chip/configs/deepseek-v2-lite-stage.py``).

The configuration is cut to a CPU size that keeps its published ratios:
one dense layer then three MoE layers, 8 routed experts of which each token
takes 2, one shared expert, a latent wider than a head, and YaRN as the
configuration states it.  Weights are drawn from a seed in float32, so the
program and the reference differ by summation order only, and the three
faults the program once had (renormalised gates, no YaRN, no latent norm)
each move the logits by far more than that.
"""

import dataclasses
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.layers import attention as A
from repro.layers import moe as M
from repro.layers.rope import apply_rope, yarn_correction_range, yarn_mscale
from repro.models import decode_step, forward, init_cache, init_params
from repro.models.config import RopeScaling
from repro.serving.engine import ServingEngine

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
SEED = 20240507
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, intermediate_size=96, moe_intermediate_size=48,
             n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
             num_hidden_layers=4, vocab_size=256, torch_dtype="float32")
# float32 program against the float32 reference: summation order alone,
# 2.7e-6 on the logits (unit scale) as measured; each fault moves them by
# more than 1 (1.19-1.45 measured).  The bound sits between, clear of both.
TOL = 1e-3
REQUESTS = [(5, 6), (3, 9), (7, 4), (4, 5)]      # (prompt, output) lengths


def _stage():
    path = CHIP / "configs" / "deepseek-v2-lite-stage.py"
    spec = importlib.util.spec_from_file_location("dsv2_stage", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cfg = json.loads(path.with_suffix(".json").read_text())
    cfg.update(SMALL)
    return mod, cfg


@pytest.fixture(scope="module")
def stage():
    mod, cfg = _stage()
    return mod, cfg, mod.make_weights(SEED, cfg)


def _served(cfg, params, requests, max_len=32):
    """ServingEngine with 3 slots; every row of logits the step computed
    for a slot it was serving, keyed (rid, position of the fed token)."""
    eng = ServingEngine(cfg, params, max_batch=3, max_len=max_len)
    step, prefill = eng._decode, eng._prefill_slot
    replaying, rows = [], {}

    def prefill_slot(i):
        replaying.append(i)
        try:
            prefill(i)
        finally:
            replaying.pop()

    def decode(p, toks, cache):
        lens = np.array(cache["len"])       # the step consumes the cache
        nxt, logits, new = step(p, toks, cache)
        slots = replaying[-1:] or [i for i, s in enumerate(eng.slots)
                                   if s.active]
        for i in slots:
            rows[eng.slots[i].rid, int(lens[i])] = np.asarray(logits[i])
        return nxt, logits, new

    eng._prefill_slot, eng._decode = prefill_slot, decode
    report = eng.run(requests)
    return rows, {r.rid: r.tokens for r in report.results}


def _requests(vocab):
    rng = np.random.default_rng(SEED)
    return [{"rid": i, "arrival": 0.0, "gen_len": g,
             "prompt": rng.integers(1, vocab, size=p, dtype=np.int32)}
            for i, (p, g) in enumerate(REQUESTS)]


def _engine_error(mod, cfg, params, program_cfg):
    """Largest |program - reference| over every logit row the engine
    served: prompt replay, then decoding through the cache."""
    reqs = _requests(cfg["vocab_size"])
    rows, served = _served(program_cfg, params, reqs)
    seqs = [np.concatenate([r["prompt"], served[r["rid"]]])[:-1]
            for r in reqs]
    tokens = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    ref = np.asarray(mod.Reference(cfg, SEED).logits(tokens))
    assert len(rows) == sum(map(len, seqs))
    return max(float(np.abs(row - ref[rid, pos]).max())
               for (rid, pos), row in rows.items())


def _renormalised_gates(cfg, monkeypatch):
    return dataclasses.replace(cfg, norm_topk_prob=True)


def _no_yarn(cfg, monkeypatch):
    return dataclasses.replace(cfg, rope_scaling=None)


def _no_latent_norm(cfg, monkeypatch):
    monkeypatch.setattr(A, "rms_norm", lambda x, w, eps=1e-6: x)
    return cfg


@pytest.mark.parametrize("fault", [None, _renormalised_gates, _no_yarn,
                                   _no_latent_norm])
def test_engine_logits_match_the_reference(stage, monkeypatch, fault):
    mod, cfg, params = stage
    program_cfg = mod.program_config(cfg)
    if fault is not None:
        program_cfg = fault(program_cfg, monkeypatch)
    err = _engine_error(mod, cfg, params, program_cfg)
    if fault is None:
        assert err < TOL
    else:
        assert err > 10 * TOL, fault.__name__


def test_forward_matches_decode_step(stage):
    mod, cfg, params = stage
    program_cfg = mod.program_config(cfg)
    toks = jnp.asarray(_requests(cfg["vocab_size"])[0]["prompt"])[None]
    full = forward(params, program_cfg, tokens=toks)
    cache = init_cache(program_cfg, 1, max_len=16)
    steps = []
    for t in range(toks.shape[1]):
        logits, cache = decode_step(params, program_cfg, toks[:, t:t + 1],
                                    cache)
        steps.append(logits)
    assert float(jnp.abs(full - jnp.stack(steps, 1)).max()) < TOL


def test_reduced_registry_config_runs_yarn_and_deepseek_gates():
    cfg = C.get_reduced("deepseek_v2_lite_16b")
    assert cfg.rope_scaling == C.get_config("deepseek_v2_lite_16b").rope_scaling
    assert not cfg.norm_topk_prob and cfg.top_k < cfg.n_routed
    assert "kv_norm" in init_params(jax.random.PRNGKey(0), cfg)["blocks"][
        "l0"]["attn"]


# -- YaRN constants against the HF formulas ---------------------------------

YARN = RopeScaling(factor=40, original_max_position_embeddings=4096,
                   beta_fast=32, beta_slow=1, mscale=0.707,
                   mscale_all_dim=0.707)


@pytest.mark.parametrize("got, want", [
    (lambda: yarn_correction_range(64, 1e4, YARN), (10, 23)),
    (lambda: round(yarn_mscale(40, 0.707), 5), 1.26080),
    (lambda: round(192 ** -0.5 * yarn_mscale(40, 0.707) ** 2, 5), 0.11472),
    (lambda: yarn_mscale(1.0, 0.707), 1.0),
])
def test_yarn_constants(got, want):
    assert got() == want


def test_yarn_frequencies_keep_divide_and_ramp():
    """Slots 0-10 keep theta^(-i/32), slots 23-31 are divided by 40, and
    the slots between blend the two on a linear ramp; cos/sin scale 1."""
    pos = jnp.ones((1, 1), jnp.int32)
    x = jnp.zeros((1, 1, 1, 64)).at[..., 32:].set(1.0)    # (0, 1) pairs
    q, _ = apply_rope(x, x, pos, 1e4, YARN)
    freq = np.arcsin(np.asarray(q[0, 0, 0, :32]) * -1.0)   # x1 cos - x2 sin
    plain = 1e4 ** (-np.arange(32) / 32)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(freq, plain / 40 * ramp + plain * (1 - ramp),
                               rtol=1e-5)
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], plain[23:] / 40, rtol=1e-5)


# -- the other configurations are bit-identical to before -------------------

def _plain_rope_oracle(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _mixtral_gates_oracle(x, router, top_k):
    logits = x.astype(jnp.float32) @ router
    vals, idx = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(vals, axis=-1)
    combine = jnp.zeros(logits.shape, jnp.float32)
    return jax.vmap(jax.vmap(lambda c, i, g: c.at[i].add(g)))(combine, idx,
                                                              gates)


def _deepseek_gates_oracle(x, router, top_k):
    probs = jax.nn.softmax(x.astype(jnp.float32) @ router, axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    keep = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1]), axis=-2) > 0
    return jnp.where(keep, probs, 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_internlm2_rope_is_bit_identical(dtype):
    cfg = C.get_config("internlm2_1_8b")
    k = jax.random.split(jax.random.PRNGKey(3))
    q = jax.random.normal(k[0], (2, 5, 4, 128)).astype(dtype)
    pos = jax.random.randint(k[1], (2, 5), 0, 2048)
    got, _ = jax.jit(lambda a, p: apply_rope(a, a, p, cfg.rope_theta))(q, pos)
    want = jax.jit(lambda a, p: _plain_rope_oracle(a, p, cfg.rope_theta))(
        q, pos)
    assert jnp.array_equal(got, want)


@pytest.mark.parametrize("rule", ["mixtral", "deepseek"])
def test_gate_rule(rule):
    E, k = 8, 2
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 16))
    params = M.init_moe(jax.random.PRNGKey(2), 16, 8, E, k,
                        dtype=jnp.float32)
    if rule == "mixtral":
        got = M._route(params, x, k, None, True)
        assert jnp.array_equal(got, _mixtral_gates_oracle(
            x, params["router"], k))
        return
    got = M._route(params, x, k, None, False)
    np.testing.assert_allclose(got, _deepseek_gates_oracle(
        x, params["router"], k), rtol=1e-6, atol=1e-7)
    assert float(got.sum(-1).max()) < 1.0          # not renormalised
