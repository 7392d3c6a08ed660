"""Granite-4.0-H on the served path, held to the benchmark's plain float32
reference (``benchmarks/chip/configs/granite-4.0-h-micro.py``).

The configuration is cut to a CPU size that keeps its structure: two
periods of (Mamba-2, Mamba-2, NoPE attention), each layer followed by its
SwiGLU MLP, one SSM group, GQA with two query heads a KV head, and the
muP scalars as published.  Weights are drawn from a seed in float32, so
the program and the reference differ by summation order only; each fault
below (a muP scalar dropped, the MLP after a Mamba layer skipped, or a
slot's state advanced by other slots' replays) moves the logits by far
more than that.
"""

import dataclasses
import importlib.util
import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import forward, transformer as T
from repro.serving.engine import ServingEngine

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
SEED = 20251002
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
             shared_intermediate_size=96, intermediate_size=96,
             num_hidden_layers=6, layer_types=["mamba", "mamba",
                                               "attention"] * 2,
             vocab_size=256, torch_dtype="float32")
# float32 program against the float32 reference, as a share of the largest
# reference logit: the program's rms eps 1e-6 against the published 1e-5
# moves the logits by 8.2e-4 as measured (the scaled embedding has std 0.1,
# so eps is 1e-3 of its mean square); summation order alone (the step-by-
# step or chunked SSD scan against the reference's scan) by 1e-6.  Each
# fault moves them by 0.25 or more; the bound sits between, clear of both.
TOL = 2e-3
REQUESTS = [(5, 6), (3, 9), (7, 4), (4, 5), (6, 3)]   # (prompt, output)


def _module():
    path = CHIP / "configs" / "granite-4.0-h-micro.py"
    spec = importlib.util.spec_from_file_location("granite_micro", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cfg = json.loads(path.with_suffix(".json").read_text())
    cfg.update(SMALL)
    return mod, cfg


@pytest.fixture(scope="module")
def granite():
    mod, cfg = _module()
    return mod, cfg, mod.make_weights(SEED, cfg)


def _served(cfg, params, requests, served_rows):
    """ServingEngine with 3 slots, warmed up as the chip benchmark warms
    it: each slot reused, by requests due at once, so each admission
    replays a prompt beside live slots."""
    eng = ServingEngine(cfg, params, max_batch=3, max_len=32)
    eng.run([{"rid": -1 - i, "arrival": 0.0, "gen_len": 2,
              "prompt": np.full(2, 1 + i, np.int32)} for i in range(3)])
    rows, results = served_rows(eng, requests)
    return rows, {r.rid: r.tokens for r in results}


def _requests(vocab):
    rng = np.random.default_rng(SEED)
    return [{"rid": i, "arrival": 0.0, "gen_len": g,
             "prompt": rng.integers(1, vocab, size=p, dtype=np.int32)}
            for i, (p, g) in enumerate(REQUESTS)]


def _engine_error(mod, cfg, params, program_cfg, served_rows):
    """Largest |program - reference| over every logit row the engine
    served, over the largest reference logit: prompt replay, then decoding
    through the cache."""
    reqs = _requests(cfg["vocab_size"])
    rows, served = _served(program_cfg, params, reqs, served_rows)
    seqs = [np.concatenate([r["prompt"], served[r["rid"]]])[:-1]
            for r in reqs]
    tokens = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    ref = np.asarray(mod.Reference(cfg, SEED).logits(tokens))
    assert len(rows) == sum(map(len, seqs))
    scale = np.abs(ref).max()
    return max(float(np.abs(row - ref[rid, pos]).max())
               for (rid, pos), row in rows.items()) / scale


def _no_residual_multiplier(cfg, monkeypatch):
    return dataclasses.replace(cfg, residual_multiplier=1.0)


def _no_embedding_multiplier(cfg, monkeypatch):
    return dataclasses.replace(cfg, embedding_multiplier=1.0)


def _default_attention_scale(cfg, monkeypatch):
    return dataclasses.replace(cfg, attn_scale=None)


def _no_logits_scaling(cfg, monkeypatch):
    return dataclasses.replace(cfg, logits_scaling=1.0)


def _no_mlp_after_mamba(cfg, monkeypatch):
    ffn = T._ffn_sublayer
    monkeypatch.setattr(T, "_ffn_sublayer", lambda c, p, x, layer=None:
                        x if "mixer" in p else ffn(c, p, x, layer))
    return cfg


def _state_shared_by_slots(cfg, monkeypatch):
    """Every step advances every slot's state and resets none."""
    step = T.mamba2_decode_step
    monkeypatch.setattr(T, "mamba2_decode_step", lambda *a, advance, reset,
                        **k: step(*a, advance=jnp.ones_like(advance),
                                  reset=jnp.zeros_like(reset), **k))
    return cfg


@pytest.mark.parametrize("fault", [None, _no_residual_multiplier,
                                   _no_embedding_multiplier,
                                   _default_attention_scale,
                                   _no_logits_scaling, _no_mlp_after_mamba,
                                   _state_shared_by_slots])
def test_engine_logits_match_the_reference(granite, monkeypatch, served_rows,
                                           fault):
    mod, cfg, params = granite
    program_cfg = mod.program_config(cfg)
    if fault is not None:
        program_cfg = fault(program_cfg, monkeypatch)
    err = _engine_error(mod, cfg, params, program_cfg, served_rows)
    if fault is None:
        assert err < TOL
    else:
        assert err > 50 * TOL, (fault.__name__, err)


def test_forward_matches_the_reference(granite):
    """The full-sequence forward (chunked SSD scan, blockwise attention)
    gives the reference's logits."""
    mod, cfg, params = granite
    toks = np.stack([r["prompt"][:3] for r in _requests(cfg["vocab_size"])])
    full = np.asarray(forward(params, mod.program_config(cfg),
                              tokens=jnp.asarray(toks)))
    ref = np.asarray(mod.Reference(cfg, SEED).logits(toks))
    assert np.abs(full - ref).max() < TOL * np.abs(ref).max()


def test_program_config_is_the_published_stack():
    """At full size: 4 periods of 9 Mamba-2 layers and one NoPE attention
    layer in the published order, every width as the config gives it, and
    3.19 B parameters in the program's pytree."""
    import jax
    from repro import configs as C
    from repro.models import init_params, param_count
    mod = _module()[0]
    raw = json.loads((CHIP / "configs" / "granite-4.0-h-micro.json")
                     .read_text())
    cfg = mod.program_config(raw)
    assert cfg == C.get_config("granite-4.0-h-micro")
    assert "".join("a" if s.kind == "attn" else "m"
                   for s in cfg.block_pattern) == "mmmmmammmm"
    assert cfg.block_repeat == 4 and raw["reduced"] == []
    shapes = jax.eval_shape(lambda: mod.make_weights(0, raw))
    assert param_count(shapes) == 3191396096
    assert jax.tree.structure(shapes) == jax.tree.structure(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))


@pytest.mark.parametrize("key, value", [("mamba_n_groups", 8),
                                        ("position_embedding_type", "rope"),
                                        ("num_local_experts", 72)])
def test_program_config_refuses_what_the_program_does_not_compute(key,
                                                                  value):
    mod, cfg = _module()
    with pytest.raises(ValueError, match=key):
        mod.program_config(dict(cfg, **{key: value}))
