"""Per-architecture smoke tests: reduced configs, one forward + one train
step on CPU, asserting output shapes and finiteness; decode consistency."""

import jax
import jax.numpy as jnp
import pytest

from repro import configs as C
from repro.launch.steps import make_train_step
from repro.models import (decode_step, encdec_forward, forward, init_cache,
                          init_encdec_params, init_params)
from repro.training.optimizer import adamw_init

RNG = jax.random.PRNGKey(0)


def _finite(x):
    return bool(jnp.isfinite(jnp.asarray(x, jnp.float32)).all())


@pytest.mark.parametrize("arch", C.ARCHS)
def test_smoke_forward(arch):
    cfg = C.get_reduced(arch)
    cfg.validate()
    B, S = 2, 16
    if cfg.encoder is not None:
        params = init_encdec_params(RNG, cfg)
        frames = jax.random.normal(RNG, (B, 12, cfg.d_model), jnp.float32)
        toks = jnp.ones((B, S), jnp.int32)
        logits = encdec_forward(params, cfg, frames, toks)
    else:
        params = init_params(RNG, cfg)
        if cfg.embeds_input:
            emb = jax.random.normal(RNG, (B, S, cfg.d_model), jnp.float32)
            logits = forward(params, cfg, embeds=emb)
        else:
            toks = jax.random.randint(RNG, (B, S), 0, cfg.vocab_size)
            logits = forward(params, cfg, tokens=toks)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert _finite(logits)


@pytest.mark.parametrize("arch", C.ARCHS)
def test_smoke_train_step(arch):
    cfg = C.get_reduced(arch)
    B, S = 2, 16
    if cfg.encoder is not None:
        params = init_encdec_params(RNG, cfg)
    else:
        params = init_params(RNG, cfg)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, microbatches=1, remat=True))
    batch = {"tokens": jnp.ones((B, S), jnp.int32),
             "labels": jnp.ones((B, S), jnp.int32)}
    if cfg.encoder is not None:
        batch["frames"] = jax.random.normal(RNG, (B, 12, cfg.d_model),
                                            jnp.float32)
    elif cfg.embeds_input:
        batch["embeds"] = jax.random.normal(RNG, (B, S, cfg.d_model),
                                            jnp.float32)
    new_params, new_opt, metrics = step(params, opt, batch)
    assert _finite(metrics["loss"])
    assert float(metrics["loss"]) > 0
    assert int(new_opt.step) == 1
    # parameters actually moved
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        params, new_params)
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("arch", ["internlm2_1_8b", "gemma3_12b",
                                  "mixtral_8x7b", "deepseek_v2_lite_16b",
                                  "mamba2_2_7b", "zamba2_7b",
                                  "qwen1_5_32b"])
def test_decode_matches_forward(arch):
    cfg = C.get_reduced(arch)
    params = init_params(RNG, cfg)
    B, S = 1, 10
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0,
                              cfg.vocab_size)
    full = forward(params, cfg, tokens=toks).astype(jnp.float32)
    cache = init_cache(cfg, B, max_len=32)
    outs = []
    for t in range(S):
        lg, cache = decode_step(params, cfg, toks[:, t:t + 1], cache)
        outs.append(lg)
    dec = jnp.stack(outs, axis=1).astype(jnp.float32)
    rel = float(jnp.abs(full - dec).max() / (jnp.abs(full).max() + 1e-9))
    assert rel < 0.05      # bf16 accumulation-order differences only


def test_param_counts_match_ir():
    """The JAX model and the APEX IR agree on parameter counts."""
    from repro.models import param_count
    for arch in ["internlm2_1_8b", "mixtral_8x7b", "mamba2_2_7b"]:
        cfg = C.get_reduced(arch)
        params = init_params(RNG, cfg)
        n_jax = param_count(params)
        n_ir = cfg.to_ir().total_params()
        # IR omits norms / small vectors; agreement within 5%
        assert abs(n_jax - n_ir) / n_jax < 0.05, (arch, n_jax, n_ir)


def test_full_config_ir_sizes():
    """Full assigned configs produce sane parameter counts (billions)."""
    expect = {"gemma3_12b": (10, 16), "qwen1_5_32b": (28, 36),
              "mixtral_8x7b": (40, 52), "mamba2_2_7b": (2.2, 3.2),
              "deepseek_v2_lite_16b": (12, 18)}
    for arch, (lo, hi) in expect.items():
        n = C.get_config(arch).to_ir().total_params() / 1e9
        assert lo <= n <= hi, f"{arch}: {n:.1f}B outside [{lo},{hi}]"


def test_shard_hint_is_identity_only_without_a_mesh():
    """No mesh: every hint is the identity.  Under a mesh, a hint that
    cannot be applied raises instead of silently replicating."""
    from repro.launch.mesh import make_mesh
    from repro.layers.hints import data_axis_names, mesh_axis_size, \
        shard_hint
    x = jnp.ones((4, 8))
    assert shard_hint(x, "data", "model") is x
    assert mesh_axis_size("model") == 1 and data_axis_names() == ()
    with jax.set_mesh(make_mesh((1, 1), ("data", "model"))):
        assert data_axis_names() == ("data",)
        with pytest.raises(Exception, match="no_such_axis"):
            jax.jit(lambda y: shard_hint(y, "no_such_axis", None))(x)
