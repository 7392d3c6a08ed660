"""Per-architecture smoke tests: reduced configs, one forward + one train
step on CPU, asserting output shapes and finiteness; decode consistency."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.launch.steps import make_train_step
from repro.models import (decode_step, encdec_forward, encdec_prefill,
                          forward, init_cache, init_encdec_params,
                          init_params)
from repro.models import transformer as T
from repro.serving.engine import make_decode_step
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
                                  "qwen1_5_32b", "granite_4_0_h_micro"])
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


def _layer_by_layer_step(params, cfg, tokens, cache):
    """``decode_step``'s oracle for a one-layer block pattern: each layer
    in turn on its own unstacked weights and cache, with no layer index,
    so an MoE layer runs ``moe_forward`` on its (E, ...) expert leaves."""
    (spec,) = cfg.block_pattern
    at = lambda tree, r: jax.tree.map(lambda a: a[r], tree)
    x, n = params["embed"][tokens], cache["len"]
    new = {"len": n + 1}
    if "prefix" in cache:
        new["prefix"] = []
        for blk, pc in zip(params["prefix"], cache["prefix"]):
            x, lc = T._layer_decode(cfg, spec, blk["l0"], x, pc["l0"], n)
            new["prefix"].append({"l0": lc})
    stacked = cache["blocks"]["l0"]
    for r in range(jax.tree.leaves(stacked)[0].shape[0]):
        x, lc = T._layer_decode(cfg, spec, at(params["blocks"]["l0"], r), x,
                                at(stacked, r), n)
        stacked = jax.tree.map(lambda s, v: s.at[r].set(v), stacked, lc)
    new["blocks"] = {"l0": stacked}
    x = T.rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    return (x @ head)[:, 0, :], new


@pytest.mark.parametrize("arch", ["mixtral_8x7b", "deepseek_v2_lite_16b"])
def test_decode_step_reads_stacked_experts_in_place(arch):
    """The decode step hands each MoE layer's routed experts to the expert
    scan stacked, with the layer index (Mixtral's gates, then DeepSeek's
    behind a dense first layer).  Over 4 steps at 2 slots its logits and
    cache are those of running each layer on its own unstacked leaves;
    3 scanned layers, each with weights of its own, so a wrong layer index
    shows (float32, to rounding)."""
    base = C.get_reduced(arch)
    cfg = dataclasses.replace(base, block_repeat=base.first_k_dense + 3,
                              dtype="float32")
    params = init_params(RNG, cfg)
    assert params["blocks"]["l0"]["ffn"]["w_up"].shape[:2] == (
        3, cfg.n_routed)
    toks = jax.random.randint(jax.random.PRNGKey(5), (4, 2, 1), 0,
                              cfg.vocab_size)
    cache = want_cache = init_cache(cfg, 2, max_len=16)
    for t in range(toks.shape[0]):
        got, cache = decode_step(params, cfg, toks[t], cache)
        want, want_cache = _layer_by_layer_step(params, cfg, toks[t],
                                                want_cache)
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-5 * float(jnp.abs(want).max()))
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-5 * float(jnp.abs(b).max()))


# slot b takes PROMPT[b] tokens alone, then JOINT more beside the others;
# the longest passes a 32-entry ring (window 16) and wraps
PROMPT, JOINT, SLOT_MAX_LEN = (3, 14, 36), 6, 48


def _slot_frames(cfg, slot):
    """An encoder-decoder slot's source frames (None for a decoder)."""
    if cfg.encoder is None:
        return None
    return jax.random.normal(jax.random.PRNGKey(slot), (1, 12, cfg.d_model),
                             jnp.float32)


def _slot_start(cfg, params, frames):
    """An empty one-slot cache; an encoder-decoder's holds the cross K/V of
    ``frames`` and has taken the start token 0."""
    if frames is None:
        return init_cache(cfg, 1, SLOT_MAX_LEN)
    _, cache, _ = encdec_prefill(params, cfg, frames,
                                 jnp.zeros((1, 1), jnp.int32), SLOT_MAX_LEN)
    return cache


def _slot_forward(cfg, params, frames, toks):
    """The full-sequence logits of the tokens a slot took, one row per
    token in ``toks`` (after the start token of an encoder-decoder)."""
    if frames is None:
        return forward(params, cfg, tokens=toks)[0]
    dec = jnp.concatenate([jnp.zeros((1, 1), toks.dtype), toks], axis=1)
    return encdec_forward(params, cfg, frames, dec)[0, 1:]


def _slot_axis(path):
    """The slot (batch) axis of a cache leaf: after the layer axis of the
    scanned leaves, first in the prefix layers' leaves, ``len`` and an SSM
    cache's ``advance``."""
    return 0 if path[0].key in ("len", "advance", "prefix") else 1


def _assert_written_only(before, after):
    """Between two caches a step may change only what it writes: each
    slot's entry at its length (modulo a ring's size) in attention K/V
    and MLA's latent and rope key, every SSM state (every slot is
    advanced here); ``len`` advances by one, cross-attention K/V and
    ``advance`` stay as they were."""
    lens = before["len"]

    def check(path, old, new):
        name = path[-1].key
        if name in ("ssm", "conv_x", "conv_bc"):
            return
        if name == "len":
            np.testing.assert_array_equal(new, old + 1)
            return
        old = old.copy()
        if name in ("k", "v", "c_kv", "k_pe"):
            seq = _slot_axis(path) + 1
            for b, n in enumerate(lens):
                at = (slice(None),) * (seq - 1) + (b, n % old.shape[seq])
                old[at] = new[at]
        assert old.tobytes() == new.tobytes(), jax.tree_util.keystr(path)

    jax.tree_util.tree_map_with_path(check, before, after)


@pytest.mark.parametrize("arch", ["internlm2_1_8b", "gemma3_12b",
                                  "mixtral_8x7b", "deepseek_v2_lite_16b",
                                  "mamba2_2_7b", "zamba2_7b",
                                  "qwen1_5_32b", "seamless_m4t_large_v2",
                                  "granite_4_0_h_micro"])
def test_donated_step_serves_slots_of_unequal_lengths(arch):
    """The engine's step (jitted, cache donated) over 3 slots that hold
    3, 14 and 36 tokens: each slot's logits are those of the same tokens
    decoded alone, and those a full-sequence forward pass gives within any
    window (float32, to rounding); every step consumes the cache it is given, and writes
    nothing but each slot's new entry or state."""
    cfg = dataclasses.replace(C.get_reduced(arch), dtype="float32")
    params = (init_params(RNG, cfg) if cfg.encoder is None
              else init_encdec_params(RNG, cfg))
    step = make_decode_step(cfg)
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7), (len(PROMPT), max(PROMPT) + JOINT), 0,
        cfg.vocab_size), np.int32)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    # a ring keeps more than its window (models/transformer.ring_size), so
    # the full sequence agrees with it only within the window
    horizon = min([s.window for s in cfg.block_pattern if s.window],
                  default=SLOT_MAX_LEN)
    starts, alone = [], []
    for b, n in enumerate(PROMPT):
        frames = _slot_frames(cfg, b)
        cache = _slot_start(cfg, params, frames)
        full = f32(_slot_forward(cfg, params, frames,      # causal
                                 jnp.asarray(toks[b:b + 1])))
        for t in range(n + JOINT):
            if t == n:
                starts.append(jax.tree.map(np.array, cache))
            _, logits, cache = step(params, jnp.asarray(toks[b:b + 1, t:t + 1]),
                                    cache)
            row = f32(logits[0])
            if t < horizon:
                np.testing.assert_allclose(row, full[t], rtol=0,
                                           atol=1e-4 * np.abs(full[t]).max())
            if t >= n:
                alone.append((b, t, row))
    cache = jax.tree_util.tree_map_with_path(
        lambda path, *xs: jnp.asarray(np.concatenate(xs, _slot_axis(path))),
        *starts)
    fed = np.asarray(PROMPT)
    for j in range(JOINT):
        before = jax.tree.map(np.array, cache)
        tok = toks[np.arange(len(PROMPT)), fed + j][:, None]
        _, logits, new = step(params, jnp.asarray(tok), cache)
        assert all(x.is_deleted() for x in jax.tree.leaves(cache))
        _assert_written_only(before, jax.tree.map(np.array, new))
        cache = new
        for b, t, row in alone:
            if t == fed[b] + j:     # batched in another order
                np.testing.assert_allclose(f32(logits[b]), row, rtol=0,
                                           atol=1e-5 * np.abs(row).max())


def test_param_counts_match_ir():
    """The JAX model and the APEX IR agree on parameter counts."""
    from repro.models import param_count
    for arch in ["internlm2_1_8b", "mixtral_8x7b", "mamba2_2_7b",
                 "granite_4_0_h_micro"]:
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
              "deepseek_v2_lite_16b": (12, 18),
              "granite_4_0_h_micro": (3.1, 3.3)}
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
