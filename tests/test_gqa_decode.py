"""Decode attention grouped by KV head (`gqa_decode_step`).

The step views the query heads as (n_kv_heads, g) groups and reads each
cached K/V entry once.  These tests hold it to the materialised-repeat
formula it replaced (kept here as the oracle), over group sizes, linear and
ring caches, cache dtypes and ragged lengths, and check the traced program
never builds a (B, Smax, n_heads, D) value again.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.layers.attention import (NEG_INF, _project_qkv, gqa_decode_step,
                                    init_attention)
from repro.layers.rope import apply_rope
from repro.models import decode_step, init_cache, init_params

D_MODEL, HEAD_DIM, B = 32, 16, 4


def _repeat_oracle(params, x, cache_k, cache_v, cache_len, *, n_heads,
                   n_kv_heads, head_dim, window=None, rope_theta=10000.0):
    """The materialised form: K/V repeated to n_heads over all of Smax."""
    Smax = cache_k.shape[1]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    q, k = apply_rope(q, k, cache_len[:, None], rope_theta)
    ring = window is not None and Smax <= window + 16
    idx = cache_len % Smax if ring else cache_len
    write = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
        c, n.astype(c.dtype), (i, 0, 0)))
    cache_k, cache_v = write(cache_k, k, idx), write(cache_v, v, idx)
    rep = n_heads // n_kv_heads
    kr = jnp.repeat(cache_k.astype(q.dtype), rep, axis=2)
    vr = jnp.repeat(cache_v.astype(q.dtype), rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                   preferred_element_type=jnp.float32) / math.sqrt(head_dim)
    valid = jnp.arange(Smax)[None, :] < jnp.minimum(cache_len + 1,
                                                    Smax)[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(vr.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vr)
    return (out.reshape(x.shape[0], 1, n_heads * head_dim) @ params["wo"],
            cache_k, cache_v)


# (compute dtype, cache dtype)
DTYPES = {"f32": (jnp.float32, jnp.float32),
          "bf16": (jnp.bfloat16, jnp.bfloat16),
          "fp8": (jnp.bfloat16, jnp.float8_e4m3fn)}
# g -> (n_heads, n_kv_heads); g = 7 is qwen2-0.5b's 14 / 2
HEADS = {1: (2, 2), 2: (4, 2), 7: (14, 2)}


def _case(g, cache_kind, dtype):
    n_heads, n_kv_heads = HEADS[g]
    compute, cache_dt = DTYPES[dtype]
    if cache_kind == "ring":
        window, Smax = 23, 24
        # absolute lengths past Smax wrap round the ring
        cache_len = jnp.array([0, 5, Smax - 1, 3 * Smax + 2], jnp.int32)
    else:
        window, Smax = None, 32
        cache_len = jnp.array([0, 5, Smax - 1, Smax // 2], jnp.int32)
    kp, kx, kk, kv = jax.random.split(jax.random.PRNGKey(g), 4)
    params = init_attention(kp, D_MODEL, n_heads, n_kv_heads, HEAD_DIM,
                            dtype=compute)
    x = jax.random.normal(kx, (B, 1, D_MODEL)).astype(compute)
    shape = (B, Smax, n_kv_heads, HEAD_DIM)
    cache_k = jax.random.normal(kk, shape).astype(cache_dt)
    cache_v = jax.random.normal(kv, shape).astype(cache_dt)
    kw = dict(n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=HEAD_DIM,
              window=window, rope_theta=10000.0)
    return (params, x, cache_k, cache_v, cache_len), kw


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("cache_kind", ["linear", "ring"])
@pytest.mark.parametrize("g", sorted(HEADS))
def test_grouped_decode_matches_repeat_oracle(g, cache_kind, dtype):
    args, kw = _case(g, cache_kind, dtype)
    y, ck, cv = jax.jit(lambda *a: gqa_decode_step(*a, **kw))(*args)
    y0, ck0, cv0 = jax.jit(lambda *a: _repeat_oracle(*a, **kw))(*args)
    assert y.dtype == y0.dtype and ck.dtype == ck0.dtype
    np.testing.assert_array_equal(np.asarray(ck.astype(jnp.float32)),
                                  np.asarray(ck0.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(cv.astype(jnp.float32)),
                                  np.asarray(cv0.astype(jnp.float32)))
    y = np.asarray(y.astype(jnp.float32))
    y0 = np.asarray(y0.astype(jnp.float32))
    assert np.isfinite(y).all()
    if dtype == "f32":
        np.testing.assert_allclose(y, y0, rtol=1e-6, atol=1e-6 * np.abs(
            y0).max())
    else:       # one bf16 rounding of the output, at most
        np.testing.assert_allclose(y, y0, rtol=2.0 ** -8,
                                   atol=2.0 ** -8 * np.abs(y0).max())


def _shapes(jaxpr):
    """Every value's shape in a jaxpr and in the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


def _repeated_kv_shapes(closed, smax, n_heads, n_kv_heads):
    """Shapes with the query heads on a cache-length axis: (.., Smax, Hq,
    ..) or the (.., Smax, Hkv, g, ..) that a repeat broadcasts first."""
    g = n_heads // n_kv_heads
    found = set()
    for shape in _shapes(closed.jaxpr):
        for i, n in enumerate(shape):
            if n == smax and (shape[i + 1:i + 2] == (n_heads,)
                              or shape[i + 1:i + 3] == (n_kv_heads, g)):
                found.add(shape)
    return found


def test_grouped_decode_builds_no_repeated_cache():
    args, kw = _case(2, "linear", "bf16")
    smax = args[2].shape[1]
    closed = jax.make_jaxpr(lambda *a: gqa_decode_step(*a, **kw))(*args)
    assert not _repeated_kv_shapes(closed, smax, kw["n_heads"],
                                   kw["n_kv_heads"])
    # the check sees the repeat where there is one
    oracle = jax.make_jaxpr(lambda *a: _repeat_oracle(*a, **kw))(*args)
    assert _repeated_kv_shapes(oracle, smax, kw["n_heads"], kw["n_kv_heads"])


def test_internlm2_decode_step_builds_no_repeated_cache():
    cfg = C.get_reduced("internlm2_1_8b")
    assert cfg.n_heads > cfg.n_kv_heads
    batch, max_len = 3, 40
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, batch, max_len)
    toks = jnp.zeros((batch, 1), jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, t, c: decode_step(p, cfg, t, c))(params, toks, cache)
    assert not _repeated_kv_shapes(closed, max_len, cfg.n_heads,
                                   cfg.n_kv_heads)


def test_grouped_decode_under_a_sequence_sharded_mesh(subproc):
    """DP1xTP4: the hints put the cache's sequence axis on "model"; the
    step then matches the same step with no mesh."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.layers.attention import gqa_decode_step, init_attention

Hq, Hkv, D, Smax, B, dm = 4, 2, 16, 32, 2, 32
params = init_attention(jax.random.PRNGKey(0), dm, Hq, Hkv, D,
                        dtype=jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, dm))
ck = jax.random.normal(jax.random.PRNGKey(2), (B, Smax, Hkv, D))
cv = jax.random.normal(jax.random.PRNGKey(3), (B, Smax, Hkv, D))
n = jnp.array([7, Smax - 1], jnp.int32)
step = jax.jit(lambda *a: gqa_decode_step(*a, n_heads=Hq, n_kv_heads=Hkv,
                                          head_dim=D))
ref = step(params, x, ck, cv, n)
with jax.set_mesh(make_mesh((1, 4), ("data", "model"))):
    got = step(params, x, ck, cv, n)
for a, b in zip(got, ref):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)
print("sharded OK")
""", devices=4)
