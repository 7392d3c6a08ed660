"""Multi-device parallel patterns (subprocess with 8 host devices):
pipeline parallelism, EP dispatch, the served decode step on a
sequence-sharded cache, elastic resharding, plan->sharding translation."""

import pytest


def test_pipeline_matches_sequential(subproc):
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.pipeline import pipeline_forward, make_pp_mesh

n_stages, n_micro, mb, S, d = 4, 8, 2, 8, 16
mesh = make_pp_mesh(n_stages, tp=2)
rng = jax.random.PRNGKey(0)
w = jax.random.normal(rng, (n_stages, d, d)) * 0.3

def stage_fn(wi, x):
    return jnp.tanh(x @ wi)

x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, S, d))
out = pipeline_forward(lambda p, x: stage_fn(p, x), w, x, mesh, n_stages)

# sequential reference
ref = x
for i in range(n_stages):
    ref = jnp.tanh(ref @ w[i])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                           atol=2e-5)
print("pipeline OK")
""", devices=8)


@pytest.mark.parametrize("norm_topk_prob", [True, False])
def test_ep_matches_dense_oracle(subproc, norm_topk_prob):
    subproc(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.layers.moe import init_moe, moe_forward
from repro.launch.mesh import make_mesh
from repro.parallel.ep import moe_ep_forward

mesh = make_mesh((2, 4), ("data", "model"))
rng = jax.random.PRNGKey(0)
d, f, E, k = 16, 32, 8, 2
params = init_moe(rng, d, f, E, k, dtype=jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, d), jnp.float32)

dense = moe_forward(params, x, k, norm_topk_prob={norm_topk_prob})
ep, drop = moe_ep_forward(params, x, k, mesh, cap_factor=8.0,
                          norm_topk_prob={norm_topk_prob})
assert float(drop) == 0.0, f"unexpected drops: {{float(drop)}}"
np.testing.assert_allclose(np.asarray(ep), np.asarray(dense), rtol=2e-4,
                           atol=2e-4)
print("ep OK")
""", devices=8)


def test_served_decode_step_sharded_matches_unsharded(subproc):
    """The engine's decode step on a (data 2, model 4) mesh, parameters by
    ``param_pspecs`` and the cache sequence-sharded by ``cache_pspecs``,
    matches the same step on one device over steps at ragged lengths."""
    subproc("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import configs as C
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.parallel.sharding import cache_pspecs, param_pspecs, to_shardings
from repro.serving.engine import make_decode_step

cfg = dataclasses.replace(C.get_reduced("internlm2_1_8b"), dtype="float32")
B, MAX_LEN, STEPS = 4, 32, 3
params = T.init_params(jax.random.PRNGKey(0), cfg)
cache = T.init_cache(cfg, B, MAX_LEN)
ks = jax.random.split(jax.random.PRNGKey(1), 3)
kv_shape = cache["blocks"]["l0"]["k"].shape
cache["blocks"]["l0"] = {"k": jax.random.normal(ks[0], kv_shape),
                         "v": jax.random.normal(ks[1], kv_shape)}
cache["len"] = jnp.asarray([3, 10, 17, 29], jnp.int32)
toks = jax.random.randint(ks[2], (STEPS, B, 1), 1, cfg.vocab_size)

step = make_decode_step(cfg)
mesh = make_mesh((2, 4), ("data", "model"))
ps = jax.device_put(params, to_shardings(param_pspecs(params, cfg, mesh),
                                         mesh))
cspecs = cache_pspecs(cache, cfg, mesh)
assert cspecs["blocks"]["l0"]["k"][2] == "model", cspecs
cs = jax.device_put(cache, to_shardings(cspecs, mesh))
assert len(cs["blocks"]["l0"]["k"].sharding.device_set) == 8

for t in range(STEPS):
    _, want, cache = step(params, toks[t], cache)
    with jax.set_mesh(mesh):
        _, got, cs = step(ps, toks[t], cs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
for a, b in zip(jax.tree.leaves(cs), jax.tree.leaves(cache)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)
np.testing.assert_array_equal(np.asarray(cs["len"]), [6, 13, 20, 32])
print("sharded decode OK")
""", devices=8)


def test_served_moe_decode_step_sharded_matches_unsharded(subproc):
    """Reduced DeepSeek (a dense layer, then 3 MoE layers of 8 experts) on
    a (data 2, model 4) mesh: the experts shard over "model", so the step
    takes the expert-sharded einsum on the layer-stacked expert leaves,
    and its logits match the unsharded step (the expert scan) over 3
    steps."""
    subproc("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import configs as C
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.parallel.sharding import cache_pspecs, param_pspecs, to_shardings
from repro.serving.engine import make_decode_step

cfg = dataclasses.replace(C.get_reduced("deepseek_v2_lite_16b"),
                          dtype="float32", block_repeat=4)
B, MAX_LEN, STEPS = 4, 32, 3
params = T.init_params(jax.random.PRNGKey(0), cfg)
cache = T.init_cache(cfg, B, MAX_LEN)
toks = jax.random.randint(jax.random.PRNGKey(2), (STEPS, B, 1), 1,
                          cfg.vocab_size)
step = make_decode_step(cfg)
mesh = make_mesh((2, 4), ("data", "model"))
pspecs = param_pspecs(params, cfg, mesh)
assert pspecs["blocks"]["l0"]["ffn"]["w_up"][1] == "model", pspecs
ps = jax.device_put(params, to_shardings(pspecs, mesh))
cs = jax.device_put(cache, to_shardings(cache_pspecs(cache, cfg, mesh),
                                        mesh))
for t in range(STEPS):
    _, want, cache = step(params, toks[t], cache)
    with jax.set_mesh(mesh):
        _, got, cs = step(ps, toks[t], cs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
print("sharded moe decode OK")
""", devices=8)


def test_elastic_reshard_roundtrip(subproc):
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.training.elastic import reshard_state

state = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
         "b": jnp.ones((8,))}
specs = {"w": P("data", "model"), "b": P("model")}
mesh_a = make_mesh((4, 2), ("data", "model"))
mesh_b = make_mesh((2, 4), ("data", "model"))   # "node failure" remesh
on_a = reshard_state(state, specs, mesh_a)
on_b = reshard_state(on_a, specs, mesh_b)
for k in state:
    np.testing.assert_array_equal(np.asarray(on_b[k]),
                                  np.asarray(state[k]))
print("elastic OK")
""", devices=8)


def test_plan_to_shardings(subproc):
    subproc("""
import jax, jax.numpy as jnp
from repro import configs as C
from repro.core import generate_schemes
from repro.models import transformer as T
from repro.parallel.plan_sharding import plan_to_shardings

cfg = C.get_reduced("internlm2_1_8b")
model_ir = cfg.to_ir()
schemes = generate_schemes(model_ir, 8)
params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))

dp_tp = [s for s in schemes if s.model_dp == 2 and s.pp_stages == 1
         and s.is_feasible_for_current_systems()][0]
mat = plan_to_shardings(dp_tp, cfg, params)
assert not mat.needs_pipeline
assert mat.mesh.shape == {"data": 2, "model": 4}

pp = [s for s in schemes if s.pp_stages == 2 and s.model_dp == 1][0]
mat2 = plan_to_shardings(pp, cfg, params)
assert mat2.needs_pipeline and mat2.pp_stages == 2
print("plan_sharding OK")
""", devices=8)


def test_distributed_train_step_runs(subproc):
    """A REAL sharded train step executes on an 8-device host mesh and
    matches the single-device loss."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs as C
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step
from repro.models import transformer as T
from repro.parallel.sharding import param_pspecs
from repro.training.optimizer import adamw_init

cfg = C.get_reduced("internlm2_1_8b")
mesh = make_mesh((2, 4), ("data", "model"))
params = T.init_params(jax.random.PRNGKey(0), cfg)
opt = adamw_init(params)
step = make_train_step(cfg, microbatches=1, remat=True)
batch = {"tokens": jnp.ones((4, 16), jnp.int32),
         "labels": jnp.ones((4, 16), jnp.int32)}

# single device
p1, o1, m1 = jax.jit(step)(params, opt, batch)

# sharded
pspecs = param_pspecs(params, cfg, mesh, fsdp=True)
sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda s: isinstance(s, P))
ps = jax.device_put(params, sh(pspecs))
ospecs = type(opt)(master=pspecs, m=pspecs, v=pspecs, step=P())
os_ = jax.device_put(opt, sh(ospecs))
bs = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
with jax.set_mesh(mesh):
    p2, o2, m2 = jax.jit(step, in_shardings=(sh(pspecs), sh(ospecs),
                         NamedSharding(mesh, P("data", None))))(ps, os_, bs)
assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-2, \
    (float(m1["loss"]), float(m2["loss"]))
print("distributed train OK")
""", devices=8)
