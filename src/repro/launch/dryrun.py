import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST run before any other import (jax locks the
# device count at first init).  Everything below is ordinary code.

import argparse          # noqa: E402
import functools         # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs as C                          # noqa: E402
from repro.launch.mesh import make_production_mesh      # noqa: E402
from repro.launch.shapes import SHAPES, input_specs     # noqa: E402
from repro.launch.steps import make_serve_step, make_train_step  # noqa: E402
from repro.launch import hlo_utils                      # noqa: E402
from repro.models import transformer as T               # noqa: E402
from repro.models import encdec as ED                   # noqa: E402
from repro.models.config import ModelConfig             # noqa: E402
from repro.parallel.sharding import (batch_pspec, cache_pspecs,  # noqa: E402
                                     param_pspecs)
from repro.training.optimizer import adamw_init         # noqa: E402

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell and record memory/cost/collective analysis.

This proves the distribution config is coherent without hardware: a
sharding mismatch, compile-time OOM, or unsupported collective fails the
cell.  Results feed EXPERIMENTS.md §Dry-run and the §Roofline analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-12b \
        --shape train_4k --multi-pod both --out results/dryrun.json
"""


# fp8 KV-cache overrides: cells whose bf16 KV cache cannot fit the pod
# (see EXPERIMENTS.md §Dry-run notes).
CACHE_DTYPE_OVERRIDES = {
    ("qwen1_5_32b", "decode_32k"): jnp.float8_e4m3fn,
}


def _struct_params(cfg: ModelConfig):
    if cfg.encoder is not None:
        return jax.eval_shape(
            lambda: ED.init_encdec_params(jax.random.PRNGKey(0), cfg))
    return jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))


def _shard(tree_specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda s: isinstance(s, P))


# q-head counts that don't divide the 16-wide model axis train without
# microbatching so the batch itself can reshard over ("data","model")
# around attention (see parallel/sharding.py head-alignment note).
_MB1_ARCHS = {"qwen2_0_5b", "qwen1_5_32b", "qwen2_vl_7b"}


def _analytic_workspace(cfg: ModelConfig, cell, mesh,
                        microbatches: int) -> float:
    """Per-device activation-workspace estimate (bytes) from the config +
    sharding layout.  Conservative (x2 live-set factor); validated against
    cells free of CPU dtype-normalization artifacts."""
    m = mesh.shape.get("model", 1)
    n_data = 1
    for a in ("pod", "data"):
        n_data *= mesh.shape.get(a, 1)
    B, S = cell.global_batch, cell.seq_len
    d = cfg.d_model
    dt = 2.0                                     # bf16
    v_loc = -(-cfg.vocab_size // m)
    hq = cfg.n_heads
    hd = cfg.resolved_head_dim

    def ceil_div(a, b):
        return -(-a // b)

    if cell.kind == "train":
        b_loc = ceil_div(ceil_div(B, microbatches), n_data)
        toks = b_loc * S
        ws = 16 * toks * d * dt                  # one live layer fwd+bwd
        ws += 2 * b_loc * 512 * v_loc * 4        # loss chunk logits (f32)
        if cfg.ffn_kind == "moe":
            # EP-sharded: e_loc experts at full width; else one expert at
            # a time with the d_ff dim TP-sharded (layers/moe.py layouts)
            if cfg.n_routed % m == 0:
                ws += 3 * ceil_div(cfg.n_routed, m) * toks \
                    * cfg.d_ff_expert * dt
            else:
                ws += 3 * toks * ceil_div(cfg.d_ff_expert, m) * dt
        elif cfg.d_ff:
            ws += 3 * toks * ceil_div(cfg.d_ff, m) * dt
        if any(s.kind == "ssm" for s in cfg.block_pattern):
            q = 128
            nC = ceil_div(S, q)
            ws += nC * b_loc * cfg.n_ssd_heads * \
                (cfg.d_inner // max(cfg.n_ssd_heads, 1)) * cfg.d_state * 4
        ws += 2 * b_loc * hq * 512 * 1024 * 4    # attention tiles (f32)
        return 2.0 * ws
    if cell.kind == "prefill":
        b_loc = ceil_div(B, n_data)
        toks = b_loc * S
        ws = 8 * toks * d * dt
        ws += 2 * b_loc * hq * 512 * 1024 * 4
        if cfg.ffn_kind == "moe":
            if cfg.n_routed % m == 0:
                ws += 3 * ceil_div(cfg.n_routed, m) * toks \
                    * cfg.d_ff_expert * dt
            else:
                ws += 3 * toks * ceil_div(cfg.d_ff_expert, m) * dt
        return 2.0 * ws
    # decode: per-layer KV repeat + scores + head logits
    b_loc = ceil_div(B, n_data)
    s_loc = S // m if S % m == 0 else S
    ws = 2 * b_loc * s_loc * hq * hd * dt        # kr/vr transient
    ws += b_loc * hq * s_loc * 4                 # scores f32
    ws += b_loc * v_loc * 4                      # logits
    ws += 8 * b_loc * d * dt * 64
    return 2.0 * ws


def lower_cell(arch: str, shape_name: str, mesh, *,
               microbatches: int = 4, cfg_override=None) -> dict:
    """Lower + compile one (arch x shape) cell on ``mesh``.

    ``cfg_override``: substitute ModelConfig (perf-iteration variants,
    e.g. head-padded deployments)."""
    cfg = cfg_override or C.get_config(arch)
    cell = SHAPES[shape_name]
    norm = C.ALIASES.get(arch, arch).replace("-", "_").replace(".", "_")
    if norm in _MB1_ARCHS and cfg_override is None:
        microbatches = 1
    cache_dtype = CACHE_DTYPE_OVERRIDES.get(
        (C.ALIASES.get(arch, arch).replace("-", "_").replace(".", "_"),
         shape_name))
    specs = input_specs(cfg, cell, cache_dtype=cache_dtype)
    params = _struct_params(cfg)
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dax = daxes if len(daxes) > 1 else daxes[0]

    # an active mesh enables PartitionSpec-based shard_hints inside model
    # code
    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        if cell.kind == "train":
            pspecs = param_pspecs(params, cfg, mesh, fsdp=True)
            opt = jax.eval_shape(adamw_init, params)
            ospecs = type(opt)(master=pspecs, m=pspecs, v=pspecs, step=P())
            # batch sharding: leading batch dim over the data axes
            bspecs = jax.tree.map(
                lambda s: P(dax, *([None] * (len(s.shape) - 1))), specs)
            step_fn = make_train_step(cfg, microbatches=microbatches,
                                      remat=True)
            lowered = jax.jit(
                step_fn,
                in_shardings=(_shard(pspecs, mesh), _shard(ospecs, mesh),
                              _shard(bspecs, mesh)),
                out_shardings=(_shard(pspecs, mesh), _shard(ospecs, mesh),
                               None),
                donate_argnums=(0, 1),      # params/opt update in place
            ).lower(params, opt, specs)
        elif cell.kind == "prefill":
            pspecs = param_pspecs(params, cfg, mesh, fsdp=False)

            def prefill_fn(p, batch):
                head = (p["embed"].T if cfg.tie_embeddings else p["head"])
                if cfg.encoder is not None:
                    memory = ED.encode(p, cfg, batch["frames"])
                    hidden = T.forward(p, cfg, tokens=batch["tokens"],
                                       enc_memory=memory, return_hidden=True)
                elif cfg.embeds_input:
                    hidden = T.forward(p, cfg, embeds=batch["embeds"],
                                       return_hidden=True)
                else:
                    hidden = T.forward(p, cfg, tokens=batch["tokens"],
                                       return_hidden=True)
                # serving prefill emits logits for the LAST position only
                return hidden[:, -1, :] @ head

            bspecs = jax.tree.map(
                lambda s: P(dax, *([None] * (len(s.shape) - 1))), specs)
            lowered = jax.jit(
                prefill_fn,
                in_shardings=(_shard(pspecs, mesh), _shard(bspecs, mesh)),
            ).lower(params, specs)
        else:  # decode
            pspecs = param_pspecs(params, cfg, mesh, fsdp=False)
            cspecs = cache_pspecs(specs["cache"], cfg, mesh)
            serve_fn = make_serve_step(cfg)
            n_data = 1
            for a in daxes:
                n_data *= mesh.shape[a]
            bdax = dax if specs["tokens"].shape[0] % n_data == 0 else None
            tok_spec = P(bdax, None)
            lowered = jax.jit(
                lambda p, t, c: serve_fn(p, t, c),
                in_shardings=(_shard(pspecs, mesh),
                              NamedSharding(mesh, tok_spec),
                              _shard(cspecs, mesh)),
                out_shardings=(NamedSharding(mesh, P(bdax)),
                               _shard(cspecs, mesh)),
                donate_argnums=(2,),        # KV cache updates in place
            ).lower(params, specs["tokens"], specs["cache"])
        t_lower = time.perf_counter() - t0

        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    summary = hlo_utils.cost_summary(compiled)
    hlo = hlo_utils.analyze(compiled.as_text())
    n_dev = mesh.devices.size
    mem = summary["memory"]
    per_dev = (mem.get("argument_size_in_bytes", 0)
               + mem.get("temp_size_in_bytes", 0)
               - mem.get("alias_size_in_bytes", 0))
    # Model-based per-device estimate: XLA's argument sizes (exact, sharded)
    # + an analytic workspace.  The raw CPU-backend temp is inflated by
    # float-normalization (bf16->f32 weight copies, fp8->f16 cache upcasts)
    # hoisted out of the layer loop — buffers a real TPU (native bf16/fp8)
    # never materializes; see EXPERIMENTS.md §Dry-run notes.
    ws = _analytic_workspace(cfg, cell, mesh, microbatches)
    per_dev_model = mem.get("argument_size_in_bytes", 0) + ws
    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "devices": n_dev,
        "kind": cell.kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        # loop-corrected per-step totals (see hlo_utils docstring); raw
        # XLA cost_analysis kept for reference (counts while bodies once)
        "dot_flops": hlo["dot_flops"],
        "collective_bytes": hlo["collective_bytes"],
        "flops_raw": summary["flops"],
        "bytes_accessed_raw": summary["bytes_accessed"],
        "memory": mem,
        "per_device_bytes_raw": per_dev,
        "workspace_model": ws,
        "per_device_bytes": per_dev_model,
        "fits_16gb": bool(per_dev_model <= 16e9),
        "status": "ok",
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="single arch id (default: all)")
    ap.add_argument("--shape", default=None,
                    help="single shape id (default: all applicable)")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"],
                    default="off")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--microbatches", type=int, default=4)
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(C.ARCHS)
    meshes = []
    if args.multi_pod in ("off", "both"):
        meshes.append(make_production_mesh(multi_pod=False))
    if args.multi_pod in ("on", "both"):
        meshes.append(make_production_mesh(multi_pod=True))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results
            if r.get("status") == "ok"}

    for mesh in meshes:
        mesh_name = "x".join(str(s) for s in mesh.devices.shape)
        for arch in archs:
            skips = C.shape_skips(arch)
            shapes = [args.shape] if args.shape else list(SHAPES)
            for shape in shapes:
                if shape in skips:
                    print(f"SKIP {arch} x {shape}: {skips[shape]}")
                    continue
                if (arch, shape, mesh_name) in done:
                    print(f"done {arch} x {shape} x {mesh_name} (cached)")
                    continue
                print(f"=== {arch} x {shape} x mesh {mesh_name} ===",
                      flush=True)
                try:
                    rec = lower_cell(arch, shape, mesh,
                                     microbatches=args.microbatches)
                    cb = sum(rec["collective_bytes"].values())
                    print(f"  ok: lower {rec['lower_s']}s compile "
                          f"{rec['compile_s']}s dot_flops "
                          f"{rec['dot_flops']:.3e} coll {cb / 1e9:.2f}GB "
                          f"per-dev {rec['per_device_bytes'] / 1e9:.2f}GB "
                          f"fits16GB={rec['fits_16gb']}", flush=True)
                except Exception as e:  # noqa: BLE001 — record and continue
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": f"error: {type(e).__name__}: {e}"}
                results = [r for r in results
                           if (r["arch"], r["shape"], r["mesh"])
                           != (arch, shape, mesh_name)]
                results.append(rec)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    ok = sum(1 for r in results if r.get("status") == "ok")
    print(f"\n{ok}/{len(results)} cells ok -> {args.out}")


if __name__ == "__main__":
    main()
