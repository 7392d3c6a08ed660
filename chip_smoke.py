"""On-chip smoke test: the serving main path at published widths on a TPU.

    python3 chip_smoke.py            # one v5e chip
    python3 chip_smoke.py --chips 4  # one four-chip host

One chip runs two phases in this one process:

* serve: ``repro.launch.serve.serve`` plans internlm2-1.8b on one v5e chip
  and serves 8 seeded requests with its FULL config (24 layers, d_model
  2048, 16 heads / 8 KV heads of 128, d_ff 8192, vocab 92544; bf16 weights
  from ``PRNGKey(0)``) through ``ServingEngine.run`` at max_batch 8 and
  max_len 2048.  Every request must finish with ``gen_len`` in-range
  tokens, and its first-token logits as served must agree with a float32
  single-request ``T.forward`` of its prompt on the same weights.
* kernels: each Pallas kernel compiled (``interpret=False``) at one real
  width against its ``ref.py``.

``--chips 4`` runs only the sharded path: the same FULL config DP1xTP4
through ``plan_to_shardings`` + ``cache_pspecs`` for a few ``decode_step``
calls, against the one-chip ``decode_step`` on the same inputs and both
against a float32 ``T.forward`` of the step tokens, and checks that each
chip holds about a quarter of the weights.

It exits non-zero, printing no result, when jax finds no TPU or when any
check fails.  The last line of stdout is one JSON object naming the
device.  Timings printed here are host-clock smoke timings, not benchmark
numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH, CLUSTER = "internlm2_1_8b", "tpu-v5e-1"
SEED = 0
MAX_BATCH, MAX_LEN = 8, 2048
REQUESTS, TRACE, ARRIVAL_RATE = 8, "creation", 2.0
MAX_PROMPT, MAX_GEN = 256, 32       # prefill replays one token per step
SHARDED_STEPS = 4
KERNEL_SEQ = 2048
INTERPRET = False                   # Pallas kernels compiled for the chip

# Served (bf16 weights and activations) vs float32 reference on the same
# weights: relative L2 error of each request's first-token logits.  bf16
# keeps 8 mantissa bits (2^-8 ~ 0.4% per rounding); 24 layers of rounded
# activations compound to a few percent at most.  Also the bound for the
# DP1xTP4 path against the same float32 reference.
LOGITS_REL_L2 = 5e-2
# One chip vs DP1xTP4, both bf16.  TP4 also rounds each layer's
# row-parallel partial sums (attention out-projection, MLP down-projection)
# to bf16 before the all-reduce, so the two paths carry independent bf16
# noise, each about as far from float32 as the served path is: their
# difference is about sqrt(2) times that.  Measured on four v5e chips at
# 2.03-2.15e-2; a wrong shard would be off by order 1.
SHARDED_REL_L2 = 3e-2
# Pallas kernel vs its ref.py: max |out - ref| over max |ref|, each about
# 10x its reading on one v5e (rms_norm 2.9e-3, decode_attention 4.2e-5,
# flash_attention 6.8e-4, ssd_scan 9.2e-6).  rms_norm's bound sits nearer
# its reading, at 2.5 bf16 ulps of the output's largest value.  ssd_scan
# takes and returns float32 and lands near float32 rounding.
KERNEL_TOL = {"rms_norm": 1e-2, "decode_attention": 5e-4,
              "flash_attention": 5e-3, "ssd_scan": 1e-4}


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {msg}")


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def rel_max(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


class CompileClock:
    """Seconds jax spends compiling, and reading compiled programs from
    the persistent cache, as its monitoring events report them."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.seconds = dict.fromkeys(self.EVENTS, 0.0)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.seconds:
            self.seconds[event] += duration

    def summary(self) -> str:
        compile_s, read_s = (self.seconds[e] for e in self.EVENTS)
        return f"compile {compile_s:.1f}s, cache reads {read_s:.1f}s"


# ---------------------------------------------------------------------------
# phase 1: serve
# ---------------------------------------------------------------------------

def float32_logits(cfg, p32, batches) -> list:
    """float32 ``T.forward`` of each token batch ``(B, S)`` in ``batches`` on
    ``p32`` (the weights upcast to float32), at full float32 matmul
    precision; returns the logits at every position, ``(B, S, vocab)``."""
    from repro.models import transformer as T
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    fwd = jax.jit(lambda p, toks: T.forward(p, cfg32, tokens=toks))
    with jax.default_matmul_precision("float32"):
        return [np.asarray(fwd(p32, toks)) for toks in batches]


def upcast(params):
    return jax.tree.map(lambda x: x.astype(jnp.float32), params)


def serve_phase(cfg, clock: CompileClock) -> None:
    from repro.launch.serve import serve, serving_requests
    from repro.models import transformer as T
    log(f"[serve] {cfg.name}: {cfg.block_repeat} layers, d_model "
        f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads} kv x "
        f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.dtype}; max_batch {MAX_BATCH}, max_len {MAX_LEN}")
    t0 = time.perf_counter()
    _, _, report = serve(ARCH, cfg, trace=TRACE, requests=REQUESTS,
                         cluster=CLUSTER, arrival_rate=ARRIVAL_RATE,
                         max_batch=MAX_BATCH, max_len=MAX_LEN,
                         max_prompt=MAX_PROMPT, max_gen=MAX_GEN,
                         seed=SEED, log=lambda m: log(f"[serve] {m}"))
    wall = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    rqs = {r["rid"]: r for r in serving_requests(
        cfg, TRACE, REQUESTS, ARRIVAL_RATE, MAX_PROMPT, MAX_GEN, SEED)}

    results = sorted(report.results, key=lambda r: r.rid)
    check(sorted(r.rid for r in results) == sorted(rqs),
          f"served {len(results)} of {len(rqs)} requests")
    for r in results:
        want = rqs[r.rid]["gen_len"]
        check(len(r.tokens) == want,
              f"request {r.rid}: {len(r.tokens)} tokens, gen_len {want}")
        check(all(0 <= t < cfg.vocab_size for t in r.tokens),
              f"request {r.rid}: token id out of [0, {cfg.vocab_size})")
        check(r.first_logits is not None
              and r.first_logits.shape == (cfg.vocab_size,)
              and bool(np.all(np.isfinite(r.first_logits))),
              f"request {r.rid}: first-token logits missing or not finite")
    prompts = [len(rqs[r.rid]["prompt"]) for r in results]
    log(f"[serve] served {len(results)}/{len(rqs)} requests, prompts "
        f"{min(prompts)}-{max(prompts)} tokens, "
        f"{sum(len(r.tokens) for r in results)} tokens generated, every "
        f"request at its gen_len, ids in range")
    log(f"[serve] smoke timing (host clock, not a benchmark): wall "
        f"{wall:.1f}s, TTFT mean {report.ttft_mean * 1e3:.1f}ms, TPOT "
        f"mean {report.tpot_mean * 1e3:.2f}ms, {report.iterations} "
        f"decode iterations")
    log(f"[serve] peak device bytes in use: {peak}")
    log(f"[serve] {clock.summary()} so far")

    # each prompt alone (batch 1), right-padded to one length (causal
    # attention: padding never reaches the prompt's last position)
    pad = max(prompts)
    batches = []
    for r in results:
        toks = np.zeros((1, pad), np.int32)
        toks[0, :len(rqs[r.rid]["prompt"])] = rqs[r.rid]["prompt"]
        batches.append(toks)
    p32 = upcast(T.init_params(jax.random.PRNGKey(SEED), cfg))  # as served
    refs = [out[0, n - 1] for out, n in
            zip(float32_logits(cfg, p32, batches), prompts)]
    del p32
    errs = [rel_l2(r.first_logits, ref) for r, ref in zip(results, refs)]
    agree = sum(int(np.argmax(r.first_logits) == np.argmax(ref))
                for r, ref in zip(results, refs))
    log(f"[serve] first-token logits vs float32 T.forward: rel L2 max "
        f"{max(errs):.3e} (tolerance {LOGITS_REL_L2:.0e}), per request "
        f"{', '.join(f'{e:.2e}' for e in errs)}; argmax agrees "
        f"{agree}/{len(results)}")
    check(max(errs) <= LOGITS_REL_L2,
          f"served logits off the float32 reference by {max(errs):.3e}")


# ---------------------------------------------------------------------------
# phase 2: kernels
# ---------------------------------------------------------------------------

def kernel_phase(cfg, ssm_cfg) -> None:
    from repro.kernels.decode_attention.decode_attention import \
        decode_attention_pallas
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_pallas
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rmsnorm.ref import rms_norm_ref
    from repro.kernels.rmsnorm.rmsnorm import rms_norm_pallas
    from repro.kernels.ssd_scan.ref import ssd_scan_ref
    from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas

    bf = jnp.bfloat16
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(jax.random.PRNGKey(SEED), 12)
    x = jax.random.normal(ks[0], (MAX_BATCH * 32, cfg.d_model), bf)
    w = jax.random.normal(ks[1], (cfg.d_model,), jnp.float32)
    q1 = jax.random.normal(ks[2], (MAX_BATCH, hq, hd), bf)
    kc = jax.random.normal(ks[3], (MAX_BATCH, KERNEL_SEQ, hkv, hd), bf)
    vc = jax.random.normal(ks[4], (MAX_BATCH, KERNEL_SEQ, hkv, hd), bf)
    lens = jnp.linspace(1, KERNEL_SEQ, MAX_BATCH).astype(jnp.int32)
    qs = jax.random.normal(ks[5], (1, KERNEL_SEQ, hq, hd), bf)
    H = ssm_cfg.n_ssd_heads
    P, N = ssm_cfg.d_inner // H, ssm_cfg.d_state
    S = KERNEL_SEQ // 4
    sx = jax.random.normal(ks[6], (1, S, H, P), jnp.float32) * 0.5
    sdt = jax.nn.softplus(jax.random.normal(ks[7], (1, S, H)))
    sa = jnp.log(jnp.linspace(1.0, 8.0, H))
    sb = jax.random.normal(ks[8], (1, S, N)) * 0.3
    sc = jax.random.normal(ks[9], (1, S, N)) * 0.3

    cases = {
        "rms_norm": (f"x {tuple(x.shape)} bf16",
                     lambda: rms_norm_pallas(x, w, interpret=INTERPRET),
                     lambda: rms_norm_ref(x, w)),
        "decode_attention": (
            f"q {tuple(q1.shape)}, kv {tuple(kc.shape)} bf16",
            lambda: decode_attention_pallas(q1, kc, vc, lens,
                                            interpret=INTERPRET),
            lambda: decode_attention_ref(q1, kc, vc, lens)),
        "flash_attention": (
            f"q {tuple(qs.shape)}, kv {tuple(kc[:1].shape)} bf16, causal",
            lambda: flash_attention_pallas(qs, kc[:1], vc[:1],
                                           interpret=INTERPRET),
            lambda: attention_ref(qs, kc[:1], vc[:1])),
        "ssd_scan": (f"x {tuple(sx.shape)}, state {N} f32 "
                     f"({ssm_cfg.name} heads)",
                     lambda: ssd_scan_pallas(sx, sdt, sa, sb, sc,
                                             interpret=INTERPRET),
                     lambda: ssd_scan_ref(sx, sdt, sa, sb, sc)),
    }
    with jax.default_matmul_precision("float32"):
        for name, (shape, kern, ref) in cases.items():
            out = np.asarray(jax.block_until_ready(kern()), np.float32)
            want = np.asarray(ref(), np.float32)
            check(out.shape == want.shape and bool(np.all(np.isfinite(out))),
                  f"{name}: output {out.shape} vs {want.shape} or not finite")
            err = rel_max(out, want)
            log(f"[kernels] {name} ({shape}): max err / max |ref| "
                f"{err:.3e} (tolerance {KERNEL_TOL[name]:.0e})")
            check(err <= KERNEL_TOL[name], f"{name} off its ref by {err:.3e}")


# ---------------------------------------------------------------------------
# --chips 4: DP1xTP4 decode vs one chip
# ---------------------------------------------------------------------------

def sharded_phase(cfg, devices) -> None:
    from repro.core import generate_schemes
    from repro.models import transformer as T
    from repro.parallel.plan_sharding import plan_to_shardings

    n = len(devices)
    scheme = next(s for s in generate_schemes(cfg.to_ir(), n)
                  if s.model_dp == 1 and s.pp_stages == 1
                  and s.stage_devices == n)
    log(f"[sharded] {cfg.name}, plan {scheme.label()} on {n} "
        f"devices, batch {MAX_BATCH}, max_len {MAX_LEN}, {SHARDED_STEPS} "
        f"decode steps")
    params = T.init_params(jax.random.PRNGKey(SEED), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                              (SHARDED_STEPS, MAX_BATCH, 1), 1,
                              cfg.vocab_size)
    step = jax.jit(lambda p, t, c: T.decode_step(p, cfg, t, c))

    cache = T.init_cache(cfg, MAX_BATCH, MAX_LEN)
    one = []
    for t in range(SHARDED_STEPS):
        logits, cache = step(params, toks[t], cache)
        one.append(np.asarray(logits, np.float32))
    del cache
    # decode steps from an empty cache = one forward over the step tokens
    p32 = upcast(params)
    ref = float32_logits(cfg, p32, [toks[:, :, 0].T])[0]   # (B, steps, V)
    del p32

    mat = plan_to_shardings(scheme, cfg, params, devices=devices)
    ps = jax.device_put(params, mat.param_shardings())
    cache = T.init_cache(cfg, MAX_BATCH, MAX_LEN)
    cs = jax.device_put(cache, mat.cache_shardings(cache, cfg))
    del params, cache
    held = dict.fromkeys(devices, 0)
    for leaf in jax.tree.leaves(ps):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    total = sum(x.nbytes for x in jax.tree.leaves(ps))
    shares = [held[d] / total for d in devices]
    per_device = ", ".join(f"{d.id}: {held[d]} ({s:.3f})"
                           for d, s in zip(devices, shares))
    log(f"[sharded] weight bytes per device: {per_device} of {total}")
    check(all(abs(s - 1 / n) < 0.02 for s in shares),
          f"weights not spread 1/{n} per device: {shares}")

    sharded = []
    with jax.set_mesh(mat.mesh):
        for t in range(SHARDED_STEPS):
            logits, cs = step(ps, toks[t], cs)
            sharded.append(np.asarray(logits, np.float32))
    vs_one = [rel_l2(a, b) for a, b in zip(sharded, one)]
    vs_f32 = {name: [rel_l2(x, ref[:, t]) for t, x in enumerate(out)]
              for name, out in (("one-chip", one), ("sharded", sharded))}
    agree = sum(int(np.sum(a.argmax(-1) == b.argmax(-1)))
                for a, b in zip(sharded, one))
    fmt = lambda errs: ", ".join(f"{e:.2e}" for e in errs)
    for name, errs in vs_f32.items():
        log(f"[sharded] {name} logits vs float32 T.forward: rel L2 per "
            f"step {fmt(errs)} (tolerance {LOGITS_REL_L2:.0e})")
    log(f"[sharded] logits vs one-chip decode_step: rel L2 per step "
        f"{fmt(vs_one)} (tolerance {SHARDED_REL_L2:.0e}); argmax agrees "
        f"{agree}/{SHARDED_STEPS * MAX_BATCH}")
    for name, errs in vs_f32.items():
        check(max(errs) <= LOGITS_REL_L2,
              f"{name} logits off the float32 reference by {max(errs):.3e}")
    check(max(vs_one) <= SHARDED_REL_L2,
          f"sharded logits off the one-chip ones by {max(vs_one):.3e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; jax found {len(devices)} "
                 f"{dev.platform!r} device(s) ({dev.device_kind})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPUs; jax found {len(devices)}")

    from repro import configs as C
    from repro.launch.compile_cache import enable_compile_cache
    log(f"[setup] {len(devices)} x {dev.device_kind}; jax {jax.__version__}; "
        f"compile cache {enable_compile_cache()}")
    clock = CompileClock()
    cfg = C.get_config(ARCH)
    if args.chips == 4:
        sharded_phase(cfg, devices[:4])
    else:
        serve_phase(cfg, clock=clock)
        kernel_phase(cfg, C.get_config("mamba2_2_7b"))
    log(f"[setup] {clock.summary()} in all")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)


if __name__ == "__main__":
    main()
