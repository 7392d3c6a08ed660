"""Chip benchmark: one cell of BENCHMARK.json, one run.

    python3 benchmarks/chip/run.py --workload internlm2.decode_batch \
        --seed 7 --seconds 51 --trace 0

A cell is a configuration (``configs/<name>.json`` with its program
adapter and plain reference in ``configs/<name>.py``) under a traffic mix
(``traffic/<name>.json``).  The run makes the weights from the seed on the
device, serves the mix through ``ServingEngine.run`` on the first chip,
measures a window of ``--seconds``, and checks what was served against
the reference (``correctness.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, each read by ``metrics/<name>.py``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared with its limit, also the last lines of stderr.

It exits non-zero without printing a result when JAX finds no TPU, or
fewer chips than the cell asks for.  JAX's persistent compilation cache
lives in ``<checkout>/.jax_cache``; traces pass through
``<checkout>/.bench_trace`` and are deleted once read.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import correctness  # noqa: E402
import harness  # noqa: E402
import stats  # noqa: E402


def log(msg: str) -> None:
    print(msg, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: pathlib.Path = ROOT,
         require_tpu: bool = True) -> int:
    args = parse(argv)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = harness.load_cell(bench, args.workload, root)
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}[args.workload]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        print(f"run.py: {args.workload} needs {chips} TPU chip(s); jax found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = devices[0]
    log(f"[setup] {args.workload}: {cell.config_name} under "
        f"{cell.traffic_name}, seed {args.seed}, {args.seconds:g} s, trace "
        f"{args.trace}; {len(devices)} x {dev.device_kind}, jax "
        f"{jax.__version__}")

    out = harness.serve(cell, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), t_start=T_START, jax=jax,
                        device=dev)
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    probe = out.probe
    late = probe.lateness
    log(f"[window] setup {out.setup_s:.3f} s; {len(probe.calls)} engine "
        f"steps; programs compiled or loaded in the window: "
        f"{out.compiles_in_window}; generator lateness over "
        f"{len(late)} idle waits: max "
        f"{(max(late) if late else 0.0) * 1e3:.3f} ms, median "
        f"{(stats.percentile(late, 50) or 0.0) * 1e3:.3f} ms")
    ctx = harness.context(cell, out)
    metrics = harness.read_metrics(
        cell.per_layer if args.trace else cell.end_to_end, ctx)
    reqs = [r for r in probe.requests.values()]
    if cell.mix["loop"] == "open":
        attempted = [r for r in reqs if r.due < probe.we]
    else:
        attempted = [r for r in reqs
                     if r.admitted is not None and r.admitted < probe.we]
    failed = sum(1 for r in attempted if r.first is None
                 or (cell.mix["loop"] == "open" and not r.done))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": None, "attempted": len(attempted),
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace and out.trace is not None:
        device["busy_s"] = out.trace["busy_s"]
        device["window_s"] = out.trace["window_s"]
        result["breakdown"] = {"device_ops": out.trace["device_ops"],
                               "idle_gaps": out.trace["idle_gaps"]}
    for name, m in metrics.items():
        log(f"[metric] {name} {m['value']!r} {m['unit']}")

    t_ref = time.perf_counter()
    checks = correctness.check(cell, out, args.seed)
    log(f"[correct] reference over {checks[0]['requests']} requests, "
        f"{checks[0]['tokens']} served tokens, in "
        f"{time.perf_counter() - t_ref:.1f} s")
    result["correct"] = all(c["value"] <= c["limit"] for c in checks)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    for c in checks:
        print(f"check {c['name']} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
