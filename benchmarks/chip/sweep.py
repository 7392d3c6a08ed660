"""The open-loop knee, found once on the chip: serve a cell's open mix at
several fixed rates, one window each, in one process.

    python3 benchmarks/chip/sweep.py --workload internlm2.chat_open \
        --seconds 51 --seed 1 --rates 0.2 0.3 0.4 0.5

For each rate it prints how many requests were due in the window, how
many of those were still waiting for a slot when it closed, how long the
rest took to drain, and the TTFT median and 95th percentile.  A rate is
sustained when requests are not left queued at the window's end and the
drain stays short.  Not part of a benchmark run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(bench, args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for rate in args.rates:
        c = dataclasses.replace(cell, mix=dict(cell.mix, rate=rate))
        out = harness.serve(c, seed=args.seed, seconds=args.seconds,
                            trace=False, t_start=time.perf_counter(), jax=jax,
                            device=dev)
        p = out.probe
        due = [r for r in p.requests.values() if r.due < p.we]
        queued = sum(1 for r in due if r.admitted is None or r.admitted >= p.we)
        ends = [r.times[-1] for r in due if r.done]
        ttft = [r.first - r.due for r in due if r.first is not None]
        gaps = [g for r in p.requests.values()
                for g in stats.gaps_ending_in(r.times, p.ws, p.we)]
        print(json.dumps({
            "rate": rate, "due": len(due), "queued_at_close": queued,
            "unfinished": sum(1 for r in due if not r.done),
            "drain_s": (max(ends) - p.we) if ends else None,
            "ttft_p50_ms": stats.percentile(ttft, 50) * 1e3,
            "ttft_p95_ms": stats.percentile(ttft, 95) * 1e3,
            "itl_p95_ms": (stats.percentile(gaps, 95) or 0) * 1e3,
            "busy_slots_mean": sum(len(x.ctxs) for x in p.calls
                                   if x.mode == "decode")
            / max(1, sum(1 for x in p.calls if x.mode == "decode"))}),
            flush=True)
        del out
    return 0


if __name__ == "__main__":
    sys.exit(main())
