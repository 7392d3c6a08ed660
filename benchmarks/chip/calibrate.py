"""Readings that set a cell's correctness limit, on the chip, in one process.

    python3 benchmarks/chip/calibrate.py --workload internlm2.decode_batch \
        --seconds 51 --seeds 1 2 3 4 5 6 7 8 9 10 11 12

For each seed it serves the cell as ``run.py`` does (same traffic, window
and sample) and then reads, over the same sampled prompts and served
tokens, two widest gaps below the float32 reference's best logit:

* served: the tokens the program served (the number ``run.py`` compares);
* control: at each of those positions, the token that the reference
  computed with float8 e4m3 weights and activations puts first.

The lower reading of the limit is the largest served gap over the seeds;
the upper one is the smallest control gap.  The last line of stdout is a
JSON object with every reading.  Not part of a benchmark run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import correctness  # noqa: E402
import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(bench, args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = harness.serve(cell, seed=seed, seconds=args.seconds,
                            trace=False, t_start=t0, jax=jax, device=dev)
        reqs = list(out.probe.requests.values())
        prompts = {r["rid"]: r["prompt"] for r in out.requests}
        picked = correctness.sample(reqs, cell.mix["sample"], seed,
                                    cell.mix["loop"])
        seqs = [(prompts[r.rid], r.tokens) for r in picked]
        g = correctness.gaps(cell.model, cell.cfg, seed, seqs,
                             cell.mix["sample"], out.max_len, control=True)
        row = {"seed": seed, "tokens": g["tokens"],
               "served": float(g["served"].max()),
               "control": float(g["control"].max()),
               "control_p50": float(sorted(g["control"])[len(g["control"]) // 2]),
               "control_share_over_served_max": float(
                   (g["control"] > g["served"].max()).mean()),
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del out
    print(json.dumps({"workload": args.workload,
                      "lower": max(r["served"] for r in rows),
                      "upper": min(r["control"] for r in rows),
                      "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
