"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--full`` runs the
complete settings; default is the quick variant for CI-style validation.

Each entry runs in a Python process of its own and this parent never
imports jax: a chip belongs to one process at a time, and an entry that
starts device-using children of its own (fig7) needs a parent that holds
none.

    PYTHONPATH=src python -m benchmarks.run --only disagg,table2
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BENCHES = {
    "disagg": "disagg_frontier",
    "table2": "table2_plan_search",
    "table3": "table3_clusters",
    "table4": "table4_energy",
    "table5": "table5_extensibility",
    "fig6": "fig6_fidelity",
    "fig7": "fig7_scaling",
    "fig8": "fig8_scaling",
    "fig9": "fig9_slo",
    "roofline": "roofline",
}

_CHILD = ("from repro.launch.compile_cache import enable_compile_cache\n"
          "enable_compile_cache()\n"
          "from benchmarks import {module}\n"
          "{module}.run(quick={quick})\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset (table2,fig6,...)")
    args, _ = ap.parse_known_args()
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    failures = 0
    print("name,us_per_call,derived", flush=True)
    for name, module in BENCHES.items():
        if only and name not in only:
            continue
        code = _CHILD.format(module=module, quick=quick)
        if subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=env).returncode:
            failures += 1
            print(f"{name},0,FAILED", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
