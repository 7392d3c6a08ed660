"""End-to-end serving driver (the paper's kind of system): APEX picks the
plan for the full architecture, then the REAL JAX engine serves a batched
request stream with the reduced config on this host — iteration-level
batching, greedy admission, preemption and all.

    PYTHONPATH=src python examples/serve_e2e.py [--arch mixtral-8x7b]
"""

import argparse

from repro import configs as C
from repro.launch.serve import serve

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--trace", default="chat")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--cluster", default="h100x8")
    args = ap.parse_args()
    serve(args.arch, C.get_reduced(args.arch), trace=args.trace,
          requests=args.requests, cluster=args.cluster)
