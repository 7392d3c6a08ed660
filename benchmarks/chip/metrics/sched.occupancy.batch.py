"""Scheduler: mean share of the ``max_batch`` slots holding a request, per
decode step in the span."""

import stats


def read(ctx):
    s0, s1 = ctx.span
    steps = [c for c in ctx.calls
             if c.mode == "decode" and stats.in_window(c.start, s0, s1)]
    if not steps:
        return None
    return 100.0 * sum(len(c.ctxs) for c in steps) / (len(steps) * ctx.max_batch)
