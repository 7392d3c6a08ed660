"""Scheduler (``ServingEngine._admit``): median time from a request's due
time to its admission into a slot, over the requests due in the span."""

import stats


def read(ctx):
    s0, s1 = ctx.span
    waits = [r.admitted - r.due for r in ctx.requests
             if stats.in_window(r.due, s0, s1) and r.admitted is not None]
    p = stats.percentile(waits, 50)
    return None if p is None else p * 1e3
