"""95th percentile of the gaps between consecutive output tokens of one
request, over every gap that ends in the window."""

import stats


def read(ctx):
    w0, w1 = ctx.window
    gaps = [g for r in ctx.requests for g in stats.gaps_ending_in(r.times, w0, w1)]
    p = stats.percentile(gaps, 95)
    return None if p is None else p * 1e3
