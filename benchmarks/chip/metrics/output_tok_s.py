"""Output tokens stamped in the window (first tokens included), over the
window's length."""

import stats


def read(ctx):
    w0, w1 = ctx.window
    n = sum(stats.count_in_window(r.times, w0, w1) for r in ctx.requests)
    return n / (w1 - w0)
