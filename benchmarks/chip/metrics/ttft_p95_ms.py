"""95th percentile of time to first token over the requests due in the
window, each timed from when it was due; the run drains them after the
window."""

import stats


def read(ctx):
    w0, w1 = ctx.window
    ttft = [r.first - r.due for r in ctx.requests
            if stats.in_window(r.due, w0, w1) and r.first is not None]
    p = stats.percentile(ttft, 95)
    return None if p is None else p * 1e3
