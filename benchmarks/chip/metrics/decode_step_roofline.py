"""Decode step's share of its roofline: the least time the chip needs for
the operations and bytes of the decode steps in the traced window (weights,
the cache at each slot's actual length, the new entries; ``costs/``) at the
device's peaks, over the device time those steps took.  Every execution of
the step program is the same program over the whole batch, so the device
time of a decode step is the mean over the traced executions."""

import peaks
import stats


def read(ctx):
    if ctx.trace is None or not ctx.trace["step_device_s"]:
        return None
    s0, s1 = ctx.span
    steps = [c for c in ctx.calls
             if c.mode == "decode" and stats.in_window(c.start, s0, s1)]
    if not steps:
        return None
    least = [peaks.least_time(*ctx.step_cost(c.ctxs), ctx.peaks)[0]
             for c in steps]
    device = ctx.trace["step_device_s"]
    return 100.0 * (sum(least) / len(least)) / (sum(device) / len(device))
