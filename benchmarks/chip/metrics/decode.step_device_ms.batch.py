"""Decode step (``decode_step``, jitted in the engine): median device time
of one execution of the step program in the traced window."""

import stats


def read(ctx):
    if ctx.trace is None:
        return None
    p = stats.percentile(ctx.trace["step_device_s"], 50)
    return None if p is None else p * 1e3
