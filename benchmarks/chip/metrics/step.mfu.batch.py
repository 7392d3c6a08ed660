"""Whole step: the operations the model needs for every token the engine
computed in the traced span (prompt replay and decode, attention at actual
lengths; ``costs/``), over the span times the chip's peak bf16 rate."""

import stats


def read(ctx):
    s0, s1 = ctx.span
    flops = sum(ctx.step_cost(c.ctxs)[0] for c in ctx.calls
                if stats.in_window(c.start, s0, s1))
    return 100.0 * flops / ((s1 - s0) * ctx.peaks.bf16_flops) if flops else None
