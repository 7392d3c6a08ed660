"""Prefill (``ServingEngine._prefill_slot``): host time of the prompt
replays that start in the span, first token fetched, per prompt token."""

import stats


def read(ctx):
    s0, s1 = ctx.span
    done = [r for r in ctx.requests if r.first is not None
            and stats.in_window(r.admitted, s0, s1)]
    tokens = sum(r.prompt_len for r in done)
    return sum(r.first - r.admitted for r in done) / tokens * 1e3 \
        if tokens else None
