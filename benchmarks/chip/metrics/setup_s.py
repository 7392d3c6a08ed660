"""Process start to the window's start: imports, weights from the seed,
compile or cache reads, warm-up, and the slot fill of a closed loop."""


def read(ctx):
    return ctx.setup_s
