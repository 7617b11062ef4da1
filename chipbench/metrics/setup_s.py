"""Process start to the first timed request: imports, weights, warm-up, warm-in."""


def read(ctx):
    return ctx.setup_s
