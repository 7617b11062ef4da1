"""Device: share of the traced sub-window in which no operation ran on the
chip, in % (1 - union of operation intervals / sub-window)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s() <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s())
