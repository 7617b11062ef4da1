"""PlacementService: mean time from submit to drain pop over the window, in
ms (``ServiceStats.queue_wait_s / n_drained``)."""


def read(ctx):
    n = ctx.stats.get("n_drained", 0)
    return ctx.stats["queue_wait_s"] / n * 1e3 if n else None
