"""PlacementService routing: share of the window's score requests answered
by a merged cross-query forward (``ServiceStats.n_cross_query / n_drained``), in %."""


def read(ctx):
    n = ctx.stats.get("n_drained", 0)
    return 100.0 * ctx.stats["n_cross_query"] / n if n else None
