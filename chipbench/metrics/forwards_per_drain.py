"""Estimator dispatch: forwards issued per drain over the window
(``ServiceStats.n_forwards / n_batches``)."""


def read(ctx):
    n = ctx.stats.get("n_batches", 0)
    return ctx.stats["n_forwards"] / n if n else None
