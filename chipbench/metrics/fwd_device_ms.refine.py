"""GNN forwards on the device: device seconds of every XLA program in the
traced sub-window, per drain the service popped in it, in ms."""


def read(ctx):
    if ctx.trace is None or not ctx.traced_stats or not ctx.traced_stats.get("n_batches"):
        return None
    busy = sum(ctx.trace.module_seconds().values())
    return 1e3 * busy / ctx.traced_stats["n_batches"] if busy > 0 else None
