"""GNN forwards on the device: device seconds of every XLA program in the
traced sub-window, per request the service popped in it (one request is one
1,024-candidate placement), in ms."""


def read(ctx):
    if ctx.trace is None or not ctx.traced_stats or not ctx.traced_stats.get("n_drained"):
        return None
    busy = sum(ctx.trace.module_seconds().values())
    return 1e3 * busy / ctx.traced_stats["n_drained"] if busy > 0 else None
