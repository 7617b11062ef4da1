"""99th-percentile latency over every request of the window, in ms (the same
population as ``req_p50_ms``)."""

import numpy as np


def read(ctx):
    return float(np.percentile(ctx.latencies_s, 99)) * 1e3 if len(ctx.latencies_s) else None
