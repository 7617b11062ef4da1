"""Load generator: 99th percentile of how late the open-loop driver fired a
request after its scheduled arrival, in ms, by the driver's own clock."""

import numpy as np


def read(ctx):
    if ctx.lag_s is None or not len(ctx.lag_s):
        return None
    lag = ctx.lag_s[np.isfinite(ctx.lag_s)]
    return float(np.percentile(lag, 99)) * 1e3 if len(lag) else None
