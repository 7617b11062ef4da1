"""Median latency over every request of the window, in ms.  Open loop: from
the scheduled arrival; closed loop: from the submit.  A failed or unanswered
request counts as beyond every limit."""

import numpy as np


def read(ctx):
    return float(np.percentile(ctx.latencies_s, 50)) * 1e3 if len(ctx.latencies_s) else None
