"""Estimator dispatch: seconds in ``costream.dispatch`` (the host-to-device
copy and the jitted forward's enqueue) inside the traced sub-window, per
request the service popped in it, in ms."""

from chipbench.spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, ["costream.dispatch"])
