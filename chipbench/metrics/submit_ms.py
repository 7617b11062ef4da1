"""Service submit: seconds the callers spent in ``costream.submit``
(``PlacementService.submit_*``) inside the traced sub-window, per request the
service popped in it, in ms."""

from chipbench.spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, ["costream.submit"])
