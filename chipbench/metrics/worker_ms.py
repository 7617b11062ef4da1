"""Service drain worker: seconds the worker spent launching and finalizing
drains (``costream.launch`` + ``costream.finalize``) inside the traced
sub-window, per request the service popped in it, in ms."""

from chipbench.spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, ["costream.launch", "costream.finalize"])
