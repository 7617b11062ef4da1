"""Device result fetch: seconds in ``costream.fetch`` (the host read of a
forward's output, which waits for the device) inside the traced sub-window,
per request the service popped in it, in ms."""

from chipbench.spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, ["costream.fetch"])
