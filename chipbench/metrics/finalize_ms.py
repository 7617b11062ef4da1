"""Service finalize: self time of ``costream.finalize`` (vote, split, counters,
resolving the futures, observers; its ``costream.fetch`` left out) inside the
traced sub-window, per request the service popped in it, in ms."""

from chipbench.spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, ["costream.finalize"], self_time=True)
