"""Mean host-observed gap between one dispatch's results landing and the
next dispatch being enqueued: ``dynamo_tick_dispatch_gap_seconds``."""


def read(ctx):
    c = ctx["counters"]
    n = c.delta("dynamo_tick_dispatch_gap_seconds_count")
    if not n:
        return None
    return 1e3 * c.delta("dynamo_tick_dispatch_gap_seconds_sum") / n
