"""Mean decode lanes per unified dispatch, from the scheduler's histogram
``dynamo_engine_mixed_batch_decode_lanes`` (sum / count over the window)."""


def read(ctx):
    c = ctx["counters"]
    n = c.delta("dynamo_engine_mixed_batch_decode_lanes_count")
    if not n:
        return None
    return c.delta("dynamo_engine_mixed_batch_decode_lanes_sum") / n
