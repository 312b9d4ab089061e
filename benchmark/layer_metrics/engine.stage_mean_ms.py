"""Mean time of one stage of a request's way to its first token, over the
requests that passed the stage's end inside the window: the engine's stage
histograms ``dynamo_engine_<stage>_seconds`` (sum / count of the window's
deltas).  Each is observed once per request where the stage ends: ingress
in ``JaxEngine.generate``, queue wait at the scheduler's first admission,
first-token service where the first token is committed.  One reader file
for the three metrics; each metric's json names its function.  A program
without the histograms gives nothing."""


def _mean_ms(ctx, stage):
    c = ctx["counters"]
    n = c.delta(f"dynamo_engine_{stage}_seconds_count")
    if not n:
        return None
    return 1e3 * c.delta(f"dynamo_engine_{stage}_seconds_sum") / n


def ingress(ctx):
    return _mean_ms(ctx, "ingress")


def queue_wait(ctx):
    return _mean_ms(ctx, "queue_wait")


def first_token_service(ctx):
    return _mean_ms(ctx, "first_token_service")
