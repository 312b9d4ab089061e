"""Share of looked-up prompt tokens whose KV came from the prefix cache:
``dynamo_engine_prefix_hit_tokens`` over ``..._prefix_lookup_tokens``."""


def read(ctx):
    c = ctx["counters"]
    looked = c.delta("dynamo_engine_prefix_lookup_tokens_total") or c.delta(
        "dynamo_engine_prefix_lookup_tokens")
    if not looked:
        return None
    hit = c.delta("dynamo_engine_prefix_hit_tokens_total") or c.delta(
        "dynamo_engine_prefix_hit_tokens")
    return 100.0 * hit / looked
