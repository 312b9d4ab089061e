"""Bytes of KV pool a cached token takes, all layers: the program's gauge
``dynamo_engine_kv_bytes_per_token`` (pool bytes over pool tokens).  3840
for the latent cache at the published widths (6 layers x 320 values x 2 B),
or the cache is no longer latent.  A program without the gauge reads
nothing."""

GAUGE = "dynamo_engine_kv_bytes_per_token"


def read(ctx):
    values = [v for (name, _labels), v in ctx["counters"].after.items() if name == GAUGE]
    return values[0] if values else None
