"""Bytes of convolution state that ride one page of the pool: the program's
gauge ``dynamo_engine_state_bytes{part="pages"}`` over the pool's pages.
81920 at LFM2-8B-A1B's widths cut to 10 convolution layers (10 x 2 rows x
2048 values x 2 B), beside the 98304 B of keys and values a page holds; more
means a wider state or one kept in a wider type.  A program without the
gauge reads nothing."""

GAUGE = "dynamo_engine_state_bytes"


def read(ctx):
    values = [
        v for (name, labels), v in ctx["counters"].after.items()
        if name == GAUGE and dict(labels).get("part") == "pages"
    ]
    pages = ctx["cfg"].get("engine", {}).get("num_pages")
    return values[0] / pages if values and pages else None
