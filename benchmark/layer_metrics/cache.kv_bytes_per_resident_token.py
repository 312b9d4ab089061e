"""Bytes of cache a token of kept context costs, over a two-kind cache:
the bytes of the pages that are live or reusable in both pools
(``dynamo_engine_kv_kind_pages{kind, state="resident"}``) over
``dynamo_engine_kv_resident_context_tokens`` (running sequences' lengths
plus the reusable blocks of the full pool), the mean of the window's two
ends.  The full layers' share of every kept token (6144 B at the published
widths and 3 full layers) plus the window pool's bytes over the context
kept; 24576 if window pages were held as full pages are.  Like
``cache.kv_bytes_per_token`` it guards the layout.  Its stderr line sets the
two ends beside what the window let go in between
(``dynamo_engine_kv_window_pages_released_total``).  A program without the
gauges reads nothing."""
import sys

PAGES = "dynamo_engine_kv_kind_pages"
TOKENS = "dynamo_engine_kv_resident_context_tokens"
RELEASED = "dynamo_engine_kv_window_pages_released_total"


def _end(samples, ctx):
    pages = {}
    tokens = None
    for (name, labels), v in samples.items():
        d = dict(labels)
        if name == PAGES and d.get("state") == "resident":
            pages[d.get("kind")] = v
        elif name == TOKENS:
            tokens = v
    if not pages or not tokens:
        return None
    released = sum(v for (name, _labels), v in samples.items() if name == RELEASED)
    print(f"cache.kv_bytes_per_resident_token: resident pages {pages}, context "
          f"{tokens:.0f} tokens, window pages released so far {released:.0f}",
          file=sys.stderr)
    model = ctx["model_costs"]
    return model.resident_bytes(
        pages, ctx["cfg"], ctx["cfg"]["engine"]["page_size"]) / tokens


def read(ctx):
    c = ctx["counters"]
    if not hasattr(ctx["model_costs"], "resident_bytes"):
        return None
    ends = [e for e in (_end(c.before, ctx), _end(c.after, ctx)) if e is not None]
    return sum(ends) / len(ends) if ends else None
