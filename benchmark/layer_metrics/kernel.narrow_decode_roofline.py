"""Roofline share of the fused decode steps' attention launch over a pool of
heads narrower than 128 lanes (the lfm2_moe family): the work-list decode
kernel over a pool that keeps two 64-wide KV heads a 128-lane row, under the
name ``paged_decode_attention_narrow``: the least time the chip could take
for the launches of the traced slice over the time they took, as measured and
with no cap.

A dispatch that carries no prefill rows (its ``dispatch`` annotation says
``step: decode``) runs ``k`` steps: the first through the packed kernel
(``kernel.narrow_attn_roofline`` reads it), the ``k - 1`` after it through
this launch, each once an attention layer over every lane: one query row a
lane against its context as the dispatch begins (it grows by one a step,
which the count leaves out, so the least time is never too long).  The least
time is counted at the true widths (``costs_lfm2.attn_launch``: heads of 64);
the launch multiplies a query's row of 128 against both heads of a pool row,
twice what it must, and pays its fixed cost for lanes that hold a page or
two: what those cost is what this share shows.  The mean least time of a
launch over the annotated fused steps is set against every event of the
kernel.  A program without such events reads nothing."""
import importlib.util
import os
import sys

FUSED = "paged_decode_attention_narrow"


def _launches_of():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "kernel.narrow_attn_roofline.py")
    spec = importlib.util.spec_from_file_location("reader_narrow_attn", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.launches


def read(ctx):
    from benchmark import trace_host  # not at import

    cfg, model, costs = ctx["cfg"], ctx["model_costs"], ctx["costs"]
    fused = _launches_of()(ctx, FUSED)
    if not fused or not hasattr(model, "state_bytes_per_page"):
        return None
    t = trace_host.table(ctx)
    if t is None:
        return None
    steps = least_all = 0.0
    for d in t["dispatches"]:
        if d["step"] != "decode" or d["k"] < 2:
            continue
        sec, _bound = costs.roofline_seconds(
            *model.attn_launch([1] * len(d["ctx"]), d["ctx"], cfg), ctx["peaks"])
        steps += d["k"] - 1
        least_all += (d["k"] - 1) * sec
    events = sum(n for n, _s in fused.values())
    seconds = sum(s for _n, s in fused.values())
    print(f"kernel.narrow_decode_roofline: {FUSED}: {events} events {seconds:.6f} s, "
          f"{steps:.0f} fused steps annotated, least a launch "
          f"{least_all / steps if steps else 0.0:.9f} s", file=sys.stderr)
    if not steps or not seconds:
        return None
    return 100.0 * events * (least_all / steps) / seconds
