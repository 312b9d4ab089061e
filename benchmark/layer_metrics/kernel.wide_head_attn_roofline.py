"""Roofline share of the packed attention launches over heads wider than the
chip's 128 lanes (the qwen3_next family: 16 query heads over 2 KV heads of
256, eight query heads a KV head, in 3 of 12 layers): the work-list kernel
under the name ``packed_ragged_attention_wide``.  The least time the chip
could take for the launches of the traced slice over the time they took, as
measured and with no cap.

What a launch was asked to do comes from the program: the ``dispatch``
annotation of every packed dispatch carries its lanes' fresh rows and context
lengths (``benchmark/trace_host.py``), and ``costs_qwen3next.attn_launch``
counts what they cannot do without.  Events are matched with dispatches by
their packed rows: the mean least time of the annotated dispatches of a
width is set against every event of that width (a chunk step's and a
decode-only dispatch's first step alike).  A program whose launches carry no
such name reads nothing."""
import re
import sys

KERNEL = "packed_ragged_attention_wide"
ROWS = re.compile(r" = \(?\w+\[(\d+),")


def launches(ctx, kernel=KERNEL):
    """By the result's first dimension: [events, seconds] of the kernel."""
    trace = ctx["trace"]
    out = {}
    for label, seconds in trace["ops"].items():
        text = trace.get("op_text", {}).get(label, "")
        if kernel not in text.split(" = ", 1)[0]:
            continue
        m = ROWS.search(text)
        got = out.setdefault(int(m.group(1)) if m else 0, [0, 0.0])
        got[0] += trace["op_counts"][label]
        got[1] += seconds
    return out


def read(ctx):
    from benchmark import trace_host  # not at import

    cfg, model, costs = ctx["cfg"], ctx["model_costs"], ctx["costs"]
    found = launches(ctx)
    if not found or not hasattr(model, "gdn_chunk_launch"):
        return None  # no wide launch in the trace, or not this family
    t = trace_host.table(ctx)
    if t is None or not t["dispatches"]:
        return None
    asked = {}  # by packed rows: [dispatches, least seconds a layer]
    for d in t["dispatches"]:
        least, _bound = costs.roofline_seconds(
            *model.attn_launch(d["q"], d["ctx"], cfg), ctx["peaks"])
        got = asked.setdefault(d["np"], [0, 0.0])
        got[0] += 1
        got[1] += least
    least = took = 0.0
    for rows, (events, seconds) in sorted(found.items()):
        n, sec = asked.get(rows, (0, 0.0))
        print(f"kernel.wide_head_attn_roofline: Np {rows}: {events} events {seconds:.6f} s, "
              f"{n} dispatches annotated, least a layer {sec / n if n else 0.0:.9f} s",
              file=sys.stderr)
        if n:
            least += events * sec / n
            took += seconds
    return 100.0 * least / took if took else None
