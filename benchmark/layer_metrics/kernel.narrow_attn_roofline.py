"""Roofline share of the packed attention launches over a pool of heads
narrower than the chip's 128 lanes (the lfm2_moe family: 32 query heads over
8 KV heads of 64).  The program keeps two such heads in one 128-lane row of
the pool and a query in its head's half of a row, so the launches are the
work-list kernel's over an ordinary pair pool of 4 heads of 128: the least
time the chip could take for the launches of the traced slice, counted at
the TRUE widths, over the time they took, as measured and with no cap.

Such a launch carries the suffix ``_narrow`` on the kernel's name
(``packed_ragged_attention_narrow``).  What it was asked to do comes from the
program: the ``dispatch`` annotation of every packed dispatch carries its
lanes' fresh rows and context lengths (``benchmark/trace_host.py``), and
``costs_lfm2.attn_launch`` counts what they cannot do without (heads of 64:
the launch multiplies twice that, which this share charges to it).  Events
are matched with dispatches by their packed rows, as
``kernel.packed_attn_roofline`` does: the mean least time of the annotated
dispatches of a width is set against every event of that width (a chunk
step's and a decode-only dispatch's first step alike).  A program whose
launches carry no such name reads nothing."""
import re
import sys

KERNEL = "packed_ragged_attention_narrow"
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
    if not found or not hasattr(model, "state_bytes_per_page"):
        return None  # no narrow launch in the trace, or not this family
    t = trace_host.table(ctx)
    if t is None or not t["dispatches"]:
        return None
    asked = {}  # by packed rows: [dispatches, least seconds a layer, compute-bound]
    for d in t["dispatches"]:
        least, bound = costs.roofline_seconds(
            *model.attn_launch(d["q"], d["ctx"], cfg), ctx["peaks"])
        got = asked.setdefault(d["np"], [0, 0.0, 0])
        got[0] += 1
        got[1] += least
        got[2] += bound == "compute"
    least = took = 0.0
    for rows, (events, seconds) in sorted(found.items()):
        n, sec, compute = asked.get(rows, (0, 0.0, 0))
        print(f"kernel.narrow_attn_roofline: Np {rows}: {events} events {seconds:.6f} s, "
              f"{n} dispatches annotated ({compute} compute-bound), least a layer "
              f"{sec / n if n else 0.0:.9f} s", file=sys.stderr)
        if n:
            least += events * sec / n
            took += seconds
    return 100.0 * least / took if took else None
