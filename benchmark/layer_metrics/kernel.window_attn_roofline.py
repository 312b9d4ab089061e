"""Roofline shares of the packed attention launches of a trunk of window
and full layers (the mellum family), one reader for both kinds: the least
time the chip could take for a kind's launches of the traced slice over the
time they took, as measured and with no cap.

A window layer's launch carries the suffix ``_window`` on the kernel's name
(the same kernel: ``packed_ragged_attention``), a full layer's none.  What a
launch was asked to do comes from the program: the ``dispatch`` annotation of
every packed dispatch carries its lanes' fresh rows and context lengths
(``benchmark/trace_host.py``), and ``costs_mellum.attn_launch`` counts what
they cannot do without: in a window layer a row reads the last
``sliding_window`` keys, in a full layer all of them.  Events are matched
with dispatches by their packed rows, as ``kernel.packed_attn_roofline``
does: the mean least time of the annotated dispatches of a width is set
against every event of that kind and width.  A program whose launches carry
no such names reads nothing."""
import re
import sys

KERNEL = "packed_ragged_attention"
SUFFIX = "_window"
ROWS = re.compile(r" = \(?\w+\[(\d+),")


def launches(ctx, kind):
    """By packed rows: [events, seconds] of the kind's launches."""
    trace = ctx["trace"]
    out = {}
    for label, seconds in trace["ops"].items():
        text = trace.get("op_text", {}).get(label, "")
        name = text.split(" = ", 1)[0]
        if KERNEL not in name or ((KERNEL + SUFFIX) in name) != (kind == "window"):
            continue
        m = ROWS.search(text)
        got = out.setdefault(int(m.group(1)) if m else 0, [0, 0.0])
        got[0] += trace["op_counts"][label]
        got[1] += seconds
    return out


def share(ctx, kind):
    from benchmark import trace_host  # not at import

    cfg, model, costs = ctx["cfg"], ctx["model_costs"], ctx["costs"]
    if not hasattr(model, "attn_launch") or not launches(ctx, "window"):
        return None  # not this family, or a program that names no window launch
    found = launches(ctx, kind)
    t = trace_host.table(ctx) if found else None
    if t is None or not t["dispatches"]:
        return None
    asked = {}  # by packed rows: [dispatches, least seconds a layer, compute-bound]
    for d in t["dispatches"]:
        least, bound = costs.roofline_seconds(
            *model.attn_launch(d["q"], d["ctx"], cfg, kind), ctx["peaks"])
        got = asked.setdefault(d["np"], [0, 0.0, 0])
        got[0] += 1
        got[1] += least
        got[2] += bound == "compute"
    least = took = 0.0
    for rows, (events, seconds) in sorted(found.items()):
        n, sec, compute = asked.get(rows, (0, 0.0, 0))
        print(f"kernel.{kind}_attn_roofline: Np {rows}: {events} events {seconds:.6f} s, "
              f"{n} dispatches annotated ({compute} compute-bound), least a layer "
              f"{sec / n if n else 0.0:.9f} s", file=sys.stderr)
        if n:
            least += events * sec / n
            took += seconds
    return 100.0 * least / took if took else None


def window(ctx):
    return share(ctx, "window")


def full(ctx):
    return share(ctx, "full")
