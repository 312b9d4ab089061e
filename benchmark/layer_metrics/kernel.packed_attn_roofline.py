"""Roofline share of the packed ragged attention kernel: the least time
the chip could take for the launches of the traced slice over the time they
took, as measured and with no cap.

What a launch was asked to do comes from the program: the ``dispatch``
annotation of every packed dispatch carries its lanes' fresh query rows and
context lengths (``benchmark/trace_host.py``), and ``benchmark/costs_attn.py``
counts the operations and bytes they cannot do without.  The kernel's events
are told by their name, and by their packed rows ``Np`` (the first dimension
of the result) which the annotation carries too: a kernel event stands for
one layer of one dispatch, so the mean least time of the annotated
dispatches of a width is set against every event of that width.  That keeps
the share right where the slice cuts a dispatch off from its events."""
import re
import sys

KERNEL = "packed_ragged_attention"
ROWS = re.compile(r" = \(?\w+\[(\d+),")


def launches(ctx):
    """By packed rows: [events, seconds] of the kernel in the trace."""
    trace = ctx["trace"]
    out = {}
    for label, seconds in trace["ops"].items():
        text = trace.get("op_text", {}).get(label, "")
        if KERNEL not in text.split(" = ", 1)[0]:
            continue
        m = ROWS.search(text)
        got = out.setdefault(int(m.group(1)) if m else 0, [0, 0.0])
        got[0] += trace["op_counts"][label]
        got[1] += seconds
    return out


def read(ctx):
    from benchmark import costs_attn, trace_host  # not at import

    t = trace_host.table(ctx)
    if t is None or not t["dispatches"]:
        return None
    costs, cfg, peaks = ctx["costs"], ctx["cfg"], ctx["peaks"]
    asked = {}  # by packed rows: [dispatches, least seconds a layer, of them compute-bound]
    for d in t["dispatches"]:
        least, bound = costs.roofline_seconds(
            *costs_attn.launch(d["q"], d["ctx"], cfg), peaks)
        got = asked.setdefault(d["np"], [0, 0.0, 0])
        got[0] += 1
        got[1] += least
        got[2] += bound == "compute"
    least = took = 0.0
    for rows, (events, seconds) in sorted(launches(ctx).items()):
        n, sec, compute = asked.get(rows, (0, 0.0, 0))
        print(f"kernel.packed_attn_roofline: Np {rows}: {events} events {seconds:.6f} s, "
              f"{n} dispatches annotated ({compute} compute-bound), least a layer "
              f"{sec / n if n else 0.0:.9f} s", file=sys.stderr)
        if n:
            least += events * sec / n
            took += seconds
    return 100.0 * least / took if took else None
