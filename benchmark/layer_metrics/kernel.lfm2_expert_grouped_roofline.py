"""Roofline share of the grouped expert product (``moe_grouped_matmul``) for
the lfm2_moe family: 32 experts of width 1792, four a token, so a 512-row
chunk leaves an expert 64 rows on average, half a row tile of the kernel.
The least time the chip could take for the launches of the traced slice over
the time they took, as measured and with no cap.

Counted over routed rows (what PR 30 and PR 36 learned): a launch's result
has ``R = K np`` rows for the packed shape's ``np``, but only the dispatch's
real rows are routed, the padding is sorted behind the groups and skipped.
What a launch was asked to do comes from the ``dispatch`` annotation
(``benchmark/trace_host.py``): ``r = K sum(q)`` rows, and
``costs_lfm2.grouped_matmul`` counts ``2 r H I`` operations and ``2 (E H I + r
H + r I)`` bytes, every expert's matrix read once.  Events are matched with
dispatches by ``R``; the mean least time of the annotated dispatches of a
width is set against every event of that width.  A step of fewer than 256
rows (a decode step, a short question) takes the capacity buffers and has no
such event.  The other families' readers of this kernel read their own
keys; this one reads ``num_experts``, ``num_experts_per_tok`` and
``moe_intermediate_size`` through ``costs_lfm2``.  A program that never takes
the grouped product reads nothing."""
import re
import sys

KERNEL = "moe_grouped_matmul"
RESULT = re.compile(r" = \(?\w+\[(\d+),(\d+)\]")


def launches(ctx):
    """By result rows: [events, seconds] of the kernel in the trace."""
    trace = ctx["trace"]
    out = {}
    for label, seconds in trace["ops"].items():
        text = trace.get("op_text", {}).get(label, "")
        m = RESULT.search(text)
        if KERNEL not in text.split(" = ", 1)[0] or not m:
            continue
        got = out.setdefault(int(m.group(1)), [0, 0.0])
        got[0] += trace["op_counts"][label]
        got[1] += seconds
    return out


def read(ctx):
    from benchmark import trace_host  # not at import

    cfg, model, costs = ctx["cfg"], ctx["model_costs"], ctx["costs"]
    if not hasattr(model, "state_bytes_per_page") or not cfg.get("num_experts"):
        return None  # not this family
    found = launches(ctx)
    t = trace_host.table(ctx) if found else None
    if t is None or not t["dispatches"]:
        return None
    k = cfg["num_experts_per_tok"]
    asked = {}  # by result rows: [dispatches, least seconds a launch, routed rows]
    for d in t["dispatches"]:
        rows = k * sum(d["q"])
        least, _bound = costs.roofline_seconds(
            *model.grouped_matmul(rows, cfg), ctx["peaks"])
        got = asked.setdefault(k * d["np"], [0, 0.0, 0])
        got[0] += 1
        got[1] += least
        got[2] += rows
    least = took = 0.0
    for rows, (events, seconds) in sorted(found.items()):
        n, sec, routed = asked.get(rows, (0, 0.0, 0))
        print(f"kernel.lfm2_expert_grouped_roofline: R {rows}: {events} events "
              f"{seconds:.6f} s, {n} dispatches annotated, {routed / n if n else 0.0:.0f} "
              f"routed rows and least {sec / n if n else 0.0:.9f} s a launch",
              file=sys.stderr)
        if n:
            least += events * sec / n
            took += seconds
    return 100.0 * least / took if took else None
