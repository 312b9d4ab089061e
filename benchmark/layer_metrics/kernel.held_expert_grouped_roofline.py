"""Roofline share of the grouped expert product (``moe_grouped_matmul``)
over the experts this chip holds, for the mistral4 family: the least time
the chip could take for the launches of the traced slice over the time they
took, as measured and with no cap.

``kernel.expert_grouped_roofline`` is the Mistral family's reader of the
same kernel and reads that family's keys; this one reads this family's
(``n_routed_experts`` held of ``router_experts``, width
``moe_intermediate_size``).  What a launch had to do depends on the tokens
its step routed, which its result's rows do not tell (they count the packed
axis, padding included, times ``num_experts_per_tok``): the ``dispatch``
annotation of every packed dispatch carries its lanes' fresh rows
(``benchmark/trace_host.py``), and ``costs_mla.held_grouped_launch`` counts
from them the rows that reach a held expert and the held experts a row
reaches; an expert no row reaches is not read, which at a question's 60
tokens is 5 of the 32.  The kernel's events are told by its name and by
their packed rows (result rows / ``num_experts_per_tok``), as the attention
readers tell theirs: the mean least time of the annotated dispatches of a
width is set against every event of that width.  A program that never takes
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
    from benchmark import costs_mla, trace_host  # not at import

    cfg, costs = ctx["cfg"], ctx["costs"]
    if not cfg.get("moe_intermediate_size"):
        return None
    found = launches(ctx)
    t = trace_host.table(ctx) if found else None
    if t is None or not t["dispatches"]:
        return None
    k = cfg["num_experts_per_tok"]
    asked = {}  # by packed rows: [dispatches, least seconds a launch]
    for d in t["dispatches"]:
        sec, _bound = costs.roofline_seconds(
            *costs_mla.held_grouped_launch(sum(d["q"]), cfg), ctx["peaks"])
        got = asked.setdefault(d["np"], [0, 0.0])
        got[0] += 1
        got[1] += sec
    least = took = 0.0
    for rows, (events, seconds) in sorted(found.items()):
        n, sec = asked.get(rows // k, (0, 0.0))
        print(f"kernel.held_expert_grouped_roofline: R {rows}: {events} events "
              f"{seconds:.6f} s, {n} dispatches annotated, least a launch "
              f"{sec / n if n else 0.0:.9f} s", file=sys.stderr)
        if n:
            least += events * sec / n
            took += seconds
    return 100.0 * least / took if took else None
