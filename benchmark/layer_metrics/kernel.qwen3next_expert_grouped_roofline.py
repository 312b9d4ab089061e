"""Roofline share of the grouped expert product (``moe_grouped_matmul``) for
the qwen3_next family: 512 routed experts of width 512, ten a token, of which
this chip holds 128: a 2048-row chunk routes 20480 assignments of which a
quarter reach a held expert, 40 rows an expert in the mean.  The least time
the chip could take for the launches of the traced slice over the time they
took, as measured and with no cap.

Counted over routed rows and held experts reached: a launch's result has ``R
= K np`` rows for the packed shape's ``np``, but only the dispatch's real rows
are routed, only those to a held expert multiply, and an expert no row
reaches is not read (``costs_qwen3next.held_grouped_launch``).  What a launch
was asked to do comes from the ``dispatch`` annotation
(``benchmark/trace_host.py``).  Events are matched with dispatches by ``R``;
the mean least time of the annotated dispatches of a width is set against
every event of that width.  The fused decode steps' launches (16 lanes) have
no annotated dispatch of their width and are left out.  A program that never
takes the grouped product reads nothing."""
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
    if not hasattr(model, "gdn_chunk_launch"):
        return None  # not this family
    found = launches(ctx)
    t = trace_host.table(ctx) if found else None
    if t is None or not t["dispatches"]:
        return None
    k = cfg["num_experts_per_tok"]
    asked = {}  # by result rows: [dispatches, least seconds a launch, tokens]
    for d in t["dispatches"]:
        tokens = sum(d["q"])
        least, _bound = costs.roofline_seconds(
            *model.held_grouped_launch(tokens, cfg), ctx["peaks"])
        got = asked.setdefault(k * d["np"], [0, 0.0, 0])
        got[0] += 1
        got[1] += least
        got[2] += tokens
    least = took = 0.0
    for rows, (events, seconds) in sorted(found.items()):
        n, sec, tokens = asked.get(rows, (0, 0.0, 0))
        print(f"kernel.qwen3next_expert_grouped_roofline: R {rows}: {events} events "
              f"{seconds:.6f} s, {n} dispatches annotated, {tokens / n if n else 0.0:.0f} "
              f"tokens and least {sec / n if n else 0.0:.9f} s a launch", file=sys.stderr)
        if n:
            least += events * sec / n
            took += seconds
    return 100.0 * least / took if took else None
