"""Roofline share of the packed latent-attention kernel
(``latent_packed_attention``): the least time the chip could take for the
launches of the traced slice over the time they took, as measured and with
no cap.

As ``kernel.packed_attn_roofline`` does for the pair pools' kernel: what a
launch was asked to do comes from the ``dispatch`` annotation of every
packed dispatch (its lanes' fresh query rows and context lengths,
``benchmark/trace_host.py``), counted by ``benchmark/costs_mla.py`` on the
path the dispatch took.  This program has one path, the absorbed form (the
annotation's ``latent`` says ``absorbed_kernel``), so that is the count.  A
kernel event is one layer of one dispatch; it is told by its name, and by
its packed rows ``Np`` (the first dimension of its result is ``Np`` x
heads), which the annotation carries too: the mean least time of the
annotated dispatches of a width is set against every event of that width.
A program without the kernel (the parent of the PR that brought it) reads
nothing."""
import re
import sys

KERNEL = "latent_packed_attention"
ROWS = re.compile(r" = \(?\w+\[(\d+),")


def launches(ctx, kernel=KERNEL):
    """By the first dimension of the result: [events, seconds] of the
    kernel in the trace."""
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
    from benchmark import costs_mla, trace_host  # not at import

    found = launches(ctx)
    if not found:
        return None
    t = trace_host.table(ctx)
    if t is None or not t["dispatches"]:
        return None
    costs, cfg, peaks = ctx["costs"], ctx["cfg"], ctx["peaks"]
    heads = cfg["num_attention_heads"]
    asked = {}  # by packed rows: [dispatches, least seconds a layer, compute-bound]
    for d in t["dispatches"]:
        least, bound = costs.roofline_seconds(
            *costs_mla.absorbed_launch(d["q"], d["ctx"], cfg), peaks)
        got = asked.setdefault(d["np"], [0, 0.0, 0])
        got[0] += 1
        got[1] += least
        got[2] += bound == "compute"
    least = took = 0.0
    for rows, (events, seconds) in sorted(found.items()):
        n, sec, compute = asked.get(rows // heads, (0, 0.0, 0))
        print(f"kernel.latent_attn_roofline: Np {rows // heads}: {events} events "
              f"{seconds:.6f} s, {n} dispatches annotated ({compute} compute-bound), "
              f"least a layer {sec / n if n else 0.0:.9f} s", file=sys.stderr)
        if n:
            least += events * sec / n
            took += seconds
    return 100.0 * least / took if took else None
