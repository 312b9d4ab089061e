"""Roofline share of the grouped expert product (``moe_grouped_matmul``):
the least time the chip could take for the launches of the traced slice
(operations and bytes from ``costs_moe.grouped_matmul``, by the rows of each
launch's result) over the time they took, as measured and with no cap.

The kernel's events are told by its name; ``R`` is the first dimension of
the result and its second says which way round the product was, which does
not change the count.  A program that has no such kernel (the parent of the
PR that brought it, or a configuration that drops) reads nothing.  The line
on stderr sets the launches beside the capacity path's products, which
``kernel.expert_mlp_roofline`` reads: the share of expert products that took
the grouped path in the slice."""
import importlib.util
import os
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
        if KERNEL not in text.split(" = ", 1)[0]:
            continue
        m = RESULT.search(text)
        if not m:
            continue
        got = out.setdefault(int(m.group(1)), [0, 0.0])
        got[0] += trace["op_counts"][label]
        got[1] += seconds
    return out


def capacity_products(ctx):
    """Events of the capacity path's products, by the rule of the reader
    beside this one (``kernel.expert_mlp_roofline.py:products``)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "kernel.expert_mlp_roofline.py")
    spec = importlib.util.spec_from_file_location("reader_expert_mlp", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return sum(n for _label, _c, n, _seconds in mod.products(ctx))


def read(ctx):
    from benchmark import costs_moe  # not at import

    cfg, costs = ctx["cfg"], ctx["costs"]
    e, h, i = cfg.get("num_local_experts"), cfg["hidden_size"], cfg["intermediate_size"]
    if not e:
        return None
    least = took = 0.0
    events = 0
    for rows, (n, seconds) in sorted(launches(ctx).items()):
        t, bound = costs.roofline_seconds(*costs_moe.grouped_matmul(rows, e, h, i), ctx["peaks"])
        print(f"kernel.expert_grouped_roofline: R {rows}: {n} events {seconds:.6f} s, "
              f"least {t:.9f} s each ({bound}-bound)", file=sys.stderr)
        least += t * n
        took += seconds
        events += n
    print(f"kernel.expert_grouped_roofline: {events} grouped product events beside "
          f"{capacity_products(ctx)} product events of the capacity path, least "
          f"{least:.6f} s of {took:.6f} s", file=sys.stderr)
    return 100.0 * least / took if took else None
