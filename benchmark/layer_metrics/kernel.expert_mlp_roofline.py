"""Roofline share of the three expert matrix products: the least time the
chip could take for them (operations and bytes from ``costs.expert_matmul``)
over the time they took, as measured and with no cap.

A product is told by what it is, not by how fast it ran: a fusion whose
result is an expert buffer ``bf16[E, C, H or I]``, one of whose operands is
the experts' weights (``bf16[..., E, H, I]`` or ``bf16[..., E, I, H]``) and
another a buffer of the same capacity, ``bf16[E, C, H or I]``.  An
elementwise fusion over a buffer of the same shape reads no weights, and a
copy of one layer's expert weights out of the layers' stack (a result
``bf16[E, I, H]``, which reads as a buffer of capacity I) reads no buffer.

The shapes are the Mistral family's (experts under ``intermediate_size``
and ``num_local_experts``): a configuration of another family brings a
reader of its own for its products, and does not list its cells here."""
import re
import sys


def products(ctx):
    """(label, C, events, seconds) of every expert product in the trace."""
    cfg, trace = ctx["cfg"], ctx["trace"]
    e, h, i = cfg.get("num_local_experts"), cfg["hidden_size"], cfg["intermediate_size"]
    result = re.compile(rf"bf16\[{e},(\d+),(?:{h}|{i})\]")
    weights = re.compile(rf"bf16\[(?:\d+,)*{e},(?:{h},{i}|{i},{h})\]")
    out = []
    for label, seconds in trace["ops"].items():
        head, sep, operands = trace.get("op_text", {}).get(label, "").partition(" fusion(")
        m = result.search(head.partition(" = ")[2])
        if not (sep and m and weights.search(operands)):
            continue
        # the buffer multiplied: the result's E and C over the other width,
        # and not the operand that was taken for the weights
        rest = weights.sub("", operands)
        if re.search(rf"bf16\[{e},{m.group(1)},(?:{h}|{i})\]", rest):
            out.append((label, int(m.group(1)), trace["op_counts"][label], seconds))
    return out


def read(ctx):
    cfg, costs = ctx["cfg"], ctx["costs"]
    e, h, i = cfg.get("num_local_experts"), cfg["hidden_size"], cfg["intermediate_size"]
    if not e:
        return None
    least = took = 0.0
    events = 0
    for _label, c, n, seconds in products(ctx):
        flops, nbytes = costs.expert_matmul(e, c, h, i)
        t, _bound = costs.roofline_seconds(flops, nbytes, ctx["peaks"])
        least += t * n
        took += seconds
        events += n
    attn = sum(n for label, n in ctx["trace"]["op_counts"].items() if "attention" in label)
    print(f"kernel.expert_mlp_roofline: {events} product events for {attn} attention "
          f"events (3 to 1 when every product is found), least {least:.6f} s of "
          f"{took:.6f} s", file=sys.stderr)
    return 100.0 * least / took if took else None
