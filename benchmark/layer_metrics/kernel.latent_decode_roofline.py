"""Roofline share of the decode attention over the latent pool, whichever
kernel serves it: the least time the chip could take for the decode
launches of the traced slice (each lane's context x one latent row, 640 B
at the published widths, and the absorbed form's operations:
``costs_mla.decode_launch``) over the time they took, as measured and with
no cap.

A dispatch that carries no prefill rows (its ``dispatch`` annotation says
``step: decode``) runs ``k`` steps: the first through the packed kernel
``latent_packed_attention`` in the executable of one row a lane, the
``k - 1`` after it through ``latent_decode_attention``, each once a layer
over every lane.  Since PR 42 such a dispatch is 1.2 steps in
longdoc-open, so nearly all of the decode attention is the first kind.
Both are counted alike: one query row a lane against its context as the
dispatch begins (it grows by one a step, which the count leaves out: under
a thousandth at these contexts).  The packed kernel's events are told by
their packed rows (result rows / heads): only the widths that the slice's
decode-only dispatches ran, so a question's 256-row launch and a chunk's
are ``kernel.latent_attn_roofline``'s alone.  The mean least time of a
launch over the annotated dispatches (every decode-only dispatch once for
the packed kernel, ``k - 1`` times for the fused one) is set against every
event of that kernel, which keeps the share right where the slice cuts a
dispatch off from its events.  A kernel without events or without an
annotated step adds nothing to either side; a program with neither reads
nothing."""
import importlib.util
import os
import sys

PACKED = "latent_packed_attention"
FUSED = "latent_decode_attention"


def _launches_of():
    """``launches`` of the packed kernel's reader: events and seconds of a
    kernel by the first dimension of its result."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "kernel.latent_attn_roofline.py")
    spec = importlib.util.spec_from_file_location("reader_latent_attn", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.launches


def read(ctx):
    from benchmark import costs_mla, trace_host  # not at import

    launches = _launches_of()
    packed, fused = launches(ctx, PACKED), launches(ctx, FUSED)
    if not packed and not fused:
        return None
    t = trace_host.table(ctx)
    if t is None:
        return None
    costs, cfg, peaks = ctx["costs"], ctx["cfg"], ctx["peaks"]
    heads = cfg["num_attention_heads"]
    firsts = {}  # by packed rows: [decode-only dispatches, least seconds a layer]
    steps = fused_least = 0.0
    for d in t["dispatches"]:
        if d["step"] != "decode":
            continue
        sec, _bound = costs.roofline_seconds(*costs_mla.decode_launch(d["ctx"], cfg), peaks)
        got = firsts.setdefault(d["np"], [0, 0.0])
        got[0] += 1
        got[1] += sec
        steps += d["k"] - 1
        fused_least += (d["k"] - 1) * sec
    least = took = 0.0
    for rows, (events, seconds) in sorted(packed.items()):
        n, sec = firsts.get(rows // heads, (0, 0.0))
        if n:
            print(f"kernel.latent_decode_roofline: {PACKED} Np {rows // heads}: {events} "
                  f"events {seconds:.6f} s, {n} decode-only dispatches annotated, least a "
                  f"launch {sec / n:.9f} s", file=sys.stderr)
            least += events * sec / n
            took += seconds
    events = sum(n for n, _s in fused.values())
    seconds = sum(s for _n, s in fused.values())
    if events or steps:
        print(f"kernel.latent_decode_roofline: {FUSED}: {events} events {seconds:.6f} s, "
              f"{steps:.0f} fused steps annotated, least a launch "
              f"{fused_least / steps if steps else 0.0:.9f} s", file=sys.stderr)
    if events and steps:
        least += events * fused_least / steps
        took += seconds
    return 100.0 * least / took if took else None
