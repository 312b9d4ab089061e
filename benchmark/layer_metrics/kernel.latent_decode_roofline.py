"""Roofline share of the latent decode kernel (``latent_decode_attention``):
the least time the chip could take for the launches of the traced slice
(each lane's context x one latent row, 640 B at the published widths, and
the absorbed form's operations: ``costs_mla.decode_launch``) over the time
they took, as measured and with no cap.

The kernel runs in the fused decode steps: a dispatch of ``k`` steps runs
its first step through the packed kernel and ``k - 1`` through this one,
once a layer, over every lane of the batch.  The ``dispatch`` annotation
carries the lanes' contexts as the dispatch begins (they grow by one a
step, which the count leaves out: under a thousandth at these contexts).
The mean least time of a launch, over the annotated dispatches weighted by
their ``k - 1`` steps, is set against every event of the slice, which keeps
the share right where the slice cuts a dispatch off from its events.  A
program without the kernel reads nothing."""
import importlib.util
import os
import sys

KERNEL = "latent_decode_attention"


def _launches(ctx):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "kernel.latent_attn_roofline.py")
    spec = importlib.util.spec_from_file_location("reader_latent_attn", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.launches(ctx, KERNEL)


def read(ctx):
    from benchmark import costs_mla, trace_host  # not at import

    found = _launches(ctx)
    if not found:
        return None
    t = trace_host.table(ctx)
    if t is None:
        return None
    costs, cfg, peaks = ctx["costs"], ctx["cfg"], ctx["peaks"]
    steps = least = 0.0
    for d in t["dispatches"]:
        if d["k"] <= 1:
            continue
        sec, _bound = costs.roofline_seconds(*costs_mla.decode_launch(d["ctx"], cfg), peaks)
        steps += d["k"] - 1
        least += (d["k"] - 1) * sec
    events = sum(n for n, _s in found.values())
    took = sum(s for _n, s in found.values())
    print(f"kernel.latent_decode_roofline: {events} events {took:.6f} s, "
          f"{steps:.0f} fused steps annotated, least a launch "
          f"{least / steps if steps else 0.0:.9f} s", file=sys.stderr)
    if not steps or not took:
        return None
    return 100.0 * events * (least / steps) / took
