"""Roofline share of the chunked gated delta rule (the qwen3_next family's
linear layers in a packed step): the least time the chip could take for the
chunks the traced slice's dispatches asked for, over the time the device
spent in the loop that runs them, as measured and with no cap.

The program runs the chunks of a packed step's segments as an XLA
composition under the scope ``gdn_chunk``: a loop of as many turns as the
dispatch has chunks, each turn a chunk of 64 rows of one lane from that
lane's state.  A device trace names an event by its instruction, not by the
scope it was traced under, so the loop is found by what it carries: a
``while`` whose tuple holds the lanes' states ``f32[B, Hv, dk, dv]`` (twice:
the working states and the snapshots in the making) and the rows' output.
A ``while`` event spans the events of its body, so the sum of such events is
the loops' time (a dispatch of decode rows alone has the loops too, of no
turn and next to no time).  What a dispatch asked comes from its ``dispatch``
annotation (``benchmark/trace_host.py``): its lanes' fresh rows ``q``;
``costs_qwen3next.gdn_chunk_launch`` counts one layer's operations and bytes
for the segments of more than one row (a decode row takes the recurrence's
one step outside the loop, as anonymous fusions, like the convolution before
it: neither has a metric), once a linear layer.  The operations are float32
products at ``highest`` precision against a peak stated for bfloat16, so a
compute-bound launch can read a sixth at most: that is the price of the
state's type in this composition, and the share says so.  A program with no
such loop (any other family, a parent of this configuration) reads
nothing."""
import sys


def loop_seconds(ctx, carried):
    """Seconds and events of the ``while`` events whose tuple holds
    ``carried`` twice; None where the trace has none."""
    from benchmark import trace_host, trace_reduce  # not at import

    path = ctx.get("xplane") or trace_reduce.find_xplane(trace_host.TRACE_DIR)
    if path is None:
        return None
    from jax.profiler import ProfileData

    total, events, planes = 0.0, 0, 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            planes += 1
            for ev in line.events:
                name = ev.name
                if name.startswith("%while") and name.count(carried) >= 2:
                    total += ev.duration_ns * 1e-9
                    events += 1
    return (total / planes, events) if planes and events else None


def read(ctx):
    from benchmark import trace_host  # not at import

    cfg, model, costs = ctx["cfg"], ctx["model_costs"], ctx["costs"]
    if not hasattr(model, "gdn_chunk_launch"):
        return None  # not this family
    lanes = cfg.get("engine", {}).get("max_batch_size")
    carried = "f32[%d,%d,%d,%d]" % (
        lanes, cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
        cfg["linear_value_head_dim"])
    found = loop_seconds(ctx, carried)
    t = trace_host.table(ctx) if found else None
    if t is None or not t["dispatches"]:
        return None
    took, events = found
    layers = model.layers_of(cfg, "linear")
    least = chunked = 0.0
    for d in t["dispatches"]:
        qs = [q for q in d["q"] if q > 1]
        if qs:
            chunked += 1
            least += layers * costs.roofline_seconds(
                *model.gdn_chunk_launch(qs, cfg), ctx["peaks"])[0]
    print(f"kernel.gdn_chunk_roofline: {events} loops {took:.6f} s, {chunked:.0f} "
          f"dispatches with chunks annotated ({chunked * layers:.0f} loops), least "
          f"{least:.6f} s", file=sys.stderr)
    # every loop of the slice counts: a dispatch without chunks turns its
    # loops no time, and one that is not annotated makes the share read low
    if not chunked or not took:
        return None
    return 100.0 * least / took
