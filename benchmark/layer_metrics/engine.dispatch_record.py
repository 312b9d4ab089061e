"""The tick loop's dispatch record over the whole window, from the two
``/metrics`` bodies the harness fetches at its ends.  The engine's commit
observes, for every dispatch it fetches, the class of the step (``chunk``: a
unified dispatch that carried prefill rows; ``decode``: one that carried
none), the executable's packed rows and the time the device spent on it as
the host reads it (this commit's clock less the later of the dispatch's
enqueue and the commit before: no two readings overlap, and their sum is at
most the wall time); and once a request, what its first token waited behind.
One reader file for nine metrics; each metric's json names its function.
A program without the families (or a mocker that mints them and observes
nothing) gives nothing."""
import json
import os
import sys

SERVICE = "dynamo_engine_dispatch_service_seconds"
STEPS = "dynamo_engine_dispatch_steps_total"
LANE_STEPS = "dynamo_engine_decode_lane_steps_total"
PARKED = "dynamo_engine_parked_seconds_total"
CLOCK = "dynamo_engine_clock_seconds"
WAIT = "dynamo_engine_first_token_wait_seconds"
FIRST = "dynamo_engine_first_token_service_seconds"
ROWS = "dynamo_engine_first_token_chunk_rows_total"
MIXED = "dynamo_engine_mixed_tokens_total"


def _ratio(num, den, scale=100.0):
    return scale * num / den if den else None


def chunk_step_mean_ms(ctx):
    """Mean service time of a chunk step (one forward pass a dispatch)."""
    c = ctx["counters"]
    return _ratio(c.delta(SERVICE + "_sum", step="chunk"),
                  c.delta(SERVICE + "_count", step="chunk"), 1e3)


def decode_step_mean_ms(ctx):
    """Service time of the decode-only dispatches over the forward passes
    they ran: a fused dispatch of k steps counts k."""
    c = ctx["counters"]
    return _ratio(c.delta(SERVICE + "_sum", step="decode"),
                  c.delta(STEPS, step="decode"), 1e3)


def decode_steps_per_dispatch(ctx):
    """Forward passes a decode-only dispatch ran, in the mean: how long the
    fused block is that a request admitted beside decoding lanes waits
    behind (1 to ``multistep_max_k``; PR 42's ceiling keeps it short)."""
    c = ctx["counters"]
    return _ratio(c.delta(STEPS, step="decode"),
                  c.delta(SERVICE + "_count", step="decode"), 1.0)


def _first_token_share(ctx, behind):
    c = ctx["counters"]
    if not c.delta(WAIT + "_count", behind=behind):
        return None
    return _ratio(c.delta(WAIT + "_sum", behind=behind), c.delta(FIRST + "_sum"))


def first_token_in_chunk_steps(ctx):
    return _first_token_share(ctx, "chunk_steps")


def first_token_in_decode_steps(ctx):
    return _first_token_share(ctx, "decode_steps")


def first_token_own_rows(ctx):
    """Of the prefill rows committed between requests' first admissions and
    their first tokens, the share that was the requests' own."""
    c = ctx["counters"]
    return _ratio(c.delta(ROWS, whose="own"), c.delta(ROWS, whose="all"))


def decode_rows_in_chunk_steps(ctx):
    c = ctx["counters"]
    return _ratio(c.delta(LANE_STEPS, step="chunk"), c.delta(LANE_STEPS))


def packed_rows_used(ctx):
    """Real rows over rows the executables ran, unified dispatches only."""
    c = ctx["counters"]
    return _ratio(c.delta(MIXED, kind="used"), c.delta(MIXED, kind="dispatched"))


def _service_in_trace(ctx):
    """Of the traced slice: the ``svc_us`` the ``device_wait`` annotations
    carry, summed, and how many carried one.  The program's clock beside
    the device's (``busy_s``), every traced run."""
    from benchmark import trace_host, trace_reduce  # not at import: parses a trace

    planes = ctx.get("planes")
    if planes is None:
        path = ctx.get("xplane") or trace_reduce.find_xplane(trace_host.TRACE_DIR)
        if path is None:
            return None
        os.environ["JAX_PLATFORMS"] = "cpu"  # before JAX is imported, as trace_host.load
        from jax.profiler import ProfileData

        planes = ProfileData.from_file(path).planes
    total_us = n = 0
    for plane in planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != trace_host.TICK:
                    continue
                stats = dict(ev.stats)
                if "svc_us" in stats:
                    total_us += int(stats["svc_us"])
                    n += 1
    return {"svc_s": total_us * 1e-6, "fetches": n} if n else None


def loop_held_device(ctx):
    """Share of the window in which the loop was awake and the device had
    none of its dispatches: window less service less parked.  The window is
    the program's own clock between the two scrapes where it exposes one
    (the harness's stopwatch stops before the second scrape).  Raw."""
    c = ctx["counters"]
    if not c.delta(SERVICE + "_count"):
        return None
    window = c.delta(CLOCK) or ctx["window_s"]
    served, parked = c.delta(SERVICE + "_sum"), c.delta(PARKED)
    info = {"window_s": window, "served_pct": 100.0 * served / window,
            "parked_pct": 100.0 * parked / window,
            "harness_window_s": ctx.get("window_s")}
    try:
        traced = _service_in_trace(ctx)
    except Exception as e:  # a trace this reader cannot parse moves no metric
        traced = {"error": repr(e)}
    if traced is not None:
        info["traced_slice"] = dict(
            traced, busy_s=ctx.get("trace", {}).get("busy_s"),
            trace_window_s=ctx.get("trace_window_s"))
    print("info " + json.dumps({"dispatch_record": info}), file=sys.stderr, flush=True)
    return 100.0 * (window - served - parked) / window
