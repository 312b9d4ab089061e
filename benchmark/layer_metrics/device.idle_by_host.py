"""Share of the traced seconds in which the device ran nothing while the
host's tick loop was in a phase: ``host_work`` under any phase but
``device_wait`` (the host had work to do before the device could go on),
``in_wait`` under ``device_wait`` (the host says it waits for a device that
runs nothing).  From ``benchmark/trace_host.py``, which prints the whole
table (parked and unattributed too; the four add up to ``device.idle_pct``).
One reader file for both metrics.  A trace without the program's
``dyn.tick`` annotations gives nothing."""


def _share(ctx, key):
    from benchmark import trace_host  # not at import: it parses a trace

    t = trace_host.table(ctx)
    return None if t is None else t["shares_pct"][key]


def host_work(ctx):
    return _share(ctx, "host_work")


def in_wait(ctx):
    return _share(ctx, "in_wait")
