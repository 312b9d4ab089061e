"""Host work per device dispatch: the TickProfiler's phases over the
window, less ``device_wait`` (the host waiting for the chip) and ``other``,
over the dispatches it counted."""


def read(ctx):
    p = ctx.get("profiler")
    if not p or not p.get("dispatches"):
        return None
    work = sum(v for k, v in p["phase_totals_s"].items()
               if k not in ("device_wait", "other"))
    return 1e3 * work / p["dispatches"]
