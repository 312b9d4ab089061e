"""Idle share of the device over the traced slice of the window:
1 - union of device operations / traced seconds.  One reader for every
cell's idle metric (each metric's json names it); nothing is clamped."""


def read(ctx):
    if not ctx["trace"].get("device_planes") or not ctx["trace_window_s"]:
        return None
    return 100.0 * (1.0 - ctx["trace"]["busy_s"] / ctx["trace_window_s"])
