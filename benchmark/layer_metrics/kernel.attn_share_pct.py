"""Share of the device's busy time spent in the attention kernels (events
named ``..._attention...``: the packed ragged kernel and the paged decode
kernel of the fused decode steps)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace.get("busy_s"):
        return None
    t = sum(s for label, s in trace["ops"].items() if "attention" in label)
    return 100.0 * t / trace["busy_s"]
