"""How near the steps come to streaming the weights at the chip's HBM
rate: weight bytes x forward passes / (device busy seconds x peak bytes/s).
Passes and busy seconds are both those of the traced slice: every layer of
a pass runs one attention kernel, so passes = attention events / layers."""


def read(ctx):
    trace, cfg = ctx["trace"], ctx["cfg"]
    if not trace.get("busy_s"):
        return None
    kernels = sum(n for label, n in trace["op_counts"].items() if "attention" in label)
    passes = kernels / cfg["num_hidden_layers"]
    if not passes:
        return None
    nbytes = ctx["costs"].weight_bytes(cfg) * passes
    return 100.0 * nbytes / (trace["busy_s"] * ctx["peaks"]["hbm_bytes_per_s"])
