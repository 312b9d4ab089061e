"""How near the steps come to streaming the weights at the chip's HBM
rate: weight bytes x forward passes / (device busy seconds x peak bytes/s).
Passes and busy seconds are both those of the traced slice.  The bytes a
pass streams and the count of passes are the configuration's own
(``ctx["model_costs"]``: the module its file names under ``"costs"``)."""


def read(ctx):
    trace, cfg, model = ctx["trace"], ctx["cfg"], ctx["model_costs"]
    if not trace.get("busy_s"):
        return None
    passes = model.forward_passes(trace["op_counts"], cfg)
    if not passes:
        return None
    nbytes = model.weight_bytes(cfg) * passes
    return 100.0 * nbytes / (trace["busy_s"] * ctx["peaks"]["hbm_bytes_per_s"])
