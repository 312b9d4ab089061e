"""Share of admissions whose convolution layers resumed from a page's
snapshot: ``dynamo_engine_state_restores`` over restores plus
``dynamo_engine_state_resets`` (admissions at position 0), over the window.
A re-admission after a preemption counts again: it resumes from a snapshot
too.  A program without the counters (no trunk with convolution layers)
reads nothing."""


def read(ctx):
    c = ctx["counters"]
    if not c.has("dynamo_engine_state_restores_total"):
        return None
    restores = c.delta("dynamo_engine_state_restores_total")
    resets = c.delta("dynamo_engine_state_resets_total")
    if not restores + resets:
        return None
    return 100.0 * restores / (restores + resets)
