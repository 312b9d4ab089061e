"""The snapshot pool of a trunk with gated delta-rule layers, from the
program's counters over the window.

``restore_pct``: admissions whose linear layers resumed from a snapshot slot,
``dynamo_engine_state_restores`` over restores plus ``dynamo_engine_state_
resets`` (admissions at position 0); this reader counts only where the
program has the snapshot pool's own counter, so a trunk with convolution
layers (``sched.state_restore_pct``) reads nothing here.  ``recompute_mean``:
tokens of a prefix hit that lay behind the snapshot it resumed from and were
computed again, ``dynamo_engine_state_snapshot_recompute_tokens`` over
restores: at most a chunk a hit."""

POOL = "dynamo_engine_state_snapshots_total"


def _counts(ctx):
    c = ctx["counters"]
    if not c.has(POOL):
        return None
    return (c, c.delta("dynamo_engine_state_restores_total"),
            c.delta("dynamo_engine_state_resets_total"))


def restore_pct(ctx):
    got = _counts(ctx)
    if got is None or not got[1] + got[2]:
        return None
    _c, restores, resets = got
    return 100.0 * restores / (restores + resets)


def recompute_mean(ctx):
    got = _counts(ctx)
    if got is None or not got[1]:
        return None
    c, restores, _resets = got
    return c.delta("dynamo_engine_state_snapshot_recompute_tokens_total") / restores
