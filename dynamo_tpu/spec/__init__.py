"""Speculative decoding subsystem: pluggable drafters + batched verify.

Decode is memory-bound (BENCH_r05 estimates ~0.5 HBM utilization at bs8):
every decode step streams the full weight set to produce ONE token per
lane.  Draft-and-verify speculation converts that headroom into tokens/s --
a cheap *drafter* proposes the next few tokens from host-side token
history, the engine scores all of them in ONE forward pass (the verify
step: ``engine/step.py:verify_and_sample``), and the longest prefix whose
drafts match the model's own samples commits in a single step.  Rejected
columns are discarded by the same host-side replay that already drops
post-finish speculative columns (``scheduler._commit_lane_column``), so a
bad draft can only cost wasted compute, never wrong output: committed
tokens are always the TARGET model's samples, which makes speculative
output distribution-exact for any sampling mode and bit-identical to plain
decode for greedy and seeded lanes (per-lane noise is a pure function of
(seed, position) -- ``sampling._lane_gumbel``).

The package is engine-agnostic: drafters see token histories, never device
state.  ``Drafter`` is the extension point; :class:`NGramDrafter` is the
model-free prompt-lookup baseline that needs no second weight load, and
:class:`~.model_drafter.ModelDrafter` (``spec/model_drafter.py``) is the
RTP-LLM-style learned proposer -- a second small weight load, TP-sharded
onto the serving mesh, registered under kind ``"model"`` when the engine
is armed with ``draft_model``.

With the packed unified dispatch (ISSUE 15), verify is not even a
separate dispatch on the serving hot path: speculating lanes' columns
fold into ``step.packed_unified_step`` as additional flat-axis segments
(``verify_and_sample`` remains the classic-path fallback),
and acceptance-aware auto-disable reverts low-acceptance lanes to plain
decode so speculation is safe to run default-on.
"""

from .drafter import (
    DRAFTERS,
    MAX_DRAFT_TOKENS,
    Drafter,
    NGramDrafter,
    SpecState,
    longest_accepted,
    make_drafter,
    register_drafter,
    spec_live,
)

__all__ = [
    "DRAFTERS",
    "MAX_DRAFT_TOKENS",
    "Drafter",
    "NGramDrafter",
    "SpecState",
    "longest_accepted",
    "make_drafter",
    "register_drafter",
    "spec_live",
]
