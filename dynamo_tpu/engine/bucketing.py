"""Shape bucketing: the ONE home of every pow2/pad rule the engine uses.

Every jitted dispatch absorbs request-shaped variability into a small,
bounded set of static shapes so the XLA compile cache stays O(log) in the
workload, never O(requests): prefill lengths bucket to powers of two,
prefix/page-table widths bucket to powers of two, prefill group batches pad
to powers of two, speculative draft columns pad to powers of two, and the
mixed-batch ragged query axis buckets to powers of two.  These rules used
to live scattered across ``step.py`` (length/page buckets), ``engine.py``
(group-batch and draft-column pads) -- drift between them would mint
surprise executables mid-serving, so they all route through here now.
``step.py`` re-exports the length/page helpers for compatibility.

Import-light on purpose (pure Python, no jax/numpy): the analyzer and the
scheduler both import it.
"""

from __future__ import annotations

import collections
from typing import List, Optional, Tuple


def pow2_bucket(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor).

    The universal pad rule: group batches (``engine._pad_batch``), draft
    columns (spec verify), soft-prompt rows, penalty-history buffers, and
    the mixed-batch ragged query axis all bucket through this, so each
    site compiles O(log(max)) executables.
    """
    n = max(int(n), int(floor))
    return 1 << max(n - 1, 0).bit_length()


def prefill_buckets(page_size: int, max_len: int) -> List[int]:
    """Power-of-two length buckets, all multiples of page_size."""
    max_len = -(-max_len // page_size) * page_size  # round up to a page multiple
    buckets = []
    b = page_size
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return buckets


def pick_bucket(buckets: List[int], n: int) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds max bucket {buckets[-1]}")


def pick_page_bucket(n_pages: int, max_pages: int) -> int:
    """Static width for page-table gathers: smallest power of two
    >= n_pages (capped at max_pages), so compile-cache entries stay few."""
    if n_pages > max_pages:
        raise ValueError(f"{n_pages} prefix pages exceed max {max_pages}")
    return min(pow2_bucket(n_pages), max_pages)


class PackedShapeBudget:
    """Bound the packed unified step's ``(Np, s_max, s_spec)`` executable set.

    The packed layout compiles one executable per (packed-axis length,
    per-lane window, spec-column width) triple.  All three axes already
    bucket to powers of two (``s_spec`` is the folded-verify column count,
    ``1 + pow2(draft)`` -- the MAX_DRAFT_TOKENS pad rule, so it draws from
    {0, 1, 2, 3, 5, 9}), but real traffic mixes decode-only ticks, short
    chunks, long-context chunks, and speculating lanes, so the cross
    product can still mint O(log budget x log chunk x log draft) triples
    -- each a fresh multi-second XLA compile landing mid-serving.  This
    budget caps the ACTIVE triple set: a dispatch whose natural triple is
    already minted (or was merged before) reuses it; a new triple mints
    freely under ``budget``; past the budget, the dispatch is merged up
    into the smallest already-minted triple that dominates it (``s_max' >=
    s_max`` as far as the launch can tell them apart, ``s_spec' >=
    s_spec``, and ``Np'`` covering the recomputed packed extent: the
    contract below) -- more padding, identical math, zero new executables.
    Padding spec columns up is legal the same way padding the window is:
    columns past a lane's ``v_lens`` are invalid, sample garbage that the
    commit walk never reads (it is bounded by the dispatched draft
    length), and their KV writes route to the trash page.  Only when
    nothing dominates does a mint evict the least-recently-used triple.

    Correctness contract: which rule holds is the launch's to say
    (``attention.PackedLaunch.item_rows``, asked once at construction).

    * ``item_rows == 0``, the window rule: the launch reads a whole
      ``s_max``-row window from every live lane's offset (the grid kernel
      of narrow heads and int8 pools, the XLA composition, the latent
      kernels).  A returned triple always satisfies ``off_last + s_max <=
      Np`` and ``total <= Np``, where ``off_last`` is the last live lane's
      segment offset, and ``s_max`` covers the longest segment.
    * ``item_rows == Q > 0``, window-free: the launch walks work items of
      at most ``Q`` rows and keeps every tile inside the packed axis (the
      pair pools' work-list kernel).  Only ``total <= Np`` binds.  Beyond
      ``Q`` a triple's ``s_max`` means nothing to that kernel -- every tile
      serves every segment length -- so a dispatch merges into the smallest
      minted ``Np >= total`` whose query block ``min(s_max, Q)`` is no
      smaller than its own: a segment cut into smaller blocks than its
      shape would give it re-reads its keys once a block.

    Padding rows carry lane id B and are inert under both.
    """

    def __init__(self, budget: int = 16, item_rows: int = 0) -> None:
        self.budget = max(int(budget), 1)
        self.item_rows = int(item_rows)
        # (Np, s_max, s_spec) -> hits, LRU order (oldest first)
        self._pairs: "collections.OrderedDict[Tuple[int, int, int], int]" = (
            collections.OrderedDict()
        )
        self.merges = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._pairs)

    @property
    def pairs(self) -> List[Tuple[int, int, int]]:
        return list(self._pairs)

    @property
    def spec_shapes(self) -> List[Tuple[int, int, int]]:
        """The minted triples carrying folded-verify columns (s_spec > 0)."""
        return [t for t in self._pairs if t[2] > 0]

    def _np_for(self, s_max: int, off_last: int, total: int) -> int:
        if self.item_rows:
            return pow2_bucket(total)
        return pow2_bucket(max(total, off_last + s_max))

    def _block(self, s_max: int) -> int:
        """What of ``s_max`` the launch can tell apart."""
        return min(s_max, self.item_rows) if self.item_rows else s_max

    def fit(
        self, s_max: int, off_last: int, total: int, s_spec: int = 0
    ) -> Tuple[int, int, int]:
        """Resolve a dispatch's natural ``(s_max, off_last, total,
        s_spec)`` to a budgeted ``(Np, s_max, s_spec)`` triple (see class
        docstring).  ``s_spec`` is 0 for spec-free dispatches -- those
        never merge into a spec-carrying executable (the spec column
        sampler would run for nothing every tick of a spec-free
        workload)."""
        nat = (self._np_for(s_max, off_last, total), s_max, s_spec)
        if nat in self._pairs:
            self._pairs[nat] += 1
            self._pairs.move_to_end(nat)
            return nat
        if len(self._pairs) < self.budget:
            self._pairs[nat] = 1
            return nat
        # merge up: smallest minted triple that dominates the dispatch
        best: Optional[Tuple[int, int, int]] = None
        for np_m, s_m, sp_m in self._pairs:
            if self._block(s_m) < self._block(s_max):
                continue
            if np_m < self._np_for(s_m, off_last, total):
                continue
            if sp_m < s_spec or (s_spec == 0 and sp_m > 0):
                continue
            if best is None or (np_m, s_m, sp_m) < best:
                best = (np_m, s_m, sp_m)
        if best is not None:
            self.merges += 1
            self._pairs[best] += 1
            self._pairs.move_to_end(best)
            return best
        # nothing dominates (e.g. a new widest shape): evict the LRU triple
        self._pairs.popitem(last=False)
        self.evictions += 1
        self._pairs[nat] = 1
        return nat
