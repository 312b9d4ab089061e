"""How many decode steps a pressure-free tick may fuse (ISSUE 42).

Fusing exists to hide the tick loop's own work behind the device's: while
one fused dispatch runs, the host commits the one before it, plans and
enqueues the next.  A block longer than that work buys nothing, and a
request admitted beside decoding lanes waits behind every step of it (and
of the generation queued behind it) before its first chunk row runs.  So
the ramp's ceiling is the smallest block that outlasts the loop's work by
``MARGIN``::

    K * (service seconds a decode step)  >=  MARGIN * (the loop's seconds a tick)

Both sides are running means of what the engine reads anyway, watched or
not: the dispatch record's service of decode-only dispatches over the steps
they fused (``JaxEngine._record_service``), and a decode-only tick's wall
time less the time it sat blocked in the commit's fetch.  No device, no
clock and no engine in here: the tick loop hands the readings over.
"""

from __future__ import annotations

from typing import Optional

# a fused block outlasts the loop's own work per tick by this factor.  Set
# by the DYN_MULTISTEP sweep of PERF.md section 6 (PR 42): the least value
# at which no cell lost tokens per second, inter-token time or device idle
# share against the constant ceiling of 8.
MARGIN = 2.0
# the means are slow (a sixty-fourth of each reading), so that the ceiling
# does not flap between two widths inside a few seconds of serving
_RATE = 1.0 / 64.0
# a reading enters its mean as at most this many times the mean: a compile,
# a collection or a stall of the whole machine is no reading of the work
_OUTLIER = 4.0


def _blend(mean: Optional[float], reading: float) -> Optional[float]:
    if reading <= 0.0:
        return mean
    if mean is None:
        return reading
    return mean + _RATE * (min(reading, _OUTLIER * mean) - mean)


class FusedStepCeiling:
    """The ceiling of the fused decode block among the widths the ramp
    visits (1, 2, 4, ... up to ``widest``, the widest block an executable
    exists for).  Until both means have a reading it is ``widest``."""

    def __init__(self, widest: int) -> None:
        self.widest = max(int(widest), 1)
        self.step_s: Optional[float] = None
        self.loop_s: Optional[float] = None

    def observe_step(self, seconds: float) -> None:
        """Service seconds per forward pass of one decode-only dispatch."""
        self.step_s = _blend(self.step_s, seconds)

    def observe_loop(self, seconds: float) -> None:
        """One decode-only tick's wall time less its blocked fetch."""
        self.loop_s = _blend(self.loop_s, seconds)

    def value(self) -> int:
        if self.step_s is None or self.loop_s is None:
            return self.widest
        k = 1
        while k < self.widest and k * self.step_s < MARGIN * self.loop_s:
            k = min(2 * k, self.widest)
        return k
