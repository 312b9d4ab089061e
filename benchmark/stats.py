"""The arithmetic from request events to end-to-end metrics, and the parser
of the Prometheus text the server exposes.  No JAX."""

from __future__ import annotations

import math
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], p: float) -> float:
    """Linear-interpolated percentile, p in [0, 100] (numpy's default)."""
    if not values:
        raise ValueError("percentile of nothing")
    v = sorted(values)
    k = (len(v) - 1) * p / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def tokens_in_window(
    events: Iterable[Tuple[float, int]], start: float, end: float
) -> int:
    """Output tokens whose chunk arrived in [start, end).  ``events`` are
    (arrival time, tokens in the chunk)."""
    return sum(n for t, n in events if start <= t < end)


def tpot_ms(token_times: Sequence[Tuple[float, int]]) -> Optional[float]:
    """(last token time - first token time) / (tokens - 1), in ms."""
    n = sum(k for _t, k in token_times)
    if n < 2 or not token_times:
        return None
    # the first chunk may carry several tokens: they all arrived at once
    first = token_times[0][0]
    last = token_times[-1][0]
    return (last - first) / (n - 1) * 1e3


def position_errors(
    got: Sequence[Sequence[float]], ref: Sequence[Sequence[float]]
) -> List[float]:
    """Per compared position, the RMS difference over its tokens'
    log-probabilities."""
    return [
        (sum((a - b) ** 2 for a, b in zip(g, r)) / len(g)) ** 0.5
        for g, r in zip(got, ref)
    ]


def compared_error(errs: Sequence[float], pct: float = 90.0) -> float:
    """The number ``correct`` holds against the tolerance: the ``pct``-th
    percentile of the position errors.  A percentile and not a mean or the
    largest, because a router's choice of experts is discontinuous: in any
    precision a rounding flips it at a few positions in a hundred, and
    those read tenths.  Up to a tenth of the positions do not move it."""
    return percentile(errs, pct)


def end_to_end(
    results: List[Dict[str, Any]], start: float, end: float, exit_time: float
) -> Dict[str, float]:
    """Every end-to-end metric the results support.  ``results`` hold, per
    request: ``due`` (when it should have been sent; the send time in a
    closed loop), ``sent``, ``chunks`` [(time, tokens)], ``finished`` (time
    or None), ``ok``."""
    out: Dict[str, float] = {}
    window = end - start
    events = [c for r in results for c in r["chunks"]]
    out["out_tok_s"] = tokens_in_window(events, start, end) / window
    tpots = []
    for r in results:
        if r["ok"] and r["finished"] is not None and start <= r["finished"] < end:
            t = tpot_ms(r["chunks"])
            if t is not None:
                tpots.append(t)
    if tpots:
        out["tpot_p90_ms"] = percentile(tpots, 90)
    ttfts = []
    for r in results:
        if not (start <= r["due"] < end):
            continue
        if r["ok"] is not False and r["chunks"]:
            ttfts.append((r["chunks"][0][0] - r["due"]) * 1e3)
        else:  # failed, or no token by the time the run ended
            ttfts.append(None)
    if ttfts:
        worst = max(
            [t for t in ttfts if t is not None] + [(exit_time - start) * 1e3]
        )
        out["ttft_p90_ms"] = percentile(
            [worst if t is None else t for t in ttfts], 90
        )
    return out


HALVES_SLACK = 1.25  # the second half may wait this many times as long as the first
HALVES_FLOOR_MS = 500.0  # or this much longer: under it the schedule, not a queue


def rate_held(line: Dict[str, Any]) -> bool:
    """Whether the system kept up with one rate of a sweep (one printed
    ``sweep`` line of ``run.py --sweep``): no request failed or was left
    without a first token, and the requests due in the second half of the
    window waited no longer at the median than those of the first (a queue
    that grows through the window is past capacity).  "No longer" has room
    for what the schedule puts into either half: ``HALVES_SLACK`` times the
    first half's median, or ``HALVES_FLOOR_MS`` more than it (lightly loaded,
    longdoc-open read 69 against 249 ms at 1.5 requests/s and 494 against
    107 at 2.0; a queue that outgrows capacity by 2% of the arrivals puts
    half a second between the halves of a 50-s window)."""
    first, second = line["ttft_p50_first_half_ms"], line["ttft_p50_second_half_ms"]
    return (not line["failed"] and not line["no_first_token"]
            and first is not None and second is not None
            and second <= max(HALVES_SLACK * first, first + HALVES_FLOOR_MS))


def knee(lines: Iterable[Dict[str, Any]]) -> Optional[float]:
    """The knee of a sweep, by the rule PRs 30 and 36 used: the highest rate
    swept at which ``out_tok_s`` still rises over the rate below and which
    held (``rate_held``), every lower rate having held too.  ``None`` where
    the lowest rate swept did not hold: sweep lower."""
    best = prev = None
    for line in sorted(lines, key=lambda l: l["rate_per_s"]):
        if not rate_held(line):
            break
        if prev is None or line["out_tok_s"] > prev:
            best = line["rate_per_s"]
        prev = line["out_tok_s"]
    return best


def pitch(knee_per_s: float, share: float = 0.75, step: float = 0.05) -> float:
    """The rate a cell runs at: ``share`` of its knee, rounded down to a
    multiple of ``step``."""
    return round(math.floor(knee_per_s * share / step + 1e-9) * step, 6)


_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str) -> Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float]:
    """{(name, sorted labels): value} of a Prometheus exposition."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if not m:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(2) or "")))
        try:
            out[(m.group(1), labels)] = float(m.group(3))
        except ValueError:
            continue
    return out


class Counters:
    """Deltas of the server's counters between two scrapes."""

    def __init__(self, before: str, after: str) -> None:
        self.before = parse_prometheus(before)
        self.after = parse_prometheus(after)

    def delta(self, name: str, **labels: str) -> float:
        """after - before, summed over the series that match ``labels``."""
        total = 0.0
        for (n, ls), v in self.after.items():
            if n != name:
                continue
            d = dict(ls)
            if any(d.get(k) != want for k, want in labels.items()):
                continue
            total += v - self.before.get((n, ls), 0.0)
        return total

    def has(self, name: str) -> bool:
        return any(n == name for n, _ in self.after)
