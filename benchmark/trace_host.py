"""Where the device's idle time goes, by what the host was doing.

The program writes its tick loop into the ``jax.profiler`` trace while its
tick profiler is on: one ``dyn.tick`` event per phase interval, with the
phase as the event's ``phase`` stat, and ``dyn.parked`` while the loop
waits for work.  They are in the same ``.xplane.pb`` as the device's
operations and on the same clock.  This module takes the device's idle gaps
(the complement of the union ``trace_reduce`` takes of the ``XLA Ops``
events) and intersects them with those intervals: idle seconds under each
phase, under ``dyn.parked``, and under nothing.

The ``dispatch`` interval of a packed dispatch also carries what the
attention kernels were asked to do (per lane the fresh query rows ``q`` and
the context ``ctx`` its last row reads, ``|``-joined; the fused steps ``k``;
the packed rows ``np``; the class ``step``, ``chunk`` with prefill rows and
``decode`` without): ``dispatches`` below, for the roofline readers.

A trace from a program without the annotations gives ``None``: the readers
then report nothing.  JAX is imported inside ``load`` only, with
``JAX_PLATFORMS=cpu`` set first: the caller is the benchmark's parent, and
no backend may start beside the child that holds the chip.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

from benchmark import trace_reduce

TICK = "dyn.tick"
PARKED = "dyn.parked"
WAIT = "device_wait"
TRACE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".bench_cache", "trace")
Interval = Tuple[float, float]
_cache: Dict[Any, Optional[Dict[str, Any]]] = {}


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        elif e > s:
            out.append((s, e))
    return out


def complement(merged: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi) that no interval of ``merged`` covers."""
    out: List[Interval] = []
    at = lo
    for s, e in merged:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Seconds covered by both of two sorted, disjoint lists."""
    total = 0.0
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _seconds(ev) -> Interval:
    s = ev.start_ns * 1e-9
    return s, s + ev.duration_ns * 1e-9


def idle_by_host(planes) -> Optional[Dict[str, Any]]:
    """The table.  ``planes`` as in ``trace_reduce.reduce_planes``.  Seconds
    are averaged over the device planes; the window runs from the first to
    the last event of either kind.  ``None`` without a device plane or
    without a single annotation."""
    phases: Dict[str, List[Interval]] = {}
    parked: List[Interval] = []
    dispatches: List[Dict[str, Any]] = []
    devices: List[List[Interval]] = []
    for plane in planes:
        device = plane.name.startswith(trace_reduce.DEVICE_PLANE)
        for line in plane.lines:
            if device:
                if line.name == trace_reduce.OPS_LINE:
                    devices.append(merge(_seconds(ev) for ev in line.events))
                continue
            for ev in line.events:
                if ev.name == PARKED:
                    parked.append(_seconds(ev))
                elif ev.name == TICK:
                    stats = dict(ev.stats)
                    phases.setdefault(str(stats.get("phase")), []).append(_seconds(ev))
                    if "q" in stats:
                        q = [int(v) for v in str(stats["q"]).split("|")]
                        dispatches.append({
                            "q": q,
                            "ctx": [int(v) for v in str(stats["ctx"]).split("|")],
                            "k": int(stats.get("k", 1)), "np": int(stats.get("np", 0)),
                            # the class the program states; a trace from
                            # before PR 41 has none: no lane with two rows
                            "step": str(stats.get("step") or (
                                "chunk" if max(q) > 1 else "decode")),
                            "start_s": _seconds(ev)[0]})
    devices = [d for d in devices if d]
    if not devices or not (phases or parked):
        return None
    host = {name: merge(iv) for name, iv in phases.items()}
    parked = merge(parked)
    every = merge([iv for m in host.values() for iv in m] + parked)
    lo = min([d[0][0] for d in devices] + [every[0][0]])
    hi = max([d[-1][1] for d in devices] + [every[-1][1]])
    n = len(devices)
    out: Dict[str, Any] = {
        "device_planes": n, "window_s": hi - lo, "busy_s": 0.0, "idle_s": 0.0,
        "idle_by_phase_s": {name: 0.0 for name in sorted(host)},
        "idle_parked_s": 0.0, "idle_no_annotation_s": 0.0,
        # a device busy while the loop is parked has no dispatch in flight
        # to be busy with: near zero when the two clocks are one
        "busy_while_parked_s": 0.0,
        "annotated_s": {name: sum(e - s for s, e in m) for name, m in sorted(host.items())},
        "parked_s": sum(e - s for s, e in parked),
        "dispatches": dispatches,
    }
    for busy in devices:
        gaps = complement(busy, lo, hi)
        out["busy_s"] += sum(e - s for s, e in busy) / n
        out["idle_s"] += sum(e - s for s, e in gaps) / n
        for name, m in host.items():
            out["idle_by_phase_s"][name] += overlap(gaps, m) / n
        out["idle_parked_s"] += overlap(gaps, parked) / n
        out["idle_no_annotation_s"] += overlap(gaps, complement(every, lo, hi)) / n
        out["busy_while_parked_s"] += overlap(busy, parked) / n
    out["idle_in_wait_s"] = out["idle_by_phase_s"].get(WAIT, 0.0)
    out["idle_host_work_s"] = sum(
        v for name, v in out["idle_by_phase_s"].items() if name != WAIT)
    return out


def idle_gaps(t: Optional[Dict[str, Any]]) -> List[List[Any]]:
    """The table as the result line's ``breakdown.idle_gaps``: seconds of
    the traced slice in which the device ran nothing, by what the host was
    doing (each tick phase, ``dyn.parked``, and ``no_annotation`` for the
    rest), the ten longest first.  They add up to the slice's idle seconds."""
    if t is None:
        return []
    gaps = dict(t["idle_by_phase_s"])
    gaps[PARKED] = t["idle_parked_s"]
    gaps["no_annotation"] = t["idle_no_annotation_s"]
    return [[k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:10]]


def load(path: str) -> Optional[Dict[str, Any]]:
    os.environ["JAX_PLATFORMS"] = "cpu"  # before JAX is imported, see above
    from jax.profiler import ProfileData

    return idle_by_host(ProfileData.from_file(path).planes)


def table(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The run's table, parsed once and printed once (``info`` on stderr:
    a reader returns one number).  ``ctx["planes"]`` stands in for the
    file where a test hands planes in.  Shares are of the traced seconds
    ``device.idle_pct.*`` divides by, so that host work + in wait + parked
    + unattributed is that metric's value."""
    if "planes" in ctx:
        t = idle_by_host(ctx["planes"])
    else:
        path = ctx.get("xplane") or trace_reduce.find_xplane(TRACE_DIR)
        if path is None:
            return None
        key = (path, os.path.getmtime(path))
        if key not in _cache:
            _cache[key] = load(path)
        t = _cache[key]
    if t is None or "shares_pct" in t:
        return t
    window = ctx.get("trace_window_s") or t["window_s"]
    idle = window - ctx.get("trace", {}).get("busy_s", t["busy_s"])
    named = {"host_work": t["idle_host_work_s"], "in_wait": t["idle_in_wait_s"],
             "parked": t["idle_parked_s"]}
    named["unattributed"] = idle - sum(named.values())
    t["shares_pct"] = {k: 100.0 * v / window for k, v in named.items()}
    t["shares_pct"]["idle"] = 100.0 * idle / window
    print("info " + json.dumps({"idle_by_host": {
        k: v for k, v in t.items() if k != "dispatches"},
        "dispatches_annotated": len(t["dispatches"])}), file=sys.stderr, flush=True)
    return t
