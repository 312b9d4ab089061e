"""From a ``jax.profiler`` trace (``.xplane.pb``) to device busy time and
the time of each device operation.

A device plane is a plane whose name starts with ``/device:TPU:``.  Its
``XLA Ops`` line holds one event per operation run on the chip, with a start
and a duration in nanoseconds.  Busy time is the union of those intervals;
operations are summed by name.  Control-flow operations (``while``,
``conditional``, ``call``) span the operations inside them, so they count
towards the union (which cannot count a nanosecond twice) but are left out
of the sums by name.  An event's name is the text of its HLO instruction.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
TEXT_LIMIT = 2000  # characters of an instruction kept: result, opcode, operands
_CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\d]*$")


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    total = 0.0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def op_name(text: str) -> str:
    """``%fusion.289`` of the instruction text a device event is named by:
    ``%fusion.289 = bf16[8,1024,14336]{2,1,0:T(8,128)(2,1)} fusion(...)``."""
    return text.split(" = ", 1)[0].strip()


def op_label(text: str) -> str:
    """An operation's name and result shape in the characters a name may
    have: ``_fusion.289___bf16_8_1024_14336_``.  The text is cut where the
    result's layout starts."""
    head = text.split("{", 1)[0].strip()
    if " = " not in head:  # no shape before a layout: keep the name alone
        head = op_name(text)
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", head)[:120]


def reduce_planes(planes) -> Dict[str, Any]:
    """``planes``: objects with ``name`` and ``lines``; lines with ``name``
    and ``events``; events with ``name``, ``start_ns``, ``duration_ns`` and
    ``stats`` (pairs).  Returns busy seconds averaged over the device planes,
    the traced span, and by operation label its seconds, its events and the
    text of its instruction (result, opcode and operands)."""
    busy: List[float] = []
    span_lo: Optional[float] = None
    span_hi: Optional[float] = None
    ops: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    text: Dict[str, str] = {}
    for plane in planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            intervals = []
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                intervals.append((s, e))
                span_lo = s if span_lo is None else min(span_lo, s)
                span_hi = e if span_hi is None else max(span_hi, e)
                if _CONTAINERS.match(op_name(ev.name)):
                    continue
                label = op_label(ev.name)
                ops[label] = ops.get(label, 0.0) + ev.duration_ns * 1e-9
                counts[label] = counts.get(label, 0) + 1
                text.setdefault(label, ev.name[:TEXT_LIMIT])
            busy.append(union_seconds(intervals))
    n = len(busy)
    return {
        "device_planes": n,
        "busy_s": sum(busy) / n if n else 0.0,
        "span_s": (span_hi - span_lo) if n and span_lo is not None else 0.0,
        "ops": ops,
        "op_counts": counts,
        "op_text": text,
    }


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    return files[-1] if files else None


def reduce_file(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)


def describe(path: str, limit: int = 40) -> str:
    """What a trace holds, for a reader who has not seen one: planes,
    lines, and the first events of each line with their statistics."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:limit]:
                out.append(
                    f"    {ev.name!r} start={ev.start_ns} dur={ev.duration_ns}"
                    f" stats={dict(ev.stats)!r}"[:600]
                )
    return "\n".join(out)


def reduce_dir(trace_dir: str, dump: Optional[str] = None) -> Dict[str, Any]:
    path = find_xplane(trace_dir)
    if path is None:
        return {"device_planes": 0, "busy_s": 0.0, "span_s": 0.0, "ops": {},
                "op_counts": {}, "op_text": {}, "error": f"no .xplane.pb under {trace_dir}"}
    out = reduce_file(path)
    if dump:
        os.makedirs(os.path.dirname(dump), exist_ok=True)
        with open(dump, "w") as f:
            f.write(describe(path))
            f.write("\n\nthe 40 operations that took longest, with their instructions\n")
            for label, sec in sorted(out["ops"].items(), key=lambda kv: -kv[1])[:40]:
                f.write(f"{sec:.6f} s x{out['op_counts'][label]} {out['op_text'][label]}\n")
    out["xplane_bytes"] = os.path.getsize(path)
    return out
