"""Tick-phase profiler + flight recorder: the host-side performance plane.

BENCH_r05 showed the *host* tick loop -- not the device -- bounding serving
speed (5.7 dispatches/s against 183 decode_steps/s), and nothing measured
how a tick's wall time splits across scheduling, batch assembly, dispatch
enqueue, device wait, commit, detokenization, and stream fanout.  This
module is that measurement:

* :class:`TickProfiler` -- per-tick phase accounting on
  ``time.perf_counter_ns`` marks.  The engine's tick loop opens a
  :class:`TickRecord` per iteration and attributes elapsed time to named
  phases (``plan``, ``assemble``, ``dispatch``, ``device_wait``,
  ``commit``, ``fanout``, ``onboard``; off-loop contributors like the
  Backend's ``detok`` feed the same histogram via :meth:`observe_phase`).
  Completed records land in a bounded ring and feed
  ``dynamo_tick_phase_seconds{phase}`` histograms, a
  ``dynamo_tick_host_occupancy`` gauge (host time / tick wall), and
  ``dynamo_tick_dispatch_gap_seconds`` -- the host-observed time from the
  previous dispatch's results landing to the next dispatch being
  enqueued, recorded as zero whenever another dispatch is already queued
  on the device.  It is NOT a bound on device idle: the device can idle
  while the host believes it is waiting for it (read the idle time by
  host phase from a device trace instead, below).

* Tick phases on the device trace's clock: while the profiler is enabled,
  every phase interval a mark closes is also a
  ``jax.profiler.TraceAnnotation`` named ``dyn.tick`` carrying
  ``phase=<name>`` (a ``dispatch`` interval of a packed dispatch also
  carries the lanes' fresh query tokens ``q``, context lengths ``ctx``,
  the fused step count ``k``, the packed rows ``np``, the class of the
  step ``step`` and the dispatch's serial ``d``; the ``device_wait``
  interval that fetched it carries the serials ``d`` and the device's
  service time ``svc_us`` as the commit read it), and the
  interval the loop is parked on its wake event is ``dyn.parked``.  A
  ``jax.profiler`` trace taken meanwhile (``POST /profile/device``) holds
  them beside the device's operations on one timeline, so each idle gap
  of the device can be put down to what the host was doing.  Inside a
  trace an annotation costs under a microsecond; outside one, a static
  check (~80 ns a mark); with the profiler disabled, nothing.

* :class:`FlightRecorder` -- on-demand snapshots of the last-N tick
  records, recent SLO violations, and registered component state (engine
  queue/KV occupancy), taken at failure edges (deadline expiry, worker
  loss, breaker open) so chaos postmortems read one JSON blob instead of
  log archaeology.  Served at ``GET /debug/flightrec``.

Overhead discipline (the ``FaultInjector`` pattern): disabled profiling is
one attribute check per site --

    tick = profiler.begin_tick() if profiler.enabled else None
    ...
    if tick is not None:
        tick.mark("plan")

Enable with ``DYN_TICK_PROFILE=1`` (or ``profiler.enable()``, or
``POST /profile/ticks {"enabled": true}`` on a live frontend).  Ring
capacity: ``DYN_TICK_RING`` (default 1024 ticks).

Export: tick records convert to the same span-dict shape
``runtime/tracing.py`` speaks, so :func:`chrome_trace` merges phase
lanes with the PR-3 request span tree into one Chrome-trace/Perfetto
timeline (``GET /profile/ticks``, ``python -m dynamo_tpu profile``).
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from . import tracing

logger = logging.getLogger("dynamo.profiling")

# Phase-duration buckets: a tick phase spans ~10us (a no-op plan pass) to
# ~100ms+ (a huge prefill's device wait).
PHASE_BUCKETS = (
    1e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
)

# The tick phases the engine marks, in canonical display order.  "other"
# absorbs unattributed slivers so a record's phases always sum to its wall.
PHASES = (
    "onboard",     # deliveries / swap-ins / prefetch + offload driving
    "plan",        # scheduler plan, admission, capacity, lane revival
    "assemble",    # host-side batch assembly (packed ragged layout, arrays)
    "dispatch",    # device enqueue (jitted call issue) + dispatch bookkeeping
    "device_wait", # blocked on device results (the one designed sync point)
    "commit",      # host commit walk (token unpack, stop rules, events)
    "fanout",      # stream fanout: per-request queue puts
    "detok",       # incremental detokenization (off-loop: Backend operator)
    "other",       # unattributed tick remainder
)


@dataclass
class TickRecord:
    """One completed tick of an engine loop."""

    idx: int
    start_s: float  # time.monotonic()
    wall_s: float = 0.0
    phases: Dict[str, float] = field(default_factory=dict)
    dispatches: Dict[str, int] = field(default_factory=dict)
    # host-observed dispatch gap(s) closed this tick: seconds between the
    # previous dispatch's results materializing on host and the next
    # dispatch being enqueued (zero when another was already queued)
    gap_s: float = 0.0
    n_gaps: int = 0
    # the dispatches this tick committed, one dict each: class of step,
    # packed rows, fused steps, real rows, and the device's service time
    # as the commit read it (``JaxEngine._commit_all``)
    dispatch_records: List[Dict[str, Any]] = field(default_factory=list)

    @property
    def host_s(self) -> float:
        return max(self.wall_s - self.phases.get("device_wait", 0.0), 0.0)

    @property
    def host_occupancy(self) -> float:
        return min(self.host_s / self.wall_s, 1.0) if self.wall_s > 0 else 0.0

    def to_dict(self) -> Dict[str, Any]:
        return {
            "idx": self.idx,
            "start_s": round(self.start_s + tracing._MONO_TO_WALL, 6),
            "wall_ms": round(self.wall_s * 1e3, 4),
            "host_occupancy": round(self.host_occupancy, 4),
            "phases_ms": {
                k: round(v * 1e3, 4) for k, v in self.phases.items()
            },
            "dispatches": dict(self.dispatches),
            "gap_ms": round(self.gap_s * 1e3, 4),
            "n_gaps": self.n_gaps,
            "dispatch_records": [dict(d) for d in self.dispatch_records],
        }

    def to_span_dicts(self) -> List[Dict[str, Any]]:
        """Span-dict form (``tracing.Span.to_dict`` shape) so tick phases
        merge with the request span tree in one Chrome-trace export: the
        tick itself is a parent span, each phase a sequential child laid
        out in canonical phase order."""
        base = self.start_s + tracing._MONO_TO_WALL
        tid = f"tick-{self.idx}"
        out: List[Dict[str, Any]] = [
            {
                "name": "tick",
                "request_id": tid,
                "start_s": round(base, 6),
                "duration_ms": round(self.wall_s * 1e3, 4),
                "component": "engine.tick",
                "attrs": {
                    "dispatches": dict(self.dispatches),
                    "host_occupancy": round(self.host_occupancy, 4),
                },
            }
        ]
        off = 0.0
        for name in PHASES:
            dur = self.phases.get(name, 0.0)
            if dur <= 0.0:
                continue
            out.append(
                {
                    "name": name,
                    "request_id": tid,
                    "start_s": round(base + off, 6),
                    "duration_ms": round(dur * 1e3, 4),
                    "component": "engine.tick",
                }
            )
            off += dur
        return out


TICK_ANNOTATION = "dyn.tick"
PARKED_ANNOTATION = "dyn.parked"
_trace_annotation: Any = None  # jax.profiler.TraceAnnotation, or False


def annotate(name: str) -> Any:
    """An OPEN ``jax.profiler.TraceAnnotation`` (close it with
    ``__exit__``), or ``None`` while no ``jax.profiler`` trace is being
    taken (one static check: an annotation opened outside a trace would
    not be recorded anyway) and where JAX is absent.  Imported on first
    use and only by a caller that profiles: a process that never enables
    the profiler never imports JAX through here, and the import starts no
    backend."""
    global _trace_annotation
    if _trace_annotation is None:
        try:
            from jax.profiler import TraceAnnotation

            _trace_annotation = TraceAnnotation
        except Exception as e:
            logger.debug("no jax.profiler.TraceAnnotation here: %s", e)
            _trace_annotation = False
    if not _trace_annotation or not _trace_annotation.is_enabled():
        return None
    ann = _trace_annotation(name)
    ann.__enter__()
    return ann


class _Tick:
    """One in-progress tick: phase marks accumulate elapsed time since the
    previous mark.  Produced by :meth:`TickProfiler.begin_tick`; closed by
    :meth:`TickProfiler.finish_tick` (or dropped via ``discard``)."""

    __slots__ = (
        "profiler", "record", "_last_ns", "_start_ns", "discarded", "_ann",
    )

    def __init__(self, profiler: "TickProfiler", idx: int) -> None:
        self.profiler = profiler
        self.record = TickRecord(idx=idx, start_s=time.monotonic())
        self._start_ns = time.perf_counter_ns()
        self._last_ns = self._start_ns
        self.discarded = False
        # the open interval, as an annotation in the jax.profiler trace
        self._ann = annotate(TICK_ANNOTATION)

    @property
    def annotating(self) -> bool:
        """Whether the open interval is being written into a
        ``jax.profiler`` trace: only then is an annotation's metadata
        worth building."""
        return self._ann is not None

    def mark(self, phase: str, **meta: Any) -> None:
        """Attribute time since the previous mark (or tick start) to
        ``phase``.  Phases may repeat; durations accumulate.  The same
        interval closes in the device trace as a ``dyn.tick`` annotation
        with ``phase`` and ``meta`` (a mark names the phase that just
        ended, so the name is given as the interval closes)."""
        self._close(phase, **meta)
        self._ann = annotate(TICK_ANNOTATION)

    def _close(self, phase: str, **meta: Any) -> None:
        """End the open interval under ``phase``; none opens after it."""
        now = time.perf_counter_ns()
        phases = self.record.phases
        phases[phase] = phases.get(phase, 0.0) + (now - self._last_ns) * 1e-9
        self._last_ns = now
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.set_metadata(phase=phase, **meta)
            ann.__exit__(None, None, None)

    def note_dispatch(self, kind: str) -> None:
        """A device dispatch was just enqueued: count it and close the
        dispatch gap against the most recent results-ready stamp."""
        d = self.record.dispatches
        d[kind] = d.get(kind, 0) + 1
        prof = self.profiler
        ready = prof._last_ready
        if ready is not None:
            prof._last_ready = None
            gap = max(time.monotonic() - ready, 0.0)
            self.record.gap_s += gap
            self.record.n_gaps += 1
            prof._observe_gap(gap)

    def note_zero_gap(self) -> None:
        """Results landed while ANOTHER dispatch was already queued on
        device (the async pipeline's steady state): the device-idle gap
        this sample represents is zero by construction, so record it as
        such -- the gap_p50 series stays honest instead of timing a
        ready->enqueue interval the device never idled through."""
        self.record.n_gaps += 1
        prof = self.profiler
        prof._last_ready = None
        prof._observe_gap(0.0)

    def discard(self) -> None:
        self.discarded = True
        self._close("other")


class TickProfiler:
    """Process-wide tick-phase profiler (module instance: :data:`profiler`).

    Thread model: one tick is driven by one engine loop at a time (the
    loop awaits every executor hop before the next mark), so ``_Tick`` is
    lock-free; the completed-record ring takes a lock (HTTP readers)."""

    def __init__(self, capacity: Optional[int] = None) -> None:
        if capacity is None:
            try:
                capacity = int(os.environ.get("DYN_TICK_RING", "1024"))
            except ValueError:
                capacity = 1024
        self.capacity = max(capacity, 8)
        self.enabled = os.environ.get("DYN_TICK_PROFILE", "") not in (
            "", "0", "false",
        )
        self._ring: "collections.deque[TickRecord]" = collections.deque(
            maxlen=self.capacity
        )
        self._idx = 0
        self._lock = threading.Lock()
        # monotonic stamp of the most recent "previous dispatch's results
        # are on host" event; consumed by the next dispatch enqueue
        self._last_ready: Optional[float] = None
        # per-entry XLA compile events (fed by runtime.compile_sentry);
        # cleared with the ring so bench legs read per-leg counts
        self._compiles: Dict[str, int] = {}

    # -- lifecycle ---------------------------------------------------------

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._compiles.clear()
        self._last_ready = None

    # -- tick accounting ---------------------------------------------------

    def begin_tick(self) -> _Tick:
        self._idx += 1
        return _Tick(self, self._idx)

    def finish_tick(self, tick: _Tick) -> None:
        """Close a tick: trailing time becomes ``other``; empty ticks
        (no dispatch, no device wait) are dropped so stall-poll loops do
        not flood the ring with no-op records."""
        if tick.discarded:
            return
        tick._close("other")
        rec = tick.record
        rec.wall_s = (time.perf_counter_ns() - tick._start_ns) * 1e-9
        if not rec.dispatches and "device_wait" not in rec.phases:
            return
        with self._lock:
            self._ring.append(rec)
        self._observe_record(rec)

    def note_compile_event(self, entry: str) -> None:
        """One XLA compilation attributed to ``entry`` (compile_sentry
        calls this on every event so tick summaries price recompiles next
        to the phases they stall)."""
        if not self.enabled:
            return
        with self._lock:
            self._compiles[entry] = self._compiles.get(entry, 0) + 1

    def note_results_ready(self) -> None:
        """The pending dispatch's outputs just materialized on host: from
        here until the next enqueue, the device has nothing new from us."""
        self._last_ready = time.monotonic()

    def observe_phase(self, phase: str, seconds: float) -> None:
        """Off-tick contribution (e.g. the Backend's detok loop runs on
        frontend tasks, not the engine loop): feeds the phase histogram
        only, never a tick record."""
        self._phase_hist().labels(phase).observe(max(seconds, 0.0))

    # -- metrics (lazy: respects metrics.set_default in tests) -------------

    def _phase_hist(self):
        from . import metrics as rtm

        return rtm.default_registry().histogram(
            "dynamo_tick_phase_seconds",
            "Host tick-loop time per phase",
            ["phase"],
            buckets=PHASE_BUCKETS,
        )

    def _observe_gap(self, gap_s: float) -> None:
        from . import metrics as rtm

        rtm.default_registry().histogram(
            "dynamo_tick_dispatch_gap_seconds",
            "Host-observed time from a dispatch's results landing to the "
            "next dispatch being enqueued; zero whenever a dispatch is "
            "already queued",
            buckets=PHASE_BUCKETS,
        ).observe(max(gap_s, 0.0))

    def _observe_record(self, rec: TickRecord) -> None:
        from . import metrics as rtm

        reg = rtm.default_registry()
        hist = self._phase_hist()
        for name, dur in rec.phases.items():
            hist.labels(name).observe(max(dur, 0.0))
        reg.histogram(
            "dynamo_tick_wall_seconds",
            "Engine tick wall time",
            buckets=PHASE_BUCKETS,
        ).observe(max(rec.wall_s, 0.0))
        reg.gauge(
            "dynamo_tick_host_occupancy",
            "Fraction of the last tick's wall spent on host work "
            "(1 - device_wait/wall); ~1.0 means the host bounds serving",
        ).set(rec.host_occupancy)
        reg.counter(
            "dynamo_ticks_total", "Engine ticks profiled"
        ).inc()

    # -- read side ---------------------------------------------------------

    def records(self, last: Optional[int] = None) -> List[TickRecord]:
        with self._lock:
            recs = list(self._ring)
        return recs[-last:] if last else recs

    def summary(self) -> Dict[str, Any]:
        """Aggregate over the ring: per-phase totals + fractions of host
        time, mean host occupancy, dispatch-gap percentiles, tick count.
        The bench's serving line prints the top-3 phases from here."""
        recs = self.records()
        with self._lock:
            compiles = dict(self._compiles)
        totals: Dict[str, float] = {}
        gaps: List[float] = []
        wall = host = 0.0
        disp = 0
        for r in recs:
            for k, v in r.phases.items():
                totals[k] = totals.get(k, 0.0) + v
            if r.n_gaps:
                gaps.append(r.gap_s / r.n_gaps)
            wall += r.wall_s
            host += r.host_s
            disp += sum(r.dispatches.values())
        host_phases = sorted(
            (
                (k, v) for k, v in totals.items()
                if k not in ("device_wait", "other")
            ),
            key=lambda kv: kv[1],
            reverse=True,
        )
        gaps.sort()

        def pct(p: float) -> Optional[float]:
            if not gaps:
                return None
            i = min(int(p * len(gaps)), len(gaps) - 1)
            return round(gaps[i] * 1e3, 3)

        return {
            "ticks": len(recs),
            "dispatches": disp,
            "wall_s": round(wall, 6),
            "host_s": round(host, 6),
            "host_occupancy": round(host / wall, 4) if wall else None,
            "phase_totals_s": {
                k: round(v, 6) for k, v in sorted(totals.items())
            },
            "top_phases": [
                [k, round(v, 6)] for k, v in host_phases
            ],
            "gap_p50_ms": pct(0.50),
            "gap_p95_ms": pct(0.95),
            "compile_events": dict(sorted(compiles.items())),
        }

    def chrome_trace(
        self, span_dicts: Optional[List[Dict[str, Any]]] = None
    ) -> Dict[str, Any]:
        """Chrome-trace JSON of the tick ring, merged with request spans
        when given (``tracing.collector.dump()``): phases land on an
        ``engine.tick`` process row next to the span tree's components."""
        dicts: List[Dict[str, Any]] = list(span_dicts or [])
        for rec in self.records():
            dicts.extend(rec.to_span_dicts())
        return tracing.chrome_trace(dicts)


profiler = TickProfiler()


async def capture_device_trace(
    duration_s: float, log_dir: Optional[str] = None
) -> Dict[str, Any]:
    """Bounded-duration ``jax.profiler`` device trace (``POST
    /profile/device``) with the tick profiler on meanwhile: device
    operations and ``dyn.tick`` phases on one timeline (TensorBoard,
    Perfetto, ``benchmark/trace_host.py``).  Degrades gracefully: on
    CPU-only stacks (or with
    jax absent / a capture already running) it returns ``ok=False`` with
    the reason instead of raising -- profiling must never take a serving
    process down."""
    import asyncio

    duration_s = min(max(float(duration_s), 0.05), 30.0)
    if log_dir is None:
        log_dir = os.path.join(
            os.environ.get("TMPDIR", "/tmp"),
            f"dynamo-device-trace-{int(time.time())}",
        )
    try:
        import jax

        # device operations and level-1 host annotations, no Python
        # tracer: tracing Python slows the host the engine shares
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    except Exception as e:
        return {"ok": False, "error": f"device trace unavailable: {e}"}
    # the tick profiler is held on for the capture, so the trace holds the
    # dyn.tick phases and dyn.parked beside the device's operations
    was_enabled = profiler.enabled
    profiler.enable()
    try:
        await asyncio.sleep(duration_s)
    finally:
        profiler.enabled = was_enabled
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            return {"ok": False, "error": f"stop_trace failed: {e}"}
    return {"ok": True, "log_dir": log_dir, "duration_s": duration_s}


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Bounded snapshots of "what was the system doing" at failure edges.

    Components register state providers (``add_provider``); a trigger site
    calls :meth:`snapshot` with a reason and gets back a snapshot id it can
    attach to the error frame / span / 504 body.  Snapshots keep the last
    ``tick_window`` tick records and the SLO plane's recent violations, so
    a chaos postmortem starts from one ``GET /debug/flightrec/{id}``.

    Per-reason throttling (``min_interval_s``) bounds snapshot work under
    mass failure (a deadline storm must not turn the recorder into the
    next bottleneck): a throttled trigger reuses the previous snapshot id.
    """

    def __init__(
        self,
        capacity: int = 16,
        tick_window: int = 64,
        min_interval_s: float = 0.25,
    ) -> None:
        self.capacity = capacity
        self.tick_window = tick_window
        self.min_interval_s = min_interval_s
        self._snaps: "collections.OrderedDict[str, Dict[str, Any]]" = (
            collections.OrderedDict()
        )
        self._providers: Dict[str, Callable[[], Any]] = {}
        self._last_by_reason: Dict[str, tuple] = {}  # reason -> (t, id)
        self._lock = threading.Lock()
        self._seq = 0

    def add_provider(self, name: str, fn: Callable[[], Any]) -> str:
        """Register a state provider; returns the key it landed under.
        A taken name gets a ``#N`` suffix instead of clobbering -- two
        colocated engines (disagg prefill+decode in one process) must
        both appear in snapshots."""
        with self._lock:
            key = name
            n = 1
            while key in self._providers and self._providers[key] != fn:
                n += 1
                key = f"{name}#{n}"
            self._providers[key] = fn
            return key

    def remove_provider(self, name: str, fn: Optional[Callable] = None) -> None:
        with self._lock:
            # equality, not identity: each bound-method access mints a new
            # object, and a second engine's provider must not be evicted
            # by the first engine's stop()
            if fn is None or self._providers.get(name) == fn:
                self._providers.pop(name, None)

    def snapshot(self, reason: str, **extra: Any) -> str:
        """Take (or, throttled, reuse) a snapshot; returns its id."""
        now = time.monotonic()
        with self._lock:
            last = self._last_by_reason.get(reason)
            if last is not None and now - last[0] < self.min_interval_s:
                return last[1]
            self._seq += 1
            snap_id = f"fr-{self._seq:04d}"
            providers = dict(self._providers)
            self._last_by_reason[reason] = (now, snap_id)
        from . import slo

        state: Dict[str, Any] = {}
        for name, fn in providers.items():
            try:
                state[name] = fn()
            except Exception as e:  # a dying component must not block the dump
                state[name] = {"error": repr(e)}
        snap = {
            "id": snap_id,
            "reason": reason,
            "ts": time.time(),
            # promoted so /flightrec rows link straight to /trace/{id}
            # (call sites pass request_id=...; trace_id aliases it)
            "trace_id": extra.get("trace_id") or extra.get("request_id"),
            "extra": extra,
            "ticks": [
                r.to_dict() for r in profiler.records(self.tick_window)
            ],
            "slo_violations": slo.tracker.recent_violations(),
            "state": state,
        }
        with self._lock:
            self._snaps[snap_id] = snap
            while len(self._snaps) > self.capacity:
                self._snaps.popitem(last=False)
        return snap_id

    def get(self, snap_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._snaps.get(snap_id)

    def list(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [
                {
                    "id": s["id"],
                    "reason": s["reason"],
                    "ts": s["ts"],
                    "trace_id": s.get("trace_id"),
                    "extra": s["extra"],
                }
                for s in self._snaps.values()
            ]

    def clear(self) -> None:
        with self._lock:
            self._snaps.clear()
            self._last_by_reason.clear()


flight_recorder = FlightRecorder()
