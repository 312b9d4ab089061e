"""Process-wide engine/runtime metrics registry.

The HTTP layer already had Prometheus coverage (``http/metrics.py``); this
module extends it inward: the engine, scheduler, KV cache, disaggregated
transfer plane, and KV router all register their series through one
lightweight facade so (a) metric families are minted in exactly one place
-- dynalint DT007 rejects inline ``Counter(...)`` construction anywhere
else -- and (b) tests can run many engines per process against private
registries, the same pattern ``ServiceMetrics`` established.

Usage::

    from dynamo_tpu.runtime import metrics as rtm

    reg = rtm.default_registry()            # or MetricsRegistry() in tests
    hits = reg.counter("dynamo_engine_prefix_hit_tokens",
                       "Prompt tokens served from the prefix cache")
    hits.inc(128)

``counter``/``gauge``/``histogram`` are get-or-create: asking twice for
the same family name returns the same object, so several engines in one
process share series instead of tripping prometheus_client's duplicate
registration error.  The full metric-name catalog lives in README
"Observability".
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)
from prometheus_client.exposition import CONTENT_TYPE_LATEST

# Engine decode/prefill dispatch->commit latency: sub-ms on an idle CPU
# mocker up to seconds for a huge prefill that also compiles.
STEP_LATENCY_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0,
)
# a request stage spans ~100us (ingress of a tokenized prompt) to tens of
# seconds (queue wait under overload)
STAGE_BUCKETS = (
    1e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0,
)
# Disagg KV export/upload legs (multi-MB device->host->wire moves).
TRANSFER_LATENCY_BUCKETS = (
    0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)
# Unit-interval ratios (overlap ratio, utilization distributions).
RATIO_BUCKETS = (0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0)


class _LabeledView:
    """``generate_latest`` target that merges a registry's default labels
    into every rendered sample.

    Families are minted unlabeled (or with their own dynamic labels); the
    identity labels are a render-time concern, so ``sample()`` readers and
    label-less in-process consumers never see them.  Explicit per-sample
    labels win on collision.
    """

    def __init__(self, registry: CollectorRegistry, labels: Dict[str, str]):
        self._registry = registry
        self._labels = labels

    def collect(self):
        from prometheus_client.metrics_core import Metric

        for m in self._registry.collect():
            out = Metric(m.name, m.documentation, m.type, getattr(m, "unit", ""))
            for s in m.samples:
                merged = dict(self._labels)
                merged.update(s.labels)
                out.samples.append(s._replace(labels=merged))
            yield out


class MetricsRegistry:
    """Get-or-create facade over a private ``CollectorRegistry``."""

    def __init__(self) -> None:
        self.registry = CollectorRegistry()
        self._families: Dict[str, Any] = {}
        self._lock = threading.Lock()
        # identity labels stamped onto every rendered sample (worker_id,
        # role): multi-worker Prometheus scrapes and fleet-observatory
        # rollups stop colliding on identical series names.  Empty dict =
        # exact legacy exposition.
        self.default_labels: Dict[str, str] = {}
        # bound methods called before every exposition (weakly held: an
        # engine that is gone takes its hook with it)
        self._before_render: List["weakref.WeakMethod"] = []

    def before_render(self, method: Callable[[], None]) -> None:
        """Call ``method`` (a bound method) before every :meth:`render`:
        for a counter whose current interval is still open when a scrape
        comes (time spent waiting is counted when the wait ends; a scrape
        in the middle of one has to see the part that has passed)."""
        with self._lock:
            self._before_render.append(weakref.WeakMethod(method))

    def set_default_labels(self, **labels: Any) -> None:
        """Replace the render-time identity label set (None values drop
        the key)."""
        with self._lock:
            self.default_labels = {
                k: str(v) for k, v in labels.items() if v is not None
            }

    def _get_or_create(
        self,
        cls,
        name: str,
        documentation: str,
        labelnames: Sequence[str],
        **kwargs: Any,
    ):
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = cls(
                    name,
                    documentation,
                    tuple(labelnames),
                    registry=self.registry,
                    **kwargs,
                )
                self._families[name] = fam
            return fam

    def counter(
        self, name: str, documentation: str, labelnames: Sequence[str] = ()
    ) -> Counter:
        return self._get_or_create(Counter, name, documentation, labelnames)

    def gauge(
        self, name: str, documentation: str, labelnames: Sequence[str] = ()
    ) -> Gauge:
        return self._get_or_create(Gauge, name, documentation, labelnames)

    def histogram(
        self,
        name: str,
        documentation: str,
        labelnames: Sequence[str] = (),
        buckets: Optional[Sequence[float]] = None,
    ) -> Histogram:
        kwargs: Dict[str, Any] = {}
        if buckets is not None:
            kwargs["buckets"] = tuple(buckets)
        return self._get_or_create(
            Histogram, name, documentation, labelnames, **kwargs
        )

    def render(self) -> Tuple[bytes, str]:
        with self._lock:
            hooks = [ref() for ref in self._before_render]
            self._before_render = [
                ref for ref, fn in zip(self._before_render, hooks)
                if fn is not None
            ]
        for fn in hooks:
            if fn is not None:
                fn()
        if self.default_labels:
            view = _LabeledView(self.registry, dict(self.default_labels))
            return generate_latest(view), CONTENT_TYPE_LATEST
        return generate_latest(self.registry), CONTENT_TYPE_LATEST

    def sample(
        self, name: str, labels: Optional[Dict[str, str]] = None
    ) -> Optional[float]:
        """Current value of one series, or None if it does not exist yet.

        Counters resolve their ``_total`` sample, histograms their
        ``_sum``; gauges read directly.  This is the read path consumers
        like the planner use instead of ad-hoc plumbing -- it walks the
        exposition output, so it works for any family without touching
        prometheus_client internals."""
        want = dict(labels or {})
        candidates = (name, name + "_total", name + "_sum")
        for metric in self.registry.collect():
            if metric.name != name:
                continue
            for s in metric.samples:
                if s.name in candidates and dict(s.labels) == want:
                    return float(s.value)
        return None


class EngineMetrics:
    """Registry-backed engine/scheduler counters and gauges.

    Shared by the JAX engine and the mocker (which must stay JAX-free, so
    the class lives here rather than under ``engine/``): chip-free stacks
    expose the same series real serving does.  The engine updates it at its
    existing synchronization points -- the dispatch->commit cycle and the
    scheduler's admission pass -- so the hot loop pays a handful of gauge
    sets per *device block*, never per token.  Family catalog with labels:
    README "Observability".
    """

    def __init__(
        self,
        registry: Optional["MetricsRegistry"] = None,
        max_slots: int = 0,
    ) -> None:
        reg = registry or default_registry()
        self.registry = reg
        self.step_latency = reg.histogram(
            "dynamo_engine_step_latency_seconds",
            "Engine device-dispatch to host-commit latency",
            ["kind"],
            buckets=STEP_LATENCY_BUCKETS,
        )
        self.occupancy = reg.gauge(
            "dynamo_engine_batch_occupancy",
            "Decode lanes currently holding a slot",
        )
        self.slots = reg.gauge(
            "dynamo_engine_batch_slots",
            "Configured decode batch lanes (max_batch_size)",
        )
        self.queue_depth = reg.gauge(
            "dynamo_engine_prefill_queue_depth",
            "Requests waiting for admission into the decode batch",
        )
        self.kv_used = reg.gauge(
            "dynamo_engine_kv_pages_used", "KV cache pages in use"
        )
        self.kv_total = reg.gauge(
            "dynamo_engine_kv_pages_total", "KV cache pages available"
        )
        self.kv_util = reg.gauge(
            "dynamo_engine_kv_utilization",
            "KV cache page utilization (used/total, 0..1)",
        )
        self.kv_bytes_per_token = reg.gauge(
            "dynamo_engine_kv_bytes_per_token",
            "KV pool bytes over the tokens it holds, all layers",
        )
        self.prefix_hits = reg.counter(
            "dynamo_engine_prefix_hit_tokens",
            "Prompt tokens whose KV was reused from the prefix cache",
        )
        self.prefix_lookups = reg.counter(
            "dynamo_engine_prefix_lookup_tokens",
            "Prompt tokens checked against the prefix cache",
        )
        self.tokens = reg.counter(
            "dynamo_engine_tokens_generated",
            "Output tokens committed by the engine",
        )
        self.preemptions = reg.counter(
            "dynamo_engine_preemptions",
            "Sequences preempted for KV-page capacity",
        )
        # dispatch accounting: every device launch the tick loop pays, by
        # kind (prefill / decode_block / unified / verify / chunk /
        # prompt_score).
        # dispatches/s vs decode steps/s is the mixed-batching health ratio
        # the bench tracks every round (ROADMAP item 2).
        self.dispatches = reg.counter(
            "dynamo_engine_dispatches_total",
            "Device dispatches issued by the engine tick loop",
            ["kind"],
        )
        # mixed-batch occupancy: how full each unified ragged dispatch ran
        # (decode lanes riding alongside how many packed prefill tokens)
        self.mixed_decode_lanes = reg.histogram(
            "dynamo_engine_mixed_batch_decode_lanes",
            "Decode lanes per unified mixed-batch dispatch",
            buckets=(0, 1, 2, 4, 8, 16, 32, 64, 128),
        )
        self.mixed_prefill_tokens = reg.histogram(
            "dynamo_engine_mixed_batch_prefill_tokens",
            "Prefill tokens packed into a unified mixed-batch dispatch",
            buckets=(0, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096),
        )
        # fresh-token accounting per unified dispatch (ISSUE 10): `used`
        # counts real rows (decode lanes + packed prefill tokens),
        # `dispatched` the rows the executable actually ran -- the
        # padded-token fraction the long-context bench reports is
        # 1 - used/dispatched
        self.mixed_tokens = reg.counter(
            "dynamo_engine_mixed_tokens",
            "Fresh-token rows per unified mixed dispatch by accounting kind",
            ["kind"],  # used | dispatched
        )
        # what the expert MLPs of decode-only dispatches read, where a step
        # of few rows takes the grouped layout (model.moe_counts_reached):
        # experts with a row, whose matrices were read, and experts held,
        # both summed over layers and steps.  Their ratio is the share of the
        # experts' bytes a decode step streams; other steps count nothing
        self.moe_experts_reached = reg.counter(
            "dynamo_engine_moe_experts_reached",
            "Experts a decode step's rows reached (matrices read), summed "
            "over layers and steps that took the grouped layout",
        )
        self.moe_experts_held = reg.counter(
            "dynamo_engine_moe_experts_held",
            "Experts held (matrices the capacity buffers would read), "
            "summed over the same layers and steps",
        )
        # packed-shape budget (ISSUE 13 satellite): active (Np, s_max)
        # executable pairs the packed unified step may dispatch -- bounded
        # by engine/bucketing.PackedShapeBudget's LRU/merge pass
        self.executable_shapes = reg.gauge(
            "dynamo_engine_executable_shapes",
            "Active packed-dispatch (Np, s_max) executable shape pairs",
        )
        # multi-step decode (ISSUE 16): decode iterations fused into the
        # last packed dispatch -- 1 = single-step (pressure or disabled),
        # up to the controller's ceiling (below) when it opens up
        self.multistep_k = reg.gauge(
            "dynamo_engine_multistep_k",
            "Decode steps fused into the last packed unified dispatch",
        )
        # the ramp's ceiling (ISSUE 42) and the two running means it is
        # worked out from, as the controller last read them
        self.multistep_ceiling = reg.gauge(
            "dynamo_engine_multistep_ceiling",
            "Most decode steps a pressure-free tick may fuse right now",
        )
        self.multistep_reading = reg.gauge(
            "dynamo_engine_multistep_reading_seconds",
            "Running means the fused-step ceiling is worked out from",
            ["of"],
        )
        # request stages (ISSUE 26): a request's time to its first token,
        # split where each leg ends and observed once per request --
        # ingress (the process received it -> the engine's queue), queue
        # wait (-> first admission), first-token service (-> first token
        # committed).  Always on: three observes per request.
        self.ingress = reg.histogram(
            "dynamo_engine_ingress_seconds",
            "Process received the request to the engine's queue (parse, "
            "template, tokenize, backend, hop)",
            buckets=STAGE_BUCKETS,
        )
        self.queue_wait = reg.histogram(
            "dynamo_engine_queue_wait_seconds",
            "Engine queue arrival to first admission into the batch",
            buckets=STAGE_BUCKETS,
        )
        self.first_token_service = reg.histogram(
            "dynamo_engine_first_token_service_seconds",
            "First admission to first token committed (prefill chunks and "
            "the steps they rode in)",
            buckets=STAGE_BUCKETS,
        )
        # the dispatch record (ISSUE 41): what the tick loop's commit knows
        # of every dispatch -- what kind of step, at what packed width, how
        # long the device served it -- over the whole time the engine runs,
        # and per request what its first token waited behind.  Always on:
        # one observe and two adds a committed dispatch, four observes a
        # request.  The mocker mints the families and observes none.
        self.dispatch_service = reg.histogram(
            "dynamo_engine_dispatch_service_seconds",
            "Time the device spent on one committed dispatch as the host "
            "reads it (the commit's clock less the later of the dispatch's "
            "enqueue and the commit before): readings never overlap and "
            "their sum is exact, one reading is not a device-side duration. "
            "step: chunk | decode | prefill | decode_block | verify; np: "
            "the executable's packed rows, 0 where it has none",
            ["step", "np"],
            buckets=STEP_LATENCY_BUCKETS,
        )
        self.dispatch_steps = reg.counter(
            "dynamo_engine_dispatch_steps",
            "Forward passes the committed dispatches ran (a fused decode "
            "dispatch runs k): service seconds over it is one step's time",
            ["step", "np"],
        )
        self.decode_lane_steps = reg.counter(
            "dynamo_engine_decode_lane_steps",
            "Decode-lane token steps (decoding lanes x forward passes) by "
            "the class of the dispatch they rode in",
            ["step"],
        )
        self.parked_seconds = reg.counter(
            "dynamo_engine_parked_seconds",
            "Time the tick loop waited on its wake event with nothing "
            "runnable (JaxEngine._park), a wait still open at the scrape "
            "counted up to it",
        )
        self.first_token_wait = reg.histogram(
            "dynamo_engine_first_token_wait_seconds",
            "A request's first admission -> first token, split by what the "
            "device served meanwhile: chunk_steps (dispatches that carried "
            "prefill rows, its own or others'), decode_steps (dispatches "
            "that carried none), no_dispatch (the rest: no dispatch of "
            "this engine on the device, or the token's fanout).  The three "
            "tile dynamo_engine_first_token_service_seconds",
            ["behind"],
            buckets=STAGE_BUCKETS,
        )
        self.first_token_chunk_rows = reg.counter(
            "dynamo_engine_first_token_chunk_rows",
            "Prefill rows committed between a request's first admission "
            "and its first token: own (its own prompt rows computed) and "
            "all (every request's); own / all is the share of the chunk "
            "budget the request got while it waited",
            ["whose"],
        )
        # the record's clock, read when /metrics is rendered: its delta
        # between two scrapes is the time the counters above are deltas
        # over, which a client's own stopwatch is not (it stops before or
        # after the second scrape).  Costs nothing between scrapes.
        self.clock = reg.gauge(
            "dynamo_engine_clock_seconds",
            "time.perf_counter() of the engine's process at this scrape "
            "(the clock of dynamo_engine_dispatch_service_seconds)",
        )
        self.clock.set_function(time.perf_counter)
        # (step, np) -> its children of the three families above
        self._service_series: Dict[Tuple[str, int], Tuple[Any, ...]] = {}
        if max_slots:
            self.slots.set(max_slots)
        # a two-kind cache's families (observe_kv_kinds), minted at the
        # first observation, and the released pages already counted
        self._kv_kinds: Optional[Tuple[Gauge, Gauge, Counter]] = None
        # a trunk with convolution layers (mint_conv_state)
        self.state_restores: Optional[Counter] = None
        self.state_resets: Optional[Counter] = None
        self.state_walkbacks: Optional[Counter] = None
        # a trunk with gated delta-rule layers (mint_delta_state)
        self.state_snapshots: Optional[Counter] = None
        self.snapshot_recompute_tokens: Optional[Counter] = None
        self.gdn_chunks: Optional[Counter] = None
        self._snapshot_slots: Optional[Tuple[Counter, Gauge]] = None
        self._snapshot_evictions = 0
        self._window_released = 0

    # -- update points (cheap; called per tick / per commit, not per token)

    def observe_sched(self, waiting: int, active: int) -> None:
        self.queue_depth.set(waiting)
        self.occupancy.set(active)

    def observe_step(self, kind: str, seconds: float) -> None:
        self.step_latency.labels(kind).observe(max(seconds, 0.0))

    def observe_dispatch(self, kind: str) -> None:
        self.dispatches.labels(kind).inc()

    def observe_service(
        self, step: str, np_rows: int, seconds: float, steps: int,
        lane_steps: int,
    ) -> None:
        """One committed dispatch of class ``step`` at ``np_rows`` packed
        rows: the device's ``seconds`` on it, the ``steps`` forward passes
        it ran, and the decode-lane token steps that rode in it."""
        series = self._service_series.get((step, np_rows))
        if series is None:
            series = self._service_series[(step, np_rows)] = (
                self.dispatch_service.labels(step, str(np_rows)),
                self.dispatch_steps.labels(step, str(np_rows)),
                self.decode_lane_steps.labels(step),
            )
        series[0].observe(max(seconds, 0.0))
        series[1].inc(steps)
        if lane_steps:
            series[2].inc(lane_steps)

    def observe_first_token_wait(
        self, chunk_s: float, decode_s: float, idle_s: float,
        own_rows: int, all_rows: int,
    ) -> None:
        """Once a request, at its first token: what it waited behind."""
        wait = self.first_token_wait
        wait.labels("chunk_steps").observe(chunk_s)
        wait.labels("decode_steps").observe(decode_s)
        wait.labels("no_dispatch").observe(idle_s)
        rows = self.first_token_chunk_rows
        rows.labels("own").inc(own_rows)
        rows.labels("all").inc(all_rows)

    def observe_mixed(self, decode_lanes: int, prefill_tokens: int) -> None:
        self.mixed_decode_lanes.observe(decode_lanes)
        self.mixed_prefill_tokens.observe(prefill_tokens)

    def observe_mixed_tokens(self, used: int, dispatched: int) -> None:
        self.mixed_tokens.labels("used").inc(used)
        self.mixed_tokens.labels("dispatched").inc(dispatched)

    def observe_moe_reach(self, reached: int, held: int) -> None:
        self.moe_experts_reached.inc(reached)
        self.moe_experts_held.inc(held)

    def observe_kv(
        self, used: int, total: int, bytes_per_token: Optional[float] = None
    ) -> None:
        self.kv_used.set(used)
        self.kv_total.set(total)
        self.kv_util.set(used / total if total else 0.0)
        if bytes_per_token is not None:
            self.kv_bytes_per_token.set(bytes_per_token)

    def observe_kv_kinds(
        self, pools: Dict[str, Any], resident_tokens: int, released: int
    ) -> None:
        """A two-kind cache's pools (``{"full": ..., "window": ...}``
        allocators), the tokens of context it keeps, and the window pages
        it has let go behind the window.  The families exist only where an
        engine serves such a cache (minted at the first observation).
        ``dynamo_engine_kv_pages_used`` itself stays the unlabelled family
        it was (the pool a prefix match walks: the full pool), since one
        family cannot carry samples with and without a label."""
        if self._kv_kinds is None:
            reg = self.registry
            self._kv_kinds = (
                reg.gauge(
                    "dynamo_engine_kv_kind_pages",
                    "KV pages of a two-kind cache by pool (kind: full | "
                    "window) and state (used: referenced by a running "
                    "sequence; resident: used or reusable)",
                    ["kind", "state"],
                ),
                reg.gauge(
                    "dynamo_engine_kv_resident_context_tokens",
                    "Tokens of context the cache keeps: running sequences' "
                    "lengths plus the reusable blocks of the pool a prefix "
                    "match walks",
                ),
                reg.counter(
                    "dynamo_engine_kv_window_pages_released",
                    "Window-pool pages let go because they fell behind the "
                    "window",
                ),
            )
        pages, tokens, released_total = self._kv_kinds
        for kind, alloc in pools.items():
            pages.labels(kind, "used").set(alloc.used_pages)
            pages.labels(kind, "resident").set(
                getattr(alloc, "resident_pages", alloc.used_pages)
            )
        tokens.set(resident_tokens)
        if released > self._window_released:
            released_total.inc(released - self._window_released)
            self._window_released = released

    def _mint_state_admissions(self, layers: str, restores: str, walkbacks: str):
        """How each admission found the state of its ``layers``: the three
        counters both kinds of trunk with state beside pages have."""
        reg = self.registry
        self.state_restores = reg.counter("dynamo_engine_state_restores", restores)
        self.state_resets = reg.counter(
            "dynamo_engine_state_resets",
            f"Admissions at position 0: the {layers} start empty",
        )
        self.state_walkbacks = reg.counter(
            "dynamo_engine_state_walkbacks", walkbacks)

    def _mint_state_bytes(self, state_bytes: Dict[str, int], parts: str) -> None:
        gauge = self.registry.gauge("dynamo_engine_state_bytes", parts, ["part"])
        for part, n in state_bytes.items():
            gauge.labels(part).set(n)

    def mint_conv_state(self, state_bytes: Dict[str, int]) -> None:
        """The families of a trunk with convolution layers (minted by the
        engine that serves one, at construction): how each admission found
        its convolution layers' state, and the bytes the state holds."""
        self._mint_state_admissions(
            "convolution layers",
            "Admissions whose convolution layers resumed from the snapshot "
            "of the page their prefix hit ends on",
            "Prefix hits shortened by a block because the whole prompt was "
            "cached (a snapshot exists at page ends only)",
        )
        self._mint_state_bytes(
            state_bytes,
            "Bytes of the convolution layers' state (part: lanes, the rows "
            "a lane carries | pages, the snapshots that ride the pages)",
        )

    def mint_delta_state(self, state_bytes: Dict[str, int]) -> None:
        """The families of a trunk with gated delta-rule layers: how each
        admission found its state (the three counters a trunk with
        convolution layers has), the snapshot pool's traffic, and the bytes
        the state holds."""
        reg = self.registry
        self._mint_state_admissions(
            "delta-rule layers",
            "Admissions whose delta-rule layers resumed from a snapshot slot",
            "Prefix hits walked back to a shallower block because the "
            "deepest matched block had no live snapshot",
        )
        self.state_snapshots = reg.counter(
            "dynamo_engine_state_snapshots",
            "Snapshots a packed dispatch wrote into a slot",
        )
        self.snapshot_recompute_tokens = reg.counter(
            "dynamo_engine_state_snapshot_recompute_tokens",
            "Tokens of prefix hits behind the snapshot they resumed from, "
            "computed again",
        )
        self.gdn_chunks = reg.counter(
            "dynamo_engine_gdn_chunks",
            "Chunks of the gated delta rule that packed steps ran through "
            "the launch gated_delta_chunks, summed over the linear layers "
            "(0 where the XLA composition runs them)",
        )
        self._snapshot_slots = (
            reg.counter(
                "dynamo_engine_state_snapshot_evictions",
                "Snapshots that gave their slot to a newer one",
            ),
            reg.gauge(
                "dynamo_engine_state_snapshot_slots_in_use",
                "Slots of the snapshot pool that hold a snapshot",
            ),
        )
        self._mint_state_bytes(
            state_bytes,
            "Bytes of the delta-rule layers' state (part: lanes, what a "
            "lane carries | slots, the snapshot pool)",
        )

    def observe_snapshot_slots(self, in_use: int, evictions: int) -> None:
        if self._snapshot_slots is None:
            return
        evicted, gauge = self._snapshot_slots
        gauge.set(in_use)
        if evictions > self._snapshot_evictions:
            evicted.inc(evictions - self._snapshot_evictions)
            self._snapshot_evictions = evictions

    def observe_executable_shapes(self, n: int) -> None:
        self.executable_shapes.set(n)

    def observe_multistep_k(self, k: int) -> None:
        self.multistep_k.set(k)

    def observe_multistep_ceiling(
        self, ceiling: int, step_s: Optional[float], loop_s: Optional[float]
    ) -> None:
        self.multistep_ceiling.set(ceiling)
        if step_s is not None:
            self.multistep_reading.labels("decode_step").set(step_s)
        if loop_s is not None:
            self.multistep_reading.labels("loop_tick").set(loop_s)


class OffloadMetrics:
    """Registry-backed multi-tier KV offload plane series (G2 host / G3
    disk / swap records): transfer volume + latency per tier, occupancy,
    tiered prefix hits, preemption kinds, and the chaos-visible failure
    counters.  Minted here (DT007) and updated only from the offload
    thread or the engine's existing commit points -- never per token.
    Catalog: README "Multi-tier KV cache (KVBM)".
    """

    def __init__(self, registry: Optional["MetricsRegistry"] = None) -> None:
        reg = registry or default_registry()
        self.registry = reg
        self.offload_bytes = reg.counter(
            "dynamo_kv_offload_bytes",
            "KV bytes demoted out of HBM (eviction snapshots, swap-outs)",
            ["tier"],  # host | swap
        )
        self.offload_latency = reg.histogram(
            "dynamo_kv_offload_seconds",
            "Device->host materialize + tier store latency per blob",
            ["tier"],
            buckets=TRANSFER_LATENCY_BUCKETS,
        )
        self.onboard_bytes = reg.counter(
            "dynamo_kv_onboard_bytes",
            "KV bytes restored into HBM pages (prefix onboards, swap-ins)",
            ["tier"],  # prefix | swap
        )
        self.onboard_latency = reg.histogram(
            "dynamo_kv_onboard_seconds",
            "Host->device scatter latency per onboarded blob",
            ["tier"],
            buckets=TRANSFER_LATENCY_BUCKETS,
        )
        self.tier_blocks = reg.gauge(
            "dynamo_kv_tier_blocks",
            "Blocks resident per offload tier (swap = budget blocks in use)",
            ["tier"],  # host | disk | swap
        )
        self.tier_hits = reg.counter(
            "dynamo_kv_tier_prefix_hits",
            "Prefix-block lookups served from an offload tier",
            ["tier"],  # host | disk
        )
        self.tier_promotes = reg.counter(
            "dynamo_kv_tier_promotes",
            "Blocks promoted up a tier ahead of use (disk->host ring via "
            "prefetch or lookup-triggered promote); deliberately not a "
            "hit -- warmth counts only lookups actually served",
            ["tier"],  # disk
        )
        self.preemptions = reg.counter(
            "dynamo_kv_preemptions",
            "Capacity preemptions by recovery kind",
            ["kind"],  # swap | recompute
        )
        self.swap_events = reg.counter(
            "dynamo_kv_swap_events",
            "Swap-plane transitions (out = parked, in = restored)",
            ["event"],  # out | in
        )
        self.swap_fallbacks = reg.counter(
            "dynamo_kv_swap_fallbacks",
            "Swap attempts that fell back to recompute, by cause",
            ["cause"],  # budget | copy_fail | truncate
        )
        self.onboard_fallbacks = reg.counter(
            "dynamo_kv_onboard_fallbacks",
            "Prefix onboards abandoned (the admission recomputed the "
            "prefix in place), by cause",
            ["cause"],  # truncate
        )
        self.copy_fails = reg.counter(
            "dynamo_kv_offload_copy_failures",
            "Offload materializations dropped (I/O errors or injected "
            "offload.copy_fail faults)",
        )
        # queue-side prefetch (ISSUE 10): tracked walks that stage
        # offloaded prefix chains toward host RAM during queue wait
        self.prefetch_issued = reg.counter(
            "dynamo_kv_prefetch_issued_blocks",
            "Prefix blocks requested by tracked queue-side prefetch walks",
        )
        self.prefetch_hits = reg.counter(
            "dynamo_kv_prefetch_hits",
            "Prefetch-staged blocks found host-resident and consumed at "
            "admission (the onboard scatter never waited on a disk read)",
        )
        self.prefetch_wasted = reg.counter(
            "dynamo_kv_prefetch_wasted_bytes",
            "Bytes prefetch-staged but never consumed (request cancelled "
            "before admission, or the admission matched elsewhere)",
        )
        self.prefetch_overlap = reg.histogram(
            "dynamo_kv_prefetch_overlap_ratio",
            "Fraction of each tracked prefetch walk that overlapped queue "
            "wait instead of the TTFT critical path (1.0 = fully hidden)",
            buckets=RATIO_BUCKETS,
        )

    def record_offload(self, tier: str, nbytes: int, seconds: float) -> None:
        self.offload_bytes.labels(tier).inc(nbytes)
        self.offload_latency.labels(tier).observe(max(seconds, 0.0))

    def record_onboard(self, tier: str, nbytes: int, seconds: float) -> None:
        self.onboard_bytes.labels(tier).inc(nbytes)
        self.onboard_latency.labels(tier).observe(max(seconds, 0.0))


class RemoteKVMetrics:
    """Registry-backed G4 remote-tier series (``dynamo_kv_g4_*``): the
    fleet-shared store's transfer volume/latency per direction, local
    residency knowledge, and the chaos-visible fetch failure causes.
    Updated only from the kv-remote thread.  Catalog: README "Fleet KV
    economy"."""

    def __init__(self, registry: Optional["MetricsRegistry"] = None) -> None:
        reg = registry or default_registry()
        self.registry = reg
        self.bytes = reg.counter(
            "dynamo_kv_g4_bytes",
            "KV frame bytes moved against the G4 fleet store, by direction",
            ["op"],  # store | fetch
        )
        self.latency = reg.histogram(
            "dynamo_kv_g4_seconds",
            "G4 store round-trip latency per blob frame, by direction",
            ["op"],
            buckets=TRANSFER_LATENCY_BUCKETS,
        )
        self.blocks = reg.gauge(
            "dynamo_kv_g4_blocks",
            "Blocks this worker knows to be resident in the G4 store "
            "(own publications + merged fleet adverts)",
        )
        self.fetch_failures = reg.counter(
            "dynamo_kv_g4_fetch_failures",
            "G4 fetches that fell back to recompute, by cause",
            ["cause"],  # fetch_fail | missing | blob_corrupt
        )

    def record_store(self, nbytes: int, seconds: float) -> None:
        self.bytes.labels("store").inc(nbytes)
        self.latency.labels("store").observe(max(seconds, 0.0))

    def record_fetch(self, nbytes: int, seconds: float) -> None:
        self.bytes.labels("fetch").inc(nbytes)
        self.latency.labels("fetch").observe(max(seconds, 0.0))


class SpecMetrics:
    """Registry-backed speculative-decoding series (``dynamo_spec_*``).

    Updated only at the engine's existing commit points (per verify
    dispatch, never per token).  ``accept_rate`` is the engine-lifetime
    running ratio -- per-request rates ride the OpenAI usage extension and
    the request span's ``spec_accept_rate`` attr instead.  Catalog: README
    "Speculative decoding".
    """

    def __init__(self, registry: Optional["MetricsRegistry"] = None) -> None:
        reg = registry or default_registry()
        self.registry = reg
        self.drafted = reg.counter(
            "dynamo_spec_drafted_tokens",
            "Draft tokens proposed and dispatched for verification",
            ["drafter"],
        )
        self.accepted = reg.counter(
            "dynamo_spec_accepted_tokens",
            "Draft tokens accepted by the verify step",
            ["drafter"],
        )
        self.verify_steps = reg.counter(
            "dynamo_spec_verify_steps",
            "Batched multi-token verify passes (standalone or folded)",
        )
        self.folded_steps = reg.counter(
            "dynamo_spec_folded_verify_steps",
            "Verify column groups folded into packed unified dispatches "
            "(ISSUE 15: no standalone verify dispatch was paid for these)",
        )
        self.auto_disabled = reg.counter(
            "dynamo_spec_auto_disabled_requests",
            "Requests whose speculation auto-disabled on low acceptance",
        )
        self.enabled_frac = reg.gauge(
            "dynamo_spec_enabled_frac",
            "Fraction of spec-armed requests still drafting "
            "(1 - auto_disabled/armed)",
        )
        self.requests = reg.counter(
            "dynamo_spec_requests",
            "Requests that ran with speculation armed",
        )
        self.accept_rate = reg.gauge(
            "dynamo_spec_accept_rate",
            "Engine-lifetime draft acceptance rate (accepted/drafted)",
        )
        self.draft_latency = reg.histogram(
            "dynamo_spec_draft_seconds",
            "Host-side drafting time per verify dispatch (all lanes)",
            buckets=STEP_LATENCY_BUCKETS,
        )
        self.verify_latency = reg.histogram(
            "dynamo_spec_verify_seconds",
            "Verify dispatch->commit latency",
            buckets=STEP_LATENCY_BUCKETS,
        )


_default = MetricsRegistry()
_default_lock = threading.Lock()


def default_registry() -> MetricsRegistry:
    return _default


def set_default(reg: MetricsRegistry) -> MetricsRegistry:
    """Swap the process-wide registry (tests); returns the previous one."""
    global _default
    with _default_lock:
        prev = _default
        _default = reg
        return prev


def render_default() -> Tuple[bytes, str]:
    return _default.render()


def set_worker_identity(
    worker_id: Optional[Any] = None, role: Optional[str] = None
) -> None:
    """Stamp this process's worker identity onto the default registry's
    rendered exposition (and keep it across test-time ``set_default``
    swaps is the caller's concern -- workers set it once at startup)."""
    labels: Dict[str, Any] = {}
    if worker_id is not None:
        labels["worker_id"] = str(worker_id)
    if role:
        labels["role"] = str(role)
    default_registry().set_default_labels(**labels)


def worker_identity() -> Dict[str, str]:
    return dict(default_registry().default_labels)
