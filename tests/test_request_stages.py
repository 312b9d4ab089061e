"""Where a request's time goes, from inside the engine (ISSUE 26): one
behaviour watched or not, request stages stamped where they happen, the
span tree written when a request finishes, tick phases and parked time in
the ``jax.profiler`` trace, and ``POST /profile/device``."""

import asyncio
import glob
import os
import re
import time

import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
from dynamo_tpu.engine.kv_cache import PageAllocator
from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, SeqState
from dynamo_tpu.http.service import HttpService
from dynamo_tpu.llm import Backend, OpenAIPreprocessor, Tokenizer
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import metrics as rtm
from dynamo_tpu.runtime import profiling, tracing
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.pipeline import link

from tests.test_serving import http_request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("ingress", "queue_wait", "first_token_service")


@pytest.fixture
def registry():
    prev = rtm.set_default(rtm.MetricsRegistry())
    yield rtm.default_registry()
    rtm.set_default(prev)


@pytest.fixture
def profiler():
    prof = profiling.profiler
    was = prof.enabled
    prof.clear()
    yield prof
    prof.clear()
    prof.enabled = was


@pytest.fixture
def traced():
    tracing.collector.clear()
    tracing.collector.enable()
    yield tracing.collector
    tracing.collector.disable()
    tracing.collector.clear()


def req(tokens, max_tokens=8) -> PreprocessedRequest:
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens),
        sampling_options=SamplingOptions(temperature=0.0),
    )


def tiny_engine(**kw) -> JaxEngine:
    cfg = dict(max_batch_size=4, max_seq_len=64, page_size=4, num_pages=64)
    cfg.update(kw)
    return JaxEngine.random_init(ModelConfig.tiny(), EngineConfig(**cfg))


async def collect(engine, request, rid=None):
    stream = await engine.generate(Context.new(request, rid))
    tokens = []
    async for item in stream:
        tokens.extend((item.data or {}).get("token_ids") or [])
    return tokens


def hist_count(registry, name):
    for metric in registry.registry.collect():
        for s in metric.samples:
            if s.name == name + "_count":
                return s.value
    return None


# -- A: one behaviour, watched or not ------------------------------------------


def _k_sequence(engine, script):
    """K over a scripted run of ticks: True = a tick under pressure (a
    prefill chunk rides it), False = a pressure-free one."""
    return [
        engine._multistep_plan_k(["chunk"] if pressure else [], 0)
        for pressure in script
    ]


def test_multistep_k_is_the_same_watched_or_not(registry, profiler):
    """The fused-step controller ramps 1, 2, 4, 8 whether or not the tick
    profiler is on -- even with a ring full of host-bound ticks, which
    used to make it jump straight to the ceiling."""
    script = [False] * 6 + [True] + [False] * 3 + [True, True] + [False] * 5
    engine = tiny_engine()
    profiler.disable()
    unwatched = _k_sequence(engine, script)
    assert unwatched[:6] == [1, 2, 4, 8, 8, 8]
    assert unwatched[6:10] == [1, 1, 2, 4]

    profiler.enable()
    for _ in range(64):  # every tick all host, no device wait
        tick = profiler.begin_tick()
        tick.note_dispatch("unified")
        tick.mark("dispatch")
        profiler.finish_tick(tick)
    assert all(r.host_occupancy == 1.0 for r in profiler.records())
    engine._ms_ramp = 1
    assert _k_sequence(engine, script) == unwatched


def test_engine_reads_nothing_the_profiler_holds():
    """No file of the engine reads the host-occupancy signal, and the
    profiler no longer offers it."""
    assert not hasattr(profiling.TickProfiler, "recent_host_occupancy")
    for path in glob.glob(
        os.path.join(ROOT, "dynamo_tpu", "engine", "*.py")
    ):
        with open(path) as f:
            assert "recent_host_occupancy" not in f.read(), path


# -- B: request stages ----------------------------------------------------------


def test_stage_histograms_count_each_request_once(run, registry):
    """After N served requests each stage histogram counts N, and per
    request ingress + queue + service is first token - received."""
    n = 6

    async def body():
        engine = tiny_engine(max_batch_size=2)  # so that some queue
        seqs = []
        enqueue = engine.sched.enqueue
        engine.sched.enqueue = lambda s: (seqs.append(s), enqueue(s))[1]
        try:
            await asyncio.gather(*[
                collect(engine, req([1, 2, 3, 4 + i], max_tokens=6))
                for i in range(n)
            ])
        finally:
            await engine.stop()
        return seqs

    seqs = run(body())
    assert len(seqs) == n
    for stage in STAGES:
        assert hist_count(registry, f"dynamo_engine_{stage}_seconds") == n
    total = 0.0
    for s in seqs:
        assert s.created_s <= s.arrival_s <= s.admitted_s <= s.first_token_s
        total += s.first_token_s - s.created_s
    summed = sum(
        registry.sample(f"dynamo_engine_{stage}_seconds") for stage in STAGES
    )
    assert summed == pytest.approx(total, abs=1e-6)


def test_readmission_keeps_the_first_admission(registry):
    """A preempted and re-admitted request keeps its first ``admitted_s``,
    counts one queue wait, and holds the interval it spent preempted."""
    sched = Scheduler(
        SchedulerConfig(max_batch_size=2, max_seq_len=32, page_size=4),
        PageAllocator(16),
    )
    from dynamo_tpu.engine.metrics import EngineMetrics

    sched.metrics = EngineMetrics()
    seq = SeqState.from_request("r", req([1] * 8, max_tokens=20), 4)
    sched.enqueue(seq)
    sched.plan()
    first = seq.admitted_s
    assert first >= seq.arrival_s and not seq.preempted
    sched.commit_prefill_token(seq, 7)
    time.sleep(0.002)
    sched._preempt(seq)
    assert seq.slot < 0 and seq.preempted_at > first
    time.sleep(0.002)
    sched.plan()
    assert seq.slot >= 0
    assert seq.admitted_s == first
    assert len(seq.preempted) == 1 and not seq.preempted_at
    lo, hi = seq.preempted[0]
    assert first < lo < hi
    assert hist_count(registry, "dynamo_engine_queue_wait_seconds") == 1


def test_stage_segments_tile_the_request():
    seq = SeqState.from_request("r", req([1, 2, 3]), 4)
    seq.arrival_s, seq.admitted_s, seq.first_token_s = 10.0, 11.0, 13.0
    seq.preempted = [(11.5, 12.0), (14.0, 15.0)]
    seq.preempted_at = 17.0  # still preempted when it ends
    assert seq.stage_segments(18.0) == [
        ("queue", 10.0, 11.0),
        ("prefill", 11.0, 11.5), ("preempted", 11.5, 12.0),
        ("prefill", 12.0, 13.0),
        ("decode", 13.0, 14.0), ("preempted", 14.0, 15.0),
        ("decode", 15.0, 17.0), ("preempted", 17.0, 18.0),
    ]
    never = SeqState.from_request("q", req([1, 2, 3]), 4)
    never.arrival_s = 1.0  # cancelled in the queue
    assert never.stage_segments(2.0) == [("queue", 1.0, 2.0)]


def test_record_span_needs_tracing_on():
    assert not tracing.collector.enabled
    assert tracing.record_span("x", "rid", 1.0, 2.0) is None
    assert tracing.collector.get("rid") == []


# -- the span tree ----------------------------------------------------------------


def _check_tree(spans):
    """One tree, children inside parents; returns spans by name."""
    by_id = {s["span_id"]: s for s in spans}
    roots = [s for s in spans if not s.get("parent_span_id")]
    assert len(roots) == 1 and roots[0]["name"] == "http.request"
    assert len({s["trace_id"] for s in spans}) == 1
    eps = 2e-3  # start_s is rounded to the microsecond, durations to it too
    for s in spans:
        if s is roots[0]:
            continue
        parent = by_id[s["parent_span_id"]]
        assert s["start_s"] >= parent["start_s"] - eps, (s, parent)
        assert (
            s["start_s"] + s["duration_ms"] / 1e3
            <= parent["start_s"] + parent["duration_ms"] / 1e3 + eps
        ), (s, parent)
    names = {}
    for s in spans:
        names.setdefault(s["name"], []).append(s)
    return names, by_id


def test_trace_endpoint_returns_the_stage_tree(run, registry, traced,
                                               model_dir):
    """GET /trace/{id}: http.request > {http.preprocess, engine.request >
    {engine.queue, engine.prefill, engine.decode}}, stages tiling the
    request, and no engine.prefill_dispatch anywhere."""

    async def body():
        tok = Tokenizer.from_model_dir(model_dir)
        engine = JaxEngine.random_init(
            ModelConfig.tiny(vocab_size=512),
            EngineConfig(max_batch_size=2, max_seq_len=64, page_size=4,
                         num_pages=64),
        )
        pipeline = link(OpenAIPreprocessor("m", tok), Backend(tok), engine)
        svc = HttpService()
        svc.manager.add_completion_model("m", pipeline)
        await svc.start()
        try:
            host, port = svc.address
            status, headers, _ = await http_request(
                host, port, "POST", "/v1/completions",
                {"model": "m", "prompt": "hello world again", "max_tokens": 6,
                 "temperature": 0},
            )
            assert status == 200
            rid = headers["x-request-id"]
            status, _h, payload = await http_request(
                host, port, "GET", f"/trace/{rid}"
            )
            assert status == 200
            return payload["spans"]
        finally:
            await svc.stop()
            await engine.stop()

    spans = run(body())
    names, by_id = _check_tree(spans)
    assert "engine.prefill_dispatch" not in names
    for name in ("http.preprocess", "engine.request", "engine.queue",
                 "engine.prefill", "engine.decode"):
        assert len(names[name]) == 1, name
    root = names["http.request"][0]
    request = names["engine.request"][0]
    assert names["http.preprocess"][0]["parent_span_id"] == root["span_id"]
    # the root starts where its first child does: the handler's entry
    assert names["http.preprocess"][0]["start_s"] == root["start_s"]
    assert request["parent_span_id"] == root["span_id"]
    stages = [s for s in spans if s.get("parent_span_id") == request["span_id"]]
    assert sorted(s["name"] for s in stages) == [
        "engine.decode", "engine.prefill", "engine.queue"]
    assert sum(s["duration_ms"] for s in stages) == pytest.approx(
        request["duration_ms"], abs=0.01)
    assert all(s["duration_ms"] > 0 for s in stages)
    assert request["attrs"]["output_tokens"] == 6
    assert request["attrs"]["preemptions"] == 0
    prefill = names["engine.prefill"][0]["attrs"]
    assert prefill["chunks"] >= 1 and prefill["prompt_tokens_computed"] >= 1
    assert {"cached", "kv_prefetch_hits", "mixed"} <= set(prefill)
    # what the first token waited behind: the three parts tile the stage
    waited = (prefill["in_chunk_steps_ms"] + prefill["in_decode_steps_ms"]
              + prefill["no_dispatch_ms"])
    assert waited == pytest.approx(
        names["engine.prefill"][0]["duration_ms"], rel=0.01, abs=0.01)
    assert prefill["in_chunk_steps_ms"] > 0
    assert prefill["chunk_rows_all"] == prefill["prompt_tokens_computed"]


def test_preempted_request_spans_tile(run, registry, traced):
    """A pool too small for two growing lanes preempts one: its
    engine.preempted span stands beside the stages, and together they
    still tile engine.request."""

    async def body():
        engine = tiny_engine(
            max_batch_size=2, num_pages=17, async_dispatch=False,
            decode_block_size=4,
        )
        try:
            async def one(i):
                rid = f"pre-{i}"
                with tracing.span("http.request", rid, bind=True):
                    await collect(
                        engine, req([1, 2, 3 + i], max_tokens=40).to_dict(),
                        rid,
                    )
            await asyncio.gather(one(0), one(1))
        finally:
            await engine.stop()

    run(body())
    preempted = 0
    for rid in ("pre-0", "pre-1"):
        spans = [s.to_dict() for s in traced.get(rid)]
        names, _ = _check_tree(spans)
        request = names["engine.request"][0]
        stages = [
            s for s in spans if s.get("parent_span_id") == request["span_id"]
        ]
        assert sum(s["duration_ms"] for s in stages) == pytest.approx(
            request["duration_ms"], abs=0.02)
        n = len(names.get("engine.preempted", []))
        assert request["attrs"]["preemptions"] == n
        preempted += n
    assert preempted >= 1


# -- C: tick phases in the jax.profiler trace ------------------------------------------


def _trace_events(trace_dir):
    from jax.profiler import ProfileData

    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    assert paths
    events = []
    for plane in ProfileData.from_file(sorted(paths)[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("dyn."):
                    events.append((ev.name, ev.duration_ns * 1e-9,
                                   dict(ev.stats)))
    return events


def _run_queue_waits():
    """By thread of this process, the seconds it has waited on a run queue
    for a core (``/proc/<pid>/task/<tid>/schedstat``, second field; empty
    where the kernel keeps none)."""
    waits = {}
    for path in glob.glob("/proc/self/task/*/schedstat"):
        try:
            with open(path) as f:
                waits[path] = int(f.read().split()[1]) * 1e-9
        except (OSError, IndexError, ValueError):
            pass
    return waits


async def _traced_burst(trace_dir, profile):
    """The tick records of a traced burst, and the longest any one thread
    of this process was kept off a core while it ran: the slack two clocks
    read a few lines apart need on a loaded machine."""
    import jax

    engine = tiny_engine()
    try:
        await collect(engine, req([1, 2, 3], max_tokens=4))  # compiles
        if profile:
            profiling.profiler.enable()
            profiling.profiler.clear()
            # a loop that parked before the profiler went on waits unwatched
            # (one wait, no slices): wake it, so that it parks again watched
            engine._wake.set()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        before = _run_queue_waits()
        try:
            await asyncio.sleep(0.2)  # parked: nothing is due
            await asyncio.gather(*[
                collect(engine, req([1, 2, 3, 4 + i], max_tokens=12))
                for i in range(3)
            ])
            await asyncio.sleep(0.2)  # parked again before the trace ends
            recs = profiling.profiler.records()
            waited = max(
                (w - before.get(tid, 0.0)
                 for tid, w in _run_queue_waits().items()), default=0.0)
        finally:
            jax.profiler.stop_trace()
    finally:
        await engine.stop()
    return recs, waited


def test_tick_phases_land_in_the_profiler_trace(run, registry, profiler,
                                                tmp_path):
    """With the tick profiler on, a jax.profiler trace holds a dyn.tick
    event per closed phase interval, whose durations add up to the tick
    records', the packed dispatches' shapes, and dyn.parked."""
    recs, waited = run(_traced_burst(str(tmp_path), True))
    events = _trace_events(str(tmp_path))
    assert {name for name, _d, _s in events} == {"dyn.tick", "dyn.parked"}
    by_phase = {}
    for name, dur, stats in events:
        if name == "dyn.tick":
            by_phase[stats["phase"]] = by_phase.get(stats["phase"], 0.0) + dur
    assert set(by_phase) <= set(profiling.PHASES)
    totals = {}
    for r in recs:
        for k, v in r.phases.items():
            totals[k] = totals.get(k, 0.0) + v
    # phases that only kept ticks hold (a discarded or empty tick has
    # annotations and no record): the two clocks agree on them.  A phase
    # of the burst sums to a few milliseconds, and the record's clock is
    # read a few lines before the annotation closes: a thread taken off its
    # core between the two moves that wait from one phase to the next, so
    # the clocks are given what the kernel says the worst-off thread waited
    # (nothing on an idle machine), and not a second try
    slack = 2e-3 + waited
    for phase in ("assemble", "device_wait", "commit"):
        assert totals[phase] > 0
        assert by_phase[phase] == pytest.approx(totals[phase], rel=0.05,
                                                abs=slack), (phase, waited)
    assert sum(by_phase.values()) >= 0.95 * sum(totals.values()) - waited
    # parked before and after the burst, less a slice at each edge
    assert sum(d for n, d, _s in events if n == "dyn.parked") > 0.2
    shaped = [s for n, _d, s in events if n == "dyn.tick" and "q" in s]
    assert shaped
    for s in shaped:
        assert s["phase"] == "dispatch"
        q = [int(v) for v in str(s["q"]).split("|")]
        ctx = [int(v) for v in str(s["ctx"]).split("|")]
        assert len(q) == len(ctx) and all(c >= n >= 1 for n, c in zip(q, ctx))
        assert int(s["k"]) >= 1 and int(s["np"]) >= sum(q)
        # the page table's width handed to the dispatch (the tiny model's
        # pool keeps the bucket: at most the scheduler's 16 pages), and for
        # fused steps what attends them after the first: here the gather
        assert 1 <= int(s["pt"]) <= 16
        assert s.get("decode") == ("xla" if int(s["k"]) > 1 else None)
        # the class of the step, and the serial its fetch names again
        prompt_rows = any(n > 1 for n in q)
        assert s["step"] == ("chunk" if prompt_rows else "decode")
        assert int(s["d"]) >= 1
    serials = [int(s["d"]) for s in shaped]
    assert serials == sorted(set(serials))
    # the device_wait interval that fetched a dispatch carries its serial
    # and the device's service time as the commit read it
    waits = [s for n, _d, s in events
             if n == "dyn.tick" and s.get("phase") == "device_wait"]
    assert waits and all("svc_us" in s and "d" in s for s in waits)
    fetched = [int(v) for s in waits for v in str(s["d"]).split("|")]
    assert fetched == sorted(set(fetched)) and set(fetched) <= set(serials)
    assert sum(int(s["svc_us"]) for s in waits) > 0
    # and the tick ring holds the same record, commit by commit
    records = [d for r in recs for d in r.to_dict()["dispatch_records"]]
    assert len(records) >= len(fetched)
    assert {d["step"] for d in records} <= {"chunk", "decode"}


def test_no_annotation_with_the_profiler_off(run, registry, profiler,
                                             tmp_path):
    profiler.disable()
    run(_traced_burst(str(tmp_path), False))
    assert _trace_events(str(tmp_path)) == []


def test_profile_device_holds_the_tick_profiler_on(run, registry, profiler,
                                                   tmp_path):
    """POST /profile/device's capture: the benchmark's trace options, the
    tick profiler on for its length and restored after, so one call yields
    dyn.tick phases beside the device's operations."""
    profiler.disable()

    async def body():
        engine = tiny_engine()
        try:
            await collect(engine, req([1, 2, 3], max_tokens=4))
            capture = asyncio.ensure_future(
                profiling.capture_device_trace(0.4, str(tmp_path)))
            await asyncio.sleep(0.1)
            assert profiler.enabled
            await collect(engine, req([1, 2, 3, 4], max_tokens=8))
            return await capture
        finally:
            await engine.stop()

    result = run(body())
    assert result["ok"], result
    assert not profiler.enabled
    names = {name for name, _d, _s in _trace_events(str(tmp_path))}
    assert "dyn.tick" in names


def test_dispatch_gap_is_not_called_a_bound_on_idle():
    with open(os.path.join(ROOT, "dynamo_tpu", "runtime", "profiling.py")) as f:
        text = f.read()
    assert not re.search(r"upper bound on (true )?device idle", text)
