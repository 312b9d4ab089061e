"""The tick loop's dispatch record (ISSUE 41): every committed dispatch
accounted for by class of step, packed rows and the device's service time;
a first token split by what it waited behind; the same record on the
profiler trace's annotations and in the tick ring.  Always on, watched or
not: nothing here switches the tick profiler or tracing on unless it says
so."""

import asyncio
import inspect
import os
import re
import time

import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
from dynamo_tpu.engine import engine as engine_mod
from dynamo_tpu.engine.engine import InflightUnified
from dynamo_tpu.http.service import HttpService
from dynamo_tpu.llm import Backend, OpenAIPreprocessor, Tokenizer
from dynamo_tpu.runtime import profiling, tracing
from dynamo_tpu.runtime.pipeline import link

from tests.test_request_stages import (  # noqa: F401  (fixtures)
    ROOT,
    collect,
    hist_count,
    profiler,
    registry,
    req,
    tiny_engine,
)
from tests.test_serving import http_request

WAIT = "dynamo_engine_first_token_wait_seconds"
PARTS = ("chunk_steps", "decode_steps", "no_dispatch")
LONG = 56  # prompt tokens: seven chunks of the 8-token budget below


def chunking_engine(**kw) -> JaxEngine:
    """A tiny engine whose long prompts take several unified chunk steps."""
    return tiny_engine(
        max_seq_len=128, num_pages=128, mixed_token_budget=8,
        prefill_chunk_tokens=8, **kw,
    )


class Spy:
    """What ``_record_service`` was handed, commit by commit."""

    def __init__(self, engine):
        self.commits = []
        inner = engine._record_service

        def record(entries, service, now):
            self.commits.append((list(entries), service, now))
            return inner(entries, service, now)

        engine._record_service = record


async def _long_and_short(engine):
    """A long prompt and, queued behind it, a short one; then a request on
    its own.  Returns the ``SeqState``s: a first request that compiles,
    then those three in that order."""
    seqs = []
    enqueue = engine.sched.enqueue
    engine.sched.enqueue = lambda s: (seqs.append(s), enqueue(s))[1]
    await collect(engine, req([1, 2, 3], max_tokens=4))  # compiles
    await asyncio.gather(
        collect(engine, req(range(1, LONG + 1), max_tokens=6)),
        collect(engine, req([7, 8, 9], max_tokens=6)),
    )
    await collect(engine, req(range(60, 80), max_tokens=4))
    return seqs


def test_first_token_wait_tiles_the_service(run, registry):
    """Profiler and tracing off.  Per request the three parts add up to
    its first-token service; the short request behind a long prompt got
    under half of the chunk rows, the request served alone all of them."""
    assert not profiling.profiler.enabled
    assert not tracing.collector.enabled

    async def body():
        engine = chunking_engine()
        try:
            return await _long_and_short(engine)
        finally:
            await engine.stop()

    seqs = run(body())
    _first, long, short, alone = seqs
    assert long.prefill_chunks >= LONG // 8
    for seq in seqs:
        chunk_s, decode_s, idle_s, rows_all = seq.first_token_wait
        service = seq.first_token_s - seq.admitted_s
        assert chunk_s > 0.0 and decode_s >= 0.0 and idle_s >= 0.0
        assert chunk_s + decode_s + idle_s == pytest.approx(service, rel=0.01)
        assert seq.prefill_tokens <= rows_all
    assert short.prefill_tokens / short.first_token_wait[3] < 0.5
    assert alone.prefill_tokens == alone.first_token_wait[3] > 0
    # the families, over all four requests (the compiling one too)
    parts = sum(registry.sample(WAIT, {"behind": p}) for p in PARTS)
    service = registry.sample("dynamo_engine_first_token_service_seconds")
    assert parts == pytest.approx(service, rel=0.01)
    assert hist_count(registry, WAIT) == 4
    rows = "dynamo_engine_first_token_chunk_rows"
    own = registry.sample(rows, {"whose": "own"})
    assert own == sum(s.prefill_tokens for s in seqs)
    assert registry.sample(rows, {"whose": "all"}) == sum(
        s.first_token_wait[3] for s in seqs) > own


def test_service_intervals_do_not_overlap(run, registry):
    """Consecutive commits' service intervals are disjoint, their sum is at
    most the wall time, and the families count what the dispatches were:
    one observe a dispatch, its steps, its decode-lane steps by class."""

    async def body():
        engine = chunking_engine()
        spy = Spy(engine)
        t0 = time.perf_counter()
        try:
            await _long_and_short(engine)
        finally:
            await engine.stop()
        return spy.commits, time.perf_counter() - t0

    commits, wall = run(body())
    assert len(commits) > LONG // 8
    prev_end = 0.0
    for entries, service, now in commits:
        assert service >= 0.0
        assert now - service >= prev_end - 1e-9
        assert now - service >= entries[0].dispatched_at - 1e-9
        prev_end = now
    assert sum(service for _e, service, _n in commits) <= wall

    flat = [e for entries, _s, _n in commits for e in entries]
    assert all(isinstance(e, InflightUnified) for e in flat)
    chunk = [e for e in flat if e.n_prefill_tokens > 0]
    decode = [e for e in flat if e.n_prefill_tokens == 0]
    assert chunk and decode
    series = {}
    for metric in registry.registry.collect():
        for s in metric.samples:
            series[(s.name, tuple(sorted(s.labels.items())))] = s.value

    def total(name, **labels):
        return sum(
            v for (n, ls), v in series.items()
            if n == name and all(dict(ls).get(k) == w for k, w in labels.items())
        )

    svc = "dynamo_engine_dispatch_service_seconds"
    assert total(svc + "_count", step="chunk") == len(chunk)
    assert total(svc + "_count", step="decode") == len(decode)
    assert total(svc + "_sum") == pytest.approx(
        sum(service for _e, service, _n in commits), rel=1e-6)
    steps = "dynamo_engine_dispatch_steps_total"
    assert total(steps, step="decode") == sum(e.n_steps for e in decode)
    assert total(steps, step="chunk") == len(chunk)
    lanes = "dynamo_engine_decode_lane_steps_total"
    for step, group in (("chunk", chunk), ("decode", decode)):
        assert total(lanes, step=step) == sum(
            e.n_decode * e.n_steps for e in group)
    # packed rows of the executables the dispatches took, as labels
    assert {
        dict(ls)["np"] for (n, ls), _v in series.items() if n == svc + "_count"
    } == {str(e.np_rows) for e in flat}
    # and beside them, the counter that had no reader: real rows / rows run
    # (counted as a dispatch goes out: one may be in flight at the end)
    used = total("dynamo_engine_mixed_tokens_total", kind="used")
    assert used >= sum(e.used_rows for e in flat) > 0


def test_a_bundled_commit_splits_its_service(registry):
    """Several entries in one commit share the bundle's service by rows x
    steps dispatched; the classic entries keep their kind as their class."""
    engine = tiny_engine()
    B = engine.cfg.max_batch_size
    prefill = engine_mod.InflightPrefill(
        sampled=None, tok=None, seq=None, slot=0, rows=3 * B)
    block = engine_mod.InflightBlock(
        sampled=None, slots=[], n_decode=2, n_steps=4)
    shares = engine._service_shares([prefill, block], 0.7)
    assert [a.step for a, _s in shares] == ["prefill", "decode_block"]
    assert [s for _a, s in shares] == pytest.approx([0.3, 0.4])
    engine._record_service([prefill, block], 0.7, 10.0)
    assert engine._served == (
        pytest.approx(0.3), pytest.approx(0.4), 3 * B, 10.0)
    assert registry.sample(
        "dynamo_engine_decode_lane_steps", {"step": "decode_block"}) == 8
    assert registry.sample(
        "dynamo_engine_dispatch_steps", {"step": "decode_block", "np": "0"}
    ) == 4


def test_a_dispatch_in_flight_counts_from_the_admission_on(registry):
    """``_service_mark`` takes the part of the serving dispatch that has
    already run, so its commit credits a request admitted meanwhile with
    what it waited for and no more."""
    engine = tiny_engine()
    head = InflightUnified(
        sampled=None, slots=[], finals=[], n_decode=1, n_steps=2, np_rows=4,
        dispatched_at=time.perf_counter() - 0.5,
    )
    engine._served = (1.0, 2.0, 7, time.perf_counter() - 0.25)
    assert engine._service_mark() == (1.0, 2.0, 7)  # nothing in flight
    engine._inflight = [[head]]
    chunk_s, decode_s, rows = engine._service_mark()
    assert chunk_s == 1.0 and rows == 7
    assert 2.25 <= decode_s < 2.5  # from the last commit, not the enqueue
    head.n_prefill_tokens = 3
    assert engine._service_mark()[0] >= 1.25


def test_parked_seconds_advance_only_while_parked(run, registry):
    """Counted where the wait ends and, for a scrape that comes in the
    middle of one, up to that scrape; nowhere else."""
    with open(engine_mod.__file__) as f:
        text = f.read()
    assert text.count("obs.parked_seconds") == 1
    assert "obs.parked_seconds" in inspect.getsource(JaxEngine._count_parked)
    assert len(re.findall(r"self\._count_parked\b", text)) == 2  # _park, the hook

    async def body():
        engine = tiny_engine()
        try:
            await collect(engine, req([1, 2, 3], max_tokens=4))
            before = registry.sample("dynamo_engine_parked_seconds")
            await asyncio.sleep(0.15)  # parked: nothing is due
            unseen = registry.sample("dynamo_engine_parked_seconds")
            registry.render()  # a scrape, the wait still open
            seen = registry.sample("dynamo_engine_parked_seconds")
            await collect(engine, req([1, 2, 3, 4], max_tokens=4))
            return before, unseen, seen, registry.sample(
                "dynamo_engine_parked_seconds")
        finally:
            await engine.stop()

    before, unseen, seen, after = run(body())
    assert unseen == before  # the wait has not ended
    assert seen - before >= 0.1  # and the scrape saw it all the same
    assert after >= seen  # its end counts the rest once, not the whole again
    assert after - before < 5.0


def test_nothing_schedules_by_the_record():
    """The scheduler copies the record onto a request and reads nothing of
    it; the fused-step controller reads the one running mean the record's
    update hands it (a decode step's service, ISSUE 42), not the record."""
    plan_k = inspect.getsource(JaxEngine._multistep_plan_k)
    for name in ("_served", "_service_mark", "first_token_wait",
                 "served_at_admission", "_inflight"):
        assert name not in plan_k, name
    with open(os.path.join(ROOT, "dynamo_tpu", "engine", "scheduler.py")) as f:
        text = f.read()
    assert "_served" not in text
    # written once, where admitted_s is; never read
    assert re.findall(r"\.served_at_admission\b[^\n]*", text) == [
        ".served_at_admission = self.service_mark()"]
    assert not re.search(r"\.first_token_wait\b", text)
    assert len(re.findall(r"self\.service_mark\b", text)) == 3


def test_mocker_mints_the_families_and_observes_none(registry):
    from dynamo_tpu.runtime.metrics import EngineMetrics

    EngineMetrics(registry)
    body = registry.render()[0].decode()
    for family in (
        "dynamo_engine_dispatch_service_seconds",
        "dynamo_engine_dispatch_steps_total",
        "dynamo_engine_decode_lane_steps_total",
        "dynamo_engine_parked_seconds_total",
        "dynamo_engine_first_token_wait_seconds",
        "dynamo_engine_first_token_chunk_rows_total",
    ):
        assert f"# TYPE {family} " in body, family
    assert "dynamo_engine_dispatch_service_seconds_count" not in body


def test_profile_ticks_returns_the_dispatch_records(run, registry, profiler,
                                                    model_dir):
    """GET /profile/ticks: each tick says what it committed (class, packed
    rows, fused steps, real rows) and how long the device took."""
    profiler.enable()

    async def body():
        tok = Tokenizer.from_model_dir(model_dir)
        engine = JaxEngine.random_init(
            ModelConfig.tiny(vocab_size=512),
            EngineConfig(
                max_batch_size=2, max_seq_len=64, page_size=4, num_pages=64),
        )
        svc = HttpService()
        svc.manager.add_completion_model(
            "m", link(OpenAIPreprocessor("m", tok), Backend(tok), engine))
        await svc.start()
        try:
            host, port = svc.address
            status, _h, _p = await http_request(
                host, port, "POST", "/v1/completions",
                {"model": "m", "prompt": "hello world again",
                 "max_tokens": 12, "temperature": 0},
            )
            assert status == 200
            status, _h, payload = await http_request(
                host, port, "GET", "/profile/ticks")
            assert status == 200
            return payload["ticks"]
        finally:
            await svc.stop()
            await engine.stop()

    ticks = run(body())
    records = [d for t in ticks for d in t["dispatch_records"]]
    assert records
    assert {d["step"] for d in records} == {"chunk", "decode"}
    for d in records:
        assert set(d) == {"step", "np", "k", "rows", "service_ms", "bundle"}
        assert d["np"] >= 1 and d["k"] >= 1 and d["bundle"] == 1
        assert 1 <= d["rows"] and d["service_ms"] >= 0.0
    # a tick's records are of the dispatches it committed: as many as the
    # ticks enqueued, but for those still in flight at the end
    enqueued = sum(sum(t["dispatches"].values()) for t in ticks)
    assert len(records) <= enqueued


def test_annotation_stats_are_built_only_inside_a_trace(run, registry,
                                                        profiler, monkeypatch):
    """The tick profiler on and no ``jax.profiler`` trace being taken: a
    mark carries no stats (nothing would record them), and the ring still
    holds the dispatch records.  Which kernels a dispatch takes was read
    once, when the engine was built."""
    metas = []
    mark = profiling._Tick.mark

    def spy(self, phase, **meta):
        metas.append((phase, meta))
        return mark(self, phase, **meta)

    monkeypatch.setattr(profiling._Tick, "mark", spy)
    profiler.enable()

    async def body():
        engine = tiny_engine()
        assert engine._latent_path is None and engine._decode_backend == "xla"
        try:
            await collect(engine, req([1, 2, 3, 4], max_tokens=12))
        finally:
            await engine.stop()

    run(body())
    assert {"dispatch", "device_wait"} <= {phase for phase, _m in metas}
    assert all(not meta for _phase, meta in metas)
    assert any(r.dispatch_records for r in profiler.records())
