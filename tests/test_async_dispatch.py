"""Async dispatch pipelining (ISSUE 13): double-buffered tick loop.

Token identity is the contract: the pipelined loop (async commit,
off-tick fanout, depth-2 inflight generations) must produce byte-for-byte
the token streams of the serial ``--no-async-dispatch`` loop -- greedy
AND seeded -- across chunked prefill, preemption, speculation, and
cancellation.  The dispatch-gap win is proven on the mocker, whose
simulated device time makes the overlap measurable chip-free.
"""

import asyncio

import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
from dynamo_tpu.engine.bucketing import PackedShapeBudget, pow2_bucket
from dynamo_tpu.mocker import MockerConfig, MockerEngine
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    SpeculationOptions,
    StopConditions,
)
from dynamo_tpu.runtime import profiling
from dynamo_tpu.runtime.engine import Annotated, Context


def make_engine(**cfg_kw) -> JaxEngine:
    defaults = dict(max_batch_size=4, max_seq_len=64, page_size=4, num_pages=64)
    defaults.update(cfg_kw)
    return JaxEngine.random_init(ModelConfig.tiny(), EngineConfig(**defaults))


def req(tokens, max_tokens=8, temp=0.0, seed=None, spec=None, **kw):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, **kw),
        sampling_options=SamplingOptions(temperature=temp, seed=seed),
        speculation=spec,
    )


async def collect(engine, request, request_id=None):
    stream = await engine.generate(Context.new(request, request_id))
    tokens, finish = [], None
    async for item in stream:
        ann = item if isinstance(item, Annotated) else Annotated.from_dict(item)
        assert not ann.is_error(), ann.error_message()
        data = ann.data
        tokens.extend(data.get("token_ids") or [])
        if data.get("finish_reason"):
            finish = data["finish_reason"]
    return tokens, finish


async def _run_workload(reqs, **cfg_kw):
    engine = make_engine(**cfg_kw)
    try:
        outs = await asyncio.gather(
            *[collect(engine, r, f"r{i}") for i, r in enumerate(reqs)]
        )
        assert engine.kv.allocator.used_pages == 0, "leaked pages"
        return outs
    finally:
        await engine.stop()


def _mixed_workload():
    """Chunked prefill + greedy + seeded lanes in one concurrent batch."""
    reqs = []
    for i in range(6):
        reqs.append(
            req(
                list(range(1 + i, 18 + i)),
                max_tokens=8,
                temp=0.8 if i % 2 else 0.0,
                seed=7 + i if i % 2 else None,
            )
        )
    return reqs


def test_pipeline_depth_and_env_override(run, monkeypatch):
    async def body():
        e = make_engine()
        assert e._pipe_depth == 2
        await e.stop()
        e = make_engine(async_dispatch=False)
        assert e._pipe_depth == 1
        await e.stop()
        monkeypatch.setenv("DYN_ASYNC_DISPATCH", "0")
        e = make_engine()
        assert e._pipe_depth == 1  # env disarms a config-armed pipeline
        await e.stop()

    run(body())


def test_token_identity_chunked_prefill(run):
    """Greedy AND seeded streams are identical across the pipelined and
    serial loops, through chunked prefill and concurrent admission."""

    async def body():
        a = await _run_workload(
            _mixed_workload(), async_dispatch=True, prefill_chunk_tokens=8
        )
        b = await _run_workload(
            _mixed_workload(), async_dispatch=False, prefill_chunk_tokens=8
        )
        assert a == b

    run(body())


def test_token_identity_classic_path(run):
    """The classic (non-mixed) dispatch path pipelines identically."""

    async def body():
        kw = dict(mixed_batching=False, prefill_chunk_tokens=8)
        a = await _run_workload(_mixed_workload(), async_dispatch=True, **kw)
        b = await _run_workload(_mixed_workload(), async_dispatch=False, **kw)
        assert a == b

    run(body())


def test_token_identity_under_preemption(run):
    """A pool tight enough to force capacity preemption mid-decode: the
    recompute path's folded streams stay identical across loop modes."""

    async def body():
        reqs = [req(list(range(1 + i, 10 + i)), max_tokens=16) for i in range(4)]
        kw = dict(num_pages=16, max_batch_size=4)
        a = await _run_workload(reqs, async_dispatch=True, **kw)
        b = await _run_workload(reqs, async_dispatch=False, **kw)
        assert a == b
        assert all(len(t) == 16 for t, _f in a)

    run(body())


def test_token_identity_with_speculation(run):
    """Spec lanes (verify dispatches riding the pipeline generations)
    produce identical streams in both loop modes."""

    async def body():
        spec = SpeculationOptions(
            enabled=True, num_draft_tokens=4, drafter="ngram"
        )
        base = [5, 6, 7, 5, 6, 7, 5, 6]
        reqs = [
            req(base, max_tokens=12, spec=spec),
            req(list(range(3, 12)), max_tokens=8),
        ]
        a = await _run_workload(reqs, async_dispatch=True)
        b = await _run_workload(reqs, async_dispatch=False)
        assert a == b

    run(body())


def test_cancellation_between_enqueue_and_commit(run):
    """Cancel landing while a dispatch generation is still uncommitted:
    the stale generation's lanes are dropped at commit and no pages leak
    (the enqueue(N+1)/commit(N) race of the ISSUE)."""

    async def body():
        engine = make_engine(async_dispatch=True)
        try:
            stream = await engine.generate(
                Context.new(req(list(range(1, 9)), max_tokens=1000), "victim")
            )
            got = []
            async for item in stream:
                got.append(item)
                if len(got) >= 2:
                    # cancel mid-flight: the teardown lands between an
                    # enqueued generation and its commit
                    stream.ctx.stop_generating()
            assert len(got) >= 2
            await asyncio.sleep(0.2)
            # a fresh request still runs cleanly afterwards
            toks, fin = await collect(
                engine, req(list(range(2, 10)), max_tokens=4), "after"
            )
            assert len(toks) == 4 and fin == "length"
            for _ in range(100):
                if engine.kv.allocator.used_pages == 0:
                    break
                await asyncio.sleep(0.05)
            assert engine.kv.allocator.used_pages == 0, "cancel leaked pages"
        finally:
            await engine.stop()

    run(body())


def test_stop_between_enqueue_and_commit(run):
    """A device-side stop (hidden stop token / max_tokens) landing while a
    later generation is already enqueued: the replay discards the
    overshoot and frees every page."""

    async def body():
        engine = make_engine(async_dispatch=True)
        try:
            # run several short requests back to back so finishes repeatedly
            # land with a younger generation enqueued
            for i in range(4):
                toks, fin = await collect(
                    engine, req(list(range(1 + i, 8 + i)), max_tokens=2), f"s{i}"
                )
                assert len(toks) == 2 and fin == "length"
            assert engine.kv.allocator.used_pages == 0
        finally:
            await engine.stop()

    run(body())


def test_fanout_worker_drains_on_stop(run):
    """Events committed before stop() reach their streams (drain-on-stop)
    and the worker task is torn down."""

    async def body():
        engine = make_engine(async_dispatch=True)
        try:
            toks, fin = await collect(engine, req([1, 2, 3], max_tokens=3))
            assert len(toks) == 3
            assert engine._fanout_task is not None
        finally:
            await engine.stop()
        assert engine._fanout_task is None and engine._fanout_q is None

    run(body())


def test_mocker_dispatch_gap_halves(run):
    """The acceptance line: on the mocker serving smoke (simulated device
    time), the double-buffered lanes cut dispatch_gap_p50 by >= 2x vs the
    serial loop -- in steady state every commit lands with the next
    dispatch already queued, so the gap collapses to zero."""

    async def leg(async_on):
        prof = profiling.profiler
        eng = MockerEngine(
            MockerConfig(
                max_batch_size=8,
                decode_s_per_step=2e-5,
                async_dispatch=async_on,
            )
        )
        try:
            outs = await asyncio.gather(
                *[
                    collect(eng, req(list(range(1 + i, 30 + i)), max_tokens=16), f"m{i}")
                    for i in range(8)
                ]
            )
            prof.clear()
            prof.enable()
            outs2 = await asyncio.gather(
                *[
                    collect(eng, req(list(range(1 + i, 30 + i)), max_tokens=48), f"n{i}")
                    for i in range(8)
                ]
            )
            psum = prof.summary()
            prof.disable()
            prof.clear()
            return outs + outs2, psum
        finally:
            await eng.stop()

    async def body():
        was = profiling.profiler.enabled
        try:
            toks_serial, serial = await leg(False)
            toks_async, asynchro = await leg(True)
            # deterministic token function: streams identical across modes
            assert toks_serial == toks_async
            gs, ga = serial["gap_p50_ms"], asynchro["gap_p50_ms"]
            assert gs is not None and gs > 0, serial
            assert ga is not None, asynchro
            assert ga <= gs / 2, (
                f"async gap_p50 {ga}ms not <= serial {gs}ms / 2"
            )
        finally:
            if was:
                profiling.profiler.enable()

    run(body())


def test_mocker_zero_latency_mode_unchanged(run):
    """decode_s_per_step == 0 (unit-test mode) keeps the same-tick commit
    even with async_dispatch on: nothing to overlap, nothing deferred."""

    async def body():
        eng = MockerEngine(MockerConfig())
        try:
            toks, fin = await collect(eng, req([1, 2, 3, 4], max_tokens=5))
            assert len(toks) == 5 and fin == "length"
            assert eng._inflight_tick is None
        finally:
            await eng.stop()

    run(body())


# ---------------------------------------------------------------------------
# packed-shape compaction (satellite)
# ---------------------------------------------------------------------------


def test_packed_shape_budget_reuse_and_merge():
    b = PackedShapeBudget(budget=2)
    # two natural triples mint freely
    p1 = b.fit(4, 10, 14)  # Np = pow2(14) = 16
    assert p1 == (16, 4, 0)
    p2 = b.fit(8, 8, 16)  # Np = pow2(16) = 16
    assert p2 == (16, 8, 0)
    assert len(b) == 2
    # a third, smaller shape merges up into a dominating minted triple
    p3 = b.fit(2, 6, 8)  # natural would be (8, 2, 0); (16,4,0) dominates
    assert p3 in ((16, 4, 0), (16, 8, 0))
    assert len(b) == 2 and b.merges == 1
    # the kernel slice rule holds for the merged triple
    np_m, s_m, _sp = p3
    assert 6 + s_m <= np_m and 8 <= np_m


def test_packed_shape_budget_eviction_on_new_widest():
    b = PackedShapeBudget(budget=1)
    assert b.fit(2, 2, 4) == (4, 2, 0)
    # nothing minted dominates a wider window: evict LRU and mint
    got = b.fit(16, 0, 16)
    assert got == (16, 16, 0)
    assert b.evictions == 1 and len(b) == 1


def test_packed_shape_budget_spec_columns():
    """Folded-verify column widths (ISSUE 15) ride the same budget: a
    spec-carrying dispatch mints/merges triples with s_spec > 0, a
    spec-free dispatch never merges INTO one (it would pay the column
    sampler for nothing), and spec widths only pad UP."""
    b = PackedShapeBudget(budget=2)
    assert b.fit(8, 0, 8, s_spec=5) == (8, 8, 5)
    # spec-free request at the budget: must not merge into the spec triple
    assert b.fit(8, 0, 8, s_spec=0) == (8, 8, 0)
    assert b.merges == 0 and len(b) == 2
    # a narrower spec width merges up into the dominating spec triple
    got = b.fit(8, 0, 8, s_spec=3)
    assert got == (8, 8, 5) and b.merges == 1
    # spec shapes are observable for the gauge test
    assert b.spec_shapes == [(8, 8, 5)]


@pytest.mark.parametrize("item_rows", [0, 16], ids=["window", "free"])
def test_packed_shape_budget_invariant_random(item_rows):
    import random

    rng = random.Random(0)
    b = PackedShapeBudget(budget=4, item_rows=item_rows)
    for _ in range(200):
        s = pow2_bucket(rng.randint(1, 64))
        off = rng.randint(0, 256)
        total = off + rng.randint(1, s)
        # ~half the dispatches speculate: the verify pad rule's widths
        sp = rng.choice((0, 0, 2, 3, 5, 9))
        np_got, s_got, sp_got = b.fit(s, off, total, s_spec=sp)
        assert sp_got >= sp
        assert sp == 0 or sp_got > 0
        assert not (sp == 0 and sp_got > 0)
        assert total <= np_got
        if item_rows:
            assert min(s_got, item_rows) >= min(s, item_rows)
        else:
            assert s_got >= s and off + s_got <= np_got
    assert len(b) <= 4


# the packed executables the benchmark's configurations fix, and the query
# block of the pair pools' work-list kernel (ragged_attention._WL_Q_BLOCK)
MISTRAL_SHAPES = [(16, 1), (128, 64), (512, 256), (1024, 512)]
MELLUM_SHAPES = [(32, 1), (256, 128), (1024, 512), (2048, 1024)]
LATENT_SHAPES = [(16, 1), (256, 128), (1024, 512), (4096, 2048)]
WINDOW, FREE = 0, 256


def _minted(shapes, item_rows):
    """A budget as ``benchmark/server.py`` fills it before any traffic."""
    b = PackedShapeBudget(len(shapes), item_rows)
    for np_, s_max in shapes:
        b.fit(s_max, np_ - s_max, np_ - s_max + 1)
    return b


# (minted shapes, rule, the dispatch's (s_nat, off_last, total)) -> triple
SHAPE_RULE_CASES = {
    # a budget-filling chunk beside 15 decode rows: the rows it has
    "free_mistral_chunk": (MISTRAL_SHAPES, FREE, (512, 15, 511), (512, 256, 0)),
    "free_mistral_full_axis": (MISTRAL_SHAPES, FREE, (512, 15, 512), (512, 256, 0)),
    "free_mellum_chunk": (MELLUM_SHAPES, FREE, (1024, 31, 1023), (1024, 512, 0)),
    # a 96-row question beside four decode rows keeps a tile of its own
    # size: never 96 one-row items, nor two blocks that each read the keys
    "free_question": (MISTRAL_SHAPES, FREE, (128, 4, 100), (512, 256, 0)),
    "free_short_chunk": (MISTRAL_SHAPES, FREE, (64, 8, 48), (128, 64, 0)),
    "free_decode_only": (MISTRAL_SHAPES, FREE, (1, 15, 16), (16, 1, 0)),
    # past the budget only the widest shape is left
    "free_over_budget": (MISTRAL_SHAPES, FREE, (512, 15, 527), (1024, 512, 0)),
    # a launch that reads windows resolves all of them as it always did
    "window_mistral_chunk": (MISTRAL_SHAPES, WINDOW, (512, 15, 511), (1024, 512, 0)),
    "window_mellum_chunk": (MELLUM_SHAPES, WINDOW, (1024, 31, 1023), (2048, 1024, 0)),
    "window_latent_chunk": (LATENT_SHAPES, WINDOW, (2048, 15, 2047), (4096, 2048, 0)),
    "window_question": (MISTRAL_SHAPES, WINDOW, (128, 4, 100), (512, 256, 0)),
    "window_short_chunk": (MISTRAL_SHAPES, WINDOW, (64, 8, 48), (128, 64, 0)),
    "window_decode_only": (MISTRAL_SHAPES, WINDOW, (1, 15, 16), (16, 1, 0)),
}


@pytest.mark.parametrize("name", list(SHAPE_RULE_CASES))
def test_packed_shape_rule_follows_the_launch(name):
    """``PackedLaunch.item_rows`` decides the rule: a launch that keeps its
    tiles inside the axis takes the smallest minted shape that holds the
    dispatch's rows, one that reads a lane's window the shape padded for
    it.  Nothing is minted or evicted either way."""
    shapes, item_rows, dispatch, want = SHAPE_RULE_CASES[name]
    b = _minted(shapes, item_rows)
    assert b.fit(*dispatch) == want
    assert sorted(b.pairs) == [(n, s, 0) for n, s in shapes]
    assert not b.evictions


@pytest.mark.parametrize("item_rows", [WINDOW, FREE], ids=["window", "free"])
@pytest.mark.parametrize(
    "shapes", [MISTRAL_SHAPES, MELLUM_SHAPES, LATENT_SHAPES],
    ids=["mistral", "mellum2", "mistral4"])
def test_benchmark_mint_is_the_same_under_both_rules(shapes, item_rows):
    assert _minted(shapes, item_rows).pairs == [(n, s, 0) for n, s in shapes]


@pytest.mark.parametrize("item_rows", [WINDOW, FREE], ids=["window", "free"])
def test_spec_triples_never_take_spec_free_dispatches(item_rows):
    b = PackedShapeBudget(2, item_rows)
    assert b.fit(1, 15, 16) == (16, 1, 0)
    assert b.fit(256, 0, 300, s_spec=3) == (512, 256, 3)
    # at the budget: the spec-carrying triple holds the rows and must not
    # take them; a narrower spec width still pads up into it
    assert b.fit(256, 10, 250) == (256 if item_rows else 512, 256, 0)
    assert b.evictions == 1
    assert b.fit(128, 0, 300, s_spec=2) == (512, 256, 3)


def test_engine_executable_shape_gauge(run):
    """The packed dispatch updates the active-shape gauge and stays under
    the budget across varied arrival shapes."""

    async def body():
        engine = make_engine()
        try:
            for i, n in enumerate((3, 7, 12, 17, 25)):
                await collect(
                    engine, req(list(range(1, n + 1)), max_tokens=2), f"g{i}"
                )
            assert 1 <= len(engine._packed_shapes) <= engine._packed_shapes.budget
        finally:
            await engine.stop()

    run(body())
