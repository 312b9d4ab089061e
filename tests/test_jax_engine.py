"""End-to-end JaxEngine tests: continuous batching, stop conditions,
cancellation, page accounting -- all on a tiny random model (CPU)."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
from dynamo_tpu.engine.kv_cache import PageAllocator, OutOfPages
from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, SeqState
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Annotated, Context


def make_engine(**cfg_kw) -> JaxEngine:
    defaults = dict(max_batch_size=4, max_seq_len=64, page_size=4, num_pages=64)
    defaults.update(cfg_kw)
    return JaxEngine.random_init(ModelConfig.tiny(), EngineConfig(**defaults))


def req(tokens, max_tokens=8, **kw) -> PreprocessedRequest:
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, **kw),
        sampling_options=SamplingOptions(temperature=0.0),
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


def test_single_request_greedy_deterministic(run):
    async def body():
        engine = make_engine()
        try:
            t1, f1 = await collect(engine, req([1, 2, 3, 4, 5], max_tokens=6))
            t2, f2 = await collect(engine, req([1, 2, 3, 4, 5], max_tokens=6))
            assert t1 == t2
            assert len(t1) == 6
            assert f1 == "length" and f2 == "length"
        finally:
            await engine.stop()

    run(body())


def test_concurrent_requests_match_solo(run):
    """Requests decoded in one batch must produce the same tokens as each
    decoded alone (lane isolation at the engine level)."""

    async def body():
        prompts = [[1, 2, 3], [9, 8, 7, 6], [5, 5, 5, 5, 5], [2, 4]]
        engine = make_engine()
        try:
            solo = [await collect(engine, req(p, max_tokens=5)) for p in prompts]
            results = await asyncio.gather(
                *[collect(engine, req(p, max_tokens=5)) for p in prompts]
            )
            assert [r[0] for r in results] == [s[0] for s in solo]
        finally:
            await engine.stop()

    run(body())


def test_more_requests_than_slots(run):
    async def body():
        engine = make_engine(max_batch_size=2)
        try:
            prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
            results = await asyncio.gather(
                *[collect(engine, req(p, max_tokens=4)) for p in prompts]
            )
            for tokens, finish in results:
                assert len(tokens) == 4
                assert finish == "length"
        finally:
            await engine.stop()

    run(body())


def test_eos_stops_generation(run):
    async def body():
        engine = make_engine()
        try:
            # discover the first greedy token, then declare it an eos token
            toks, _ = await collect(engine, req([1, 2, 3], max_tokens=3))
            r = req([1, 2, 3], max_tokens=10)
            r.eos_token_ids = [toks[0]]
            tokens, finish = await collect(engine, r)
            assert tokens == []
            assert finish == "eos"
            # ignore_eos overrides
            r2 = req([1, 2, 3], max_tokens=4, ignore_eos=True)
            r2.eos_token_ids = [toks[0]]
            tokens2, finish2 = await collect(engine, r2)
            assert len(tokens2) == 4
        finally:
            await engine.stop()

    run(body())


def test_cancellation_frees_pages(run):
    async def body():
        engine = make_engine()
        try:
            stream = await engine.generate(
                Context.new(req([1, 2, 3, 4], max_tokens=1000))
            )
            got = []
            async for item in stream:
                got.append(item)
                if len(got) == 2:
                    stream.ctx.stop_generating()
            assert len(got) >= 2
            # let the loop process the cancellation; a multistep block or a
            # mid-flight bucket compile can hold the tick for a while, so
            # poll generously and break the moment the pages come back
            for _ in range(500):
                await asyncio.sleep(0.01)
                if engine.kv.allocator.used_pages == 0:
                    break
            assert engine.kv.allocator.used_pages == 0
            assert engine.sched.num_active == 0
        finally:
            await engine.stop()

    run(body())


def test_pages_freed_after_completion(run):
    async def body():
        engine = make_engine()
        try:
            await collect(engine, req([1, 2, 3, 4, 5, 6, 7], max_tokens=9))
            assert engine.kv.allocator.used_pages == 0
            m = engine.metrics()
            assert m.kv_active_blocks == 0
            assert m.request_active_slots == 0
            assert m.request_total_slots == 4
        finally:
            await engine.stop()

    run(body())


def test_sampled_generation_runs(run):
    async def body():
        engine = make_engine()
        try:
            r = req([1, 2, 3], max_tokens=5)
            r.sampling_options = SamplingOptions(temperature=0.8, top_p=0.9, top_k=40)
            tokens, finish = await collect(engine, r)
            assert len(tokens) == 5
        finally:
            await engine.stop()

    run(body())


# -- scheduler unit tests ----------------------------------------------------


def test_page_allocator():
    a = PageAllocator(8)
    assert a.free_pages == 7
    p = a.alloc(3)
    assert len(p) == 3 and 0 not in p
    assert a.alloc(0) == []
    assert a.free_pages == 4
    with pytest.raises(OutOfPages):
        a.alloc(5)
    a.free(p)
    assert a.free_pages == 7


def test_scheduler_preemption_restarts_youngest():
    alloc = PageAllocator(8)  # 7 usable pages
    sched = Scheduler(
        SchedulerConfig(max_batch_size=2, max_seq_len=32, page_size=4), alloc
    )
    old = SeqState.from_request("old", req([1] * 8, max_tokens=100), 4)
    young = SeqState.from_request("young", req([2] * 8, max_tokens=100), 4)
    sched.enqueue(old)
    plan = sched.plan()
    assert [s.request_id for s, _ in plan.prefills] == ["old"]
    sched.enqueue(young)
    young.arrival_s = old.arrival_s + 1
    plan = sched.plan()
    assert [s.request_id for s, _ in plan.prefills] == ["young"]
    # old: 2 pages, young: 2 pages, 3 free. grow both to page boundaries
    for seq in (old, young):
        for t in range(4):
            sched.commit_prefill_token(seq, 7) if t == 0 else sched._commit_token(seq, 7)
    # both now need a new page on next decode; plenty free
    sched.ensure_decode_capacity()
    assert len(old.pages) == 3 and len(young.pages) == 3
    # exhaust the pool: 1 free page left; grow till preemption
    while True:
        for seq in (old, young):
            if seq.slot >= 0:
                for _ in range(4):
                    sched._commit_token(seq, 7)
        preempted = sched.ensure_decode_capacity()
        if preempted:
            assert preempted[0].request_id == "young"
            break
    assert old.slot >= 0
    assert sched.waiting and sched.waiting[0].request_id == "young"
    # preempted sequence keeps its generated tokens in the re-prefill prompt
    assert len(sched.waiting[0].prompt) > 8


def test_stop_token_ids_hidden():
    alloc = PageAllocator(16)
    sched = Scheduler(
        SchedulerConfig(max_batch_size=1, max_seq_len=32, page_size=4), alloc
    )
    seq = SeqState.from_request(
        "x", req([1, 2, 3], max_tokens=10, stop_token_ids_hidden=[42]), 4
    )
    sched.enqueue(seq)
    sched.plan()
    ev = sched.commit_prefill_token(seq, 42)
    assert ev.token is None
    assert ev.finished == FinishReason.STOP


def test_min_tokens_suppresses_eos():
    alloc = PageAllocator(16)
    sched = Scheduler(
        SchedulerConfig(max_batch_size=1, max_seq_len=32, page_size=4), alloc
    )
    r = req([1, 2, 3], max_tokens=10, min_tokens=3)
    r.eos_token_ids = [42]
    seq = SeqState.from_request("x", r, 4)
    sched.enqueue(seq)
    sched.plan()
    ev = sched.commit_prefill_token(seq, 42)
    assert ev.token == 42 and ev.finished is None  # eos suppressed below min
    ev = sched._commit_token(seq, 42)
    assert ev.token == 42 and ev.finished is None
    ev = sched._commit_token(seq, 42)
    assert ev.token is None and ev.finished == FinishReason.EOS


def test_oversized_prompt_errors_cleanly(run):
    async def body():
        engine = make_engine(max_seq_len=16)
        try:
            stream = await engine.generate(Context.new(req([1] * 40)))
            items = [item async for item in stream]
            assert any(
                (i if isinstance(i, Annotated) else Annotated.from_dict(i)).is_error()
                for i in items
            )
            assert engine._queues == {}
        finally:
            await engine.stop()

    run(body())


def test_unadmittable_prompt_fails_not_spins(run):
    """A prompt within max_seq_len but larger than the page pool must get an
    error, not hang the engine loop."""

    async def body():
        engine = make_engine(max_seq_len=60, num_pages=4)  # 3 usable pages=12 toks
        try:
            stream = await engine.generate(Context.new(req([1] * 40, max_tokens=4)))
            items = [item async for item in stream]
            anns = [
                i if isinstance(i, Annotated) else Annotated.from_dict(i)
                for i in items
            ]
            assert any(a.is_error() for a in anns)
            # engine still serves admittable requests afterwards
            tokens, finish = await collect(engine, req([1, 2, 3], max_tokens=2))
            assert len(tokens) == 2
        finally:
            await engine.stop()

    run(body())


def test_greedy_invariant_to_decode_block_size(run):
    """Pipelined decode must not corrupt output when the layout changes
    mid-stream (page growth, admission, slot release): the same greedy
    request must yield identical tokens for any decode_block_size, with
    max_tokens spanning many blocks and page-growth events."""

    async def body():
        results = {}
        for K in (4, 64):
            engine = make_engine(decode_block_size=K, grow_chunk_pages=1)
            try:
                results[K] = await collect(engine, req([1, 2, 3], max_tokens=40))
            finally:
                await engine.stop()
        assert results[4][0] == results[64][0]
        assert len(results[4][0]) == 40

    run(body())


def test_greedy_invariant_under_concurrent_admission(run):
    """Admission mid-decode forces device-state rebuilds; earlier requests'
    outputs must be unaffected by later arrivals."""

    async def body():
        engine = make_engine(decode_block_size=4)
        try:
            solo, _ = await collect(engine, req([5, 6, 7], max_tokens=24))

            async def staggered():
                first = asyncio.create_task(
                    collect(engine, req([5, 6, 7], max_tokens=24))
                )
                await asyncio.sleep(0.05)  # let the first enter decode
                second = asyncio.create_task(
                    collect(engine, req([9, 9], max_tokens=24))
                )
                return await first, await second

            (t1, _), _ = await staggered()
            assert t1 == solo
        finally:
            await engine.stop()

    run(body())


def test_top_p_only_is_not_greedy(run):
    """temperature unset + top_p set must sample (temp 1.0), not argmax."""

    async def body():
        engine = make_engine()
        try:
            greedy, _ = await collect(engine, req([1, 2, 3], max_tokens=12))
            r = req([1, 2, 3], max_tokens=12)
            r.sampling_options = SamplingOptions(top_p=0.95)
            runs = [await collect(engine, r) for _ in range(4)]
            # at least one sampled run differs from greedy
            assert any(t != greedy for t, _ in runs)
        finally:
            await engine.stop()

    run(body())


def test_preemption_respects_max_tokens_total():
    """Stop accounting must span preemptions: tokens streamed before a
    preemption count against max_tokens after the restart."""
    alloc = PageAllocator(16)
    sched = Scheduler(
        SchedulerConfig(max_batch_size=1, max_seq_len=64, page_size=4), alloc
    )
    seq = SeqState.from_request("x", req([1, 2, 3], max_tokens=6), 4)
    sched.enqueue(seq)
    sched.plan()
    sched.commit_prefill_token(seq, 7)
    for _ in range(2):
        sched._commit_token(seq, 7)
    assert seq.num_generated == 3
    sched._preempt(seq)
    assert seq.prior_generated == 3 and seq.num_generated == 0
    assert len(seq.prompt) == 6  # generated folded in
    sched.plan()
    ev = sched.commit_prefill_token(seq, 7)
    assert ev.finished is None
    ev = sched._commit_token(seq, 7)
    assert ev.finished is None
    ev = sched._commit_token(seq, 7)
    assert ev.finished == FinishReason.LENGTH  # 3 + 3 == max_tokens


def test_device_state_never_aliases_scheduler_mirrors(run):
    """The device-side decode state must be a COPY of the host mirrors: on
    CPU, jnp.asarray aliases numpy buffers zero-copy, and the scheduler
    mutates its mirrors in place -- an async in-flight decode block reading
    a mutated page table scatters stale writes into pages that now belong
    to another sequence (corrupting reused prefix pages).  Regression test
    for that aliasing."""

    async def body():
        engine = make_engine()
        try:
            sched = engine.sched
            sched.tokens[0] = 11
            sched.seq_lens[0] = 3
            sched.page_table[0, 0] = 7
            engine._push_device_state()
            # in-place mirror mutation (what plan()/commit do on later ticks)
            sched.tokens[0] = 99
            sched.seq_lens[0] = 9
            sched.page_table[0, 0] = 42
            assert int(engine._dev["tokens"][0]) == 11
            assert int(engine._dev["seq_lens"][0]) == 3
            assert int(engine._dev["page_table"][0, 0]) == 7
        finally:
            await engine.stop()

    run(body())


def test_churn_determinism_no_drain_pipeline(run):
    """Adversarial churn over the no-drain dirty-row pipeline: staggered
    admissions, mid-stream cancellation, slot reuse, prefix hits, and page
    pressure (preemption + eviction) must never corrupt another request's
    stream -- every surviving request reproduces its solo greedy output."""

    async def body():
        import random as _r

        rng = _r.Random(7)
        prompts = [
            [rng.randint(1, 250) for _ in range(rng.choice([3, 5, 9, 13]))]
            for _ in range(10)
        ]
        shared = [7, 7, 7, 7, 8, 8, 8, 8]  # common prefix for reuse traffic
        prompts += [shared + [i] for i in range(4)]

        # solo baselines on a roomy engine
        solo = {}
        eng = make_engine(max_batch_size=1, num_pages=128, max_seq_len=64)
        try:
            for i, p in enumerate(prompts):
                solo[i], _ = await collect(eng, req(p, max_tokens=6))
        finally:
            await eng.stop()

        # churny engine: tiny batch, tight pool, offload on
        engine = make_engine(
            max_batch_size=3, num_pages=24, max_seq_len=64,
            host_offload_blocks=64,
        )
        try:
            async def one(i, delay):
                await asyncio.sleep(delay)
                ctx = Context.new(req(prompts[i], max_tokens=6))
                stream = await engine.generate(ctx)
                if i % 5 == 1:
                    # cancel some mid-stream
                    got = []
                    async for item in stream:
                        got.extend((item.data or {}).get("token_ids") or [])
                        if len(got) >= 2:
                            ctx.ctx.stop_generating()
                            break
                    return i, None
                toks = []
                async for item in stream:
                    assert not item.is_error(), item.error_message()
                    toks.extend((item.data or {}).get("token_ids") or [])
                return i, toks

            results = await asyncio.gather(
                *(one(i, (i % 7) * 0.015) for i in range(len(prompts)))
            )
            for i, toks in results:
                if toks is None:
                    continue
                assert toks == solo[i], (
                    f"request {i} diverged under churn: {toks} != {solo[i]}"
                )
            # run the shared-prefix pack again: reuse path must also agree
            for i in range(len(prompts) - 4, len(prompts)):
                toks, _ = await collect(engine, req(prompts[i], max_tokens=6))
                assert toks == solo[i]
        finally:
            await engine.stop()

    run(body())


def test_chunked_prefill_matches_unchunked(run):
    """Chunked prefill (any chunk size) must reproduce the single-dispatch
    greedy output exactly -- the chunks restart the suffix machinery at
    page-aligned offsets over the same pages."""

    async def body():
        prompt = [((i * 7) % 200) + 1 for i in range(30)]
        ref_engine = make_engine(num_pages=64, max_seq_len=64)
        try:
            expect, fin = await collect(ref_engine, req(prompt, max_tokens=6))
        finally:
            await ref_engine.stop()

        for chunk in (4, 8, 12, 13):  # incl. a non-page-aligned size
            engine = make_engine(
                num_pages=64, max_seq_len=64, prefill_chunk_tokens=chunk
            )
            try:
                toks, f = await collect(engine, req(prompt, max_tokens=6))
                assert toks == expect, f"chunk={chunk}: {toks} != {expect}"
                assert f == fin
            finally:
                await engine.stop()

    run(body())


def test_chunked_prefill_interleaves_with_decode(run):
    """While a long prompt chunk-prefills, an already-running request keeps
    decoding: the short request must finish before the chunked one emits
    its first token."""

    async def body():
        engine = make_engine(
            num_pages=64, max_seq_len=64, prefill_chunk_tokens=4,
            decode_block_size=2,
        )
        try:
            order = []
            decoding = asyncio.Event()

            async def short():
                stream = await engine.generate(
                    Context.new(req([5, 6, 7], max_tokens=8)))
                toks = []
                async for item in stream:
                    toks.extend((item.data or {}).get("token_ids") or [])
                    if len(toks) >= 2:
                        decoding.set()
                order.append("short-done")
                return toks

            async def long_prompt():
                ctx = Context.new(req(list(range(1, 29)), max_tokens=2))
                stream = await engine.generate(ctx)
                first = True
                toks = []
                async for item in stream:
                    got = (item.data or {}).get("token_ids") or []
                    if got and first:
                        order.append("long-first-token")
                        first = False
                    toks.extend(got)
                return toks

            t_short = asyncio.ensure_future(short())
            # short admitted and decoding, told by its tokens: a sleep says
            # so only where the first step's compile is already cached, and
            # since a tick fuses no more decode steps than hide its own
            # work the short request has no other head start (six tokens
            # to go against seven chunks of the long prompt)
            await decoding.wait()
            t_long = asyncio.ensure_future(long_prompt())
            await asyncio.gather(t_short, t_long)
            assert order.index("short-done") < order.index("long-first-token")
        finally:
            await engine.stop()

    run(body())


def test_chunked_prefill_cancel_mid_chunking_frees_pages(run):
    async def body():
        engine = make_engine(
            num_pages=64, max_seq_len=64, prefill_chunk_tokens=4
        )
        try:
            ctx = Context.new(req(list(range(1, 25)), max_tokens=4))
            stream = await engine.generate(ctx)
            await asyncio.sleep(0.02)  # a chunk or two dispatched
            ctx.ctx.stop_generating()
            async for _ in stream:
                pass
            # the release happens on the tick after the cancel drains; on
            # a loaded single-core box (mid-compile) that tick can take
            # well over a fixed 50ms -- poll instead of guessing
            for _ in range(100):
                if engine.sched.num_active == 0:
                    break
                await asyncio.sleep(0.05)
            assert engine.sched.num_active == 0
        finally:
            await engine.stop()

    run(body())


def test_chunked_prefill_chunk_smaller_than_page(run):
    """A prefill_chunk_tokens below page_size must normalize up to a page,
    not crash the tick loop on an overrunning intermediate chunk."""

    async def body():
        prompt = list(range(1, 23))
        ref = make_engine(num_pages=64, max_seq_len=64)
        try:
            expect, _ = await collect(ref, req(prompt, max_tokens=4))
        finally:
            await ref.stop()
        engine = make_engine(
            num_pages=64, max_seq_len=64, prefill_chunk_tokens=3  # < page 4
        )
        try:
            toks, _ = await collect(engine, req(prompt, max_tokens=4))
            assert toks == expect
        finally:
            await engine.stop()

    run(body())


def test_capacity_frozen_write_lands_on_trash_page():
    """A lane frozen at its page capacity keeps executing (SPMD cannot skip);
    its repeated KV write at page_idx == table width must route to trash
    page 0 -- clamping would scribble over the lane's own last live page
    every step (corrupting KV later reused via regrowth or prefix cache)."""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import attention as att

    L, N, page, Hkv, D = 2, 6, 4, 2, 8
    kv = jnp.zeros((L, 2, N, page, Hkv, D), jnp.float32)
    # lane owns pages [3, 5]; it is full: position == 2 pages * 4 slots
    pt = jnp.asarray([[3, 5]], jnp.int32)
    pos_frozen = jnp.asarray([8], jnp.int32)  # == P * page (out of range)
    k = jnp.ones((1, Hkv, D), jnp.float32)
    out = att.write_decode_kv(kv, k, k * 2.0, pt, pos_frozen, jnp.int32(0))
    # pages 3 and 5 untouched; the write landed on trash page 0
    assert float(jnp.max(jnp.abs(out[0, :, 3]))) == 0.0
    assert float(jnp.max(jnp.abs(out[0, :, 5]))) == 0.0
    assert float(jnp.max(jnp.abs(out[0, 0, 0]))) == 1.0
    # in-range write still lands where it should (page 5, slot 1)
    pos_live = jnp.asarray([5], jnp.int32)
    out2 = att.write_decode_kv(kv, k, k * 2.0, pt, pos_live, jnp.int32(0))
    assert float(jnp.max(jnp.abs(out2[0, 0, 5, 1] - 1.0))) == 0.0
    assert float(jnp.max(jnp.abs(out2[0, 1, 5, 1] - 2.0))) == 0.0


def test_engine_embed_pooled_vectors(run):
    """JaxEngine.embed: unit-norm mean-pooled vectors, deterministic,
    pad-invariant (solo == batched), length-sensitive, bounds-checked."""

    async def main():
        engine = make_engine()
        try:
            a = [5, 6, 7, 8]
            b = [9, 10, 11]
            batch = await engine.embed([a, b, a])
            solo = await engine.embed([a])
            over = None
            try:
                await engine.embed([[1] * 100])
            except ValueError as e:
                over = str(e)
            empty = None
            try:
                await engine.embed([[]])
            except ValueError as e:
                empty = str(e)
            return batch, solo, over, empty
        finally:
            await engine.stop()

    batch, solo, over, empty = run(main())
    H = ModelConfig.tiny().hidden_size
    assert len(batch) == 3 and all(len(v) == H for v in batch)
    for v in batch:
        assert abs(sum(x * x for x in v) - 1.0) < 1e-4
    assert batch[0] == batch[2]  # same input -> same vector
    assert batch[0] != batch[1]
    # bucketing/padding must not leak across lanes
    assert np.allclose(batch[0], solo[0], atol=1e-5)
    assert over and "exceeds" in over
    assert empty and "non-empty" in empty


def test_engine_embed_interleaves_with_generate(run):
    """Embedding calls share the executor with the decode loop without
    corrupting in-flight generation (the trunk read never writes KV)."""

    async def main():
        engine = make_engine()
        try:
            ref, _ = await collect(engine, req([3, 4, 5], max_tokens=12))
            gen_task = asyncio.create_task(
                collect(engine, req([3, 4, 5], max_tokens=12))
            )
            vecs = await engine.embed([[7, 8, 9, 10, 11]])
            tokens, finish = await gen_task
            return ref, tokens, vecs
        finally:
            await engine.stop()

    ref, tokens, vecs = run(main())
    assert tokens == ref  # generation unaffected by the concurrent embed
    assert len(vecs) == 1


# -- logprobs ----------------------------------------------------------------


def test_logprobs_emitted_when_requested(run):
    """A request with sampling_options.logprobs gets per-token logprobs (and
    top-N alternatives) aligned with its tokens; a plain request gets none.
    Greedy decoding makes the chosen token the top-1 alternative, pinning
    the device's log-softmax against its own top-k (reference protocol:
    openai/completions/aggregator.rs:43)."""

    async def main():
        engine = make_engine()
        r = PreprocessedRequest(
            token_ids=[1, 2, 3, 4],
            stop_conditions=StopConditions(max_tokens=6),
            sampling_options=SamplingOptions(temperature=0.0, logprobs=2),
        )
        stream = await engine.generate(Context.new(r))
        toks, lps, tops = [], [], []
        async for item in stream:
            d = item.data or {}
            toks.extend(d.get("token_ids") or [])
            lps.extend(d.get("logprobs") or [])
            tops.extend(d.get("top_logprobs") or [])
        # plain request on the same engine: no logprob keys in its stream
        stream2 = await engine.generate(Context.new(req([5, 6, 7])))
        saw_lp = False
        async for item in stream2:
            d = item.data or {}
            if d.get("logprobs") is not None:
                saw_lp = True
        await engine.stop()
        return toks, lps, tops, saw_lp

    toks, lps, tops, saw_lp = run(main())
    assert len(toks) == 6
    assert len(lps) == 6 and len(tops) == 6
    assert not saw_lp
    import math

    for t, lp, top in zip(toks, lps, tops):
        assert math.isfinite(lp) and lp <= 0.0
        assert len(top) == 2  # clamped to the requested width
        # greedy: the chosen token IS the argmax -> top-1 matches exactly
        assert top[0][0] == t
        assert abs(top[0][1] - lp) < 1e-5
        assert top[0][1] >= top[1][1]


def test_logprobs_chosen_only(run):
    """logprobs=0: chosen-token logprobs flow, no alternatives."""

    async def main():
        engine = make_engine()
        r = PreprocessedRequest(
            token_ids=[1, 2, 3],
            stop_conditions=StopConditions(max_tokens=4),
            sampling_options=SamplingOptions(temperature=0.0, logprobs=0),
        )
        stream = await engine.generate(Context.new(r))
        lps, tops = [], None
        async for item in stream:
            d = item.data or {}
            lps.extend(d.get("logprobs") or [])
            if d.get("top_logprobs") is not None:
                tops = d["top_logprobs"]
        await engine.stop()
        return lps, tops

    lps, tops = run(main())
    assert len(lps) == 4 and all(lp <= 0.0 for lp in lps)
    assert tops is None


def test_per_request_seed_deterministic(run):
    """A seeded sampling request reproduces its output exactly -- across
    runs AND regardless of batchmates -- and different seeds diverge
    (seed was previously parsed but silently ignored)."""

    async def main():
        engine = make_engine()

        async def one(seed, prompt=(1, 2, 3, 4)):
            r = PreprocessedRequest(
                token_ids=list(prompt),
                stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
                sampling_options=SamplingOptions(temperature=1.0, seed=seed),
            )
            stream = await engine.generate(Context.new(r))
            toks = []
            async for item in stream:
                toks.extend((item.data or {}).get("token_ids") or [])
            return toks

        solo = await one(1234)
        again = await one(1234)
        other = await one(99)
        # same seed with a concurrent batchmate occupying another lane
        import asyncio as _a

        batched, _ = await _a.gather(one(1234), one(7, prompt=(9, 8, 7)))
        await engine.stop()
        return solo, again, other, batched

    solo, again, other, batched = run(main())
    assert len(solo) == 8
    assert solo == again
    assert solo == batched  # lane placement / batchmates don't matter
    assert solo != other


def test_frequency_penalty_suppresses_repeats(run):
    """A strong frequency penalty must change what a lane samples relative
    to the unpenalized same-seed run, penalizing repeated tokens -- and a
    penalized lane must not perturb an unpenalized batchmate."""

    async def main():
        engine = make_engine()

        async def one(freq, seed=5, prompt=(1, 2, 3)):
            r = PreprocessedRequest(
                token_ids=list(prompt),
                stop_conditions=StopConditions(max_tokens=12, ignore_eos=True),
                sampling_options=SamplingOptions(
                    temperature=0.0, seed=seed, frequency_penalty=freq,
                ),
            )
            stream = await engine.generate(Context.new(r))
            toks = []
            async for item in stream:
                toks.extend((item.data or {}).get("token_ids") or [])
            return toks

        base = await one(0.0)
        pen = await one(8.0)  # huge: every repeat is crushed
        import asyncio as _a

        mate, _ = await _a.gather(one(0.0), one(8.0, seed=6, prompt=(7, 8)))
        await engine.stop()
        return base, pen, mate

    base, pen, mate = run(main())
    assert len(base) == 12 and len(pen) == 12
    # greedy on a tiny random model repeats itself; a crushing frequency
    # penalty must force distinct tokens
    assert len(set(pen)) > len(set(base))
    assert len(set(pen)) >= 10
    assert mate == base  # penalized batchmate never perturbs this lane


def test_penalty_history_survives_preemption(run):
    """Recompute preemption folds generated tokens into the prompt; the
    penalty histogram rebuild must still count them as OUTPUT (vLLM keeps
    output_token_ids across preemption)."""

    async def main():
        engine = make_engine()
        r = PreprocessedRequest(
            token_ids=[1, 2, 3],
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(
                temperature=1.0, seed=3, frequency_penalty=1.0
            ),
        )
        stream = await engine.generate(Context.new(r))
        toks = []
        async for item in stream:
            toks.extend((item.data or {}).get("token_ids") or [])
        seq = None
        # find the finished seq is gone; emulate the fold on a fresh seq
        from dynamo_tpu.engine.scheduler import SeqState

        s2 = SeqState.from_request(
            "x",
            PreprocessedRequest(
                token_ids=[1, 2, 3],
                stop_conditions=StopConditions(max_tokens=6),
                sampling_options=SamplingOptions(frequency_penalty=1.0),
            ),
            engine.sched.block_size,
        )
        # simulate one preemption fold: 2 generated tokens absorbed
        s2.prompt = s2.prompt + [41, 42]
        s2.prior_generated = 2
        hist = engine._output_tokens(s2)
        await engine.stop()
        return toks, hist

    toks, hist = run(main())
    assert len(toks) == 6
    assert hist[:2] == [41, 42]  # folded output reconstructed as output


def test_mixed_sampling_features_isolate(run):
    """A batch mixing greedy, seeded sampling, top-k/top-p, logprobs, and
    penalties: every request completes, and the greedy request's output is
    bit-identical to running it alone -- no cross-lane contamination from
    any feature's device state (filters flag, logprob packing, penalty
    histograms, seeded gumbel)."""

    async def main():
        engine = make_engine()

        async def greedy_alone():
            eng2 = make_engine()
            toks, _ = await collect(eng2, req([5, 6, 7, 8], max_tokens=10))
            await eng2.stop()
            return toks

        solo = await greedy_alone()

        async def one(opts, prompt, want_lp=False):
            r = PreprocessedRequest(
                token_ids=list(prompt),
                stop_conditions=StopConditions(max_tokens=10, ignore_eos=True),
                sampling_options=opts,
            )
            stream = await engine.generate(Context.new(r))
            toks, lps = [], []
            async for item in stream:
                d = item.data or {}
                assert not item.is_error(), item.error_message()
                toks.extend(d.get("token_ids") or [])
                lps.extend(d.get("logprobs") or [])
            if want_lp:
                assert len(lps) == len(toks)
            return toks

        import asyncio as _a

        results = await _a.gather(
            one(SamplingOptions(temperature=0.0), (5, 6, 7, 8)),
            one(SamplingOptions(temperature=1.0, seed=42), (1, 2)),
            one(SamplingOptions(temperature=0.9, top_k=5, top_p=0.9,
                                seed=7), (3, 4, 5)),
            one(SamplingOptions(temperature=0.0, logprobs=3), (9, 10),
                want_lp=True),
            one(SamplingOptions(temperature=1.0, seed=11,
                                frequency_penalty=1.5,
                                presence_penalty=0.5), (11, 12, 13)),
        )
        await engine.stop()
        return solo, results

    solo, results = run(main())
    assert all(len(t) == 10 for t in results)
    assert results[0] == solo  # greedy untouched by any batchmate feature


def test_apply_penalties_formula():
    """Unit math vs the OpenAI + HF formulas on a packed histogram:
    prompt-only tokens feel repetition but NOT frequency/presence
    (output-only semantics); generated tokens feel all three."""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.sampling import PROMPT_FLAG, apply_penalties

    logits = jnp.asarray([[2.0, -1.0, 0.5, 3.0]], jnp.float32)
    counts = jnp.asarray(
        [[PROMPT_FLAG, 2, 0, PROMPT_FLAG + 1]], jnp.int32
    )  # tok0: prompt-only; tok1: generated x2; tok2: unseen; tok3: both
    freq = jnp.asarray([0.5], jnp.float32)
    pres = jnp.asarray([0.25], jnp.float32)
    rep = jnp.asarray([2.0], jnp.float32)
    got = np.asarray(apply_penalties(logits, counts, freq, pres, rep))[0]
    # tok0: rep only (logit>0 -> /2), no freq/pres (out_count 0)
    assert abs(got[0] - 1.0) < 1e-6
    # tok1: rep (logit<0 -> *2), freq 2*0.5, pres 0.25
    assert abs(got[1] - (-2.0 - 1.0 - 0.25)) < 1e-6
    # tok2: untouched
    assert abs(got[2] - 0.5) < 1e-6
    # tok3: rep (/2), freq 1*0.5, pres 0.25
    assert abs(got[3] - (1.5 - 0.5 - 0.25)) < 1e-6


def test_repetition_penalty_changes_output_rep1_noop(run):
    """rep=1.0 is bit-identical to no penalty; a strong rep (prompt tokens
    included, HF semantics) changes greedy output."""

    async def main():
        engine = make_engine()

        async def one(rp):
            r = PreprocessedRequest(
                token_ids=[1, 2, 3, 4],
                stop_conditions=StopConditions(max_tokens=10, ignore_eos=True),
                sampling_options=SamplingOptions(
                    temperature=0.0, repetition_penalty=rp
                ),
            )
            stream = await engine.generate(Context.new(r))
            toks = []
            async for item in stream:
                toks.extend((item.data or {}).get("token_ids") or [])
            return toks

        base = await one(None)
        noop = await one(1.0)
        strong = await one(50.0)
        await engine.stop()
        return base, noop, strong

    base, noop, strong = run(main())
    assert len(base) == 10
    assert noop == base
    assert strong != base
    # a crushing rep forbids re-sampling anything seen -- including the
    # PROMPT tokens for the very first (prefill-sampled) token
    assert len(set(strong)) == 10
    assert strong[0] not in (1, 2, 3, 4)
