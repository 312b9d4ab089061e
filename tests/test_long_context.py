"""Long-context fast path (ISSUE 10): KV-budget admission, fully-packed
ragged prefill, prefetch-overlapped onboarding.

The contracts under test:

* **bit-identity** -- the packed ragged layout produces token-identical
  streams to the classic separate-dispatch paths, for greedy AND seeded
  lanes, across chunked prefill, preemption, and spec-decode composition;
* **scheduling only** -- KV-budget admission and queue-side prefetch
  change WHICH TICK a request admits on, never its tokens;
* **starvation freedom both directions** -- a budget-blocked long head
  does not stall short traffic (skip-ahead), and short traffic cannot
  hold the head back forever (aging floor);
* **prefetch hygiene** -- staged chains pin the host ring until
  admission consumes them, and a cancel before admission frees the
  pins (the leak fix);
* the **CPU bench smoke**: the packed padded-token fraction is
  accounted, and warm-prefix long-prompt TTFT improves with prefetch
  on vs off.
"""

import asyncio
import time

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
from dynamo_tpu.engine.kv_cache import PageAllocator
from dynamo_tpu.engine.scheduler import (
    KVAdmitConfig,
    Scheduler,
    SchedulerConfig,
    SeqState,
    parse_kv_admit_spec,
)
from dynamo_tpu.block_manager import PagePool
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    SpeculationOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Annotated, Context


def make_engine(**cfg_kw) -> JaxEngine:
    defaults = dict(max_batch_size=4, max_seq_len=64, page_size=4, num_pages=64)
    defaults.update(cfg_kw)
    return JaxEngine.random_init(ModelConfig.tiny(), EngineConfig(**defaults))


def req(tokens, max_tokens=8, sampling=None, spec=None, **kw):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, **kw),
        sampling_options=sampling or SamplingOptions(temperature=0.0),
        speculation=spec,
    )


async def collect(engine, request):
    stream = await engine.generate(Context.new(request))
    tokens, finish = [], None
    async for item in stream:
        ann = item if isinstance(item, Annotated) else Annotated.from_dict(item)
        assert not ann.is_error(), ann.error_message()
        data = ann.data
        tokens.extend(data.get("token_ids") or [])
        if data.get("finish_reason"):
            finish = data["finish_reason"]
    return tokens, finish


async def run_batch(prompts, max_tokens=6, sampling=None, **cfg_kw):
    engine = make_engine(**cfg_kw)
    try:
        return await asyncio.gather(
            *[
                collect(engine, req(p, max_tokens=max_tokens, sampling=sampling))
                for p in prompts
            ]
        )
    finally:
        await engine.stop()


# -- packed-ragged kernel parity ---------------------------------------------


def _mk_packed_case(B, page, Pp, Hq, Hkv, D, bases, qlens, seed=0, L=2):
    """Packed-layout inputs + the equivalent rectangle, from one random
    draw, so the two layouts see identical per-token values."""
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    num_pages = 1 + B * Pp
    kv_pages = jnp.asarray(
        rs.randn(L, 2, num_pages, page, Hkv, D).astype(np.float32)
    )
    pt = np.zeros((B, Pp), np.int32)
    for b in range(B):
        used = -(-bases[b] // page) if bases[b] else 0
        pt[b, :used] = 1 + b * Pp + np.arange(used)
    qlens = np.asarray(qlens, np.int32)
    total = int(qlens.sum())
    s_max = 1
    while s_max < max(int(qlens.max()), 1):
        s_max *= 2
    seg_off = np.zeros((B,), np.int32)
    lane, rel = [], []
    off = 0
    max_end = 1
    for b in range(B):
        ql = int(qlens[b])
        if ql == 0:
            continue
        seg_off[b] = off
        lane += [b] * ql
        rel += list(range(ql))
        max_end = max(max_end, off + s_max)
        off += ql
    Np = 1
    while Np < max(total, max_end):
        Np *= 2
    lane = np.asarray(lane + [B] * (Np - len(lane)), np.int32)
    rel = np.asarray(rel + [0] * (Np - len(rel)), np.int32)
    qp = rs.randn(Np, Hq, D).astype(np.float32)
    kp = rs.randn(Np, Hkv, D).astype(np.float32)
    vp = rs.randn(Np, Hkv, D).astype(np.float32)
    S = s_max
    qr = np.zeros((B, S, Hq, D), np.float32)
    kr = np.zeros((B, S, Hkv, D), np.float32)
    vr = np.zeros((B, S, Hkv, D), np.float32)
    for n in range(total):
        qr[lane[n], rel[n]] = qp[n]
        kr[lane[n], rel[n]] = kp[n]
        vr[lane[n], rel[n]] = vp[n]
    return (
        jnp.asarray(qp), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(qr), jnp.asarray(kr), jnp.asarray(vr),
        kv_pages, jnp.asarray(pt),
        jnp.asarray(bases, np.int32), jnp.asarray(seg_off),
        jnp.asarray(qlens), jnp.asarray(lane), jnp.asarray(rel),
        s_max, total,
    )


@pytest.mark.parametrize(
    "B,page,Pp,Hq,Hkv,D,bases,qlens",
    [
        # decode rows + a long chunk + an idle lane
        (4, 8, 4, 4, 2, 16, [16, 0, 11, 24], [1, 8, 5, 0]),
        # one big prefill + one decode row (the rectangle-waste shape)
        (2, 8, 8, 8, 2, 32, [0, 40], [16, 1]),
        # windows past one query block: s_max 512 walks four 128-row
        # blocks, the 130-row lane two, the decode row one
        (3, 8, 8, 4, 2, 16, [24, 40, 0], [300, 1, 130]),
    ],
)
def test_packed_kernel_matches_rectangle(B, page, Pp, Hq, Hkv, D, bases, qlens):
    from dynamo_tpu.ops.ragged_attention import (
        packed_ragged_attention,
        packed_ragged_attention_xla,
        ragged_paged_attention_xla,
    )

    (qp, kp, vp, qr, kr, vr, kv_pages, pt, base, seg_off, qn, lane, rel,
     s_max, total) = _mk_packed_case(B, page, Pp, Hq, Hkv, D, bases, qlens)
    rect = np.asarray(
        ragged_paged_attention_xla(qr, kr, vr, kv_pages, pt, base, qn, 1)
    )
    packed_xla = np.asarray(
        packed_ragged_attention_xla(
            qp, kp, vp, kv_pages, pt, base, seg_off, qn, lane, rel, s_max, 1
        )
    )
    packed_plas = np.asarray(
        packed_ragged_attention(
            qp, kp, vp, kv_pages, pt, base, seg_off, qn, s_max, 1,
            group=2, interpret=True,
        )
    )
    lane_np, rel_np = np.asarray(lane), np.asarray(rel)
    for n in range(total):
        b, i = lane_np[n], rel_np[n]
        # XLA packed reference runs the EXACT rectangle math: bit-equal
        np.testing.assert_array_equal(packed_xla[n], rect[b, i])
        np.testing.assert_allclose(
            packed_plas[n], rect[b, i], rtol=2e-5, atol=2e-5
        )


def test_packed_kernel_per_tp_shard_matches_reference():
    """Under a tp mesh the dispatch runs the kernel per shard over the head
    axes (GSPMD cannot partition a Mosaic kernel): contiguous head shards
    keep each query head with its own kv head, so the sharded call equals
    the unsharded reference.  Interpret mode, two virtual devices."""
    import jax
    from jax.sharding import PartitionSpec as P

    from dynamo_tpu.engine import attention as att
    from dynamo_tpu.ops.ragged_attention import (
        packed_ragged_attention,
        packed_ragged_attention_xla,
    )
    from dynamo_tpu.parallel.mesh import serving_mesh

    (qp, kp, vp, _qr, _kr, _vr, kv_pages, pt, base, seg_off, qn, lane, rel,
     s_max, total) = _mk_packed_case(
        3, 8, 4, 8, 4, 16, [16, 0, 11], [1, 8, 5])
    ref = np.asarray(packed_ragged_attention_xla(
        qp, kp, vp, kv_pages, pt, base, seg_off, qn, lane, rel, s_max, 1))
    heads = P(None, "tp", None)
    with jax.set_mesh(serving_mesh(tp=2, devices=jax.devices()[:2])):
        got = jax.jit(lambda *ops: att._per_shard(
            lambda q, k, v, pool, pt, base, off, lens: packed_ragged_attention(
                q, k, v, pool, pt, base, off, lens, s_max, 1, group=2,
                interpret=True,
            ),
            ops,
            (heads, heads, heads, att._POOL_SPEC, P(), P(), P(), P()),
            heads,
        ))(qp, kp, vp, kv_pages, pt, base, seg_off, qn)
    np.testing.assert_allclose(
        np.asarray(got)[:total], ref[:total], rtol=2e-5, atol=2e-5
    )


# -- KV-budget admission (scheduler level) -----------------------------------


def test_kv_admit_spec_parsing():
    assert parse_kv_admit_spec(None) is None
    assert parse_kv_admit_spec("off") is None
    assert parse_kv_admit_spec("0") is None
    assert parse_kv_admit_spec(False) is None
    on = parse_kv_admit_spec("on")
    assert isinstance(on, KVAdmitConfig) and on.util == 0.9
    a = parse_kv_admit_spec("util=0.8,headroom=64,reserve=4,floor_s=1.5,skips=2")
    assert (a.util, a.headroom_tokens, a.reserve_pages, a.floor_s,
            a.max_skips) == (0.8, 64, 4, 1.5, 2)
    with pytest.raises(ValueError):
        parse_kv_admit_spec("util=0.8,bogus=1")
    with pytest.raises(ValueError):
        parse_kv_admit_spec("headroom")


def _seq(n_tokens, max_tokens=8, tag=""):
    return SeqState.from_request(
        f"r-{tag}-{n_tokens}-{np.random.randint(1 << 30)}",
        PreprocessedRequest(
            token_ids=list(range(1, n_tokens + 1)),
            stop_conditions=StopConditions(max_tokens=max_tokens),
            sampling_options=SamplingOptions(temperature=0.0),
            eos_token_ids=[0],
        ),
        16,
    )


def test_budget_admission_starvation_free_both_directions():
    """Skip-ahead keeps short traffic flowing past a budget-blocked long
    head; the aging floor then stops the skip-ahead so the head admits
    once pages free -- neither side starves."""
    pool = PagePool(64, pages_per_block=1)
    sched = Scheduler(
        SchedulerConfig(
            max_batch_size=4, max_seq_len=1024, page_size=16,
            kv_admit=KVAdmitConfig(util=0.9, floor_s=0.5, max_skips=2),
        ),
        pool,
    )
    small1, small2, small3 = _seq(32, 16), _seq(32, 16), _seq(32, 16)
    big = _seq(640, 256)  # predicted 56 pages: fits alone, not alongside
    sched.enqueue(small1)
    sched.plan()
    assert small1.slot >= 0
    sched.enqueue(big)
    sched.enqueue(small2)
    sched.plan()
    # direction 1: the long head is budget-blocked, shorts keep admitting
    assert big.slot < 0
    assert small2.slot >= 0
    assert sched.admit_skips >= 1 and sched.admit_blocked >= 1
    # direction 2: once the head ages past floor_s, nothing skips it
    big.arrival_s = time.monotonic() - 10.0
    sched.enqueue(small3)
    sched.plan()
    assert small3.slot < 0, "aged head must stop skip-ahead"
    for s in (small1, small2):
        sched._release_slot(s)
    sched.plan()
    assert big.slot >= 0, "head admits once pages free"
    assert small3.slot >= 0 or small3 in sched.waiting


def test_budget_admission_empty_batch_always_admits():
    """A request whose prediction exceeds the whole budget still runs
    when the batch is empty (the physical floor is the only gate)."""
    pool = PagePool(64, pages_per_block=1)
    sched = Scheduler(
        SchedulerConfig(
            max_batch_size=2, max_seq_len=2048, page_size=16,
            kv_admit=KVAdmitConfig(util=0.5),
        ),
        pool,
    )
    huge = _seq(512, 512)  # predicted 64 pages > 0.5 * 63
    sched.enqueue(huge)
    sched.plan()
    assert huge.slot >= 0


def test_budget_admission_token_identity(run):
    """Budget admission reorders admission ticks under pressure, never
    tokens: the same prompts produce the same streams with it on/off,
    greedy and seeded."""
    prompts = [[7] * 24, [1, 2, 3, 4, 5], list(range(1, 17)), [9, 8] * 6]
    samp = SamplingOptions(temperature=0.8, top_p=0.9, seed=11)

    async def body():
        kw = dict(num_pages=32, max_seq_len=64)  # tight: skips happen
        on = await run_batch(prompts, kv_admit_budget="on", **kw)
        off = await run_batch(prompts, kv_admit_budget=None, **kw)
        assert on == off
        s_on = await run_batch(prompts, sampling=samp, kv_admit_budget="on", **kw)
        s_off = await run_batch(prompts, sampling=samp, kv_admit_budget=None, **kw)
        assert s_on == s_off

    run(body())


# -- packed == classic bit-identity ------------------------------------------


def test_packed_matches_classic(run):
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [5] * 14, [2, 4]]

    async def body():
        packed = await run_batch(prompts)
        classic = await run_batch(prompts, mixed_batching=False)
        assert packed == classic
        assert all(len(t) == 6 for t, _ in packed)

    run(body())


def test_packed_chunked_prefill_identity(run):
    """Long prompts split across packed unified dispatches (chunk 8,
    budget 12) match the classic chunked path."""
    prompts = [list(range(1, 33)), [7] * 29, [3, 1, 4, 1, 5, 9, 2, 6] * 3]
    kw = dict(
        prefill_chunk_tokens=8, mixed_token_budget=12,
        max_seq_len=128, num_pages=128,
    )

    async def body():
        packed = await run_batch(prompts, **kw)
        classic = await run_batch(prompts, mixed_batching=False, **kw)
        assert packed == classic

    run(body())


def test_packed_seeded_sampling_identity(run):
    samp = SamplingOptions(temperature=0.9, top_p=0.95, seed=4242)
    prompts = [[1, 2, 3, 4, 5], [8, 6, 7, 5, 3, 0, 9]]

    async def body():
        packed = await run_batch(prompts, max_tokens=10, sampling=samp)
        classic = await run_batch(
            prompts, max_tokens=10, sampling=samp, mixed_batching=False
        )
        assert packed == classic

    run(body())


def test_packed_preemption_identity(run):
    """Capacity preemption under the packed layout reproduces the exact
    streams of the classic path in the same tight pool and of an
    uncontended pool."""
    prompts = [[11, 12, 13, 14], [5, 6, 7, 8], [9, 10, 11, 12]]

    async def one(num_pages, **kw):
        return await run_batch(
            prompts, max_tokens=12, num_pages=num_pages,
            max_seq_len=64, **kw,
        )

    async def body():
        tight_packed = await one(14)
        tight_classic = await one(14, mixed_batching=False)
        roomy = await one(64)
        assert tight_packed == tight_classic == roomy

    run(body())


def test_packed_spec_compose_identity(run):
    """Speculating lanes compose with packed unified dispatches (their
    verify columns folded into the launch) exactly as with the classic
    path's standalone verify."""
    pat = [3, 1, 4, 1, 5]
    prompts = [(pat * 5)[:20], [7, 7, 8, 8] * 3]
    spec = SpeculationOptions(enabled=True, num_draft_tokens=3)

    async def one(mixed):
        engine = make_engine(
            max_seq_len=128, num_pages=128, mixed_batching=mixed
        )
        try:
            return await asyncio.gather(
                *[
                    collect(
                        engine,
                        req(p, max_tokens=10, spec=spec, ignore_eos=True),
                    )
                    for p in prompts
                ]
            )
        finally:
            await engine.stop()

    async def body():
        assert await one(True) == await one(False)

    run(body())


def test_packed_padded_accounting(run):
    """A packed run accounts its padding: real rows <= rows dispatched,
    what the bench's padded fraction is computed from."""

    async def body():
        engine = make_engine(
            max_seq_len=128, num_pages=128, prefill_chunk_tokens=16,
            mixed_token_budget=24,
        )
        try:
            await asyncio.gather(
                *[
                    collect(engine, req(p, max_tokens=6))
                    for p in [list(range(1, 29)), [5, 4], [9] * 3]
                ]
            )
            used = engine.mixed_used_tokens
            disp = engine.mixed_dispatched_tokens
            assert 0 < used <= disp
        finally:
            await engine.stop()

    run(body())


# -- prefetch-overlapped onboarding ------------------------------------------


def _offload_engine_kw(td):
    return dict(
        host_offload_blocks=8,
        disk_offload_blocks=256,
        disk_offload_dir=str(td / "g3"),
    )


def test_prefetch_cancel_frees_pins(run, tmp_path):
    """A queued request whose prefetch staged blocks is cancelled before
    admission: every ring pin is released and the bytes count as wasted
    (the ISSUE 10 leak fix)."""
    from dynamo_tpu.offload import BlockMeta
    from dynamo_tpu.tokens.sequence import TokenBlockSequence

    async def body():
        engine = make_engine(
            max_batch_size=1, max_seq_len=64, num_pages=64,
            **_offload_engine_kw(tmp_path),
        )
        try:
            oe = engine.offload_engine
            prompt = list(range(1, 21))  # 5 blocks of 4
            hashes = TokenBlockSequence(
                prompt, block_size=engine.sched.block_size
            ).sequence_hashes()
            kv = engine.kv
            blob = np.zeros(
                (kv.pages.shape[0], 2, 1, kv.page_size) + kv.pages.shape[4:],
                np.float32,
            )
            for h in hashes[:3]:
                oe._ex.submit(oe.host.put, h, blob, BlockMeta()).result()
            # occupy the only slot so the prefetch target stays queued
            blocker = asyncio.ensure_future(
                collect(engine, req([42, 43], max_tokens=16, ignore_eos=True))
            )
            for _ in range(200):
                await asyncio.sleep(0.01)
                if engine.sched.num_active >= 1:
                    break
            queued = SeqState.from_request(
                "queued-prefetch",
                req(prompt, max_tokens=4),
                engine.sched.block_size,
            )
            engine.sched.enqueue(queued)
            engine._drive_prefetch()
            oe.drain()
            assert oe.host.pinned_blocks == 3
            # cancel before admission: pins must free, bytes count wasted
            engine.sched.cancel(queued)
            engine._cancel_prefetch(queued.request_id)
            assert oe.host.pinned_blocks == 0
            assert oe.prefetch_wasted_bytes > 0
            await blocker
        finally:
            await engine.stop()

    run(body())


def test_prefetch_identity_and_hits(run, tmp_path):
    """Warm-prefix onboarding through the prefetch path is
    token-identical to recompute (prefetch changes scheduling, never
    tokens), and the hit/overlap accounting fires."""

    async def body():
        engine = make_engine(
            max_batch_size=2, max_seq_len=64, page_size=4, num_pages=48,
            **_offload_engine_kw(tmp_path),
        )
        try:
            target = list(range(1, 25))
            cold, _ = await collect(engine, req(target, max_tokens=4))

            async def churn():
                # cycle the pool so the target's blocks evict into tiers
                for i in range(6):
                    await collect(
                        engine,
                        req([50 + i] + list(range(60, 90)), max_tokens=1),
                    )
                engine.offload_engine.drain()

            await churn()
            engine._prefetch_window = 0  # warm, prefetch off
            off_tokens, _ = await collect(engine, req(target, max_tokens=4))
            await churn()
            engine._prefetch_window = 8  # warm, prefetch on
            on_tokens, _ = await collect(engine, req(target, max_tokens=4))
            assert cold == off_tokens == on_tokens
            stats = engine.offload_engine.stats()
            assert stats["prefetch_issued"] > 0
            assert engine.offload_engine.host.pinned_blocks == 0
        finally:
            await engine.stop()

    run(body())


# -- the CPU bench smoke ------------------------------------------------------


def test_bench_long_context_smoke(run):
    """The run_long_context scenario at CPU scale: the packed
    padded-token fraction is a fraction, warm-prefix long TTFT improves
    with prefetch on vs off, overlap ratio sane, preemption/admission
    counters present."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import run_long_context

    async def body():
        out = await run_long_context(
            np.random.RandomState(0),
            lengths=(128, 256, 512),
            counts=(3, 2, 2),
            osl=4,
        )
        assert 0.0 <= out["lctx_padded_frac_packed"] < 1.0
        assert (
            out["lctx_warm_long_ttft_ms_prefetch_on"]
            < out["lctx_warm_long_ttft_ms_prefetch_off"]
        )
        ratio = out["lctx_prefetch_overlap_ratio"]
        assert ratio is None or 0.0 <= ratio <= 1.0
        assert out["lctx_prefetch_hits"] > 0
        assert out["lctx_admit_skips"] >= 0
        assert out["lctx_slo_ttft_target_ms"] > 0
        for name in ("short", "mid", "long"):
            assert out[f"lctx_ttft_p50_ms_{name}"] > 0
            # per-bucket SLO attainment stamps (ISSUE 12): a fraction
            # when the bucket has samples
            att = out[f"lctx_slo_ttft_attainment_{name}"]
            assert att is not None and 0.0 <= att <= 1.0

    run(body())


# -- sustained soak (slow lane) ----------------------------------------------


@pytest.mark.slow
def test_long_context_soak(run, tmp_path):
    """Sustained 128k-class mix (scaled): several rounds of mixed
    short/long traffic through budget admission + packed prefill +
    offload churn, asserting no leaks (pages, pins, swap records) and
    per-round token determinism."""

    async def body():
        engine = make_engine(
            max_batch_size=4, max_seq_len=256, page_size=8, num_pages=160,
            prefill_chunk_tokens=32, mixed_token_budget=48,
            kv_admit_budget="on",
            host_offload_blocks=32, disk_offload_blocks=512,
            disk_offload_dir=str(tmp_path / "g3"),
        )
        try:
            rs = np.random.RandomState(7)
            mix = [rs.randint(1, 255, (L,)).tolist()
                   for L in (24, 24, 96, 192) for _ in range(2)]
            first = None
            for _round in range(4):
                got = await asyncio.gather(
                    *[collect(engine, req(p, max_tokens=8)) for p in mix]
                )
                if first is None:
                    first = got
                else:
                    assert got == first  # warm rounds reproduce cold tokens
            alloc = engine.kv.allocator
            assert engine.sched.num_active == 0
            assert engine.offload_engine.host.pinned_blocks == 0
            assert not engine._swapped
            # every page either free or held by registered (reusable) blocks
            assert alloc.free_pages > 0
        finally:
            await engine.stop()

    run(body())
