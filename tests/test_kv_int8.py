"""int8-quantized paged KV pool (ISSUE 13).

Three contract layers:

* **kernel parity** -- the packed grid kernel's fused dequant (interpret
  mode) matches the XLA reference over a quantized pool;
* **accuracy** -- greedy decode over an int8 pool matches the bf16/f32
  engine on the tiny model, and prefill logits over int8-written KV stay
  within a documented tolerance of the full-width pool (per-row scales:
  the quantization error is bounded by amax/254 per element);
* **byte-exactness** -- every egress path (offload tiers, swap
  snapshots, external delivery, disagg export) round-trips the quantized
  (data, scales) pair bit-for-bit, and cross-dtype delivery converts
  through the one shared quantization rule.
"""

import asyncio

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
from dynamo_tpu.engine.kv_cache import (
    PagedKVCache,
    QuantKV,
    coerce_kv_blob,
    dequantize_kv_blob,
    blob_from_bytes,
    blob_nbytes,
    blob_to_bytes,
    concat_blob_pages,
    pad_page_axis,
    parse_kv_dtype,
    quantize_kv_blob,
    quantize_kv_rows,
)
from dynamo_tpu.offload import BlockMeta, DiskTier, HostTier
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Annotated, Context


def make_engine(**cfg_kw) -> JaxEngine:
    defaults = dict(max_batch_size=4, max_seq_len=64, page_size=4, num_pages=64)
    defaults.update(cfg_kw)
    return JaxEngine.random_init(ModelConfig.tiny(), EngineConfig(**defaults))


def req(tokens, max_tokens=8, temp=0.0, seed=None):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens),
        sampling_options=SamplingOptions(temperature=temp, seed=seed),
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


def _rand_blob(rng, L=2, n=4, page=4, Hkv=2, D=8):
    return rng.standard_normal((L, 2, n, page, Hkv, D)).astype(np.float32)


# ---------------------------------------------------------------------------
# quantization rule + blob helpers
# ---------------------------------------------------------------------------


def test_parse_kv_dtype():
    assert parse_kv_dtype(None) is None
    assert parse_kv_dtype("") is None
    assert parse_kv_dtype("int8") == "int8"
    assert parse_kv_dtype("bf16") == "bfloat16"
    with pytest.raises(ValueError):
        parse_kv_dtype("int4")


def test_quantize_rule_device_matches_host():
    """The jitted write path and the host blob conversion share ONE rule:
    same bytes out of both."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((6, 2, 8)).astype(np.float32)
    qd, sd = quantize_kv_rows(jnp.asarray(x))
    host = quantize_kv_blob(x[None, None, None])  # [1,1,1,6,2,8]
    np.testing.assert_array_equal(np.asarray(qd), host.q[0, 0, 0])
    np.testing.assert_allclose(np.asarray(sd), host.s[0, 0, 0], rtol=1e-6)


def test_quantize_error_bound_and_roundtrip_stability():
    rng = np.random.default_rng(1)
    dense = _rand_blob(rng)
    q = quantize_kv_blob(dense)
    deq = dequantize_kv_blob(q, np.float32)
    # per-row error bound: half an int8 step of that row's scale
    err = np.abs(deq - dense)
    bound = q.s[..., None, None] * 0.5 + 1e-7
    assert np.all(err <= bound)
    # re-quantizing the dequantized blob reproduces the same int8 bytes
    q2 = quantize_kv_blob(deq)
    np.testing.assert_array_equal(q.q, q2.q)


def test_pack_unpack_bytes_bit_exact():
    rng = np.random.default_rng(2)
    q = quantize_kv_blob(_rand_blob(rng))
    buf = blob_to_bytes(q)
    assert len(buf) == blob_nbytes(q.shape, "int8")
    back = blob_from_bytes(buf, q.shape, "int8")
    np.testing.assert_array_equal(back.q, q.q)
    np.testing.assert_array_equal(back.s, q.s)


def test_blob_concat_pad_getitem():
    rng = np.random.default_rng(3)
    a, b = quantize_kv_blob(_rand_blob(rng, n=2)), quantize_kv_blob(
        _rand_blob(rng, n=3)
    )
    cat = concat_blob_pages([a, b])
    assert cat.shape[2] == 5 and cat.s.shape[2] == 5
    padded = pad_page_axis(cat, 8)
    assert padded.shape[2] == 8 and padded.s.shape[2] == 8
    np.testing.assert_array_equal(padded[1:2].q, padded.q[1:2])
    np.testing.assert_array_equal(padded[:, :, 1:3].s, padded.s[:, :, 1:3])
    with pytest.raises(IndexError):
        padded[:, :, :, :, 0]  # reaching past the shared scale axes


def test_coerce_blob_directions():
    rng = np.random.default_rng(4)
    dense = _rand_blob(rng)
    q = quantize_kv_blob(dense)
    # same-domain: pass-through (identity, byte-exact)
    assert coerce_kv_blob(q, True, jnp.int8) is q
    assert coerce_kv_blob(dense, False, jnp.float32) is dense
    # cross-domain: the shared rule
    np.testing.assert_array_equal(coerce_kv_blob(dense, True, jnp.int8).q, q.q)
    np.testing.assert_allclose(
        coerce_kv_blob(q, False, np.float32),
        dequantize_kv_blob(q, np.float32),
    )


def test_pool_footprint_accounting():
    cfg = ModelConfig.tiny()
    dense = PagedKVCache(cfg, num_pages=32, page_size=4)
    quant = PagedKVCache(cfg, num_pages=32, page_size=4, dtype="int8")
    assert quant.quantized and str(quant.dtype) == "int8"
    # int8 data is itemsize/2 (vs bf16) or /4 (vs f32) plus the scale rows
    assert quant.bytes_per_page < dense.bytes_per_page
    scale_bytes = cfg.num_layers * 2 * 4 * 4
    assert quant.bytes_per_page == (
        cfg.num_layers * 2 * 4 * cfg.num_kv_heads * cfg.head_dim + scale_bytes
    )
    assert quant.pool_bytes == quant.bytes_per_page * 32


# ---------------------------------------------------------------------------
# kernel parity (fused dequant, interpret mode)
# ---------------------------------------------------------------------------


def _kernel_operands(rng):
    L, P, page, Hkv, D, B = 2, 16, 8, 2, 16, 2
    dense = rng.standard_normal((L, 2, P, page, Hkv, D)).astype(np.float32)
    pool = quantize_kv_blob(dense)
    pool = QuantKV(q=jnp.asarray(pool.q), s=jnp.asarray(pool.s))
    pt = jnp.asarray(rng.integers(1, P, (B, 8)).astype(np.int32))
    base = jnp.asarray([16, 9], np.int32)
    q_lens = jnp.asarray([8, 1], np.int32)
    return pool, pt, base, q_lens


def test_packed_kernel_int8_parity_interpret():
    from dynamo_tpu.ops.ragged_attention import (
        packed_ragged_attention,
        packed_ragged_attention_xla,
    )

    rng = np.random.default_rng(6)
    pool, pt, base, q_lens = _kernel_operands(rng)
    Np, s_max, Hq, Hkv, D, B = 16, 8, 4, 2, 16, 2
    qp = jnp.asarray(rng.standard_normal((Np, Hq, D)).astype(np.float32))
    kp = jnp.asarray(rng.standard_normal((Np, Hkv, D)).astype(np.float32))
    vp = jnp.asarray(rng.standard_normal((Np, Hkv, D)).astype(np.float32))
    seg_off = jnp.asarray([0, 8], np.int32)
    lane = np.full((Np,), B, np.int32)
    lane[:8] = 0
    lane[8] = 1
    rel = np.zeros((Np,), np.int32)
    rel[:8] = np.arange(8)
    ref = packed_ragged_attention_xla(
        qp, kp, vp, pool, pt, base, seg_off, q_lens,
        jnp.asarray(lane), jnp.asarray(rel), s_max, layer=0,
    )
    out = packed_ragged_attention(
        qp, kp, vp, pool.q, pt, base, seg_off, q_lens, s_max, layer=0,
        interpret=True, kv_scales=pool.s,
    )
    m = lane < B
    np.testing.assert_allclose(
        np.asarray(ref)[m], np.asarray(out)[m], atol=2e-5
    )


# ---------------------------------------------------------------------------
# engine accuracy: greedy + logit tolerance
# ---------------------------------------------------------------------------


def test_greedy_decode_matches_reference(run):
    """Greedy streams over the int8 pool match the full-width engine on
    the tiny model, on the packed and the classic dispatch paths."""

    async def body():
        prompts = [list(range(1 + i, 14 + i)) for i in range(3)]

        async def runs(**kw):
            e = make_engine(**kw)
            try:
                return await asyncio.gather(
                    *[collect(e, req(p, max_tokens=6), f"q{i}")
                      for i, p in enumerate(prompts)]
                )
            finally:
                await e.stop()

        ref = await runs()
        for kw in (
            dict(kv_dtype="int8"),
            dict(kv_dtype="int8", mixed_batching=False),
        ):
            assert await runs(**kw) == ref, kw

    run(body())


def test_int8_logit_tolerance():
    """Documented accuracy bound: decode logits computed over int8-written
    KV stay within atol=0.15 / high cosine of the full-width pool on the
    tiny model (per-row scales bound the element error by amax/254)."""
    from dynamo_tpu.engine.model import init_params
    from dynamo_tpu.engine.step import decode_step, prefill_step

    cfg = ModelConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = np.arange(1, 13, dtype=np.int32)[None].repeat(1, axis=0)
    T = toks.shape[1]
    page = 4
    n_pages = T // page + 1
    table = np.zeros((1, 8), np.int32)
    table[0, :n_pages] = np.arange(1, n_pages + 1)
    outs = {}
    for dtype in (None, "int8"):
        kv = PagedKVCache(cfg, num_pages=16, page_size=page, dtype=dtype)
        logits, pages = prefill_step(
            params, cfg, kv.pages, jnp.asarray(toks),
            jnp.asarray([T], np.int32), jnp.asarray(table),
        )
        step_logits, _pages = decode_step(
            params, cfg, pages, jnp.asarray([3], np.int32),
            jnp.asarray([T], np.int32), jnp.asarray(table),
        )
        outs[dtype] = (
            np.asarray(logits, np.float32),
            np.asarray(step_logits, np.float32),
        )
    for a, b in zip(outs[None], outs["int8"]):
        cos = float(
            np.sum(a * b)
            / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-9)
        )
        assert cos > 0.999, cos
        np.testing.assert_allclose(a, b, atol=0.15)


# ---------------------------------------------------------------------------
# byte-exact round trips: tiers, swap, delivery, export
# ---------------------------------------------------------------------------


def test_host_tier_ring_roundtrip_bit_exact():
    rng = np.random.default_rng(7)
    tier = HostTier(capacity_blocks=2)
    blobs = {h: quantize_kv_blob(_rand_blob(rng)) for h in (11, 22)}
    for h, b in blobs.items():
        tier.put(h, b, BlockMeta(kv_dtype="int8"))
    for h, b in blobs.items():
        got, meta = tier.get_ram(h)
        assert isinstance(got, QuantKV)
        np.testing.assert_array_equal(got.q, b.q)
        np.testing.assert_array_equal(got.s, b.s)
        assert meta.kv_dtype == "int8"
    assert tier.ring_nbytes > 0  # pair landed in the dual ring


def test_disk_tier_roundtrip_bit_exact(tmp_path):
    rng = np.random.default_rng(8)
    tier = DiskTier(str(tmp_path), capacity_blocks=4)
    blob = quantize_kv_blob(_rand_blob(rng))
    tier.put(33, blob, BlockMeta(block_hash=1, position=2, kv_dtype="int8"))
    got, meta = tier.get(33)
    assert isinstance(got, QuantKV)
    np.testing.assert_array_equal(got.q, blob.q)
    np.testing.assert_array_equal(got.s, blob.s)
    assert meta.kv_dtype == "int8" and meta.position == 2


def test_host_tier_demotes_pair_to_disk(tmp_path):
    rng = np.random.default_rng(9)
    disk = DiskTier(str(tmp_path), capacity_blocks=8)
    tier = HostTier(capacity_blocks=1, parent=disk)
    b1 = quantize_kv_blob(_rand_blob(rng))
    b2 = quantize_kv_blob(_rand_blob(rng))
    tier.put(1, b1, BlockMeta(kv_dtype="int8"))
    tier.put(2, b2, BlockMeta(kv_dtype="int8"))  # demotes 1 to disk
    got, _meta = tier.get(1)  # promotes back through the pair-aware path
    np.testing.assert_array_equal(got.q, b1.q)
    np.testing.assert_array_equal(got.s, b1.s)


def test_slice_scatter_pool_roundtrip_bit_exact():
    """Device egress primitives: slice pages out of a quantized pool,
    round-trip through host, scatter back -- identical pool bytes (the
    swap-snapshot/offload-eviction path in miniature)."""
    from dynamo_tpu.engine.kv_cache import scatter_block_pages, slice_block_pages
    from dynamo_tpu.offload import to_host

    rng = np.random.default_rng(10)
    cfg = ModelConfig.tiny()
    kv = PagedKVCache(cfg, num_pages=16, page_size=4, dtype="int8")
    seeded = quantize_kv_blob(
        rng.standard_normal(kv.pages.shape).astype(np.float32)
    )
    pool = QuantKV(q=jnp.asarray(seeded.q), s=jnp.asarray(seeded.s))
    ids = jnp.asarray([3, 7, 2], np.int32)
    snap = slice_block_pages(pool, ids)
    host = to_host(snap)
    assert isinstance(host, QuantKV)
    pool2 = scatter_block_pages(pool, ids, QuantKV(
        q=jnp.asarray(host.q), s=jnp.asarray(host.s)
    ))
    snap2 = slice_block_pages(pool2, ids)
    np.testing.assert_array_equal(np.asarray(snap2.q), host.q)
    np.testing.assert_array_equal(np.asarray(snap2.s), host.s)


def test_gather_scatter_layer_pages_roundtrip_bit_exact():
    from dynamo_tpu.engine.kv_cache import gather_layer_pages, scatter_layer_pages

    rng = np.random.default_rng(11)
    cfg = ModelConfig.tiny()
    kv = PagedKVCache(cfg, num_pages=16, page_size=4, dtype="int8")
    seeded = quantize_kv_blob(
        rng.standard_normal(kv.pages.shape).astype(np.float32)
    )
    pool = QuantKV(q=jnp.asarray(seeded.q), s=jnp.asarray(seeded.s))
    layers = jnp.asarray([0, 1], np.int32)
    ids = jnp.asarray([5, 9], np.int32)
    chunk = gather_layer_pages(pool, layers, ids)
    pool2 = scatter_layer_pages(pool, layers, ids, chunk)
    chunk2 = gather_layer_pages(pool2, layers, ids)
    np.testing.assert_array_equal(np.asarray(chunk2.q), np.asarray(chunk.q))
    np.testing.assert_array_equal(np.asarray(chunk2.s), np.asarray(chunk.s))


def test_offload_prefix_roundtrip_token_identity(run):
    """Eviction -> tier -> onboard over an int8 pool: the warm re-run
    reuses quantized tier blobs and reproduces the cold stream exactly
    (byte-exact restore implies token identity)."""

    async def body():
        engine = make_engine(
            kv_dtype="int8", host_offload_blocks=32, num_pages=32
        )
        try:
            prompt = list(range(1, 17))
            cold = await collect(engine, req(prompt, max_tokens=4), "cold")
            # churn the pool so the prefix evicts into the host tier
            for i in range(6):
                await collect(
                    engine, req(list(range(40 + 8 * i, 56 + 8 * i)),
                                max_tokens=2), f"churn{i}"
                )
            if engine.offload_engine is not None:
                engine.offload_engine.drain()
            warm = await collect(engine, req(prompt, max_tokens=4), "warm")
            assert warm == cold
        finally:
            await engine.stop()

    run(body())


def test_swap_preemption_int8_token_identity(run):
    """Swap-based preemption over an int8 pool (quantized SwapRecord
    blobs): identical output to an uncontended run."""

    async def body():
        prompts = [list(range(1 + i, 10 + i)) for i in range(4)]

        async def runs(**kw):
            e = make_engine(**kw)
            try:
                return await asyncio.gather(
                    *[collect(e, req(p, max_tokens=16), f"s{i}")
                      for i, p in enumerate(prompts)]
                )
            finally:
                await e.stop()

        roomy = await runs(kv_dtype="int8")
        tight = await runs(
            kv_dtype="int8", num_pages=20, host_offload_blocks=32
        )
        assert tight == roomy

    run(body())


def test_external_delivery_int8_bit_exact_and_identity(run):
    """Disagg delivery between two int8 engines: the delivered pool pages
    are bit-identical to the exported blob (quantized-domain exactness)
    and decode continues token-identically to a local prefill."""

    async def body():
        from dynamo_tpu.engine.kv_cache import slice_block_pages
        from dynamo_tpu.engine.sampling import unpack_sampled_logprobs

        prompt = list(range(1, 13))
        prefiller = make_engine(kv_dtype="int8")
        decoder = make_engine(kv_dtype="int8")
        local = make_engine(kv_dtype="int8")
        try:
            blob, row = await prefiller.prefill_export(req(prompt, max_tokens=9))
            assert isinstance(blob, QuantKV)
            first = int(np.asarray(row).reshape(-1)[0])
            stream = await decoder.generate_external(
                Context.new(req(prompt, max_tokens=9), "ext")
            )
            assert decoder.deliver_external("ext", blob, row)
            tokens = []
            lane_pages = None
            async for item in stream:
                data = item.data or {}
                tokens.extend(data.get("token_ids") or [])
                if tokens and lane_pages is None:
                    # first token committed: capture the lane's delivered
                    # page ids (host-side list; the device snapshot waits
                    # for the engine to go idle -- the tick loop donates
                    # the pool buffer on every dispatch)
                    seq = next(
                        s for s in decoder.sched.slots
                        if s is not None and s.request_id == "ext"
                    )
                    lane_pages = list(seq.pages[: blob.shape[2]])
            # stream done, engine idle, pages not yet reused: the delivered
            # pages hold the exported blob bit-for-bit (quantized domain)
            assert lane_pages is not None
            await asyncio.sleep(0.1)
            ids = jnp.asarray(lane_pages, np.int32)
            snap = slice_block_pages(decoder.kv.pages, ids)
            np.testing.assert_array_equal(
                np.asarray(snap.q), np.asarray(blob.q)
            )
            np.testing.assert_array_equal(
                np.asarray(snap.s), np.asarray(blob.s)
            )
            ref, _fin = await collect(local, req(prompt, max_tokens=9))
            assert tokens[0] == first == ref[0]
            assert tokens == ref
        finally:
            await prefiller.stop()
            await decoder.stop()
            await local.stop()

    run(body())


def test_cross_dtype_delivery(run):
    """A bf16 prefiller feeding an int8 decode pool (and vice versa):
    delivery converts through the shared rule and decode proceeds with a
    sane greedy stream."""

    async def body():
        prompt = list(range(2, 14))
        bf = make_engine()
        q = make_engine(kv_dtype="int8")
        try:
            # bf16 blob -> int8 pool
            blob, row = await bf.prefill_export(req(prompt, max_tokens=6))
            assert not isinstance(blob, QuantKV)
            stream = await q.generate_external(
                Context.new(req(prompt, max_tokens=6), "x1")
            )
            assert q.deliver_external("x1", blob, row)
            toks = []
            async for item in stream:
                toks.extend((item.data or {}).get("token_ids") or [])
            ref, _ = await collect(q, req(prompt, max_tokens=6), "local")
            assert toks == ref  # cross-dtype delivery stays exact
            # int8 blob -> bf16 pool
            qblob, qrow = await q.prefill_export(req(prompt, max_tokens=6))
            assert isinstance(qblob, QuantKV)
            stream = await bf.generate_external(
                Context.new(req(prompt, max_tokens=6), "x2")
            )
            assert bf.deliver_external("x2", qblob, qrow)
            toks2 = []
            async for item in stream:
                toks2.extend((item.data or {}).get("token_ids") or [])
            assert len(toks2) == 6
        finally:
            await bf.stop()
            await q.stop()

    run(body())


def test_export_stream_chunks_and_nbytes(run):
    """The chunked export stream over an int8 pool yields QuantKV parts
    whose assembled pair equals the monolithic export, and its wire
    nbytes accounts for data + scales."""

    async def body():
        engine = make_engine(kv_dtype="int8")
        try:
            prompt = list(range(3, 15))
            streams = await engine.prefill_export_batch_stream(
                [req(prompt, max_tokens=4)]
            )
            st = streams[0]
            assert not isinstance(st, Exception), st
            assert st.quantized
            assert st.nbytes == blob_nbytes(st.shape, "int8")
            blob = await st.assemble()
            assert isinstance(blob, QuantKV)
            mono, _row = await engine.prefill_export(req(prompt, max_tokens=4))
            np.testing.assert_array_equal(np.asarray(blob.q), mono.q)
            np.testing.assert_array_equal(np.asarray(blob.s), mono.s)
        finally:
            await engine.stop()

    run(body())


def test_wire_staging_roundtrip_bit_exact():
    """The disagg/prefix-onboard wire framing for quantized blobs: the
    sender packs (data | scales) per layer slab, the staging buffer's
    quant layout re-derives identical byte bounds from (shape, dtype),
    and layer_slice/payload unpack the exact pair."""
    from dynamo_tpu.engine.kv_cache import layer_chunk_spans
    from dynamo_tpu.offload import KVStagingBuffer
    from dynamo_tpu.runtime.transports.codec import (
        ChunkAssembler,
        iter_chunk_frames,
    )

    rng = np.random.default_rng(12)
    blob = quantize_kv_blob(_rand_blob(rng, L=4))
    spans = layer_chunk_spans(4, 2)
    staging = KVStagingBuffer.for_layer_spans(blob.shape, "int8", spans)
    assert isinstance(staging.payload(), QuantKV)
    bpl = blob_nbytes(blob.shape, "int8") // 4
    assert staging.bounds == [(lo * bpl, hi * bpl) for lo, hi in spans]
    asm = ChunkAssembler(staging.memoryview, staging.bounds)
    done = []
    for idx, (lo, hi) in enumerate(spans):
        raw = blob_to_bytes(blob[lo:hi])
        for frame in iter_chunk_frames(idx, staging.bounds[idx][0], raw, 64):
            done.extend(asm.add(frame))
    assert sorted(done) == list(range(len(spans)))
    for lo, hi in spans:
        part = staging.layer_slice(lo, hi)
        assert isinstance(part, QuantKV)
        np.testing.assert_array_equal(part.q, blob.q[lo:hi])
        np.testing.assert_array_equal(part.s, blob.s[lo:hi])
    # whole-blob framing (the prefix-onboard donor path): payload()
    # unpacks the assembled pair bit-for-bit
    whole_raw = blob_to_bytes(blob)
    st2 = KVStagingBuffer.for_byte_chunks(blob.shape, "int8", 96)
    asm2 = ChunkAssembler(st2.memoryview, st2.bounds)
    for idx, (lo_b, _hi_b) in enumerate(st2.bounds):
        asm2.add(
            next(
                iter_chunk_frames(
                    idx, lo_b, whole_raw[lo_b:_hi_b], 96
                )
            )
        )
    whole = st2.payload()
    np.testing.assert_array_equal(whole.q, blob.q)
    np.testing.assert_array_equal(whole.s, blob.s)


def test_async_dispatch_composes_with_int8(run):
    """The two tentpole halves together: pipelined loop over a quantized
    pool, identical to the serial bf16-pool baseline's int8 run."""

    async def body():
        reqs = [req(list(range(1 + i, 15 + i)), max_tokens=6) for i in range(4)]

        async def runs(**kw):
            e = make_engine(kv_dtype="int8", **kw)
            try:
                return await asyncio.gather(
                    *[collect(e, r, f"c{i}") for i, r in enumerate(reqs)]
                )
            finally:
                await e.stop()

        assert await runs(async_dispatch=True) == await runs(
            async_dispatch=False
        )

    run(body())


# ---------------------------------------------------------------------------
# one module knows the format (ISSUE 52)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("module", [
    "offload", "llm.disagg", "llm.prefix_onboard", "parallel.sharding",
    "engine.engine", "engine.step",
])
def test_the_kv_movers_do_not_name_the_pair(module):
    """What moves a blob asks ``engine/kv_cache.py`` for arrays or bytes and
    never looks inside: the module's AST holds no ``QuantKV``."""
    import ast
    import importlib

    path = importlib.import_module(f"dynamo_tpu.{module}").__file__
    with open(path) as f:
        tree = ast.parse(f.read())
    named = [
        node.lineno for node in ast.walk(tree)
        if "QuantKV" in (
            getattr(node, "id", None), getattr(node, "attr", None),
            getattr(node, "name", None),
        )
    ]
    assert not named, f"{path} names QuantKV at lines {named}"


# the sentences as the tree raised them before they were one table: a kind's
# own reason behind ``{what} is not supported over``, or the capability's own
_OVER = {
    "two_kind": "{what} is not supported over a two-kind cache (window and "
    "full layers, layer_types): its pages live in two pools with two page "
    "tables a lane, and the transfer formats and meshes carry one",
    "conv": "{what} is not supported over a trunk with convolution layers "
    "(layer_types 'conv'): a sequence carries two rows a layer beside its "
    "pages, which only the packed step and the fused decode steps carry, "
    "snapshot and restore",
    "latent": "{what} is not supported over a latent cache (MLA): the "
    "transfer formats carry K/V pairs per head and per layer",
}
_MOVES = ("two_kind", "conv")
_SHIPS = ("two_kind", "conv", "latent")
_REFUSED = {  # capability -> (what, the kinds that refuse it)
    "mesh": ("a serving mesh (tp, dp, sp or pp)", _MOVES),
    "sp_pp": ("", ()),
    "offload": ("host/disk KV offload and swap preemption", _MOVES),
    "remote_tier": ("the remote KV tier (G4)", _MOVES),
    "int8_pool": ("an int8 pool", _MOVES),
    "sharded_pool": ("a sharded pool", _MOVES),
    "window_layers": ("a trunk of window and full layers", ("conv",)),
    "disagg_serving": ("disaggregated serving (a remote prefill's KV)", _SHIPS),
    "kv_delivery": ("a remote prefill's KV delivery", _SHIPS),
    "prefill_export": ("a disaggregated prefill export", _SHIPS),
    "block_export": ("a KV block export", _SHIPS),
    "unmixed": ("serving without mixed batching", _MOVES),
    "classic_dispatch": (
        "a request with sampling penalties, a soft prompt or speculation "
        "(the classic prefill and verify dispatches)", _MOVES),
    "scoring": (
        "a request for the prompt's log-probabilities (the scoring step)",
        ("conv",)),
    "embedding": ("pooled embeddings (the embedding step)", ("conv",)),
    "unmasked_decode_step": (
        "a decode step that is not told which lanes it advances", ("conv",)),
    "classic_step": (
        "a step outside the packed step and the decode steps (classic "
        "prefill, verify, scoring, embedding)", ("conv",)),
}
_LATENT_OWN = {
    "sp_pp": "sp/pp meshes are not supported over a latent cache (MLA): "
    "their prefill routes and stage pools assume K/V pairs per head and per "
    "layer",
    "offload": "host/disk KV offload is not supported over a latent cache "
    "(MLA): the tiers move [L, 2, pages, page, Hkv, D] blocks, and a latent "
    "pool holds two layers' rows a slab",
    "remote_tier": "the remote KV tier (G4) is not supported over a latent "
    "cache (MLA): it ships the offload tiers' K/V blocks",
    "int8_pool": "kv_dtype int8 is not supported over a latent cache (MLA): "
    "one scale a row would span c_kv and the rotated key, whose ranges "
    "differ",
}
_KIND_ENGINES = {}


def _engine_of(kind):
    """One served toy engine a kind, from that kind's own test module."""
    if kind not in _KIND_ENGINES:
        if kind == "pair":
            engine = make_engine()
        else:
            import importlib

            t = importlib.import_module({
                "two_kind": "tests.test_mellum", "conv": "tests.test_lfm2",
                "latent": "tests.test_mla",
            }[kind])
            cfg = t.tiny()
            engine_config = getattr(
                t, "engine_config",
                lambda: EngineConfig(max_batch_size=2, max_seq_len=64,
                                     page_size=16, num_pages=16))
            engine = JaxEngine(t.model_config(cfg),
                               t.W.build_params(cfg, t.SEED), engine_config())
        _KIND_ENGINES[kind] = engine
    return _KIND_ENGINES[kind]


def test_the_table_holds_the_capabilities_the_engine_asks_for():
    from dynamo_tpu.engine.kv_cache import KV_REFUSALS

    assert set(KV_REFUSALS) == set(_REFUSED)


@pytest.mark.parametrize("kind", ["pair", "two_kind", "conv", "latent"])
@pytest.mark.parametrize("capability", sorted(_REFUSED))
def test_a_kind_refuses_a_capability_with_the_table_s_sentence(
    capability, kind
):
    from dynamo_tpu.engine.kv_cache import kv_refusal

    what, kinds = _REFUSED[capability]
    want = None
    if kind == "latent" and capability in _LATENT_OWN:
        want = _LATENT_OWN[capability]
    elif kind in kinds:
        want = _OVER[kind].format(what=what)
    engine = _engine_of(kind)
    assert kv_refusal(engine.model_cfg, capability) == want
    if want is None:
        engine._refuse(capability)
    else:
        with pytest.raises(ValueError) as raised:
            engine._refuse(capability)
        assert str(raised.value) == want
