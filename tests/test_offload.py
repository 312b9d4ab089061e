"""KV offload tiers (G2 host / G3 disk), swap-based preemption, and their
engine integration.

Reference capability: block_manager offload.rs:76-80 -- eviction cascades
G1 -> G2 -> G3; admission lookups promote blocks back up; preemption
swaps the victim's KV out and restores it through the chunked scatter
path instead of recomputing.
"""

import asyncio
import threading

import numpy as np
import pytest

from dynamo_tpu.offload import (
    BlockMeta,
    DiskTier,
    HostTier,
    KVOffloadEngine,
    env_offload_spec,
)
from dynamo_tpu.runtime import faults

from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
from dynamo_tpu.tokens.sequence import TokenBlockSequence
from tests.test_jax_engine import collect, req


@pytest.fixture
def injector():
    """The process injector, disarmed on the way out."""
    faults.injector.disable()
    yield faults.injector
    faults.injector.disable()


def _blob(seed, shape=(2, 2, 1, 4, 2, 8)):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


def test_host_tier_lru_and_capacity():
    t = HostTier(2)
    t.put(1, _blob(1), BlockMeta(position=0))
    t.put(2, _blob(2), BlockMeta(position=1))
    t.put(3, _blob(3), BlockMeta(position=2))  # evicts 1 (LRU, no parent)
    assert t.get(1) is None
    blob, meta = t.get(2)
    assert meta.position == 1 and np.array_equal(blob, _blob(2))
    assert len(t) == 2


def test_host_tier_demotes_to_disk_and_promotes_back(tmp_path):
    disk = DiskTier(str(tmp_path), capacity_blocks=4)
    t = HostTier(1, parent=disk)
    t.put(1, _blob(1), BlockMeta(block_hash=11))
    t.put(2, _blob(2), BlockMeta(block_hash=22))  # demotes 1 to disk
    assert len(t) == 1 and len(disk) == 1
    blob, meta = t.get(1)  # disk hit, promoted back to G2
    assert meta.block_hash == 11 and np.array_equal(blob, _blob(1))
    assert disk.hits == 1


def test_disk_tier_capacity_deletes_files(tmp_path):
    disk = DiskTier(str(tmp_path), capacity_blocks=2)
    for i in range(4):
        disk.put(i, _blob(i), BlockMeta())
    assert len(disk) == 2
    assert disk.get(0) is None and disk.get(1) is None
    blob, _ = disk.get(3)
    assert np.array_equal(blob, _blob(3))
    files = list(tmp_path.iterdir())
    assert len(files) == 2


def _offload_engine(**kw):
    defaults = dict(
        max_batch_size=2,
        max_seq_len=64,
        page_size=4,
        num_pages=17,  # 16 usable = 4 blocks of 4 pages... (block=page here)
        host_offload_blocks=32,
    )
    defaults.update(kw)
    return JaxEngine.random_init(ModelConfig.tiny(), EngineConfig(**defaults))


def test_engine_offload_roundtrip(run):
    """Fill the pool with A, force eviction with B, re-run A: the blocks
    come back from G2 (onboarding), the output is identical, and the
    prefix-cache hit counter moves."""

    async def body():
        prompt_a = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]  # 3 blocks of 4
        prompt_b = [7, 7, 7, 7, 8, 8, 8, 8, 6, 6, 6, 6]

        from dynamo_tpu.tokens.sequence import TokenBlockSequence

        engine = _offload_engine()
        try:
            first_a, _ = await collect(engine, req(prompt_a, max_tokens=4))
            a_hashes = TokenBlockSequence(
                prompt_a, block_size=engine.sched.block_size
            ).sequence_hashes()
            pool = engine.sched.pool

            def a_resident():
                return sum(1 for h in a_hashes if pool.is_registered(h))

            # B churns the pool until A's registered blocks are all evicted
            for i in range(12):
                if a_resident() == 0:
                    break
                await collect(
                    engine, req([(p + i) % 30 for p in prompt_b], max_tokens=4)
                )
            assert a_resident() == 0, "A's blocks must have been evicted"
            # barrier: eviction snapshots materialize on the offload thread
            engine.offload_engine.drain()
            assert len(engine.offload) > 0, "evictions must have offloaded"

            hits_before = engine._prefix_hits
            second_a, _ = await collect(engine, req(prompt_a, max_tokens=4))
            assert second_a == first_a  # onboarded KV reproduces the stream
            assert engine._prefix_hits > hits_before
            assert engine.offload.hits > 0
        finally:
            await engine.stop()

    run(body())


def test_engine_offload_disk_spill_roundtrip(run, tmp_path):
    """G2 capacity 1 forces spills to G3; a re-run still reconstructs its
    prefix from disk."""

    async def body():
        prompt_a = [3, 1, 4, 1, 5, 9, 2, 6]
        engine = _offload_engine(
            host_offload_blocks=1,
            disk_offload_blocks=16,
            disk_offload_dir=str(tmp_path / "g3"),
        )
        try:
            first_a, _ = await collect(engine, req(prompt_a, max_tokens=4))
            assert engine.offload.parent is not None
            for i in range(16):
                engine.offload_engine.drain()
                if len(engine.offload.parent) > 0:
                    break
                await collect(
                    engine,
                    req([(9 + i + j) % 30 for j in range(12)], max_tokens=4),
                )
            assert len(engine.offload.parent) > 0, "G3 must hold spills"
            # a disk-resident prefix onboards via the queue-side prefetch
            # (promote to the host ring) + the chunked scatter; make the
            # promote deterministic for the assertion below
            a_hashes = TokenBlockSequence(
                prompt_a, block_size=engine.sched.block_size
            ).sequence_hashes()
            engine.offload_engine.prefetch(a_hashes)
            engine.offload_engine.drain()
            second_a, _ = await collect(engine, req(prompt_a, max_tokens=4))
            assert second_a == first_a
        finally:
            await engine.stop()

    run(body())


def test_offload_disabled_by_default(run):
    """With DYN_KV_OFFLOAD unset and no config blocks, the plane is a
    no-op: no tiers, no offload thread, no swap hook."""

    async def body():
        engine = JaxEngine.random_init(
            ModelConfig.tiny(),
            EngineConfig(max_batch_size=2, max_seq_len=32, page_size=4,
                         num_pages=16),
        )
        try:
            assert engine.offload is None
            assert engine.offload_engine is None
            assert engine.sched.swap_out is None
            await collect(engine, req([1, 2, 3], max_tokens=2))
            assert not [
                t for t in threading.enumerate()
                if t.name.startswith("kv-offload")
            ], "no offload thread may start when the plane is unarmed"
        finally:
            await engine.stop()

    run(body())


def test_env_offload_spec_grammar():
    assert env_offload_spec({}) is None
    assert env_offload_spec({"DYN_KV_OFFLOAD": "off"}) is None
    assert env_offload_spec({"DYN_KV_OFFLOAD": "1"}) == {
        "host": 256, "disk": 0, "dir": None, "swap": True,
    }
    spec = env_offload_spec(
        {"DYN_KV_OFFLOAD": "host=64,disk=128,dir=/tmp/kv,swap=0"}
    )
    assert spec == {"host": 64, "disk": 128, "dir": "/tmp/kv", "swap": False}
    with pytest.raises(ValueError):
        env_offload_spec({"DYN_KV_OFFLOAD": "host=abc"})
    with pytest.raises(ValueError):
        env_offload_spec({"DYN_KV_OFFLOAD": "bogus=1"})


def test_env_var_arms_engine(run, monkeypatch):
    """DYN_KV_OFFLOAD turns the plane on without any config blocks."""
    monkeypatch.setenv("DYN_KV_OFFLOAD", "host=8")

    async def body():
        engine = JaxEngine.random_init(
            ModelConfig.tiny(),
            EngineConfig(max_batch_size=2, max_seq_len=32, page_size=4,
                         num_pages=16),
        )
        try:
            assert engine.offload_engine is not None
            assert engine.offload_engine.host.capacity == 8
            assert engine.sched.swap_out is not None
            await collect(engine, req([1, 2, 3], max_tokens=2))
        finally:
            await engine.stop()

    run(body())


# -- swap-based preemption ---------------------------------------------------


def _pressure_engine(swap: bool, num_pages: int = 13, **kw):
    """A pool two growing sequences cannot share: admission fits both, but
    decode growth runs dry and the younger lane gets preempted.  Pinned to
    the serial tick loop: these tests assert the swap path actually FIRES,
    which needs deterministic preemption-vs-commit timing -- under the
    async pipeline a load-dependent commit lag can legitimately turn a
    swap into the (equally correct) recompute fallback.  The async+swap
    compose is covered by test_kv_int8/test_async_dispatch identity
    tests."""
    defaults = dict(
        max_batch_size=2,
        max_seq_len=64,
        page_size=4,
        num_pages=num_pages,
        host_offload_blocks=32,
        swap_preemption=swap,
        async_dispatch=False,
    )
    defaults.update(kw)
    return JaxEngine.random_init(ModelConfig.tiny(), EngineConfig(**defaults))


async def _run_pressure_pair(engine, prompt_a, prompt_b, max_tokens=24):
    """Run two concurrent requests through a tight pool; returns their
    outputs in request order."""
    (ta, _), (tb, _) = await asyncio.gather(
        collect(engine, req(prompt_a, max_tokens=max_tokens)),
        collect(engine, req(prompt_b, max_tokens=max_tokens)),
    )
    return ta, tb


def test_swap_preemption_token_identical(run):
    """The acceptance invariant: swap-based preemption produces exactly
    the tokens recompute preemption does (and both match an uncontended
    pool), while actually exercising the swap path."""

    prompt_a = [3, 1, 4, 1, 5, 9, 2, 6]
    prompt_b = [2, 7, 1, 8, 2, 8, 1, 8]

    async def one(swap: bool, num_pages: int):
        engine = _pressure_engine(swap, num_pages=num_pages)
        try:
            out = await _run_pressure_pair(engine, prompt_a, prompt_b)
            return out, engine.sched.preempt_swap, engine.sched.preempt_recompute
        finally:
            await engine.stop()

    async def body():
        roomy, _, _ = await one(swap=True, num_pages=41)
        swap_out, n_swap, _ = await one(swap=True, num_pages=13)
        reco_out, _, n_reco = await one(swap=False, num_pages=13)
        assert n_swap >= 1, "swap preemption must have been exercised"
        assert n_reco >= 1, "recompute preemption must have been exercised"
        assert swap_out == reco_out == roomy

    run(body())


def test_swap_budget_exhausted_falls_back_to_recompute(run):
    """A zero swap budget declines every swap-out; preemption still works
    (recompute), output unchanged, nothing leaks."""

    async def body():
        engine = _pressure_engine(True, num_pages=13)
        engine.offload_engine.swap_blocks = 0  # exhaust the budget
        try:
            ta, tb = await _run_pressure_pair(
                engine, [3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8, 1, 8]
            )
            assert ta and tb
            assert engine.sched.preempt_swap == 0
            assert engine.sched.preempt_recompute >= 1
            assert engine.offload_engine.swap_fallbacks >= 1
            assert engine.kv.allocator.used_pages == 0  # no leaked pages
        finally:
            await engine.stop()

    run(body())


def test_swap_copy_fail_chaos_recomputes_cleanly(run, injector):
    """offload.copy_fail on the swap snapshot: the swap-out declines and
    the victim takes the recompute path -- identical output, no leaked
    pages, counters advance."""

    prompt_a = [3, 1, 4, 1, 5, 9, 2, 6]
    prompt_b = [2, 7, 1, 8, 2, 8, 1, 8]

    async def body():
        baseline_engine = _pressure_engine(True, num_pages=41)
        try:
            baseline = await _run_pressure_pair(
                baseline_engine, prompt_a, prompt_b
            )
        finally:
            await baseline_engine.stop()

        injector.configure("seed=3;offload.copy_fail=1:match=swap/")
        engine = _pressure_engine(True, num_pages=13)
        try:
            out = await _run_pressure_pair(engine, prompt_a, prompt_b)
            assert out == baseline
            assert injector.fire_count("offload.copy_fail") >= 1
            assert engine.sched.preempt_swap == 0  # every swap-out declined
            assert engine.sched.preempt_recompute >= 1
            assert engine.offload_engine.swap_fallbacks >= 1
            assert engine.kv.allocator.used_pages == 0
            assert engine.offload_engine._swap_used == 0  # budget released
        finally:
            await engine.stop()

    run(body())


def test_swap_host_blob_path_token_identical(run):
    """With the device staging budget off, restores ride the host blob
    (the long-park spill) -- still token-identical, still counted."""

    prompt_a = [3, 1, 4, 1, 5, 9, 2, 6]
    prompt_b = [2, 7, 1, 8, 2, 8, 1, 8]

    async def body():
        roomy = _pressure_engine(True, num_pages=41)
        try:
            baseline = await _run_pressure_pair(roomy, prompt_a, prompt_b)
        finally:
            await roomy.stop()
        engine = _pressure_engine(True, num_pages=13)
        engine.offload_engine.swap_device_blocks = 0  # host restores only
        try:
            out = await _run_pressure_pair(engine, prompt_a, prompt_b)
            assert out == baseline
            assert engine.sched.preempt_swap >= 1
            assert engine.offload_engine.swap_ins >= 1
            det = engine.offload_engine.onboard_detail.get("swap")
            assert det is not None and det[0] > 0  # host-blob bytes moved
        finally:
            await engine.stop()

    run(body())


def test_swap_onboard_truncate_chaos_recomputes_cleanly(run, injector):
    """onboard.truncate on the swap restore: the ready blob is discarded
    and the lane recomputes -- identical output, no leaked pages."""

    prompt_a = [3, 1, 4, 1, 5, 9, 2, 6]
    prompt_b = [2, 7, 1, 8, 2, 8, 1, 8]

    async def body():
        baseline_engine = _pressure_engine(True, num_pages=41)
        try:
            baseline = await _run_pressure_pair(
                baseline_engine, prompt_a, prompt_b
            )
        finally:
            await baseline_engine.stop()

        injector.configure("seed=3;onboard.truncate=1:match=swap/")
        engine = _pressure_engine(True, num_pages=13)
        try:
            out = await _run_pressure_pair(engine, prompt_a, prompt_b)
            assert out == baseline
            assert injector.fire_count("onboard.truncate") >= 1
            assert engine.offload_engine.swap_fallbacks >= 1
            assert engine.kv.allocator.used_pages == 0
            assert engine.offload_engine._swap_used == 0
        finally:
            await engine.stop()

    run(body())


# -- eviction/onboard chaos + races -----------------------------------------


def test_evict_copy_fail_chaos_is_a_cache_miss(run, injector):
    """offload.copy_fail on eviction snapshots: blocks never land in G2,
    re-runs recompute instead of onboarding -- same output, counter moves."""

    async def body():
        injector.configure("seed=1;offload.copy_fail=1:match=evict/")
        prompt_a = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]
        engine = _offload_engine()
        try:
            first_a, _ = await collect(engine, req(prompt_a, max_tokens=4))
            for i in range(12):
                await collect(
                    engine,
                    req([(7 + p + i) % 30 for p in prompt_a], max_tokens=4),
                )
            engine.offload_engine.drain()
            assert injector.fire_count("offload.copy_fail") >= 1
            assert len(engine.offload) == 0, "failed copies must not land"
            second_a, _ = await collect(engine, req(prompt_a, max_tokens=4))
            assert second_a == first_a  # recompute reproduces the stream
            assert engine.kv.allocator.used_pages == 0
        finally:
            await engine.stop()

    run(body())


def test_prefix_onboard_truncate_chaos_recomputes(run, injector):
    """onboard.truncate on a tiered prefix onboard: the admission keeps
    its pages, prefills the whole prompt, and produces identical output
    with zero leaked pages."""

    async def body():
        prompt_a = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]
        engine = _offload_engine()
        try:
            first_a, _ = await collect(engine, req(prompt_a, max_tokens=4))
            pool = engine.sched.pool
            a_hashes = TokenBlockSequence(
                prompt_a, block_size=engine.sched.block_size
            ).sequence_hashes()
            for i in range(12):
                if not any(pool.is_registered(h) for h in a_hashes):
                    break
                await collect(
                    engine,
                    req([(7 + p + i) % 30 for p in prompt_a], max_tokens=4),
                )
            engine.offload_engine.drain()
            assert len(engine.offload) > 0
            injector.configure("seed=1;onboard.truncate=1")
            second_a, _ = await collect(engine, req(prompt_a, max_tokens=4))
            assert second_a == first_a
            assert injector.fire_count("onboard.truncate") >= 1
            assert engine.kv.allocator.used_pages == 0
        finally:
            await engine.stop()

    run(body())


def test_eviction_during_offload_race_preserves_content(run):
    """The freed pages are reused by new prefills immediately after the
    eviction dispatch; the offloaded snapshot must still hold the
    pre-reuse contents (device program order), proven by the onboarded
    re-run reproducing the original stream."""

    async def body():
        prompt_a = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]
        engine = _offload_engine()
        try:
            first_a, _ = await collect(engine, req(prompt_a, max_tokens=4))
            pool = engine.sched.pool
            a_hashes = TokenBlockSequence(
                prompt_a, block_size=engine.sched.block_size
            ).sequence_hashes()
            # churn back-to-back so every eviction's pages are re-prefilled
            # while its snapshot may still be materializing
            for i in range(12):
                if not any(pool.is_registered(h) for h in a_hashes):
                    break
                await asyncio.gather(
                    collect(
                        engine,
                        req([(7 + p + i) % 30 for p in prompt_a], max_tokens=4),
                    ),
                    collect(
                        engine,
                        req([(13 + p + i) % 30 for p in prompt_a], max_tokens=4),
                    ),
                )
            engine.offload_engine.drain()
            hits_before = engine.offload_engine.tier_hits["host"]
            second_a, _ = await collect(engine, req(prompt_a, max_tokens=4))
            assert second_a == first_a
            assert engine.offload_engine.tier_hits["host"] > hits_before
        finally:
            await engine.stop()

    run(body())


def test_host_ring_is_single_allocation():
    """The G2 store is one preallocated buffer: puts recycle slots, no
    per-put growth."""
    t = HostTier(4)
    for i in range(16):
        t.put(i, _blob(i), BlockMeta(position=i))
    assert len(t) == 4
    ring = t._ring["blob"]
    assert ring.shape[0] == 4
    for i in range(16, 32):
        t.put(i, _blob(i), BlockMeta(position=i))
    assert t._ring["blob"] is ring  # never reallocated
    blob, meta = t.get(31)
    assert np.array_equal(blob, _blob(31)) and meta.position == 31
    # returned blobs are decoupled from slot recycling
    for i in range(32, 40):
        t.put(i, _blob(i), BlockMeta())
    assert np.array_equal(blob, _blob(31))


def test_kv_offload_engine_lookup_is_ram_only(tmp_path):
    """lookup() never blocks on disk: a G3-only block misses, the async
    promote runs on the offload thread, and the retry hits in RAM."""
    eng = KVOffloadEngine(2, 8, str(tmp_path / "g3"))
    try:
        eng.disk.put(99, _blob(99), BlockMeta(position=7))
        assert eng.lookup(99) is None  # disk-only: schedules the promote
        eng.drain()
        hit = eng.lookup(99)
        assert hit is not None
        blob, meta, tier = hit
        assert tier == "host" and meta.position == 7
        assert np.array_equal(blob, _blob(99))
        # the promote is counted as a promote, the served lookup as the
        # hit -- a promoted-but-unserved block must not inflate warmth
        assert eng.disk_promotes == 1 and eng.tier_hits["host"] == 1
        assert eng.tier_hits["disk"] == 0
        assert 0.0 < eng.tier_hit_rate <= 1.0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# the stored and the wire form, byte for byte (ISSUE 52): the bytes below
# are spelled here, in the format the tree wrote before kv_cache owned it
# ---------------------------------------------------------------------------

_GOLDEN_SHAPE = (1, 2, 1, 2, 1, 2)  # [layers, 2, pages, page, Hkv, D]
_GOLDEN = {
    "dense": dict(
        dtype="float32",
        blob=np.arange(8, dtype=np.float32).reshape(_GOLDEN_SHAPE),
        # eight little-endian float32: 0.0 .. 7.0
        payload=b"".join(
            bytes.fromhex(h) for h in (
                "00000000", "0000803f", "00000040", "00004040",
                "00008040", "0000a040", "0000c040", "0000e040",
            )
        ),
    ),
    "quant": dict(
        dtype="int8",
        blob=np.array([1, -2, 3, -4, 5, -6, 7, -127], np.int8).reshape(
            _GOLDEN_SHAPE),
        blob_scales=np.array([0.5, 1.0, 2.0, 0.25], np.float32).reshape(
            _GOLDEN_SHAPE[:4]),
        # the int8 data, then the float32 row scales
        payload=bytes.fromhex("01fe03fc05fa0781")
        + bytes.fromhex("0000003f" "0000803f" "00000040" "0000803e"),
    ),
}


def _golden_blob(form):
    from dynamo_tpu.engine.kv_cache import QuantKV

    g = _GOLDEN[form]
    if form == "quant":
        return QuantKV(q=g["blob"], s=g["blob_scales"])
    return g["blob"]


def _assert_golden(form, got):
    g = _GOLDEN[form]
    if form == "quant":
        assert type(got).__name__ == "QuantKV"
        data, scales = got.q, got.s
        assert scales.dtype == np.float32
        np.testing.assert_array_equal(scales, g["blob_scales"])
    else:
        assert isinstance(got, np.ndarray)
        data = got
    assert data.dtype == np.dtype(g["dtype"]) and data.shape == _GOLDEN_SHAPE
    np.testing.assert_array_equal(data, g["blob"])


@pytest.mark.parametrize("form", ["dense", "quant"])
def test_a_disk_block_keeps_its_stored_form(form, tmp_path):
    g = _GOLDEN[form]
    tier = DiskTier(str(tmp_path), capacity_blocks=4)
    # a block as the parent wrote it: one .npz, the data under ``blob``, an
    # int8 pool's row scales under ``blob_scales``, the meta beside them
    arrays = {k: g[k] for k in ("blob", "blob_scales") if k in g}
    np.savez(tier._path(7), **arrays, block_hash=3, parent_sequence_hash=2,
             position=1, kv_dtype=g["dtype"])
    tier._lru[7] = None
    got, meta = tier.get(7)
    _assert_golden(form, got)
    assert (meta.block_hash, meta.parent_sequence_hash, meta.position,
            meta.kv_dtype) == (3, 2, 1, g["dtype"])
    # and what the change writes is that file
    tier.put(8, _golden_blob(form), meta)
    with np.load(tier._path(8)) as z:
        assert sorted(z.files) == sorted(
            [*arrays, "block_hash", "parent_sequence_hash", "position",
             "kv_dtype"])
        for k, a in arrays.items():
            assert z[k].dtype == a.dtype
            np.testing.assert_array_equal(z[k], a)
        assert (int(z["block_hash"]), int(z["position"]),
                str(z["kv_dtype"])) == (3, 1, g["dtype"])


@pytest.mark.parametrize("form", ["dense", "quant"])
def test_a_wire_frame_keeps_its_bytes(form):
    import struct

    from dynamo_tpu.engine.kv_cache import (
        blob_from_bytes,
        blob_nbytes,
        blob_to_bytes,
    )
    from dynamo_tpu.offload import pack_kv_blob_frame, unpack_kv_blob_frame

    g = _GOLDEN[form]
    # the payload of every transfer (disagg delivery, prefix onboard, G4)
    assert blob_to_bytes(_golden_blob(form)) == g["payload"]
    assert blob_nbytes(_GOLDEN_SHAPE, g["dtype"]) == len(g["payload"])
    _assert_golden(
        form, blob_from_bytes(g["payload"], _GOLDEN_SHAPE, g["dtype"]))
    # the G4 frame around it: u32-LE header length | JSON header | payload
    hdr = (
        '{"v": 1, "kind": "%s", "dtype": "%s", "shape": [1, 2, 1, 2, 1, 2], '
        '"meta": {"block_hash": 5, "parent_sequence_hash": 4, "position": 3, '
        '"kv_dtype": "%s"}, "payload_nbytes": %d}'
        % (form, g["dtype"], g["dtype"], len(g["payload"]))
    ).encode()
    frame = struct.pack("<I", len(hdr)) + hdr + g["payload"]
    meta = BlockMeta(block_hash=5, parent_sequence_hash=4, position=3,
                     kv_dtype=g["dtype"])
    assert pack_kv_blob_frame(_golden_blob(form), meta) == frame
    got, got_meta = unpack_kv_blob_frame(frame)
    _assert_golden(form, got)
    assert got_meta == meta
