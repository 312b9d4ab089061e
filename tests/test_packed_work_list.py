"""The packed launch over a pair pool as a work list
(``ops.ragged_attention._work_list_kernel``): interpret mode on the CPU,
against the XLA twin ``packed_ragged_attention_xla``.

The kernel reads every key from the pool, so a case scatters the dispatch's
fresh rows first, as ``step.packed_unified_step`` does; the twin reads the
pool below ``base`` and the fresh rows beside it.  The int8 pool keeps the
grid kernel and is compared on that path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import attention as att
from dynamo_tpu.engine.kv_cache import QuantKV, quantize_kv_blob
from dynamo_tpu.ops import ragged_attention as ra

PAGE, D, LAYER = 8, 128, 1


def _case(Hq, Hkv, bases, qlens, *, window=0, seed=0, dtype=np.float32,
          poison=False, s_max=None):
    """A packed dispatch of lanes at ``bases`` bringing ``qlens`` fresh rows:
    every lane owns pages for ``base + q`` positions.  With ``poison`` every
    page wholly behind a lane's window is one shared page of NaN."""
    rs = np.random.RandomState(seed)
    B = len(bases)
    need = [-(-(b + n) // PAGE) for b, n in zip(bases, qlens)]
    P = 1
    while P < max(need + [1]):
        P *= 2
    num_pages = 2 + sum(need)
    pool = rs.randn(2, 2, num_pages, PAGE, Hkv, D).astype(np.float32)
    pt = np.zeros((B, P), np.int32)
    nxt = 1
    for b in range(B):
        pt[b, : need[b]] = nxt + np.arange(need[b])
        nxt += need[b]
    if poison:
        pool[:, :, num_pages - 1] = np.nan
        for b in range(B):
            if qlens[b]:
                behind = max(bases[b] - window + 1, 0) // PAGE
                pt[b, :behind] = num_pages - 1
    qlens = np.asarray(qlens, np.int32)
    if s_max is None:
        s_max = 1
        while s_max < max(int(qlens.max()), 1):
            s_max *= 2
    seg_off = np.zeros((B,), np.int32)
    lane, rel, off, end = [], [], 0, 1
    for b in range(B):
        n = int(qlens[b])
        if n:
            seg_off[b] = off
            lane += [b] * n
            rel += list(range(n))
            end = max(end, off + s_max)
            off += n
    Np = 1
    while Np < max(off, end):
        Np *= 2
    lane = np.asarray(lane + [B] * (Np - off), np.int32)
    rel = np.asarray(rel + [0] * (Np - off), np.int32)
    q = rs.randn(Np, Hq, D).astype(np.float32)
    k = rs.randn(Np, Hkv, D).astype(np.float32)
    v = rs.randn(Np, Hkv, D).astype(np.float32)
    arrays = [jnp.asarray(x, dtype) for x in (q, k, v, pool)] + [
        jnp.asarray(x) for x in
        (pt, np.asarray(bases, np.int32), seg_off, qlens, lane, rel)
    ]
    return arrays, s_max, off


def _scatter(pool, k, v, pt, base, lane, rel):
    B = pt.shape[0]
    pos = base[jnp.clip(lane, 0, B - 1)] + rel
    return att.write_packed_kv(pool, k, v, pt, lane, pos, lane < B, LAYER)


CASES = {
    # a lane of several query blocks (300 rows: two of 256) beside one-row
    # decode lanes, no window
    "blocks_beside_decode": dict(
        Hq=4, Hkv=2, bases=[24, 700, 0, 3], qlens=[300, 1, 130, 1]),
    # a window shorter than the context: every page wholly behind it is
    # poisoned, so a fetch of one shows as NaN in the result
    "window_behind_is_never_fetched": dict(
        Hq=4, Hkv=2, bases=[1100, 1500, 0, 37], qlens=[300, 1, 130, 1],
        window=100, poison=True),
    # a window that cuts inside the fresh rows themselves
    "window_inside_the_chunk": dict(
        Hq=4, Hkv=2, bases=[0, 90], qlens=[200, 1], window=64),
    # a prefix hit: the chunk starts at base > 0, not on a page boundary
    "prefix_hit": dict(Hq=4, Hkv=2, bases=[533, 12], qlens=[70, 9]),
    # idle lanes between live ones; the packed axis ends in padding rows
    "idle_lanes_and_padding": dict(
        Hq=4, Hkv=2, bases=[16, 0, 11, 0, 24], qlens=[1, 0, 5, 0, 1]),
    # folded verify columns: segments of 1 + draft rows beside decode rows
    "verify_columns": dict(
        Hq=4, Hkv=2, bases=[40, 77, 130, 9], qlens=[5, 1, 3, 9]),
    # the (lanes, 1) first step of a fused decode dispatch: one tile
    "decode_step": dict(
        Hq=4, Hkv=2, bases=[600, 1000, 0, 3], qlens=[1, 1, 0, 1], window=512),
    # no grouping: a query head a kv head
    "mha": dict(Hq=2, Hkv=2, bases=[20, 300], qlens=[40, 1]),
    # eight query heads a kv head
    "gqa8": dict(Hq=8, Hkv=1, bases=[20, 300], qlens=[40, 1], window=128),
}


@pytest.mark.parametrize("name", list(CASES) + ["bf16", "int8_grid"])
def test_packed_work_list_matches_xla(name):
    if name == "int8_grid":
        return _int8_takes_the_grid_kernel()
    kw = dict(CASES.get(name) or CASES["blocks_beside_decode"])
    dtype = jnp.bfloat16 if name == "bf16" else np.float32
    window = kw.get("window", 0)
    (q, k, v, pool, pt, base, off, lens, lane, rel), s_max, total = _case(
        dtype=dtype, **kw)
    # the twin multiplies a masked key by zero, the kernel never reads it
    clean = jnp.nan_to_num(pool)
    ref = np.asarray(ra.packed_ragged_attention_xla(
        q, k, v, clean, pt, base, off, lens, lane, rel, s_max, LAYER, window,
    ).astype(jnp.float32))
    assert ra._takes_work_list(D, False)
    got = np.asarray(ra.packed_ragged_attention(
        q, k, v, _scatter(pool, k, v, pt, base, lane, rel), pt, base, off,
        lens, s_max, LAYER, window, interpret=True,
    ).astype(jnp.float32))
    assert got.shape == q.shape  # [Np, Hq, D]: what the roofline reader keys on
    tol = 3e-2 if name == "bf16" else 2e-5
    np.testing.assert_allclose(got[:total], ref[:total], rtol=tol, atol=tol)
    # rows no lane owns come out as zeros, like the twin's
    assert not got[total:].any() and not ref[total:].any()


def _int8_takes_the_grid_kernel():
    """An int8 pool keeps the grid kernel, which dequantizes the row scales
    in the read and takes the fresh rows from ``k``/``v``; written first or
    not, the pool below ``base`` is all it reads."""
    (q, k, v, pool, pt, base, off, lens, lane, rel), s_max, total = _case(
        4, 2, [24, 40, 0], [30, 1, 13])
    assert not ra._takes_work_list(D, True)
    blob = quantize_kv_blob(np.asarray(pool))
    quant = QuantKV(q=jnp.asarray(blob.q), s=jnp.asarray(blob.s))
    ref = np.asarray(ra.packed_ragged_attention_xla(
        q, k, v, quant, pt, base, off, lens, lane, rel, s_max, LAYER))
    written = _scatter(quant, k, v, pt, base, lane, rel)
    got = np.asarray(ra.packed_ragged_attention(
        q, k, v, written.q, pt, base, off, lens, s_max, LAYER, group=2,
        interpret=True, kv_scales=written.s,
    ))
    np.testing.assert_allclose(got[:total], ref[:total], rtol=2e-5, atol=2e-5)


def _eqns(jaxpr):
    """Every equation under ``jaxpr``, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for x in p if isinstance(p, (list, tuple)) else [p]:
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _count(jaxpr, name):
    return sum(eqn.primitive.name == name for eqn in _eqns(jaxpr))


def test_dma_descriptors_do_not_grow_with_the_key_block(monkeypatch):
    """The set-up budget, where a CPU can guard it: a key block's pages are
    copied in rolled loops, so the kernel's jaxpr holds as many DMA starts
    at 512 keys a block as at 128 (PR 31's kernel was refused for the
    seconds its unrolled copies cost every executable's trace)."""
    (q, _k, _v, pool, pt, base, off, lens, _l, _r), s_max, _ = _case(
        4, 2, [24, 700], [300, 1])

    def starts(keys):
        monkeypatch.setattr(ra, "_WL_KEY_BLOCK", keys)
        jaxpr = jax.make_jaxpr(
            lambda *a: ra._packed_work_list_attention(
                *a, s_max=s_max, layer=LAYER, window=64, interpret=False)
        )(q, pool, pt, base, off, lens)
        return _count(jaxpr.jaxpr, "dma_start"), _count(jaxpr.jaxpr, "dma_wait")

    at_128, at_512 = starts(128), starts(512)
    assert at_128 == at_512
    # a tile: queries in, a page's K and V in one copy at the first fetch
    # and at the next block's, rows out
    assert at_512[0] <= 4 * len(ra._work_list_tiles(s_max, q.dtype)[1])


def test_item_counts_follow_the_work_list():
    """The tick's ``dispatch`` annotation counts the launch's items and its
    small tiles on the host as the device builds them."""
    from dynamo_tpu.ops.latent_attention import packed_work_list

    lens = [300, 1, 0, 256, 8, 9, 513]
    assert ra.packed_item_counts(lens, 512) == (2 + 1 + 1 + 1 + 1 + 3, 3)
    assert ra.packed_item_counts([1, 0, 1, 1], 1) == (3, 3)
    qb = ra._work_list_tiles(512, jnp.bfloat16)[0]
    rows = np.asarray(packed_work_list(
        jnp.zeros(7, jnp.int32), jnp.zeros(7, jnp.int32),
        jnp.asarray(lens, jnp.int32), 2048, qb)[3])
    assert (rows > 0).sum() == 9
    assert ((rows > 0) & (rows <= ra._WL_SMALL_ROWS)).sum() == 3


def test_one_step_dispatches_take_the_whole_page_table(run, monkeypatch):
    """Where the packed launch walks a work list, a dispatch of one step is
    handed the page table at its full width (no executable per bucket of
    it); the fused steps, whose decode kernel walks the table, keep the
    bucket.  The tokens are the same either way."""
    from dynamo_tpu.engine import attention as att
    from tests.test_request_stages import collect, req, tiny_engine

    def served(work_list):
        monkeypatch.setattr(
            att, "packed_launch",
            lambda *a: att.PackedLaunch(work_list, lambda Np, s_max: True))
        engine = tiny_engine(max_seq_len=512, num_pages=300, page_size=4)
        assert engine._packed_full_table is work_list
        widths = {}
        for name in ("packed_unified_step", "packed_unified_multistep"):
            fn = getattr(engine._fns, name)

            def spy(*a, _fn=fn, _name=name, **kw):
                widths.setdefault(_name, set()).add(a[8].shape[1])
                return _fn(*a, **kw)

            monkeypatch.setattr(engine._fns, name, spy)

        async def body():
            try:
                return await collect(
                    engine, req(list(range(1, 40)), max_tokens=24))
            finally:
                await engine.stop()

        return run(body()), widths, engine.sched.max_pages

    want, bucketed, full = served(False)
    got, widths, _ = served(True)
    assert got == want
    assert min(bucketed["packed_unified_step"]) < full
    assert widths["packed_unified_step"] == {full}
    fused = "packed_unified_multistep"
    assert widths[fused] == bucketed[fused] and min(widths[fused]) < full


def _pallas_calls(jaxpr):
    """``(name, grid rank)`` of every ``pallas_call`` under ``jaxpr``."""
    return [
        (eqn.params["name"], len(eqn.params["grid_mapping"].grid))
        for eqn in _eqns(jaxpr) if eqn.primitive.name == "pallas_call"
    ]


# what ``attention.packed_launch`` says of a pool, beside the kernel the
# step's dispatch then traces over it: (walks a work list, (name, grid rank))
LAUNCHES = {
    "bf16_d128": (True, ("packed_ragged_attention", 1)),
    "int8_d128": (False, ("packed_ragged_attention", 2)),
    "bf16_d64": (False, ("packed_ragged_attention", 2)),
    "latent": (True, ("latent_packed_attention", 1)),
    "cpu": (False, None),
}


@pytest.mark.parametrize("name", list(LAUNCHES))
def test_packed_launch_reports_what_the_dispatch_traces(name, monkeypatch):
    """The rule "work list or grid, Pallas or XLA, fits or not" is written
    once (``attention._packed_backend``): what the engine is told at
    construction is the launch its step traces."""
    from dynamo_tpu.engine.kv_cache import LatentKV

    work_list, kernel = LAUNCHES[name]
    monkeypatch.setattr(att, "_on_tpu", lambda: name != "cpu")
    Np, s_max, Hq, Hkv, B, P = 32, 16, 4, 2, 2, 4
    d = 64 if name == "bf16_d64" else D
    pt = jnp.zeros((B, P), jnp.int32)
    vec = jnp.zeros((B,), jnp.int32)
    row = jnp.zeros((Np,), jnp.int32)
    if name == "latent":
        Hkv, d = 1, 192  # a row [c_kv | k_r] of 128 + 64, two layers a slab
        pool = LatentKV(jnp.zeros((1, 1, 9, PAGE, 1, 2 * d), jnp.bfloat16), 128)
    else:
        pool = jnp.zeros((2, 2, 9, PAGE, Hkv, d), jnp.bfloat16)
        if name == "int8_d128":
            pool = QuantKV(q=pool.astype(jnp.int8),
                           s=jnp.zeros(pool.shape[:4], jnp.float32))
    q = jnp.zeros((Np, Hq, d), jnp.bfloat16)
    k = jnp.zeros((Np, Hkv, d), jnp.bfloat16)

    def step_attention(q, k, pool):
        if name == "latent":
            return att.latent_packed_attention_dispatch(
                q, k, pool, 1, pt, vec, vec, vec, row, row, row, row < Np,
                s_max)[0]
        return att.packed_ragged_attention_dispatch(
            q, k, k, pool, 1, pt, vec, vec, vec, row, row, s_max)

    launch = att.packed_launch(pool, Hq, Hkv, d, jnp.bfloat16)
    traced = _pallas_calls(jax.make_jaxpr(step_attention)(q, k, pool).jaxpr)
    assert traced == ([kernel] if kernel else [])
    assert launch.walks_work_list is work_list
    # only the grid kernel, which holds the packed operands, bounds a shape
    grid = kernel is not None and kernel[1] == 2
    assert launch.fits(1024, 512)
    assert launch.fits(1 << 20, 1 << 19) is not grid


def test_budget_the_grid_kernel_cannot_hold_fails_construction(monkeypatch):
    """The engine's part of the rule: the widest shape its mixed budget can
    mint must pass the launch's ``fits`` at construction, and the error
    names the largest budget that does (the tiny model's 16-wide heads
    take the grid kernel, which holds the packed operands in VMEM)."""
    from tests.test_request_stages import tiny_engine

    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    with pytest.raises(ValueError, match="largest budget that fits is") as e:
        tiny_engine(page_size=8, mixed_token_budget=1 << 16)
    ok = int(str(e.value).rsplit(" ", 1)[1])
    assert 8 < ok < 1 << 16
    engine = tiny_engine(page_size=8, mixed_token_budget=ok)
    assert engine._packed_fits(2 * ok, ok) and not engine._packed_full_table
