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
          poison=False, s_max=None, D=D):
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
# Two KV heads of 256, eight query heads a head (Qwen3-Next's): a page as one
# matrix is no view of such a pool (``ra._pages_are_matrices``), and a one-row
# item reads its key blocks a token a row of heads, a kv head's own query
# rows against that head's keys (PR 54).  Contexts cross several key blocks.
WIDE = dict(Hq=16, Hkv=2, D=256)
CASES.update({
    # the (lanes, 1) launch: one-row items alone, an idle lane among them
    "wide_one_row_items": dict(
        WIDE, bases=[600, 1500, 0, 0, 3, 511, 512], qlens=[1, 1, 0, 1, 1, 1, 1]),
    # one-row items behind a 256-row tile (and a 44-row one)
    "wide_rows_behind_a_256_row_tile": dict(
        WIDE, bases=[24, 700, 1100, 3], qlens=[300, 1, 1, 1]),
    # a window: the pages behind it are poisoned
    "wide_window": dict(
        WIDE, bases=[1100, 1500, 0, 37], qlens=[130, 1, 1, 1], window=100,
        poison=True),
    # lanes with no rows between live ones; the axis ends in padding
    "wide_idle_lanes": dict(
        WIDE, bases=[516, 0, 11, 0, 1024], qlens=[1, 0, 5, 0, 1]),
    # four heads of 256, two query heads a head: the same body
    "wide_four_heads": dict(
        Hq=8, Hkv=4, D=256, bases=[20, 600], qlens=[9, 1]),
})
# launches of the one-row tile alone: the serial kernel has no such tile
ONE_ROW_LAUNCHES = ("decode_step", "wide_one_row_items")


@pytest.mark.parametrize(
    "name", list(CASES) + ["bf16", "wide_bf16", "int8_grid"])
def test_packed_work_list_matches_xla(name):
    if name == "int8_grid":
        return _int8_takes_the_grid_kernel()
    kw = dict(CASES.get(name) or CASES[
        "wide_rows_behind_a_256_row_tile" if name == "wide_bf16"
        else "blocks_beside_decode"])
    dtype = jnp.bfloat16 if name.endswith("bf16") else np.float32
    window = kw.get("window", 0)
    (q, k, v, pool, pt, base, off, lens, lane, rel), s_max, total = _case(
        dtype=dtype, **kw)
    # the twin multiplies a masked key by zero, the kernel never reads it
    clean = jnp.nan_to_num(pool)
    ref = np.asarray(ra.packed_ragged_attention_xla(
        q, k, v, clean, pt, base, off, lens, lane, rel, s_max, LAYER, window,
    ).astype(jnp.float32))
    assert ra._takes_work_list(q.shape[2], False)
    assert ra._pages_are_matrices(*k.shape[1:]) is not name.startswith("wide")
    got = np.asarray(ra.packed_ragged_attention(
        q, k, v, _scatter(pool, k, v, pt, base, lane, rel), pt, base, off,
        lens, s_max, LAYER, window, interpret=True,
    ).astype(jnp.float32))
    assert got.shape == q.shape  # [Np, Hq, D]: what the roofline reader keys on
    tol = 3e-2 if name.endswith("bf16") else 2e-5
    np.testing.assert_allclose(got[:total], ref[:total], rtol=tol, atol=tol)
    # rows no lane owns come out as zeros, like the twin's
    assert not got[total:].any() and not ref[total:].any()


# Window-free shapes: the engine hands this kernel the smallest minted shape
# that holds a dispatch's rows (``bucketing.PackedShapeBudget`` under
# ``PackedLaunch.item_rows``), so a tile may reach the end of the packed axis
# and a segment may be longer than the triple's ``s_max``.  ``(lanes' fresh
# rows, s_max of the launch)``; every case packs into ``Np`` 512.
FULL_AXIS_CASES = {
    # total == Np: a 496-row chunk between decode rows, one in the last row
    "decode_row_in_the_last_row": dict(
        qlens=[1] * 5 + [496] + [1] * 11, s_max=256),
    # a chunk whose tail block starts inside the last 256 rows
    "tail_block_overhangs": dict(qlens=[1] * 5 + [500], s_max=256),
    # two chunks in one dispatch, the second's tail block clamped
    "two_chunks": dict(qlens=[200, 1, 300, 1], s_max=256),
    # a segment longer than the triple's s_max, decode rows behind it whose
    # small tiles start early over rows the chunk's wide tile wrote
    "segment_longer_than_s_max": dict(qlens=[506] + [1] * 6, s_max=256),
    # the same under a window, as a two-kind trunk's window layer launches it
    "window": dict(qlens=[506] + [1] * 6, s_max=256, window=64,
                   suffix="_window"),
    # a short segment at a small query block: several small-tile items
    "small_blocks": dict(qlens=[1] * 3 + [21] + [1] * 6, s_max=16, Np=32),
    # PR 40's case, extended (PR 46): two wide tiles, then eight one-row
    # items whose 8-row tiles all start at row 504, over the second wide
    # tile's span and over each other; each reads its span of the output
    # only after the item before it has landed there
    "wide_tile_then_eight_rows": dict(qlens=[504] + [1] * 8, s_max=256),
}


@pytest.mark.parametrize("name", list(FULL_AXIS_CASES))
def test_packed_work_list_fills_the_axis(name):
    """Against the twin on the windowed layout of the same lanes (``Np``
    padded for every lane's window): the first ``total`` rows of both
    layouts are the same segments."""
    kw = dict(FULL_AXIS_CASES[name])
    qlens, s_max, window = kw["qlens"], kw["s_max"], kw.get("window", 0)
    bases = [(37 * (b + 1)) % 300 for b in range(len(qlens))]
    (q, k, v, pool, pt, base, off, lens, lane, rel), s_nat, total = _case(
        4, 2, bases, qlens, window=window)
    ref = np.asarray(ra.packed_ragged_attention_xla(
        q, k, v, pool, pt, base, off, lens, lane, rel, s_nat, LAYER, window))
    Np = kw.get("Np", 512)
    assert Np // 2 < total <= Np < q.shape[0]  # the windowed layout is wider
    written = _scatter(pool, k, v, pt, base, lane, rel)
    got = np.asarray(ra.packed_ragged_attention(
        q[:Np], k[:Np], v[:Np], written, pt, base, off, lens, s_max, LAYER,
        window, interpret=True, name_suffix=kw.get("suffix", ""),
    ))
    assert got.shape == (Np,) + q.shape[1:]
    np.testing.assert_allclose(got[:total], ref[:total], rtol=2e-5, atol=2e-5)
    assert not got[total:].any()  # rows no item owns stay zero


def test_a_tile_wider_than_the_axis_is_refused():
    (q, k, v, pool, pt, base, off, lens, _l, _r), _, _ = _case(
        4, 2, [3], [5])
    with pytest.raises(ValueError, match="does not fit"):
        ra.packed_ragged_attention(
            q[:8], k[:8], v[:8], pool, pt, base, off, lens, 16, LAYER,
            interpret=True)


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


# -- the decode launch of the fused steps: the same kernel, one item a lane ----


def _decode_case(lens, *, window=0, P=1024, Hq=4, Hkv=2, seed=0,
                 dtype=np.float32, pool_dtype=None, D=D):
    """Decode lanes holding ``lens`` tokens (the new one's row already in
    the pool) under a table ``P`` pages wide, far wider than any lane's
    pages.  Every entry a lane must not read, past its allocation or wholly
    behind its window (a released page's stale entry), points at one page
    of NaN: a fetch of it shows in the result."""
    rs = np.random.RandomState(seed)
    B = len(lens)
    need = [-(-n // PAGE) for n in lens]
    num_pages = 2 + sum(need)
    pool = rs.randn(2, 2, num_pages, PAGE, Hkv, D).astype(np.float32)
    pool[:, :, num_pages - 1] = np.nan
    pt = np.full((B, P), num_pages - 1, np.int32)
    nxt = 1
    for b in range(B):
        behind = max(lens[b] - window, 0) // PAGE if window else 0
        pt[b, behind: need[b]] = nxt + np.arange(behind, need[b])
        nxt += need[b]
    q = rs.randn(B, Hq, D).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(pool, pool_dtype or dtype),
            jnp.asarray(pt), jnp.asarray(lens, jnp.int32))


def _assert_decode_rows(got, q, pool, pt, lens, layer, window, tol):
    """``got`` against the XLA gather on the layer's slice; a lane without a
    token has no item and keeps its zeros."""
    ref = np.asarray(att.paged_decode_attention(
        q, jnp.nan_to_num(pool[layer]), pt, lens, window).astype(jnp.float32))
    got = np.asarray(got.astype(jnp.float32))
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(got[live], ref[live], rtol=tol, atol=tol)
    assert not got[~live].any()


KEY_BLOCK = 512  # ra._WL_KEY_BLOCK at this file's page of 8
DECODE_CASES = {
    # no token, one token, exactly a page, exactly a key block, one past it
    "edges": dict(lens=[0, 1, PAGE, KEY_BLOCK, KEY_BLOCK + 1, 600, 0, 77]),
    # mistral-7b's window: at it, one past it, well past it, far under it
    "window_4096": dict(lens=[4096, 4097, 5000, 100], window=4096),
    # mellum2's: the window's first key in the middle of a page and a block
    "window_1024": dict(lens=[1024, 1025, 3003, 7, 0], window=1024),
    "gqa8": dict(lens=[300, 1, 1029], window=128, Hq=8, Hkv=1),
    "mha": dict(lens=[520, 9], Hq=2, Hkv=2),
    "bf16": dict(lens=[0, 1, 513, 2050], window=1024, dtype=jnp.bfloat16),
    # an explicit float32 pool under a bf16 model: a key block is converted
    # in VMEM, as the packed launch over that pool does
    "f32_pool": dict(lens=[1, 513, 700], dtype=jnp.bfloat16,
                     pool_dtype=jnp.float32),
    # two KV heads of 256, eight query heads a head: the one-row tile over
    # the pool's own form (PR 54)
    "wide_edges": dict(
        WIDE, lens=[0, 1, PAGE, KEY_BLOCK, KEY_BLOCK + 1, 1600, 0, 77]),
    "wide_window_1024": dict(WIDE, lens=[1024, 1025, 3003, 7, 0], window=1024),
    "wide_bf16": dict(
        WIDE, lens=[0, 1, 513, 2050], window=1024, dtype=jnp.bfloat16),
    "wide_four_heads": dict(Hq=8, Hkv=4, D=256, lens=[520, 9, 0, 1100]),
}


# lists that take every hand-over between the items of a launch (PR 46, the
# section further down)
HANDOVER_DECODE = {
    # nothing before it, nothing behind it
    "one_live_item": dict(lens=[0, 0, 700, 0]),
    # an idle lane parts two runs: the item behind it fetches for itself
    "live_idle_live": dict(lens=[300, 0, 300, 0, 0, 41]),
    # the one block of the first item is its last: it brings at once, for an
    # item that then fetches six more blocks; and the reverse
    "one_block_then_seven": dict(lens=[100, 3500]),
    "seven_blocks_then_one": dict(lens=[3500, 100]),
    # 2, 3, 1, 3, 2, 1, 2 blocks: items start on either slot, and end on the
    # slot they started on (odd) and on the other (even)
    "ends_on_either_slot": dict(lens=[600, 1100, 100, 1030, 520, 9, 513]),
    # a window layer: the first block brought is block 3, 0, 2 and 7
    "window_first_block_is_not_block_0": dict(
        lens=[3003, 1025, 2050, 5000], window=1024),
    # no token in the first, a middle and the last lane
    "no_token_first_middle_last": dict(lens=[0, 300, 0, 513, 40, 0]),
    # the slots a token a row of heads: items of 2, 3, 1 and 3 blocks, an idle
    # lane between them, under a window whose first block is not block 0
    "wide_ends_on_either_slot": dict(WIDE, lens=[600, 1100, 0, 100, 1030]),
    "wide_window": dict(WIDE, lens=[1500, 0, 1025, 40], window=512),
}


@pytest.mark.parametrize("name", list(DECODE_CASES) + list(HANDOVER_DECODE))
def test_decode_work_list_matches_the_gather(name):
    kw = dict(DECODE_CASES.get(name) or HANDOVER_DECODE[name])
    window = kw.get("window", 0)
    q, pool, pt, lens = _decode_case(**kw)
    got = ra.decode_work_list_attention(
        q, pool, pt, lens, LAYER, window, interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    tol = 3e-2 if q.dtype == jnp.bfloat16 else 2e-5
    _assert_decode_rows(got, q, pool, pt, lens, LAYER, window, tol)


def test_decode_launch_of_a_two_kind_trunk(monkeypatch):
    """Through ``attention.layer_view`` and the dispatch's gate, as
    ``step._decode_once`` runs a layer: a window layer of a two-kind trunk
    reads its own pool by its own table, whose entries behind the window are
    stale (the pages were released; here they point at NaN), under the name
    ``paged_decode_attention_window``; a full layer reads every key under
    ``paged_decode_attention``.  One launch a layer, and it is not the
    packed one."""
    from jax.experimental.pallas import tpu as pltpu

    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.kv_cache import KindKV

    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    cfg = ModelConfig.tiny(
        num_layers=8, head_dim=D, sliding_window=64,
        layer_pattern=("sliding", "sliding", "sliding", "full"))
    lens = [300, 65, 1, 0]
    q, full, pt_full, kv_lens = _decode_case(lens, P=64, seed=1)
    _, win, pt_win, _ = _decode_case(lens, window=64, P=64, seed=2)
    win = jnp.concatenate([win] * 3)  # six window layers, two full
    kv, table = KindKV(full, win), jnp.stack([pt_full, pt_win])
    k_new = jnp.ones((len(lens), 2, D), q.dtype)

    def layer(kind, index):
        def run(q, kv, table, kv_lens):
            lv = att.layer_view(cfg, kv, table, index, kind)
            pos = jnp.maximum(kv_lens - 1, 0)
            pool = att.write_decode_kv(
                lv.kv, k_new, k_new, lv.table, pos, lv.layer)
            out = att.decode_attention_dispatch(
                q, pool, lv.table, kv_lens, lv.layer, lv.window, lv.suffix)
            return out, pool, lv.layer

        return run

    # layer 6 is the second period's last window layer: index 5 of its pool
    for kind, index, at, window, name in (
        ("sliding", 6, 5, 64, "paged_decode_attention_window"),
        ("full", 7, 1, 0, "paged_decode_attention"),
    ):
        run = layer(kind, index)
        assert _pallas_calls(
            jax.make_jaxpr(run)(q, kv, table, kv_lens).jaxpr) == [(name, 1)]
        with pltpu.force_tpu_interpret_mode():
            got, pool, idx = jax.block_until_ready(run(q, kv, table, kv_lens))
        assert int(idx) == at
        _assert_decode_rows(
            got, q, pool, table[int(kind == "sliding")], kv_lens, at, window,
            2e-5)


# -- the items of a launch overlap (PR 46) -------------------------------------
#
# An item that a live item follows starts that item's queries and first key
# block as it enters its own last block, and leaves its output copy in flight
# for that item to wait for.  At tiles of several rows the arithmetic is PR
# 45's: those launches are held bit for bit to the kernel with PR 45's serial
# order of copies (``tests/work_list_serial.py``).  The one-row tile (every
# decode launch) multiplies a query head a row with the key block as it lies,
# which sums a softmax in another order: its launches are held to the gather,
# in the plain interpreter and in the TPU's, over lists that take every
# hand-over.



def _packed_call(name):
    """A packed case of ``CASES`` or ``FULL_AXIS_CASES`` as the arguments of
    ``packed_ragged_attention``."""
    if name in CASES:
        kw = dict(CASES[name])
        window = kw.get("window", 0)
        (q, k, v, pool, pt, base, off, lens, lane, rel), s_max, _ = _case(**kw)
        Np, suffix = q.shape[0], ""
    else:
        kw = dict(FULL_AXIS_CASES[name])
        qlens, s_max, window = kw["qlens"], kw["s_max"], kw.get("window", 0)
        bases = [(37 * (b + 1)) % 300 for b in range(len(qlens))]
        (q, k, v, pool, pt, base, off, lens, lane, rel), _, _ = _case(
            4, 2, bases, qlens, window=window)
        Np, suffix = kw.get("Np", 512), kw.get("suffix", "")
    written = _scatter(pool, k, v, pt, base, lane, rel)
    return (q[:Np], k[:Np], v[:Np], written, pt, base, off, lens, s_max,
            LAYER, window), dict(name_suffix=suffix)


def _serial_order(monkeypatch):
    """Launches traced from here on take PR 45's kernel: call the entry
    points unjitted (``__wrapped__``), a jitted one keeps what it traced."""
    from tests.work_list_serial import serial_work_list_kernel

    monkeypatch.setattr(ra, "_work_list_kernel", serial_work_list_kernel)


@pytest.mark.parametrize(
    "name", [n for n in list(CASES) + list(FULL_AXIS_CASES)
             if n not in ONE_ROW_LAUNCHES])
def test_overlapped_packed_items_match_the_serial_order_bit_for_bit(
        name, monkeypatch):
    """Items of several rows: what moved is when a copy starts and who waits
    for it, and not one bit of the result.  A one-row item beside them takes
    the one-row tile, whose arithmetic is this PR's (a softmax summed in
    another order): the same to rounding.  (``decode_step`` launches that
    tile alone and the serial kernel has none: the cases above.)"""
    args, kw = _packed_call(name)
    lens, off = np.asarray(args[7]), np.asarray(args[6])
    got = np.asarray(ra.packed_ragged_attention(*args, interpret=True, **kw))
    _serial_order(monkeypatch)
    want = np.asarray(
        ra.packed_ragged_attention.__wrapped__(*args, interpret=True, **kw))
    # a segment's last row is an item of its own where one row is left over
    qb = ra._work_list_tiles(args[8], got.dtype)[0]
    one_row = np.zeros(got.shape[0], bool)
    one_row[(off + lens - 1)[lens % qb == 1]] = True
    np.testing.assert_array_equal(got[~one_row], want[~one_row])
    np.testing.assert_allclose(got[one_row], want[one_row], rtol=2e-5, atol=2e-5)
    assert (~one_row).sum() > one_row.sum()


@pytest.fixture
def tpu_interpreter():
    """The TPU interpreter with its race detector on: a copy moves when it
    is waited for and not before, so a wait that comes late, or for the
    wrong copy, shows in the result; a vector access that a copy in flight
    could meet is a race; and a semaphore left off zero when the grid ends
    fails the launch."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as ipc
    from jax.experimental.pallas import tpu as pltpu

    yield pltpu.InterpretParams(detect_races=True), lambda: ipc.races.races_found
    pltpu.reset_tpu_interpret_mode_state()


@pytest.mark.parametrize("name", list(HANDOVER_DECODE))
def test_overlapped_decode_items_wait_for_what_they_read(name, tpu_interpreter):
    params, raced = tpu_interpreter
    kw = dict(HANDOVER_DECODE[name])
    window = kw.get("window", 0)
    q, pool, pt, lens = _decode_case(**kw)
    # (waited for before the gather is traced: the interpreter's callbacks
    # run JAX operations of their own, and a launch still in flight under a
    # main thread that compiles has hung a whole run of the tests, PR 54)
    got = jax.block_until_ready(ra.decode_work_list_attention(
        q, pool, pt, lens, LAYER, window, interpret=params))
    _assert_decode_rows(got, q, pool, pt, lens, LAYER, window, 2e-5)
    assert not raced()


@pytest.mark.parametrize(
    "name", ["blocks_beside_decode", "idle_lanes_and_padding", "verify_columns",
             "two_chunks", "window", "small_blocks", "wide_tile_then_eight_rows",
             "wide_rows_behind_a_256_row_tile", "wide_idle_lanes"])
def test_overlapped_packed_items_wait_for_what_they_read(name, tpu_interpreter):
    """A tile that spans rows of the items before it reads them only once
    they have landed, and the last live item leaves nothing in flight."""
    params, raced = tpu_interpreter
    args, kw = _packed_call(name)
    # one launch at a time, each waited for (see the decode launches above)
    want = np.asarray(ra.packed_ragged_attention(*args, interpret=True, **kw))
    got = np.asarray(ra.packed_ragged_attention(*args, interpret=params, **kw))
    np.testing.assert_array_equal(got, want)
    assert not raced()


@pytest.mark.parametrize("Hkv,D,as_it_lies", [
    (8, 128, True), (4, 128, True), (1, 128, True), (8, 256, True),
    (2, 256, False), (4, 256, False)])
def test_the_pool_picks_the_one_row_body(Hkv, D, as_it_lies):
    """Read off the pool's shape alone: where a page as one matrix is the
    pool's own bytes the launch is handed that view and holds the slots of
    that form; where it is not (a few heads wider than the lanes) it is
    handed the pool once, makes no second form of it, and holds slots a token
    a row of heads, at the ``(lanes, 1)`` launch too."""
    assert ra._pages_are_matrices(Hkv, D) is as_it_lies
    q, pool, pt, lens = _decode_case([9, 40], P=8, Hq=2 * Hkv, Hkv=Hkv, D=D)
    jaxpr = jax.make_jaxpr(lambda *a: ra.decode_work_list_attention(
        *a, LAYER, interpret=False))(q, pool, pt, lens)
    (call,) = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    shapes = [v.aval.shape for v in call.invars]
    flat_page = (2, 2) + (pool.shape[2], PAGE * Hkv, D)
    assert (flat_page in shapes) is as_it_lies
    assert shapes.count(pool.shape) == 1
    KB = ra._key_block(PAGE)
    scratch = [
        s.shape for s in ra._work_list_scratch(
            [(1, 8)], 2 * Hkv, Hkv, D, PAGE, q.dtype, pool.dtype)
        if hasattr(s, "shape")]
    assert ((2, 2, KB * Hkv, D) in scratch) is as_it_lies
    assert ((2, 2, KB, Hkv, D) in scratch) is not as_it_lies


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
    # PR 40's kernel started 5 copies a tile of its two (queries in, the
    # tile's span of the output in, a page's K and V at the first fetch and
    # at the next block's, rows out).  Since PR 46 there are three tiles, and
    # an item starts what the item after it begins with at any tile's size
    # (three query copies, three first fetches; one of each runs): 6, beside
    # its own next block's pages and its rows out: 8 a tile, one more where
    # a tile of several rows reads its span of the output back; the first of
    # a run of live items starts the same 6 for itself, once: 6 + 3 * 8 + 2
    tiles = ra._work_list_tiles(s_max, q.dtype)[1]
    assert [copy for copy, _ in tiles] == [1, 8, 256]
    assert at_512 == (32, 20)


def test_item_counts_follow_the_work_list():
    """The tick's ``dispatch`` annotation counts the launch's items and its
    small tiles on the host as the device builds them."""
    from dynamo_tpu.ops.latent_attention import packed_work_list

    lens = [300, 1, 0, 256, 8, 9, 513]
    assert ra.packed_item_counts(lens, 512) == (2 + 1 + 1 + 1 + 1 + 3, 3, 8)
    assert ra.packed_item_counts([1, 0, 1, 1], 1) == (3, 3, 2)
    assert ra.packed_item_counts([0, 0], 64) == (0, 0, 0)
    assert ra.packed_item_counts([5], 64) == (1, 1, 0)
    qb = ra._work_list_tiles(512, jnp.bfloat16)[0]
    rows = np.asarray(packed_work_list(
        jnp.zeros(7, jnp.int32), jnp.zeros(7, jnp.int32),
        jnp.asarray(lens, jnp.int32), 2048, qb)[3])
    assert (rows > 0).sum() == 9
    assert ((rows > 0) & (rows <= ra._WL_SMALL_ROWS)).sum() == 3
    # chained: a live item behind a live one (the list packs them first)
    assert ((rows[1:] > 0) & (rows[:-1] > 0)).sum() == 8


def test_unified_dispatches_take_the_whole_page_table(run, monkeypatch):
    """Where the packed launch walks a work list, so does the fused steps'
    decode launch, and every unified dispatch, of one step or of ``K``, is
    handed the page table at its full width: no executable per bucket of it.
    A pool that keeps the grid kernels keeps the bucket.  The tokens are the
    same either way, and the ``dispatch`` annotation says which it was:
    ``pt``, and for ``k > 1`` what attends the steps after the first."""
    from dynamo_tpu.engine import attention as att
    from dynamo_tpu.runtime import profiling
    from tests.test_request_stages import collect, req, tiny_engine

    marks = []
    mark = profiling._Tick.mark

    def spy_mark(self, phase, **meta):
        if phase == "dispatch" and "pt" in meta:
            marks.append(meta)
        return mark(self, phase, **meta)

    class Open:
        """An open annotation: the stats are built only while a profiler
        trace is being taken (``_Tick.annotating``)."""

        def set_metadata(self, **meta):
            pass

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(profiling._Tick, "mark", spy_mark)
    monkeypatch.setattr(profiling, "annotate", lambda name: Open())
    monkeypatch.setattr(profiling.profiler, "enabled", True)

    def served(work_list):
        monkeypatch.setattr(
            att, "packed_launch",
            lambda *a: att.PackedLaunch(work_list, lambda Np, s_max: True))
        engine = tiny_engine(max_seq_len=512, num_pages=300, page_size=4)
        assert engine._packed_full_table is work_list
        del marks[:]
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

        return run(body()), widths, engine.sched.max_pages, list(marks)

    want, bucketed, full, grid_marks = served(False)
    got, widths, _, marks_full = served(True)
    assert got == want
    for name in ("packed_unified_step", "packed_unified_multistep"):
        assert min(bucketed[name]) < full, name
        assert widths[name] == {full}, name
    assert {m["pt"] for m in marks_full} == {full}
    # one request: a chunk's items, then one decode row; every item but the
    # launch's first starts on copies the item before it put in flight
    for m in marks_full:
        assert m["items"] >= 1 and 0 <= m["small"] <= m["items"]
        assert m["chained"] == m["items"] - 1
    assert grid_marks and min(m["pt"] for m in grid_marks) < full
    # the stat is read where the step's trace reads it: on the CPU, the gather
    fused = [m for m in marks_full if m["k"] > 1]
    assert fused and all(m["decode"] == "xla" for m in fused)
    assert all("decode" not in m for m in marks_full if m["k"] == 1)


@pytest.mark.parametrize("sampling", ["greedy", "seeded"])
def test_chunk_step_runs_the_rows_it_has(run, monkeypatch, sampling):
    """The engine's half of the window-free shape rule, over the kernel
    itself (interpreted): a chunk that fills the token budget beside three
    decoding lanes is dispatched at the rows it has, 251 of 256, where the
    window rule pads the axis to 512 for the last lane's ``s_max`` window,
    and every request's tokens are the same under both.  Which rule an
    engine takes is ``packed_launch``'s to say (``item_rows``)."""
    import asyncio
    import functools

    from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    from tests.test_request_stages import collect

    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    traced = []
    for name in ("packed_ragged_attention", "decode_work_list_attention"):
        kernel = functools.partial(getattr(ra, name), interpret=True)

        def spy(*a, _kernel=kernel, _name=name, **kw):
            traced.append(_name)
            return _kernel(*a, **kw)

        monkeypatch.setattr(ra, name, spy)
    # widths no other test serves: the step's jit cache holds what was
    # traced for a configuration, kernel or composition
    model = ModelConfig.tiny(head_dim=D, vocab_size=251)
    real_launch = att.packed_launch

    def request(tokens, max_tokens, seed):
        options = (
            SamplingOptions(temperature=0.0) if sampling == "greedy"
            else SamplingOptions(temperature=0.9, top_p=0.95, seed=seed)
        )
        return PreprocessedRequest(
            token_ids=list(tokens),
            stop_conditions=StopConditions(max_tokens=max_tokens),
            sampling_options=options,
        )

    def served(window_free):
        def launch(*a):
            real = real_launch(*a)
            assert real.walks_work_list and real.item_rows == ra._WL_Q_BLOCK
            return real if window_free else real._replace(item_rows=0)

        monkeypatch.setattr(att, "packed_launch", launch)
        engine = JaxEngine.random_init(model, EngineConfig(
            max_batch_size=4, max_seq_len=512, page_size=PAGE, num_pages=256,
            mixed_token_budget=256))
        assert engine._packed_shapes.item_rows == (256 if window_free else 0)
        dispatches = []
        observe = engine.obs.observe_mixed_tokens
        monkeypatch.setattr(
            engine.obs, "observe_mixed_tokens",
            lambda used, disp: (dispatches.append((used, disp)),
                                observe(used, disp))[1])

        async def body():
            try:
                short = [
                    asyncio.ensure_future(collect(engine, request(
                        [7 + i] * (9 + i), 64, seed=11 + i)))
                    for i in range(3)
                ]
                while engine._steps < 4:  # the three lanes are decoding
                    await asyncio.sleep(0.005)
                document = await collect(engine, request(
                    [(13 * j) % 250 + 1 for j in range(300)], 4, seed=5))
                return [await s for s in short] + [document]
            finally:
                await engine.stop()

        return run(body()), dispatches, engine._packed_shapes.pairs

    want, windowed, shapes_w = served(False)
    got, free, shapes_f = served(True)
    assert "packed_ragged_attention" in traced
    assert got == want and all(len(t) == 64 for t in got[:3])
    # the budget-filling chunk: 248 rows (page-aligned) beside 3 decode rows
    assert max(windowed) == (251, 512) and (512, 256, 0) in shapes_w
    assert max(free) == (251, 256) and (256, 256, 0) in shapes_f
    used, dispatched = max(free)
    assert used / dispatched >= 0.97


DECODE_BACKENDS = {
    # pool: (dtype, D, quant) under a bf16 model -> what attends a fused step
    "bf16_d128": "work_list",
    "f32_d128": "work_list",
    "bf16_d64": "grid",
    "f32_d64": "xla",
    "int8_d128": "xla",
    "latent": "latent",
    "cpu": "xla",
}


@pytest.mark.parametrize("name", list(DECODE_BACKENDS))
def test_decode_backend_follows_the_packed_launch(name, monkeypatch):
    """The fused steps' half of the rule: a pool whose packed launch walks a
    work list (``attention.packed_launch``, what the engine takes the whole
    table on) gets the decode launch that walks one too, and the dispatch
    traces the kernel ``decode_backend`` names."""
    from dynamo_tpu.engine.kv_cache import LatentKV

    monkeypatch.setattr(att, "_on_tpu", lambda: name != "cpu")
    Hq, Hkv, B, P = 4, 2, 2, 4
    d = 64 if name.endswith("d64") else D
    pool = jnp.zeros(
        (2, 2, 9, PAGE, Hkv, d),
        jnp.float32 if name.startswith("f32") else jnp.bfloat16)
    if name == "int8_d128":
        pool = QuantKV(q=pool.astype(jnp.int8),
                       s=jnp.zeros(pool.shape[:4], jnp.float32))
    if name == "latent":
        Hkv, d = 1, 192
        pool = LatentKV(jnp.zeros((1, 1, 9, PAGE, 1, 2 * d), jnp.bfloat16), 128)
    backend = att.decode_backend(pool, Hq, d, jnp.bfloat16)
    assert backend == DECODE_BACKENDS[name]
    walks = att.packed_launch(pool, Hq, Hkv, d, jnp.bfloat16).walks_work_list
    assert walks is (backend in ("work_list", "latent"))
    traced = _pallas_calls(jax.make_jaxpr(
        lambda q, pool: att.decode_attention_dispatch(
            q, pool, jnp.zeros((B, P), jnp.int32), jnp.ones((B,), jnp.int32),
            1)
    )(jnp.zeros((B, Hq, d), jnp.bfloat16), pool).jaxpr)
    assert [n for n, _rank in traced] == {
        "work_list": ["paged_decode_attention"],
        "grid": [None],  # a one-kind trunk's grid launch carries no name
        "latent": ["latent_decode_attention"],
        "xla": [],
    }[backend]


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
