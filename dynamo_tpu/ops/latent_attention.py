"""Pallas attention kernels over a latent KV pool (MLA, TPU).

A latent pool (``kv_cache.LatentKV``) holds one row a token a layer, ``[c_kv
(C) | RoPE(k_r) (R)]``, shared by every head: no K/V pair and no head axis;
two layers' rows lie side by side in a slab row ``[c | c' | k_r | k_r']`` so
that every part starts on a 128-lane tile.  A layer's keys are fetched as
its own C columns and the 2R-wide tile of both layers' rotated keys, which
its queries meet with zeros on the neighbour's half.
``model._latent_attention`` carries the queries into
that space (the absorbed form), so attention is multi-query over the row:
keys the whole row, values its first ``C`` columns, ``Hq`` query heads a
token against ONE row a key.  At 32 heads that is 32 x (2 W + 2 C) = 36 864
operations on a 640-byte row, 58 a byte against the chip's ridge of 240:
decode is bound by the pool's bytes, a prefill chunk by the MXU.

One kernel body serves both launches; what differs is the work list.

* :func:`latent_packed_attention` -- the packed mixed step
  (``step.packed_unified_step``).  The dispatch's fresh rows are already in
  the pool (``attention.latent_packed_attention_dispatch`` scatters them
  first), so every key is read from pages and the causal mask
  ``kpos <= qpos`` alone tells fresh from resident.
* :func:`latent_decode_attention` -- the fused decode steps: one query row a
  lane.

**Work items, not a lane x page grid.**  A grid of (lane, page group) pays
a grid step for every group of the TABLE's width, live or not; at 32k-token
contexts that is thousands of dead steps a lane.  (The pair pools' packed
launch walked one until it took this form too:
``ragged_attention._work_list_kernel``, over :func:`packed_work_list`.)
Here the grid is a list of work items -- (lane, block of up
to ``qb`` query rows) -- built on the device from the dispatch's segment
table, and each item loops over exactly the key blocks its rows can see
(``fori_loop`` with a dynamic trip count).  Pages are fetched HBM->VMEM by
explicit DMA in rolled loops over a key block's LIVE pages (a page is two
copies, whatever the block's size: the kernel's jaxpr does not grow with
it), double-buffered against the block's compute; queries and the output
move by DMA too, because a packed axis of thousands of rows x 32 heads x 320
does not fit VMEM whole.

**The tile.**  A key block is ``_KEY_BLOCK`` = 512 keys for every item.  An
item's rows are walked inside the block in sub-tiles of ``_SUB_TOKENS``
tokens x ``Hq`` heads (a float32 score tile of 2 MB at 32 heads), so the
block's keys are fetched once an item and the float32 accumulator is read
and rescaled once per 512 keys and sub-tile, not once per 128.  Two
sub-tiles a loop step are independent chains: one's softmax runs under the
other's products.  The loop stops at the item's last live sub-tile, starts
at the first one that can see the block, and builds the causal mask only
for sub-tiles the block's last key is past (a 2048-row chunk deep in a
document masks its last blocks alone).  The running maximum and sum are
kept lane-dense, ``[rows, 128]`` with every lane alike.  An item with a
handful of rows (a decode lane riding a mixed step, or the decode launch)
takes one sub-tile of ``_SMALL_ROWS`` tokens.

Output rows past an item's own (the tail of its last loop step) overlap the
next segment; items run in ascending row order and each waits for its
output copy, so the owner's write lands last.  Rows no item covers keep the
zeros the output buffer is created with.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128

# query rows (tokens) of one work item of a packed launch, and the rows at
# or under which an item takes the small tile
_Q_BLOCK = 256
_SMALL_ROWS = 8
# a wide item's rows are walked in sub-tiles of this many tokens (x Hq
# query rows: the float32 score tile is [32 * Hq, KB]), ``_CHAINS`` of them
# a loop step: independent chains, one's softmax under another's products
_SUB_TOKENS = 32
_CHAINS = 2
# keys a key block holds, whatever the tile
_KEY_BLOCK = 512
VMEM_LIMIT_BYTES = 100 << 20


def _wide_tile(qb: int):
    """``(tokens a sub-tile, sub-tiles a loop step)`` for the items of more
    than ``_SMALL_ROWS`` rows; a step's tokens divide ``qb``, so no step
    reaches past the item's block of the packed axis.  None where a query
    block holds no such item (the decode launch)."""
    if qb <= _SMALL_ROWS:
        return None
    st = math.gcd(qb, _SUB_TOKENS)
    return st, _CHAINS if (qb // st) % _CHAINS == 0 else 1


def _lanes(x, n: int):
    """``x [rows, 128]``, the same value in every lane, as ``[rows, n]``."""
    if n % _LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return x if n == _LANES else pltpu.repeat(x, n // _LANES, axis=1)


def _latent_kernel(
    # scalar prefetch
    layer_ref,  # [2] the layer's slab and its half of it
    pt_ref,  # [B, P] page table
    w_lane,  # [W] lane of each work item
    w_row0,  # [W] its first row in the packed axis
    w_pos0,  # [W] that row's position
    w_rows,  # [W] its rows (0 = no work)
    # operands (HBM)
    q_hbm,  # [Np * Hq, C + 2R] absorbed queries, zeros on the other half
    kv_hbm,  # [slabs, num_pages, page, 2 (C + R)]
    _o_init,  # the zeroed output buffer (aliased to o_hbm)
    o_hbm,  # [Np * Hq, C]
    # scratch
    q_v,  # [qb * Hq, C + 2R] an item's queries, heads-minor
    kbuf,  # [2, KB, C + 2R] two slots of a key block's pages
    rel_scr,  # [sub-tile rows, KB] key column less the row's token
    m_scr, l_scr,  # [qb * Hq, 128] running max and sum, every lane alike
    acc_scr,  # [qb * Hq, C]
    o_v,  # [qb * Hq, C]
    sem_q, sem_kv, sem_o,
    *,
    small: int,
    wide,
    Hq: int,
):
    w = pl.program_id(0)
    rows = w_rows[w]
    KB, Wd = kbuf.shape[1], kbuf.shape[2]
    C = o_v.shape[1]
    page = kv_hbm.shape[2]
    P = pt_ref.shape[1]
    n_pg = KB // page
    R2 = Wd - C  # both layers' rotated keys
    scale = 1.0 / ((C + R2 // 2) ** 0.5)
    slab = layer_ref[0]
    mine = pl.ds(pl.multiple_of(layer_ref[1] * C, C), C)  # my c_kv columns

    @pl.when(w == 0)
    def _once():
        # a block's dead pages are never fetched: what the slots hold there
        # meets a probability of zero, and must be finite
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        # rows lie heads-minor: row r of a sub-tile is token r // Hq
        col = jax.lax.broadcasted_iota(jnp.int32, rel_scr.shape, 1)
        tok = jax.lax.broadcasted_iota(jnp.int32, rel_scr.shape, 0) // Hq
        rel_scr[...] = col - tok

    def page_parts(pid, slot, j):
        """A page's two copies into place ``j`` of a slot: the layer's c_kv
        columns, the k_r tile."""
        src = kv_hbm.at[slab, pid]
        at = pl.ds(pl.multiple_of(j * page, page), page)
        return (
            pltpu.make_async_copy(
                src.at[:, mine], kbuf.at[slot, at, pl.ds(0, C)],
                sem_kv.at[slot],
            ),
            pltpu.make_async_copy(
                src.at[:, pl.ds(2 * C, R2)], kbuf.at[slot, at, pl.ds(C, R2)],
                sem_kv.at[slot],
            ),
        )

    def fetch(lane, kb, slot, pg_hi):
        """Start the copies of key block ``kb``'s live pages."""
        def start(pg, carry):
            for c in page_parts(pt_ref[lane, pg], slot, pg - kb * n_pg):
                c.start()
            return carry

        jax.lax.fori_loop(
            kb * n_pg, jnp.minimum((kb + 1) * n_pg, pg_hi), start, 0
        )

    def wait(kb, slot, pg_hi):
        """Wait for key block ``kb``'s copies: a DMA semaphore counts bytes,
        so a block whose pages are all live is one wait for the whole slot;
        the last block waits page by page."""
        live = jnp.minimum((kb + 1) * n_pg, pg_hi) - kb * n_pg

        @pl.when(live == n_pg)
        def _():
            pltpu.make_async_copy(
                kbuf.at[1 - slot], kbuf.at[slot], sem_kv.at[slot]
            ).wait()

        @pl.when(live < n_pg)
        def _():
            def done(pg, carry):
                for c in page_parts(0, slot, 0):
                    c.wait()
                return carry

            jax.lax.fori_loop(0, live, done, 0)

    def attend(st, chains, may_skip_mask, lane, row_at, pos0):
        """Online softmax of an item over the key blocks its rows can see.
        Its rows are walked in steps of ``chains`` sub-tiles of ``st``
        tokens, as many steps as hold a live row."""
        SR = st * Hq  # query rows of a sub-tile
        T, TR = st * chains, st * chains * Hq  # tokens and rows of a step
        n_steps = (rows + T - 1) // T
        last = pos0 + rows - 1  # the last live row's position
        pg_hi = jnp.minimum(last // page + 1, P)
        n_kb = last // KB + 1

        def rows_of(i):
            return pl.ds(pl.multiple_of(i * TR, TR), TR)

        def each_step(body, lo=0, hi=n_steps):
            def step(i, carry):
                body(i)
                return carry

            jax.lax.fori_loop(lo, hi, step, 0)

        def q_in(i):
            return pltpu.make_async_copy(
                q_hbm.at[pl.ds(row_at + i * TR, TR)], q_v.at[rows_of(i)],
                sem_q.at[0],
            )

        def o_out(i):
            return pltpu.make_async_copy(
                o_v.at[rows_of(i)], o_hbm.at[pl.ds(row_at + i * TR, TR)],
                sem_o.at[0],
            )

        each_step(lambda i: q_in(i).start())
        fetch(lane, 0, 0, pg_hi)

        def clear(i):
            at = rows_of(i)
            m_scr[at] = jnp.full((TR, _LANES), _NEG_INF, jnp.float32)
            l_scr[at] = jnp.zeros((TR, _LANES), jnp.float32)
            acc_scr[at] = jnp.zeros((TR, C), jnp.float32)

        each_step(clear)
        each_step(lambda i: q_in(0).wait())

        def block(kb, carry):
            slot = kb % 2
            wait(kb, slot, pg_hi)

            @pl.when(kb + 1 < n_kb)
            def _():
                fetch(lane, kb + 1, 1 - slot, pg_hi)

            k = kbuf[slot]  # [KB, C + 2R]
            kc = k[:, :C]

            def update(i, masked):
                for ch in range(chains):
                    at = pl.ds(pl.multiple_of(i * TR + ch * SR, SR), SR)
                    s = jax.lax.dot_general(
                        q_v[at], k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ) * scale  # [SR, KB]
                    if masked:
                        # kpos <= qpos, both less the sub-tile's first row's
                        # position and the block's first key
                        first = pos0 + (i * chains + ch) * st - kb * KB
                        s = jnp.where(rel_scr[:SR] <= first, s, _NEG_INF)
                    m_prev = m_scr[at]
                    m_new = jnp.maximum(
                        m_prev, jnp.max(s, axis=-1, keepdims=True)
                    )  # [SR, 128]
                    alpha = jnp.exp(m_prev - m_new)
                    p = jnp.exp(s - _lanes(m_new, KB))
                    pv = jax.lax.dot_general(
                        p.astype(kc.dtype), kc, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )  # [SR, C]
                    m_scr[at] = m_new
                    l_scr[at] = l_scr[at] * alpha + jnp.sum(
                        p, axis=-1, keepdims=True
                    )
                    acc_scr[at] = acc_scr[at] * _lanes(alpha, C) + pv

            # steps wholly under the block's first key have nothing to add
            lo = jnp.maximum(kb * KB - pos0, 0) // T
            # and from the step whose first row sees the block's last key
            # on, no row needs the mask
            free = n_steps
            if may_skip_mask:
                reach = jnp.maximum((kb + 1) * KB - 1 - pos0, 0)
                free = jnp.minimum((reach + T - 1) // T, n_steps)
                each_step(lambda i: update(i, False), free, n_steps)
            each_step(lambda i: update(i, True), lo, free)
            return carry

        jax.lax.fori_loop(0, n_kb, block, 0)

        def finish(i):
            at = rows_of(i)
            o_v[at] = (
                acc_scr[at] * _lanes(1.0 / l_scr[at], C)
            ).astype(o_v.dtype)
            # whole steps go out: what lies past the item's rows in the
            # last one is another item's, or nothing
            o_out(i).start()

        each_step(finish)
        each_step(lambda i: o_out(0).wait())

    @pl.when(rows > 0)
    def _item():
        args = (w_lane[w], pl.multiple_of(w_row0[w] * Hq, Hq), w_pos0[w])
        if wide is None:
            attend(small, 1, False, *args)
            return

        @pl.when(rows <= small)
        def _():
            attend(small, 1, False, *args)

        @pl.when(rows > small)
        def _():
            attend(*wide, True, *args)


def _launch(
    q, kv_pages, page_table, layer, lane, row0, pos0, rows, *, qb, name,
    interpret,
):
    """One launch over a work list; ``q`` is ``[Np, Hq, C + R]`` and
    ``kv_pages`` a ``kv_cache.LatentKV``."""
    Np, Hq, _ = q.shape
    C, R = kv_pages.c, kv_pages.r
    slabs, _, num_pages, page, _, width = kv_pages.shape
    if q.shape[2] != C + R:
        raise ValueError(
            f"not a latent pool for {q.shape[2]}-wide queries: "
            f"{kv_pages.shape}, c_kv {C}"
        )
    layer = jnp.clip(jnp.asarray(layer, jnp.int32), 0, 2 * slabs - 1)
    half = layer % 2
    # the rotated part of a query sits under its own layer's half of the
    # k_r tile, zeros under the neighbour's
    zeros = jnp.zeros((Np, Hq, R), q.dtype)
    q_r = q[..., C:]
    q = jnp.concatenate(
        [q[..., :C], jnp.where(half == 0, q_r, zeros),
         jnp.where(half == 0, zeros, q_r)], axis=-1,
    )
    Wd = C + 2 * R
    small, wide = min(_SMALL_ROWS, qb), _wide_tile(qb)
    KB = max(page, _KEY_BLOCK // page * page)
    pool = kv_pages.data.reshape(slabs, num_pages, page, width)
    rows_t = qb * Hq
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(lane.shape[0],),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((rows_t, Wd), q.dtype),
            pltpu.VMEM((2, KB, Wd), kv_pages.dtype),
            pltpu.VMEM((max(small, wide[0] if wide else 0) * Hq, KB), jnp.int32),
            pltpu.VMEM((rows_t, _LANES), jnp.float32),
            pltpu.VMEM((rows_t, _LANES), jnp.float32),
            pltpu.VMEM((rows_t, C), jnp.float32),
            pltpu.VMEM((rows_t, C), q.dtype),
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    i32 = lambda x: x.astype(jnp.int32)  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_latent_kernel, small=small, wide=wide, Hq=Hq),
        out_shape=jax.ShapeDtypeStruct((Np * Hq, C), q.dtype),
        grid_spec=grid_spec,
        input_output_aliases={8: 0},  # the zeroed buffer, after 6 scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=name,
    )(
        jnp.stack([layer // 2, half]),
        jnp.clip(i32(page_table), 0, num_pages - 1),
        i32(lane), i32(row0), i32(pos0), i32(rows),
        q.reshape(Np * Hq, Wd), pool, jnp.zeros((Np * Hq, C), q.dtype),
    )
    return out.reshape(Np, Hq, C)


def packed_work_list(base, seg_off, q_lens, Np: int, qb: int):
    """The packed launch's work items from the dispatch's segment table:
    ``(lane, row0, pos0, rows)``, each ``[B + Np // qb]`` (a lane with rows
    has at most one partial block, so that many always suffice); items past
    the last live one have 0 rows."""
    B = q_lens.shape[0]
    W = B + Np // qb
    blocks = (q_lens + qb - 1) // qb  # [B]
    cum = jnp.cumsum(blocks)
    w = jnp.arange(W, dtype=jnp.int32)
    lane = jnp.clip(jnp.searchsorted(cum, w, side="right"), 0, B - 1)
    i = w - (cum - blocks)[lane]
    live = w < cum[-1]
    rows = jnp.where(live, jnp.minimum(qb, q_lens[lane] - i * qb), 0)
    return lane, seg_off[lane] + i * qb, base[lane] + i * qb, rows


@functools.partial(jax.jit, static_argnames=("s_max", "interpret"))
def latent_packed_attention(
    q: jax.Array,  # [Np, Hq, C + R] absorbed queries (lane's row i at base + i)
    kv_pages,  # kv_cache.LatentKV, this dispatch's rows already in it
    page_table: jax.Array,  # [B, P]
    base: jax.Array,  # [B] position of each lane's first fresh row
    seg_off: jax.Array,  # [B] lane's segment offset into the packed axis
    q_lens: jax.Array,  # [B] fresh rows per lane (0 = no segment)
    s_max: int,  # static per-lane window capacity (off + s_max <= Np)
    layer: jax.Array | int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of a packed dispatch over a latent pool that
    already holds the dispatch's rows: ``[Np, Hq, C]``."""
    Np = q.shape[0]
    qb = min(s_max, _Q_BLOCK)
    if s_max % qb:
        raise ValueError(f"s_max {s_max} is not a multiple of {qb}")
    lane, row0, pos0, rows = packed_work_list(
        base.astype(jnp.int32), seg_off.astype(jnp.int32),
        q_lens.astype(jnp.int32), Np, qb,
    )
    return _launch(
        q, kv_pages, page_table, layer, lane, row0, pos0, rows, qb=qb,
        name="latent_packed_attention", interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def latent_decode_attention(
    q: jax.Array,  # [B, Hq, C + R] one absorbed query a lane
    kv_pages,  # kv_cache.LatentKV
    page_table: jax.Array,  # [B, P]
    kv_lens: jax.Array,  # [B] rows in the cache, the new token's included
    layer: jax.Array | int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over a latent pool: ``[B, Hq, C]``."""
    B = q.shape[0]
    lane = jnp.arange(B, dtype=jnp.int32)
    lens = jnp.maximum(kv_lens.astype(jnp.int32), 1)
    return _launch(
        q, kv_pages, page_table, layer, lane, lane, lens - 1,
        jnp.ones((B,), jnp.int32), qb=1, name="latent_decode_attention",
        interpret=interpret,
    )
