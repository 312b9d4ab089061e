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
explicit DMA, ``KB / page`` pages a key block, double-buffered against the
block's compute; queries and the output move by DMA too, because a packed
axis of thousands of rows x 32 heads x 320 does not fit VMEM whole.

An item with a handful of rows (a decode lane riding a mixed step) takes a
small-tile branch, so it does not pay a 256-row prefill tile's arithmetic.

Output rows past an item's own (the tail of its last block) overlap the
next segment; items run in ascending row order and each waits for its
output copy, so the owner's write lands last.  Rows no item covers keep the
zeros the output buffer is created with.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# query rows (tokens) of one work item of a packed launch, and the rows at
# or under which an item takes the small tile
_Q_BLOCK = 256
_SMALL_ROWS = 8
# keys a key block holds: short for the wide prefill tile (the score tile
# is [qb * Hq, KB] in float32), long where the tile is a few rows
_KB_WIDE_TILE = 128
_KB_SMALL_TILE = 512
VMEM_LIMIT_BYTES = 100 << 20


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
    q_v, kbuf, m_scr, l_scr, acc_scr, o_v, sem_q, sem_kv, sem_o,
    *,
    qb: int,
    small: int,
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

    def parts(src_page, slot, j):
        """A page's two copies: the layer's c_kv columns, the k_r tile."""
        at = pl.ds(j * page, page)
        return (
            pltpu.make_async_copy(
                src_page.at[:, mine], kbuf.at[slot, at, pl.ds(0, C)],
                sem_kv.at[slot],
            ),
            pltpu.make_async_copy(
                src_page.at[:, pl.ds(2 * C, R2)],
                kbuf.at[slot, at, pl.ds(C, R2)], sem_kv.at[slot],
            ),
        )

    def fetch(lane, kb, slot):
        for j in range(n_pg):
            pid = pt_ref[lane, jnp.minimum(kb * n_pg + j, P - 1)]
            for c in parts(kv_hbm.at[slab, pid], slot, j):
                c.start()

    def wait(slot):
        for j in range(n_pg):
            for c in parts(kv_hbm.at[0, 0], slot, j):
                c.wait()

    def attend(nrow, pos0, n_kb, lane, row_at):
        """Online softmax of the tile's first ``nrow`` tokens over key
        blocks ``0 .. n_kb``."""
        R_ = nrow * Hq
        m_scr[:R_] = jnp.full((R_, 1), _NEG_INF, jnp.float32)
        l_scr[:R_] = jnp.zeros((R_, 1), jnp.float32)
        acc_scr[:R_] = jnp.zeros((R_, C), jnp.float32)
        q = q_v[:R_]
        # a row's position: its token's place in the item, heads-minor
        tok = jax.lax.broadcasted_iota(jnp.int32, (R_, KB), 0) // Hq
        qpos = pos0 + tok

        def block(kb, carry):
            slot = kb % 2
            wait(slot)

            @pl.when(kb + 1 < n_kb)
            def _():
                fetch(lane, kb + 1, 1 - slot)

            k = kbuf[slot]  # [KB, C + 2R]
            kc = k[:, :C]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [R_, KB]
            kpos = kb * KB + jax.lax.broadcasted_iota(
                jnp.int32, (R_, KB), 1
            )
            s = jnp.where(kpos <= qpos, s * scale, _NEG_INF)
            m_prev = m_scr[:R_]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            pv = jax.lax.dot_general(
                p.astype(kc.dtype), kc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [R_, C]
            m_scr[:R_] = m_new
            l_scr[:R_] = l_scr[:R_] * alpha + jnp.sum(
                p, axis=-1, keepdims=True
            )
            acc_scr[:R_] = acc_scr[:R_] * alpha + pv
            return carry

        jax.lax.fori_loop(0, n_kb, block, 0)
        o_v[:R_] = (acc_scr[:R_] / l_scr[:R_]).astype(o_v.dtype)
        # only the tile's own rows go out: what lies past them in o_v is
        # another item's, or nothing
        out = pltpu.make_async_copy(
            o_v.at[pl.ds(0, R_)], o_hbm.at[pl.ds(row_at, R_)], sem_o.at[0]
        )
        out.start()
        out.wait()

    @pl.when(rows > 0)
    def _item():
        lane = w_lane[w]
        pos0 = w_pos0[w]
        row_at = pl.multiple_of(w_row0[w] * Hq, Hq)
        at = pl.ds(row_at, qb * Hq)
        q_in = pltpu.make_async_copy(q_hbm.at[at], q_v, sem_q.at[0])
        q_in.start()
        n_kb = (pos0 + rows + KB - 1) // KB  # key blocks some row can see
        fetch(lane, 0, 0)
        q_in.wait()
        if small < qb:
            @pl.when(rows <= small)
            def _():
                attend(small, pos0, n_kb, lane, row_at)

            @pl.when(rows > small)
            def _():
                attend(qb, pos0, n_kb, lane, row_at)
        else:
            attend(qb, pos0, n_kb, lane, row_at)


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
    small = min(_SMALL_ROWS, qb)
    KB = _KB_WIDE_TILE if qb > small else _KB_SMALL_TILE
    KB = max(page, KB // page * page)
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
            pltpu.VMEM((rows_t, 1), jnp.float32),
            pltpu.VMEM((rows_t, 1), jnp.float32),
            pltpu.VMEM((rows_t, C), jnp.float32),
            pltpu.VMEM((rows_t, C), q.dtype),
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    i32 = lambda x: x.astype(jnp.int32)  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_latent_kernel, qb=qb, small=small, Hq=Hq),
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
