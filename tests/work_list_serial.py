"""The work-list kernel as it was before its items overlapped (PR 45's
``ops.ragged_attention._work_list_kernel``, the arithmetic line for line):
the reference the overlapped kernel's items of several rows are held to bit
for bit (``test_packed_work_list.py``).  The one-row tile's arithmetic is PR
46's own (a query head a row over the block as it lies): where this kernel
ran a one-row item on the small tile, the two agree to rounding.  Test-only: the program has one order of copies, the
overlapped one."""

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.ragged_attention import _NEG_INF, _WL_SCORE_BYTES


def serial_work_list_kernel(
    # scalar prefetch
    layer_ref,  # [1] layer index
    pt_ref,  # [B, P] page table
    w_lane,  # [W] lane of each work item
    w_row0,  # [W] its first row in the packed axis
    w_pos0,  # [W] that row's position
    w_rows,  # [W] its rows (0 = no work)
    # operands (HBM)
    q_hbm,  # [Np, Hq, D]
    kv_hbm,  # [L, 2, num_pages, page, Hkv, D], the fresh rows in it
    _kv_flat,  # the one-row tile's view of the pool: PR 46's
    _o_init,  # the zeroed output buffer (aliased to o_hbm)
    o_hbm,  # [Np, Hq, D]
    # scratch, as ``ragged_attention._work_list_scratch`` lays it out
    q_v,  # [rows_t, Hq, D] an item's queries as they lie in HBM
    o_v,  # [rows_t, Hq, D]
    _kflat, _slot_ref,  # the one-row tile's slots, the hand-over's scalar
    sem_q, sem_kv, sem_o,
    q_t,  # [Hkv, n_rep * rows_t, D] heads-major
    kbuf,  # [2, 2, KB, Hkv, D] two slots of a key block's K and V pages
    kv_t,  # [2, Hkv, KB, D] the current block heads-major
    m_scr, l_scr,  # [Hkv, n_rep * rows_t, 1]
    acc_scr,  # [Hkv, n_rep * rows_t, D]
    *,
    tiles,
    window: int,
):
    """PR 45's order of copies: an item starts its queries and its first key
    block, waits for each with nothing to compute, and waits for its output
    copy before the grid moves on; nothing of one item overlaps another."""
    tiles = tiles[-2:]  # a one-row item took the small tile
    w = pl.program_id(0)
    rows = w_rows[w]
    _, _, KB, Hkv, D = kbuf.shape
    Np, Hq = q_hbm.shape[0], q_v.shape[1]
    n_rep = Hq // Hkv
    page = kv_hbm.shape[3]
    P = pt_ref.shape[1]
    n_pg = KB // page
    scale = 1.0 / (D ** 0.5)
    layer = layer_ref[0]
    # kv heads a step, while their score tiles stay within budget
    M_wide = n_rep * tiles[-1][1]
    hb = math.gcd(Hkv, max(_WL_SCORE_BYTES // (4 * M_wide * KB), 1))

    @pl.when(w == 0)
    def _clear():
        # a block's dead pages are never fetched: what the slots hold there
        # meets a probability of zero, and must be finite
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)

    def page_copy(pid, slot, j):
        """One page's K and V into place ``j`` of a slot."""
        return pltpu.make_async_copy(
            kv_hbm.at[layer, :, pid],
            kbuf.at[slot, :, pl.ds(j * page, page)],
            sem_kv.at[slot],
        )

    def pages_of(kb, pg_lo, pg_hi):
        """The live pages of key block ``kb``."""
        return jnp.maximum(kb * n_pg, pg_lo), jnp.minimum((kb + 1) * n_pg, pg_hi)

    def fetch(lane, kb, slot, pg_lo, pg_hi):
        lo, hi = pages_of(kb, pg_lo, pg_hi)

        def start(pg, carry):
            page_copy(pt_ref[lane, pg], slot, pg - kb * n_pg).start()
            return carry

        jax.lax.fori_loop(lo, hi, start, 0)

    def wait(kb, slot, pg_lo, pg_hi):
        lo, hi = pages_of(kb, pg_lo, pg_hi)

        def done(pg, carry):
            page_copy(0, slot, 0).wait()
            return carry

        jax.lax.fori_loop(lo, hi, done, 0)

    def attend(copy, nrow, lane, row0, pos0):
        """Online softmax of an item's ``rows`` tokens over the key blocks
        they can see, on a tile of ``nrow`` rows of which ``copy`` move."""
        M = n_rep * nrow
        # the tile's first row and its position: the item's, or ``shift``
        # rows earlier where the tile would overhang the axis (a one-row
        # tile never does, and is traced as it always was)
        clamps = copy > 1
        start, pos_t = row0, pos0
        if clamps:
            start = jnp.minimum(row0, Np - copy)
            shift = row0 - start
            pos_t = pos0 - shift
        q_in = pltpu.make_async_copy(
            q_hbm.at[pl.ds(start, copy)], q_v.at[pl.ds(0, copy)], sem_q.at[0]
        )
        q_in.start()
        o_span = o_hbm.at[pl.ds(start, copy)]
        if clamps:
            o_in = pltpu.make_async_copy(
                o_span, o_v.at[pl.ds(0, copy)], sem_o.at[0]
            )
            o_in.start()
        last = pos0 + rows - 1  # the last live row's position
        first = jnp.maximum(pos0 - window + 1, 0) if window > 0 else 0
        pg_lo, pg_hi = first // page, jnp.minimum(last // page + 1, P)
        kb_lo, kb_hi = first // KB, last // KB + 1
        fetch(lane, kb_lo, 0, pg_lo, pg_hi)
        q_in.wait()
        q_t[:, :M] = (
            q_v[:nrow].transpose(1, 0, 2).reshape(Hkv, M, D)
        )
        m_scr[:, :M] = jnp.full((Hkv, M, 1), _NEG_INF, jnp.float32)
        l_scr[:, :M] = jnp.zeros((Hkv, M, 1), jnp.float32)
        acc_scr[:, :M] = jnp.zeros((Hkv, M, D), jnp.float32)

        def block(kb, carry):
            slot = (kb - kb_lo) % 2
            wait(kb, slot, pg_lo, pg_hi)

            @pl.when(kb + 1 < kb_hi)
            def _():
                fetch(lane, kb + 1, 1 - slot, pg_lo, pg_hi)

            for side in range(2):
                kv_t[side] = (
                    kbuf[slot, side].transpose(1, 0, 2).astype(kv_t.dtype)
                )
            # a row's position: heads of one kv group lie (n_rep, nrow); the
            # rows before the item's own (positions under ``pos0``) and
            # after it are computed like any and never written
            tok = jax.lax.rem(
                jax.lax.broadcasted_iota(jnp.int32, (M, KB), 0), nrow
            )
            qpos = pos_t + tok
            kpos = kb * KB + jax.lax.broadcasted_iota(jnp.int32, (M, KB), 1)
            keep = kpos <= qpos
            if window > 0:
                keep = keep & (kpos > qpos - window)

            def heads(i, carry):
                # ``hb`` kv heads a step: independent chains the scheduler
                # can overlap (one head's softmax under another's matmul)
                hs = pl.ds(i * hb, hb)
                k, v = kv_t[0, hs], kv_t[1, hs]  # [hb, KB, D]
                s = jax.lax.dot_general(
                    q_t[hs, :M], k, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )  # [hb, M, KB]
                s = jnp.where(keep, s * scale, _NEG_INF)
                m_prev = m_scr[hs, :M]
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=-1, keepdims=True)
                )
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                )  # [hb, M, D]
                m_scr[hs, :M] = m_new
                l_scr[hs, :M] = l_scr[hs, :M] * alpha + jnp.sum(
                    p, axis=-1, keepdims=True
                )
                acc_scr[hs, :M] = acc_scr[hs, :M] * alpha + pv
                return carry

            jax.lax.fori_loop(0, Hkv // hb, heads, 0)
            return carry

        jax.lax.fori_loop(kb_lo, kb_hi, block, 0)
        out = (acc_scr[:, :M] / l_scr[:, :M]).astype(o_v.dtype)
        out = out.reshape(Hq, nrow, D).transpose(1, 0, 2)  # [nrow, Hq, D]
        at = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        if clamps:
            mine = (at >= shift) & (at < shift + rows)
            o_in.wait()
            o_v[:nrow] = jnp.where(mine, out, o_v[:nrow])
        else:
            o_v[:nrow] = jnp.where(at < rows, out, jnp.zeros_like(out))
        o_out = pltpu.make_async_copy(
            o_v.at[pl.ds(0, copy)], o_span, sem_o.at[0]
        )
        o_out.start()
        o_out.wait()

    @pl.when(rows > 0)
    def _item():
        args = (w_lane[w], w_row0[w], w_pos0[w])
        (small, small_t), (wide, wide_t) = tiles[0], tiles[-1]
        if len(tiles) == 1:
            attend(small, small_t, *args)
            return

        @pl.when(rows <= small)
        def _():
            attend(small, small_t, *args)

        @pl.when(rows > small)
        def _():
            attend(wide, wide_t, *args)
