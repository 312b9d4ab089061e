"""The gated delta rule's chunks as one launch, ``gated_delta_chunks``.

A packed step's segments of more than one row run the recurrence

    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
    o_t = S^T q_t

in chunks of ``CHUNK`` rows (``engine/attention.py``: ``gdn_chunk_terms``
and ``gdn_chunk_apply`` say what a chunk computes, and stay the path off the
chip and this kernel's reference).  Here the chunks of every such segment
run in one Pallas launch, in the work-list idiom of
``ragged_attention._work_list_kernel``: one item a *run* (a lane's segment
before, and from, the position its snapshot is taken at), the value heads'
states ``[Hv, dk, dv]`` float32 held in VMEM across the run's chunks, and
the next chunk's rows in flight while this one computes.

What the launch reads and writes, all as it lies:

- ``x [Np, C]`` float32: the convolved rows ``[q | k | v]`` as the
  convolution leaves them, a head a ``dk``-wide slab of columns, not yet
  normalised (the L2 norm of ``q`` and ``k`` is the kernel's: it has the slab
  in registers anyway).  A copy starts on whole tiles of an array's last two
  axes and a run starts at any row: a chunk copies the aligned ``CHUNK + 8``
  rows that hold its own, all columns at once, and turns a slab's rows to the
  front as it reads it.  A key head's slab is read once for the ``Hv / Hk``
  value heads it serves; rows past a run's end are masked here.
- ``gb [2 Hv, Npad]``: ``g`` and ``beta`` a head a row, the packed axis along
  the lanes.  A chunk
  copies the aligned 256-lane window that holds its rows and takes its
  running sum of ``g`` and its ``beta`` out of it with one product each
  against a mask, which shifts, masks and sums at once.
- a run's state from the lane (``lanes[layer, b]``), from the slot the plan
  restores it from, from zero for a fresh lane, or from VMEM where the run
  before it was the same lane's; its last state to the lane and, where the
  plan names one, to the snapshot's slot.  ``lanes`` and ``slots`` are
  updated in place: a lane with no run is neither read nor written.
- ``o [Np, Hv, dv]`` float32: a run's own rows and no others (a last chunk
  of fewer rows goes out in pieces of 32, 16, .. 1).  Rows of no run are
  not written: the caller masks them (a select that fuses into whatever
  reads ``o`` costs nothing; zeroing 33 MB in front of the launch took 0.09
  ms a layer).

Every product is float32 at ``Precision.HIGHEST``, the exponent is masked
above the diagonal, ``T = (I - A)^-1`` is the doubling product: the
composition's mathematics, term by term (``V'' = T (beta (v - exp(G) k S))``
would save a product and reads a third further from a float64 recurrence:
the rounding of ``k S`` then passes through ``T``).  What differs is the
order of sums: the two products of a doubling step that share a right-hand
side are one product of 128 rows, as are ``K' S`` and ``Qg S``, ``K K^T`` and
``Q K^T``, ``T (beta v)`` and ``T (beta exp(G) k)``; and a key head's value
heads lie side by side in the lanes, so that a product of two ``[64, 64]``
matrices is one of ``[64, 128]`` against a block diagonal.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ragged_attention import _tile_bytes, _vmem_limit

KERNEL_NAME = "gated_delta_chunks"
# rows of a chunk: ``attention.GDN_CHUNK``, the composition's (a restore gives
# the bits of the lane that went on only while both cut alike; tested)
CHUNK = 64
# lanes of the window of ``gb`` a chunk copies: whole 128-lane tiles that
# hold ``CHUNK`` rows from any start
_WINDOW = 256
_PRECISION = jax.lax.Precision.HIGHEST
# key heads a turn of the head loop: independent chains of products the
# scheduler can overlap (1.99 ms a launch of 2048 rows at one, 1.88 at four,
# 1.85 at eight; PERF.md section 6, PR 55)
_KEY_HEADS_A_TURN = 4

# where a run's state comes from (``src`` of the work list)
SRC_CARRY, SRC_ZERO, SRC_LANE, SRC_SLOT = 0, 1, 2, 3


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(
        a, b, dims, precision=_PRECISION, preferred_element_type=jnp.float32)


_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _doubling_steps(K: int) -> int:
    """Squarings of ``A`` until ``A^(2^n)`` with ``2^n >= K`` is zero."""
    return max(K - 1, 1).bit_length() - 1


# row counts a chunk of fewer than ``CHUNK`` rows goes out in
_PIECES = [1 << s for s in range(CHUNK.bit_length() - 2, -1, -1)]


def _kernel(
    # scalar prefetch: the layer, then the work list, an item a run
    layer_ref,  # [1]
    w_rows,  # [items] the run's rows (0 = no work)
    w_row0,  # [items] its first row in the packed axis
    w_lane,  # [items] its lane
    w_src,  # [items] where its state comes from (SRC_*)
    w_at,  # [items] the slot it restores from (SRC_SLOT)
    w_put,  # [items] 1: its last state is the lane's
    w_snap,  # [items] the slot its last state is a snapshot in, or -1
    # operands (HBM)
    x_hbm,  # [Np, C]
    gb_hbm,  # [2 Hv, Npad]
    lanes_in, slots_in,  # [Ll, B | S, Hv, dk, dv]
    o_hbm, lanes_out, slots_out,
    # scratch
    xbuf,  # [2, CHUNK + 8, C]: two slots of the aligned rows that hold a chunk
    win,  # [2, 2 Hv, _WINDOW]
    o_v,  # [2, CHUNK, Hv, dv]
    S,  # [Hv, dk, dv] the run's state
    G0, GT, GR, BT,  # [Hv, rep CHUNK]: a chunk's g, its sum up to each row
    # and from each row on, and its beta, the rows rep times along the lanes
    GC,  # [Hv, dv]: the chunk's sum of g, in every lane
    pend,  # SMEM [2]: an output slot's copy is in flight
    sem_in, sem_o, sem_s,
    *,
    Hk: int,
    dk: int,
):
    w = pl.program_id(0)
    Np = x_hbm.shape[0]
    Hv, dv = o_hbm.shape[1:]
    rep = Hv // Hk
    K = CHUNK
    RW = xbuf.shape[1]  # rows a chunk copies
    layer = layer_ref[0]
    rows, row0, b = w_rows[w], w_row0[w], w_lane[w]
    n_chunks = jax.lax.div(rows + K - 1, K)
    W = rep * K  # lanes of a key head's value heads side by side

    def state_copy(src, dst):
        return pltpu.make_async_copy(src, dst, sem_s.at[0])

    def rows_copy(start, slot):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(start, RW)], xbuf.at[slot], sem_in.at[slot])

    def window_copy(w0, slot):
        return pltpu.make_async_copy(
            gb_hbm.at[:, pl.ds(w0, _WINDOW)], win.at[slot], sem_in.at[slot])

    def window_of(row):
        return pl.multiple_of(jax.lax.div(row, 128) * 128, 128)

    def rows_of(row):
        """The first of the aligned rows copied for a chunk that starts at
        ``row``: earlier where they would overhang the axis."""
        return pl.multiple_of(
            jnp.minimum(jax.lax.div(row, 8) * 8, Np - RW), 8)

    def fetch(c, slot):
        row = row0 + c * K
        rows_copy(rows_of(row), slot).start()
        window_copy(window_of(row), slot).start()

    def landed(slot):
        rows_copy(0, slot).wait()
        window_copy(0, slot).wait()

    def out_copy(oslot, at, row, size):
        return pltpu.make_async_copy(
            o_v.at[oslot, pl.ds(at, size)], o_hbm.at[pl.ds(row, size)],
            sem_o.at[oslot])

    def slot_is_free(oslot):
        @pl.when(pend[oslot] == 1)
        def _():
            out_copy(oslot, 0, 0, K).wait()
            pend[oslot] = 0

    def chunk(c, carry):
        slot = jax.lax.rem(c, 2)
        row = row0 + c * K
        n = jnp.minimum(rows - c * K, K)  # the chunk's own rows
        landed(slot)

        @pl.when(c + 1 < n_chunks)
        def _():
            fetch(c + 1, 1 - slot)

        # g and beta of every head, the chunk's rows along the lanes: the
        # window's lanes [off, off + n) shifted to the front by a product
        # against a mask, g also summed on the way: up to each row (rows past
        # n add nothing, so the sum stays at its last), from each row on,
        # and over the chunk; the K lanes repeated for each of a key head's
        # value heads
        off = row - window_of(row)
        lw = _iota((2 * Hv, _WINDOW), 1)
        gb = jnp.where((lw >= off) & (lw < off + n), win[slot], 0.0)
        l = _iota((_WINDOW, W), 0) - off
        j = jax.lax.rem(_iota((_WINDOW, W), 1), K)
        one = lambda m: m.astype(jnp.float32)  # noqa: E731
        G0[...] = _dot(gb[:Hv], one(l == j))
        GT[...] = _dot(gb[:Hv], one(l <= j))
        GR[...] = _dot(gb[:Hv], one(l > j))
        GC[...] = _dot(gb[:Hv], jnp.ones((_WINDOW, dv), jnp.float32))
        BT[...] = _dot(gb[Hv:], one(l == j))
        slot_is_free(slot)  # the output slot of two chunks ago
        own_k = _iota((K, dk), 0) < n
        own_v = _iota((K, dv), 0) < n
        # a key head's value heads side by side in the lanes: [K, rep K]
        ii, at = _iota((K, W), 0), _iota((K, W), 1)
        blk = jax.lax.div(at, K)
        jj = at - blk * K
        eye = ii == jj
        blk_row = jax.lax.div(_iota((1, W), 1), K)
        wi, wj = _iota((W, W), 0), _iota((W, W), 1)
        same = jax.lax.div(wi, K) == jax.lax.div(wj, K)
        after = one(same & (wi > wj))  # a block: rows below the diagonal

        turn = jax.lax.rem(RW - (row - rows_of(row)), RW)

        def slab(col, width):
            """The chunk's rows of columns ``[col, col + width)``, its own
            first: the copy's rows turned by where the chunk starts in it."""
            if width % 128 == 0:
                col = pl.multiple_of(col, 128)
            held = xbuf[slot, :, pl.ds(col, width)]
            return pltpu.roll(held, turn, 0)[:K]

        def unit(x):
            return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

        def side_by_side(parts, where):
            """``parts[r]`` in the lanes of value head ``r``."""
            out = parts[0]
            for r in range(1, rep):
                out = jnp.where(where == r, parts[r], out)
            return out

        def diagonal(x):
            """``[K, rep K]`` as the block diagonal ``[rep K, rep K]``: a
            product against it multiplies each head's block by its own."""
            return jnp.where(same, jnp.concatenate([x] * rep, axis=0), 0.0)

        def by_head(xs):
            """``xs[r] [K, d]`` as ``[rep K, rep d]``, head ``r``'s in its
            rows and columns: ``[T_0 | T_1 ..]`` times it is ``[T_0 x_0 |
            T_1 x_1 ..]``."""
            zero = jnp.zeros_like(xs[0])
            return jnp.concatenate([
                jnp.concatenate(
                    [x if c == r else zero for c in range(rep)], axis=1)
                for r, x in enumerate(xs)], axis=0)

        def key_head(hk):
            q = unit(jnp.where(own_k, slab(hk * dk, dk), 0.0)) * dk ** -0.5
            k = unit(jnp.where(own_k, slab((Hk + hk) * dk, dk), 0.0))
            v = slab(2 * Hk * dk + hk * rep * dv, rep * dv)
            kq = _dot(
                jnp.concatenate([k, q], axis=0),
                jnp.concatenate([k] * rep, axis=0), _NT)  # [2K, rep K]
            kk, qk = kq[:K], kq[K:]
            heads = [hk * rep + r for r in range(rep)]

            def column(ref, r):
                """A head's row of ``ref`` as a column ``[K, 1]``."""
                return jnp.sum(
                    jnp.where(eye & (blk == r), ref[pl.ds(heads[r], 1), :], 0.0),
                    axis=1, keepdims=True)

            b_cols = [column(BT, r) for r in range(rep)]
            # exp(G_i - G_j) from the sum of g over (j, i] itself: a
            # difference of two running sums rounds at the size of the sums
            g_rows = side_by_side([G0[pl.ds(h, 1), :] for h in heads], blk_row)
            diff = _dot(jnp.where(jj <= ii, g_rows, 0.0), after)
            below = jnp.exp(jnp.where(ii > jj, diff, -jnp.inf))
            upto = jnp.exp(jnp.where(ii >= jj, diff, -jnp.inf))
            A = -side_by_side(b_cols, blk) * kk * below
            # T = (I - A)^-1 = (I + A)(I + A^2)(I + A^4)..: a step's two
            # products share their right-hand side
            P = _dot(A, diagonal(A))
            T = eye.astype(jnp.float32) + A
            for _ in range(_doubling_steps(K) - 1):
                tp = _dot(jnp.concatenate([T, P], axis=0), diagonal(P))
                T, P = T + tp[:K], tp[K:]
            T = T + _dot(T, diagonal(P))
            # V' = T (beta v) and K' = T (beta exp(G) k), every head of the
            # group in one product; then V'' = V' - K' S, a state at a time
            e_cols = [jnp.exp(column(GT, r)) for r in range(rep)]
            tv = _dot(T, jnp.concatenate([
                by_head([
                    b * jnp.where(own_v, v[:, r * dv:(r + 1) * dv], 0.0)
                    for r, b in enumerate(b_cols)]),
                by_head([(b * e) * k for b, e in zip(b_cols, e_cols)]),
            ], axis=1))  # [K, rep dv + rep dk]
            Vpp = []
            for r, h in enumerate(heads):
                Kp = tv[:, rep * dv + r * dk:rep * dv + (r + 1) * dk]
                ks = _dot(jnp.concatenate([Kp, e_cols[r] * q], axis=0), S[h])
                Vpp.append(tv[:, r * dv:(r + 1) * dv] - ks[:K])
                o_v[slot, :, h, :] = ks[K:]  # Qg S; W V'' joins it below
            O = _dot(qk * upto, by_head(Vpp))  # [K, rep dv]
            for r, h in enumerate(heads):
                o_v[slot, :, h, :] += O[:, r * dv:(r + 1) * dv]
                g_end = GC[pl.ds(h, 1), :]  # [1, dv], the same in every lane
                Kd = jnp.exp(column(GR, r)) * k
                S[h] = jnp.exp(g_end) * S[h] + _dot(Kd, Vpp[r], _TN)

        group = math.gcd(Hk, _KEY_HEADS_A_TURN)

        def key_heads(i, carry):
            for t in range(group):
                key_head(i * group + t)
            return carry

        jax.lax.fori_loop(0, Hk // group, key_heads, 0)

        @pl.when(n == K)
        def _():
            out_copy(slot, 0, row, K).start()
            pend[slot] = 1

        @pl.when(n < K)
        def _():
            # the run's last rows and no others, in pieces
            for size in _PIECES:
                at = n & ~(2 * size - 1)  # the larger pieces before it

                @pl.when((n & size) != 0)
                def _():
                    cp = out_copy(slot, at, row + at, size)
                    cp.start()
                    cp.wait()

        return carry

    @pl.when(rows > 0)
    def _run():
        src = w_src[w]
        pend[0] = 0
        pend[1] = 0
        fetch(0, 0)

        @pl.when(src == SRC_ZERO)
        def _():
            S[...] = jnp.zeros(S.shape, S.dtype)

        @pl.when(src == SRC_LANE)
        def _():
            cp = state_copy(lanes_in.at[layer, b], S)
            cp.start()
            cp.wait()

        @pl.when(src == SRC_SLOT)
        def _():
            cp = state_copy(slots_in.at[layer, w_at[w]], S)
            cp.start()
            cp.wait()

        jax.lax.fori_loop(0, n_chunks, chunk, 0)
        slot_is_free(0)
        slot_is_free(1)
        put, snap = w_put[w] == 1, w_snap[w]
        to_lane = state_copy(S, lanes_out.at[layer, b])
        to_slot = state_copy(S, slots_out.at[layer, jnp.maximum(snap, 0)])
        pl.when(put)(to_lane.start)
        pl.when(snap >= 0)(to_slot.start)
        pl.when(put)(to_lane.wait)
        pl.when(snap >= 0)(to_slot.wait)


def chunks_of(q_lens, base, plan) -> int:
    """Chunks one layer's launch runs for a dispatch, on the host: the
    device's cut of its segments into runs (``attention.delta_runs``)."""
    total = 0
    for n, at, restore, slot, pos in zip(q_lens, base, *plan):
        n = int(n)
        if n < 1 or (n == 1 and not ((restore >= 0 and at > 0) or slot >= 0)):
            continue
        cut = min(max(int(pos) - int(at), 0), n) if slot >= 0 else n
        total += -(-cut // CHUNK) + -(-(n - cut) // CHUNK)
    return total


@functools.partial(jax.jit, static_argnames=("Hk", "Hv", "interpret"))
def gated_delta_chunks(
    x: jax.Array,  # [Np, C] f32 convolved rows [q | k | v], not normalised
    g: jax.Array,  # [Np, Hv] f32 log-decay
    beta: jax.Array,  # [Np, Hv]
    lanes: jax.Array,  # [Ll, B, Hv, dk, dv] f32
    slots: jax.Array,  # [Ll, S, Hv, dk, dv] f32
    layer: jax.Array,  # index among the linear layers
    run_len: jax.Array,  # [2 B] rows of a lane's two runs (0 = none)
    run_off: jax.Array,  # [2 B] their first rows
    src: jax.Array,  # [B] where a lane's first run starts from (SRC_*)
    restore: jax.Array,  # [B] the slot, where that is SRC_SLOT
    snap: jax.Array,  # [B] the slot the first run's last state goes to, or -1
    *,
    Hk: int,
    Hv: int,
    interpret: bool = False,
):
    """The launch over a dispatch's runs, a lane's two side by side in the
    list: the second goes on from the first's state in VMEM.  Returns ``(o
    [Np, Hv, dv], lanes, slots)``: ``o`` holds the runs' rows and is not
    initialised elsewhere, the other two are updated in place."""
    Np, C = x.shape
    dk, dv = lanes.shape[-2:]
    if C != 2 * Hk * dk + Hv * dv or Np % 8:
        raise ValueError(f"rows {x.shape} for {Hk} and {Hv} heads of {dk} x {dv}")
    K = CHUNK
    short = max(K + 8 - Np, 0)  # a packed axis shorter than a chunk's copy
    if short:
        x, g, beta = (jnp.pad(a, ((0, short), (0, 0))) for a in (x, g, beta))
    rows = Np + short
    n_items = run_len.shape[0]
    i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    run_len = i32(run_len)
    second = jnp.arange(n_items) % 2 == 1
    lane = jnp.arange(n_items, dtype=jnp.int32) // 2
    go_on = jnp.roll(run_len, -1) > 0  # (of a first run:) a second follows
    w_src = jnp.where(second, SRC_CARRY, i32(src)[lane])
    w_put = jnp.where(second | ~go_on, 1, 0).astype(jnp.int32)
    w_snap = jnp.where(second, -1, i32(snap)[lane])
    w_at = jnp.clip(i32(restore)[lane], 0, slots.shape[1] - 1)
    # g and beta a head a row, padded to whole windows
    n_pad = -(-rows // 128) * 128 + 128
    gb = jnp.concatenate([g.T, beta.T]).astype(jnp.float32)
    gb = jnp.pad(gb, ((0, 0), (0, n_pad - rows)))
    f32 = jnp.float32
    rep = Hv // Hk
    need = (
        _tile_bytes((2, K + 8, C), f32)
        + _tile_bytes((2, K, Hv, dv), f32)
        + _tile_bytes((Hv, dk, dv), f32)
        + _tile_bytes((2, 2 * Hv, _WINDOW), f32)
        + (8 << 20)  # a key head's matrices in flight
    )
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    n_scalars = 8
    o, lanes, slots = pl.pallas_call(
        functools.partial(_kernel, Hk=Hk, dk=dk),
        out_shape=(
            jax.ShapeDtypeStruct((rows, Hv, dv), f32),
            jax.ShapeDtypeStruct(lanes.shape, lanes.dtype),
            jax.ShapeDtypeStruct(slots.shape, slots.dtype),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_scalars,
            grid=(n_items,),
            in_specs=[hbm] * 4,
            out_specs=(hbm, hbm, hbm),
            scratch_shapes=[
                pltpu.VMEM((2, K + 8, C), f32),
                pltpu.VMEM((2, 2 * Hv, _WINDOW), f32),
                pltpu.VMEM((2, K, Hv, dv), f32),
                pltpu.VMEM((Hv, dk, dv), f32),
                pltpu.VMEM((Hv, rep * K), f32),
                pltpu.VMEM((Hv, rep * K), f32),
                pltpu.VMEM((Hv, rep * K), f32),
                pltpu.VMEM((Hv, rep * K), f32),
                pltpu.VMEM((Hv, dv), f32),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,)),
            ],
        ),
        # lanes and slots, after the scalars, x and gb
        input_output_aliases={n_scalars + 2: 1, n_scalars + 3: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(need),
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )(
        jnp.clip(i32(layer), 0, lanes.shape[0] - 1).reshape(1),
        run_len, i32(run_off), lane, w_src, w_at, w_put, w_snap,
        x, gb, lanes, slots,
    )
    return o[:Np], lanes, slots
