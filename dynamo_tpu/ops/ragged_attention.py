"""Ragged paged attention for a mixed prefill+decode batch: one launch over
the packed token axis (TPU Pallas kernels and their XLA reference).

The serving gap this closes (ROADMAP item 2, *Ragged Paged Attention* in
PAPERS.md): prefill and decode used to run as separate XLA dispatches that
alternate on the chip, so every admitted prompt stalled the decode batch
and TTFT traded off against ITL.  The packed step takes **ragged
per-sequence query lengths** over the paged KV layout -- a decode lane
contributes one query row, a chunked-prefill lane its chunk, a speculating
lane its verify columns -- on ONE flat token axis of ``Np`` rows with
per-lane segment offsets, and serves the whole batch in one launch.

Geometry: lane ``b``'s query row ``i`` sits at packed row ``seg_off[b] +
i`` and at absolute position ``base[b] + i`` (``base`` = committed cache
length); rows at ``i >= q_lens[b]`` are padding whose output the host never
reads (their KV writes route to trash page 0, the engine-wide invalid-row
convention).  Softmax is the flash-style online max/sum rescale in f32
VMEM scratch.

What is here:

* :func:`ragged_paged_attention_xla` -- the pure-XLA reference over a lane
  rectangle ``[B, S]``: gather the table's pages as the prefix, concatenate
  the fresh columns, one masked softmax.  The parity oracle of every kernel
  below and, through :func:`packed_ragged_attention_xla` (unpack, call it,
  repack), the CPU tier-1 code path.
* :func:`packed_ragged_attention` -- the packed launch, two kernels chosen
  by the pool at trace time (:func:`_takes_work_list`).  A dense pool of
  128-lane heads walks a **work list** with its pages by DMA (the section
  ahead of :func:`_work_list_kernel`).  An int8 pool and heads narrower than
  128 lanes keep the **page-group grid** (:func:`_packed_kernel`): grid
  ``(B, P/G + 1)``, the page table rides as scalar prefetch, each step
  fetches ``G`` pages of the lane's resident prefix (positions ``<
  base[b]``) as independently pipelined block operands, and the final step
  attends the dispatch's own fresh K/V causally at token granularity.

* :func:`decode_work_list_attention` -- the decode launch of the fused
  steps over a dense pool of 128-lane heads: the work-list kernel at the
  ``(lanes, 1)`` tile with a fixed list, one item a lane, a query head a
  row over each key block as it lies in the pool (a kv head's rows over
  that head's keys where the pool has a few heads wider than the lanes).

``interpret=True`` runs a kernel through the Pallas interpreter
(CPU-testable); ``engine.attention.packed_ragged_attention_dispatch``
resolves kernel or reference at trace time like every other dispatch gate.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# Physical VMEM the kernels may ask the compiler for: a v5e/v6e core holds
# 128 MiB (jax.experimental.pallas.tpu.get_tpu_info), and the default scoped
# limit (16 MiB) refuses the packed kernel from s_max 256 up.  Each
# ``pallas_call`` below passes the footprint it computes from its own block
# and scratch shapes as ``vmem_limit_bytes``; a shape whose footprint passes
# this cap is refused by :func:`packed_shape_fits` before the engine can mint
# it.
VMEM_CAP_BYTES = 100 << 20

# rows of the int8 pool's row-scale array one scale block carries: the TPU
# lowering wants a block's second-minor dimension divisible by 8, so a page's
# scales ride in the aligned 8-page group that holds them
_SCALE_ROWS = 8


def _vmem_limit(need: int) -> int:
    """What a call asks the compiler for: half again over the footprint it
    counts (the limit is a ceiling, not a reservation), at least the
    default 16 MiB, never past the cap."""
    return min(VMEM_CAP_BYTES, max(need + need // 2, 16 << 20))


def _sublanes(dtype) -> int:
    """Rows of one packed (sublane) tile of ``dtype``: 8 of 32 bits."""
    return 8 * max(4 // jnp.dtype(dtype).itemsize, 1)


def _tile_bytes(shape, dtype) -> int:
    """Bytes ``shape`` occupies in VMEM: the last dimension pads to 128
    lanes, the one before it to the dtype's sublane pack (8 rows of 32
    bits)."""
    item = jnp.dtype(dtype).itemsize
    sub = _sublanes(dtype)
    dims = list(shape)
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) > 1:
        dims[-2] = -(-dims[-2] // sub) * sub
    n = item
    for d in dims:
        n *= d
    return n


def _scale_column(s_ref, kv_idx, row, page):
    """One page's row scales as a ``[page, 1]`` column.  ``s_ref`` is the
    ``[1, 2, _SCALE_ROWS, page]`` block holding the page's aligned group;
    ``row`` picks the page inside it.  The scales arrive along lanes and
    the dequant multiplies along sublanes, so the row is turned with a
    masked lane reduction (iota, select, sum) -- ops every TPU generation
    lowers, on one vreg."""
    srow = s_ref[0, kv_idx, pl.ds(row, 1), :]  # [1, page]
    r = jax.lax.broadcasted_iota(jnp.int32, (page, page), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (page, page), 1)
    return jnp.sum(
        jnp.where(r == c, jnp.broadcast_to(srow, (page, page)), 0.0),
        axis=1, keepdims=True,
    )


def _dequant_block(blk, s_ref, kv_idx, row, out_dtype):
    """In-kernel fused dequant of one fetched page block, returned
    heads-major: ``blk`` is the raw ``[page, Hkv, D]`` VMEM tile (int8 for
    a quantized pool), ``s_ref`` its row-scale group block (None for dense
    pools) and ``row`` the page's row inside that group.  The multiply
    runs on the VMEM-resident tile right after the HBM fetch -- the pool's
    int8 bytes are the only thing that ever streams.  Dense pools whose
    dtype differs from the compute dtype (an explicit ``--kv-dtype
    float32`` under a bf16 model) convert here too -- ``lax.dot_general``
    rejects mixed operand dtypes.  Returns ``[Hkv, page, D]``."""
    if s_ref is None:
        blk = blk if blk.dtype == out_dtype else blk.astype(out_dtype)
        return blk.transpose(1, 0, 2)
    col = _scale_column(s_ref, kv_idx, row, blk.shape[0])
    return (
        blk.astype(jnp.float32).transpose(1, 0, 2) * col[None]
    ).astype(out_dtype)


def _group_kv(kv_refs, s_refs, rows, kv_idx, out_dtype):
    """The fetched page group's K (``kv_idx`` 0) or V (1) as one
    ``[Hkv, G*page, D]`` block."""
    return jnp.concatenate(
        [
            _dequant_block(r[0, kv_idx, 0], sr, kv_idx, row, out_dtype)
            for r, sr, row in zip(kv_refs, s_refs, rows)
        ],
        axis=1,
    )


def _for_blocks(n, body) -> None:
    """Run ``body(i)`` for ``i`` in ``[0, n)``: inline for a static single
    block (the shape every small dispatch has), else one ``fori_loop`` so
    the body is emitted once whatever ``n`` is."""
    if isinstance(n, int) and n == 1:
        body(0)
        return

    def step(i, carry):
        body(i)
        return carry

    jax.lax.fori_loop(0, n, step, 0)


def _kv_stream_bytes(page, Hkv, D, G, kv_dtype, quant) -> int:
    """The grid kernel's double-buffered page-group operands."""
    n = 2 * G * 2 * _tile_bytes((page, Hkv, D), kv_dtype)
    if quant:
        n += 2 * G * 2 * _tile_bytes((_SCALE_ROWS, page), jnp.float32)
    return n


def _body_bytes(rows, keys, Hkv, D, dtype) -> int:
    """Live values of one accumulate body over ``rows`` query rows (all
    heads) against ``keys`` keys: the f32 score chain (scores, mask,
    probabilities and their casts -- four live copies), the K/V group in
    f32 and compute dtype, the query block and the f32 PV product."""
    return (
        4 * _tile_bytes((rows, keys), jnp.float32)
        + 4 * Hkv * _tile_bytes((keys, D), jnp.float32)
        + 2 * _tile_bytes((rows, D), jnp.float32)
        + 2 * _tile_bytes((rows, D), dtype)
    )


def ragged_paged_attention_xla(
    q: jax.Array,  # [B, S, Hq, D]
    k: jax.Array,  # [B, S, Hkv, D] fresh keys
    v: jax.Array,  # [B, S, Hkv, D]
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    page_table: jax.Array,  # [B, P]
    base: jax.Array,  # [B]
    q_lens: jax.Array,  # [B]
    layer: jax.Array | int = 0,
    window: int = 0,
) -> jax.Array:
    """Pure-XLA reference of ragged attention: gather the full table's
    pages as the prefix key block (masked at token granularity by
    ``kpos < base``), concatenate the fresh columns, one masked softmax.
    Same math as ``engine.attention.prefill_prefix_attention`` run with
    the whole page table as the prefix -- the kernels' parity oracle and
    the CPU tier-1 code path.  Takes either pool form: a ``QuantKV``
    pool's pages dequantize right after the gather (same rule the fused
    kernel applies per VMEM tile)."""
    from ..engine.kv_cache import (
        gather_layer_kv, index_kv_layer, kv_data, kv_num_layers,
    )

    B, S, Hq, D = q.shape
    data = kv_data(kv_pages)
    L = kv_num_layers(kv_pages)
    page_size = data.shape[3]
    P = page_table.shape[1]
    Hkv = k.shape[2]
    n_rep = Hq // Hkv

    lyr = jnp.clip(jnp.asarray(layer, jnp.int32), 0, L - 1)
    layer_kv = index_kv_layer(kv_pages, lyr)
    kp = gather_layer_kv(layer_kv, 0, page_table, q.dtype).reshape(
        B, P * page_size, Hkv, D
    )
    vp = gather_layer_kv(layer_kv, 1, page_table, q.dtype).reshape(
        B, P * page_size, Hkv, D
    )

    def rep(x):
        return x if n_rep == 1 else jnp.repeat(x, n_rep, axis=-2)

    keys = rep(jnp.concatenate([kp, k], axis=1))
    vals = rep(jnp.concatenate([vp, v], axis=1))
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, keys) * scale

    local = jnp.arange(S)
    kpos = jnp.arange(P * page_size)
    prefix_valid = kpos[None, :] < base[:, None]  # [B, Kp]
    fresh_valid = local[None, :] < q_lens[:, None]  # [B, S]
    causal = local[None, :] <= local[:, None]  # [Sq, Sk]
    if window > 0:
        q_abs = base[:, None] + local[None, :]  # [B, Sq]
        prefix_win = kpos[None, None, :] > q_abs[:, :, None] - window
        mask_prefix = jnp.broadcast_to(
            (prefix_valid[:, None, :] & prefix_win)[:, None],
            (B, 1, S, P * page_size),
        )
        causal = causal & (local[:, None] - local[None, :] < window)
    else:
        mask_prefix = jnp.broadcast_to(
            prefix_valid[:, None, None, :], (B, 1, S, P * page_size)
        )
    mask_fresh = jnp.broadcast_to(
        causal[None, None, :, :] & fresh_valid[:, None, None, :], (B, 1, S, S)
    )
    mask = jnp.concatenate([mask_prefix, mask_fresh], axis=-1)
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vals)


# ---------------------------------------------------------------------------
# fully-packed ragged layout (ISSUE 10): flat token axis + per-lane offsets
# ---------------------------------------------------------------------------
#
# A lane rectangle ``[B, S]`` pads EVERY lane's query axis to the
# dispatch's max chunk, so one long prefill chunk makes the whole batch pay
# its width -- with B=8 lanes, a 512-token chunk next to 7 decode lanes is
# 4096 rows for 519 real tokens.  The packed layout carries the dispatch's
# fresh tokens on ONE flat axis of length pow2_bucket(total) with per-lane
# segment offsets: the trunk (embed / QKV / MLP / logits -- the bulk of
# prefill FLOPs) runs exactly the packed rows, and attention resolves each
# token's lane through the offset tables.  Segments are packed contiguously
# in slot order, one segment per lane, decode lanes contributing a single row.


# query rows one accumulate body handles: a lane's ``s_max`` window is
# walked in blocks of this many rows, so the score tile, the emitted code and
# the compile time stop growing with ``s_max`` (a whole 512-row window in one
# body asked for 32 MiB of scores and most of a minute of compile)
_Q_BLOCK = 128


def _packed_kernel(
    # scalar prefetch
    layer_ref,  # [1] layer index (SMEM)
    pt_ref,  # [B, P] page table (SMEM)
    base_ref,  # [B] committed cache length = first fresh position (SMEM)
    off_ref,  # [B] lane's segment offset into the packed axis (SMEM)
    len_ref,  # [B] fresh rows per lane (SMEM)
    *refs,  # G kv blocks (+ G row-scale group blocks when the pool is
    # int8), packed q, packed fresh k/v, o_ref, m/l/acc scratch
    G: int,
    s_max: int,
    quant: bool = False,
    window: int = 0,
):
    """Grid ``(B, P/G + 1)``: steps ``p < P/G`` stream the lane's resident
    prefix page groups, the final step folds in the dispatch's own fresh K/V
    with per-token causal masking; one online-softmax accumulator serves
    both phases.  The operands are PACKED: the whole packed
    ``[Np, H, D]`` q / fresh-k / fresh-v arrays ride as single VMEM
    blocks (revisited every step, so they transfer once), and lane ``b``
    reads its ``s_max``-row window at ``off_ref[b]`` with dynamic
    slices, ``_Q_BLOCK`` rows at a time.  The caller guarantees
    ``off + s_max <= Np`` for every live lane (packed-axis padding rule
    in the step assembly), so a slice never clamps and rows stay aligned.
    Only the query blocks that hold live rows (``< q_len``) are walked,
    and the fresh phase pairs query block ``i`` with key blocks ``<= i``
    (later ones are wholly above the causal diagonal).

    Output aliasing: lane ``b``'s final step writes whole query blocks,
    whose tail (rows past ``q_len``) overlaps the NEXT lanes' segments --
    safe because the grid walks lanes in ascending order, so a later
    lane's write overwrites any garbage a predecessor spilled into its
    rows.  Idle lanes (``q_len == 0``) skip both compute and the write
    (their offset is 0 and would clobber the first live lane)."""
    kv_refs = refs[:G]
    s_refs = refs[G : 2 * G] if quant else [None] * G
    q_ref, fk_ref, fv_ref, o_ref, m_scr, l_scr, acc_scr = refs[
        2 * G if quant else G :
    ]
    b = pl.program_id(0)
    p = pl.program_id(1)
    npg = pl.num_programs(1) - 1
    page = kv_refs[0].shape[3]
    Hkv = kv_refs[0].shape[4]
    D = kv_refs[0].shape[5]
    Hq = q_ref.shape[1]
    n_rep = Hq // Hkv
    scale = 1.0 / (D ** 0.5)
    nq, qb = m_scr.shape[0], m_scr.shape[1] // Hq  # query blocks x rows

    base = base_ref[b]
    off = off_ref[b]
    q_len = len_ref[b]
    live_lane = q_len > 0
    # query blocks holding live rows; static when the window is one block
    n_live = 1 if nq == 1 else (q_len + qb - 1) // qb

    @pl.when((p == 0) & ((b == 0) | live_lane))
    def _init():
        def init(i):
            m_scr[i] = jnp.full(m_scr.shape[1:], _NEG_INF, m_scr.dtype)
            l_scr[i] = jnp.zeros(l_scr.shape[1:], l_scr.dtype)
            acc_scr[i] = jnp.zeros(acc_scr.shape[1:], acc_scr.dtype)

        _for_blocks(nq, init)

    @pl.when((b == 0) & (p == 0))
    def _zero_out():
        # pad rows of the packed output are never overwritten by a lane's
        # window; zero once so the host-bound array holds no uninitialized
        # memory
        o_ref[:] = jnp.zeros_like(o_ref)

    def q4(i):
        # query block i of the lane window -> [Hkv, n_rep, qb, D]
        qw = q_ref[pl.ds(off + i * qb, qb)]
        return qw.transpose(1, 0, 2).reshape(Hkv, n_rep, qb, D)

    def accumulate(i, s, v):  # s [Hkv, n_rep, qb, K], v [Hkv, K, D]
        s2 = s.reshape(Hq * qb, s.shape[-1])
        m_prev = m_scr[i]
        m_cur = jnp.max(s2, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(s2 - m_new)
        pv = jax.lax.dot_general(
            probs.reshape(Hkv, n_rep * qb, s.shape[-1]).astype(v.dtype), v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_scr[i] = m_new
        l_scr[i] = l_scr[i] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        acc_scr[i] = acc_scr[i] * alpha + pv.reshape(Hq * qb, D)

    grp_base = p * G * page
    live = live_lane & (p < npg) & (grp_base < base)
    if window > 0:
        live = live & (grp_base + G * page > base - window)

    @pl.when(live)
    def _prefix():
        # each page's row inside its scale group (int8 pools only)
        rows = [
            pt_ref[b, p * G + g] % _SCALE_ROWS if quant else 0
            for g in range(G)
        ]
        k = _group_kv(kv_refs, s_refs, rows, 0, q_ref.dtype)
        v = _group_kv(kv_refs, s_refs, rows, 1, q_ref.dtype)  # [Hkv, G*page, D]

        def block(i):
            s = jax.lax.dot_general(
                q4(i), k,
                dimension_numbers=(((3,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale  # [Hkv, n_rep, qb, G*page]
            kpos = grp_base + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, dimension=3
            )
            keep = kpos < base
            if window > 0:
                qpos = base + i * qb + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, dimension=2
                )
                keep = keep & (kpos > qpos - window)
            accumulate(i, jnp.where(keep, s, _NEG_INF), v)

        _for_blocks(n_live, block)

    @pl.when(live_lane & (p == npg))
    def _fresh():
        def block(i):
            q = q4(i)

            def keys(j):
                at = pl.ds(off + j * qb, qb)
                fk = fk_ref[at].transpose(1, 0, 2)  # [Hkv, qb, D]
                fv = fv_ref[at].transpose(1, 0, 2)
                s = jax.lax.dot_general(
                    q, fk,
                    dimension_numbers=(((3,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                ) * scale  # [Hkv, n_rep, qb, qb]
                qi = i * qb + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, dimension=2
                )
                kj = j * qb + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, dimension=3
                )
                keep = (kj <= qi) & (kj < q_len)
                if window > 0:
                    keep = keep & (qi - kj < window)
                accumulate(i, jnp.where(keep, s, _NEG_INF), fv)

            _for_blocks(1 if nq == 1 else i + 1, keys)
            l = l_scr[i]
            safe = jnp.where(l > 0.0, l, 1.0)
            out = (acc_scr[i] / safe).reshape(Hkv, n_rep, qb, D)
            o_ref[pl.ds(off + i * qb, qb)] = (
                out.reshape(Hq, qb, D).transpose(1, 0, 2).astype(o_ref.dtype)
            )

        _for_blocks(n_live, block)


# ---------------------------------------------------------------------------
# the packed launch over a dense pair pool: a work list, pages by DMA
# ---------------------------------------------------------------------------
#
# The grid kernel above pays a grid step for every page group of the TABLE's
# width on every lane, live or not, multiplies 64 keys a step, and holds the
# whole packed q/k/v/out in VMEM.  A dense (bf16/f32) pool takes this kernel
# instead: the grid is a list of work items -- (lane, block of up to
# ``_WL_Q_BLOCK`` query rows), built on the device by
# ``latent_attention.packed_work_list`` -- and an item loops over exactly the
# key blocks its rows can see: from the block that holds position ``pos0 -
# window + 1`` (block 0 without a window) to the block of its last row's own
# position.  The dispatch's fresh rows are already in the pool (the step
# scatters them first), so there is one source of keys and ``kpos <= qpos``
# alone parts fresh from resident.  A key block is ``_WL_KEY_BLOCK`` keys:
# its K and V pages come HBM->VMEM by DMA, issued and waited for in rolled
# loops over the block's LIVE pages (the descriptors in the kernel's jaxpr do
# not grow with the block), double-buffered against the block's compute.  An
# item's queries come in and its rows go out by DMA; nothing is resident but
# a tile, so VMEM does not grow with ``Np``.  An item of a few rows (a decode
# lane riding a chunk, every lane of a fused dispatch's first step) takes a
# small tile.
#
# The items of a launch overlap (PR 46).  The list is scalar prefetch, so an
# item knows the one after it: as it enters its last key block it starts that
# item's query copy (``q_v`` is free once the queries lie heads-major or in
# registers) and the page copies of that item's first key block, into the
# slot its own last block does not use; which slot that is travels in an SMEM
# scalar.  Its output copy it starts and leaves: the next item waits for it
# where it next writes ``o_v``, or, where its tile spans rows of the output
# that earlier items wrote, before it reads that span (after its first
# block's compute, so the rows have had that long to land).  Only the first
# of a run of live items fetches for itself, only the last waits for its own
# output, and every semaphore is back at zero when the grid ends.
#
# The one-row tile (every decode row: alone in the ``(lanes, 1)`` launches,
# the packed step's and the fused steps', or an item beside a chunk's) has
# its own body.  Measured alone (PERF.md section 5, PR 46), a launch of
# one-row items spent its time turning each key block heads-major and running
# 16 padded rows a query head, not waiting for copies.  The kernel is handed
# the pool a second time as the same bytes seen ``[page * Hkv, D]`` a page; a
# one-row item's pages land as one ``[KB * Hkv, D]`` matrix a block, its
# ``Hq`` query heads are the rows of one product against all of it, and a
# mask keeps, for a head, the columns of its own kv head: no transpose, no
# head loop, no padding rows, at ``Hkv`` times the multiplications, which the
# MXU has to spare while the block's bytes arrive.
#
# That second form is handed over only where it is a view of the pool
# (:func:`_pages_are_matrices`: heads of 128 lanes, or whole sublane tiles of
# heads).  A pool of a few heads wider than the lanes (Qwen3-Next's 2 of 256)
# lies a head's lane tiles side by side, two heads' bf16 in one word; a page
# as one matrix is then a relayout of the pool in HBM, which XLA made in front
# of every launch (3.9 ms for a layer's 0.54 GB, three a step: PERF.md, PR 54).
# Over such a pool the one-row tile reads its key blocks from the pool's own
# form, a token a row of heads, into slots of the form the several-row body
# uses, turns a block heads-major as that body does, and multiplies a kv head's
# own ``n_rep`` query rows with that head's keys, head by head over the few
# heads: no second form of the pool, no column of another head.
#
# A tile never leaves the packed axis: one that would overhang ``Np`` (a
# decode row in the last packed rows, the tail block of a chunk that fills
# the axis) starts ``shift`` rows early, at ``Np - copy``, and the item's
# rows lie ``shift`` into it.  So nothing but ``total <= Np`` binds the
# packed shape (``engine.attention.PackedLaunch.item_rows``).  An item
# writes its own rows into what its tile's span of the output holds, read
# first: items run in ascending row order and each reads its span only once
# the item before it has landed, so the rows before its own are what earlier
# items wrote, the rows after it the zeros the output buffer is created
# with, which the rows no item covers keep.

# query rows (tokens) of one work item, and the rows at or under which an
# item takes the small tile
_WL_Q_BLOCK = 256
_WL_SMALL_ROWS = 8
# keys of one key block
_WL_KEY_BLOCK = 512
# float32 score tiles ``[heads, rows, keys]`` one step of the head loop may
# hold: at 32 query heads over 8 that is four kv heads a step, which the chip
# ran a quarter faster than one (their chains overlap) and no slower than
# eight (PERF.md, PR 32)
_WL_SCORE_BYTES = 8 << 20


def _takes_work_list(D: int, quant: bool) -> bool:
    """Which packed kernel a pool takes, read off the input at trace time:
    the work list for a dense pool whose head dimension is whole 128-lane
    tiles (Mosaic will not slice a page of narrower rows for a DMA); the
    grid kernel for an int8 pool, whose row scales it dequantizes in the
    read, and for narrow heads."""
    return not quant and D % 128 == 0


def _pages_are_matrices(Hkv: int, D: int) -> bool:
    """Whether a page seen as one matrix ``[page * Hkv, D]`` is a view of the
    pool: its own bytes, so that the reshape costs nothing and a page can be
    copied as it lies into a slot of that form.  True where a token's row of
    heads is one tile of lanes wide (``D`` 128) or whole sublane tiles high.
    A few heads wider than the lanes (Qwen3-Next's 2 of 256) lie a head's
    lane tiles side by side where the matrix wants them a row apart: there
    the reshape is a relayout of the pool in HBM, and the one-row tile reads
    the pool's own form instead (:func:`_work_list_kernel`)."""
    return D <= 128 or Hkv % 8 == 0


def _work_list_tiles(s_max: int, dtype):
    """The kernel's tiles as ``(copy rows, tile rows)``, smallest first: an
    item takes the smallest that holds its rows, moves ``copy`` rows by DMA
    and computes on a tile padded to whole sublanes.  The one-row tile has a
    body of its own (a query head a row); the ``(lanes, 1)`` step has it
    alone."""
    qb = min(s_max, _WL_Q_BLOCK)
    sub = _sublanes(dtype)
    copies = sorted({1, min(_WL_SMALL_ROWS, qb), qb})
    return qb, [(copy, max(copy, sub)) for copy in copies]


def packed_item_counts(q_lens, s_max: int):
    """``(items, small, chained)`` of a packed launch over a dense pool, from
    the lanes' fresh rows on the host: its live work items, how many of them
    have at most ``_WL_SMALL_ROWS`` rows (the one-row and the small tile),
    and how many find their queries and first key block already in flight,
    because the item before them in the list is live (the list packs its
    live items first, so all but the first: the tick's ``dispatch``
    annotation)."""
    qb, _ = _work_list_tiles(s_max, jnp.float32)
    small = min(_WL_SMALL_ROWS, qb)
    items = n_small = 0
    for n in q_lens:
        full, rest = divmod(int(n), qb)
        items += full + (rest > 0)
        n_small += full * (qb <= small) + (0 < rest <= small)
    return items, n_small, max(items - 1, 0)


def _work_list_kernel(
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
    kv_flat,  # the same pool, a page as one matrix [page * Hkv, D], where
    # that is a view (:func:`_pages_are_matrices`); else None
    _o_init,  # the zeroed output buffer (aliased to o_hbm)
    o_hbm,  # [Np, Hq, D]
    # scratch: :func:`_work_list_scratch` (None: not held at this launch)
    q_v,  # [rows_t, Hq, D] an item's queries as they lie in HBM
    o_v,  # [rows_t, Hq, D]
    kflat,  # [2, 2, KB * Hkv, D] two slots of a key block as it lies
    slot_ref,  # [1] SMEM: the slot the next item's first key block takes
    sem_q, sem_kv, sem_o,
    q_t=None,  # [Hkv, M, D] a tile of several rows' queries heads-major
    kbuf=None,  # [2, 2, KB, Hkv, D] two slots, a token a row of heads
    kv_t=None, m_scr=None, l_scr=None, acc_scr=None,  # tiles of several rows
    *,
    tiles,
    window: int,
):
    """One work item a grid step, in list order, and the items overlap: an
    item that a live item follows starts that item's query copy and the page
    copies of its first key block as it enters its own last block, and
    leaves its output copy in flight for that item to wait for.  So a live
    item behind a live one begins with copies that have had a block's
    compute to land, and only the first of a run fetches for itself.

    Two bodies, by the item's tile.  A tile of several rows (chunks, verify
    columns) turns a key block heads-major and runs a kv head's group of
    rows against its keys.  The one-row tile (a decode row, alone in the
    ``(lanes, 1)`` launches or beside a chunk) has a query head a row, and
    the pool's shape says how it reads its keys (:func:`_pages_are_matrices`,
    at trace time).  Where a page as one matrix is a view of the pool (heads
    of 128: every pair pool but Qwen3-Next's) it multiplies its ``Hq`` rows
    with the block as it lies, every kv head's keys side by side as ``KB *
    Hkv`` columns, and masks the columns of other heads, so that nothing is
    transposed and no row is padding (``attend_row``).  Where it is not (a
    few heads wider than the lanes: 2 of 256) there is no such operand: the
    block comes a token a row of heads, is turned heads-major, and a kv
    head's own ``n_rep`` rows meet that head's keys, head by head
    (``attend_row_by_head``)."""
    w = pl.program_id(0)
    W = pl.num_programs(0)
    Np, Hq, D = q_hbm.shape
    page, Hkv = kv_hbm.shape[3:5]
    n_rep = Hq // Hkv
    KB = _key_block(page)
    P = pt_ref.shape[1]
    n_pg = KB // page
    scale = 1.0 / (D ** 0.5)
    layer = layer_ref[0]
    as_it_lies = _pages_are_matrices(Hkv, D)  # the one-row tile's key blocks

    def item(i):
        """Item ``i`` of the list: ``(lane, first row, that row's position,
        rows)`` and the pages ``[pg_lo, pg_hi)`` and key blocks ``[kb_lo,
        kb_hi)`` its rows can see."""
        pos0, n = w_pos0[i], w_rows[i]
        last = pos0 + n - 1  # the last live row's position
        first = jnp.maximum(pos0 - window + 1, 0) if window > 0 else 0
        div = jax.lax.div  # of positions, which are never negative
        return (w_lane[i], w_row0[i], pos0, n), (
            div(first, page), jnp.minimum(div(last, page) + 1, P),
            div(first, KB), div(last, KB) + 1,
        )

    # this item and its neighbours in the list: one with no rows neither
    # brings nor is brought for
    (lane, row0, pos0, rows), (pg_lo, pg_hi, kb_lo, kb_hi) = me = item(w)
    after = item(jnp.minimum(w + 1, W - 1))
    next_rows = jnp.where(w + 1 < W, after[0][3], 0)
    prev_rows = jnp.where(w > 0, w_rows[jnp.maximum(w - 1, 0)], 0)

    @pl.when(w == 0)
    def _clear():
        # a block's dead pages are never fetched: what the slots hold there
        # meets a probability of zero, and must be finite
        for slots in (kflat, kbuf):
            if slots is not None:
                slots[...] = jnp.zeros(slots.shape, slots.dtype)
        slot_ref[0] = 0

    def on_tile(n, body) -> None:
        """``body(copy, nrow)`` at the tile an item of ``n`` rows takes: the
        smallest that holds them."""
        if len(tiles) == 1:
            body(*tiles[0])
            return
        under = 0
        for copy, nrow in tiles:
            fits = n > under
            if copy < tiles[-1][0]:
                fits = fits & (n <= copy)
            pl.when(fits)(functools.partial(body, copy, nrow))
            under = copy

    def tile_start(copy, row0):
        """A tile's first row: the item's, or earlier where the tile would
        overhang the axis (a one-row tile never does)."""
        return jnp.minimum(row0, Np - copy) if copy > 1 else row0

    def q_copy(copy, start):
        return pltpu.make_async_copy(
            q_hbm.at[pl.ds(start, copy)], q_v.at[pl.ds(0, copy)], sem_q.at[0]
        )

    def o_copy(copy, start):
        """A tile's rows out, into its span of the output."""
        return pltpu.make_async_copy(
            o_v.at[pl.ds(0, copy)], o_hbm.at[pl.ds(start, copy)], sem_o.at[0]
        )

    def page_copy(flat, pid, slot, j):
        """One page's K and V into place ``j`` of a slot, as it lies
        (``flat``: the one-row tile's) or a token a row of heads."""
        pool, slots = (kv_flat, kflat) if flat else (kv_hbm, kbuf)
        n = pool.shape[3]
        return pltpu.make_async_copy(
            pool.at[layer, :, pid], slots.at[slot, :, pl.ds(j * n, n)],
            sem_kv.at[slot],
        )

    def pages_of(kb, pg_lo, pg_hi):
        """The live pages of key block ``kb``."""
        return jnp.maximum(kb * n_pg, pg_lo), jnp.minimum((kb + 1) * n_pg, pg_hi)

    def fetch(flat, lane, kb, slot, pg_lo, pg_hi):
        lo, hi = pages_of(kb, pg_lo, pg_hi)

        def start(pg, carry):
            page_copy(flat, pt_ref[lane, pg], slot, pg - kb * n_pg).start()
            return carry

        jax.lax.fori_loop(lo, hi, start, 0)

    def wait(flat, kb, slot, pg_lo, pg_hi):
        lo, hi = pages_of(kb, pg_lo, pg_hi)

        def done(pg, carry):
            page_copy(flat, 0, slot, 0).wait()
            return carry

        jax.lax.fori_loop(lo, hi, done, 0)

    def bring(it, slot):
        """Start what an item begins with: its queries, and its first key
        block's pages into ``slot``, both as its tile takes them."""
        (lane, row0, _, n), (pg_lo, pg_hi, kb_lo, _) = it

        def start(copy, _nrow):
            q_copy(copy, tile_start(copy, row0)).start()
            fetch(copy == 1 and as_it_lies, lane, kb_lo, slot, pg_lo, pg_hi)

        on_tile(n, start)

    def previous_rows_are_out():
        """The item before left its output copy in flight: until it lands,
        ``o_v`` is its source, and its rows of the output are not there for
        a tile that spans them to read."""

        @pl.when(prev_rows > 0)
        def _():
            on_tile(prev_rows, lambda copy, _nrow: o_copy(copy, 0).wait())

    def walk(flat, compute, carry):
        """The item's key blocks in order from the slot it was handed, each
        waited for while the next one's pages, or after the last what the
        next item begins with, are in flight: ``compute(kb, slot, carry)``."""
        slot0 = slot_ref[0]

        def block(kb, carry):
            slot = jax.lax.rem(slot0 + kb - kb_lo, 2)
            wait(flat, kb, slot, pg_lo, pg_hi)

            @pl.when(kb + 1 < kb_hi)
            def _():
                fetch(flat, lane, kb + 1, 1 - slot, pg_lo, pg_hi)

            @pl.when((kb + 1 == kb_hi) & (next_rows > 0))
            def _():
                bring(after, 1 - slot)

            return compute(kb, slot, carry)

        carry = jax.lax.fori_loop(kb_lo, kb_hi, block, carry)
        # the next item's first block went into the slot the last block left
        slot_ref[0] = jax.lax.rem(slot0 + kb_hi - kb_lo, 2)
        return carry

    def send(copy, start):
        """The tile's rows out; waited for where ``o_v`` is next written:
        by the next item, or here when none follows."""
        o_out = o_copy(copy, start)
        o_out.start()

        @pl.when(next_rows == 0)
        def _():
            o_out.wait()

    def attend_row(copy, _nrow):
        """The one-row tile: online softmax of the item's token, a query
        head a row, over the key blocks as they lie."""
        dt = q_v.dtype
        C = KB * Hkv  # a block's columns: key ``c // Hkv`` of kv head ``c % Hkv``
        q_copy(copy, row0).wait()
        q = q_v[0]  # [Hq, D]; ``q_v`` is free from here on
        col = jax.lax.broadcasted_iota(jnp.int32, (Hq, C), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (Hq, C), 0)
        group = jax.lax.rem(col, Hkv) * n_rep  # the column's first query head
        own = (head >= group) & (head < group + n_rep)

        def compute(kb, slot, carry):
            m_prev, l_prev, acc = carry
            k = kflat[slot, 0].astype(dt)  # [C, D]
            v = kflat[slot, 1].astype(dt)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [Hq, C]
            # key ``c // Hkv`` of the block is at or before the token's
            # position, and inside its window
            keep = own & (col < (pos0 - kb * KB + 1) * Hkv)
            if window > 0:
                keep = keep & (col >= (pos0 - window + 1 - kb * KB) * Hkv)
            s = jnp.where(keep, s * scale, _NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            pv = jax.lax.dot_general(
                p.astype(dt), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [Hq, D]
            return (m_new, l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                    acc * alpha + pv)

        _, l, acc = walk(True, compute, (
            jnp.full((Hq, 1), _NEG_INF, jnp.float32),
            jnp.zeros((Hq, 1), jnp.float32),
            jnp.zeros((Hq, D), jnp.float32),
        ))
        previous_rows_are_out()
        o_v[0] = (acc / l).astype(o_v.dtype)
        send(copy, row0)

    def attend_row_by_head(copy, _nrow):
        """The one-row tile where a page is no matrix: the key blocks come a
        token a row of heads, and a kv head's own ``n_rep`` query rows meet
        that head's keys alone, head by head over the few heads there are."""
        dt = q_v.dtype
        q_copy(copy, row0).wait()
        # (sliced as float32, whose rows are whole sublanes where n_rep is)
        q = q_v[0].astype(jnp.float32)  # ``q_v`` is free from here on
        q_of = [q[h * n_rep:(h + 1) * n_rep].astype(dt) for h in range(Hkv)]
        at = jax.lax.broadcasted_iota(jnp.int32, (n_rep, KB), 1)

        def compute(kb, slot, carry):
            kpos = kb * KB + at
            keep = kpos <= pos0
            if window > 0:
                keep = keep & (kpos > pos0 - window)
            # the block heads-major, as ``attend_rows`` turns it (a head's
            # rows read one by one out of the slot took six times as long on
            # the chip; the pool packs two heads' bf16 into one word, so no
            # copy can write a head's rows apart): [Hkv, KB, D]
            k, v = (kbuf[slot, side].transpose(1, 0, 2).astype(dt)
                    for side in range(2))
            out = []
            for h, (m_prev, l_prev, acc) in enumerate(carry):
                s = jax.lax.dot_general(
                    q_of[h], k[h], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [n_rep, KB]
                s = jnp.where(keep, s * scale, _NEG_INF)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                pv = jax.lax.dot_general(
                    p.astype(dt), v[h], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [n_rep, D]
                out.append((
                    m_new, l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                    acc * alpha + pv))
            return tuple(out)

        heads = walk(False, compute, ((
            jnp.full((n_rep, 1), _NEG_INF, jnp.float32),
            jnp.zeros((n_rep, 1), jnp.float32),
            jnp.zeros((n_rep, D), jnp.float32),
        ),) * Hkv)
        previous_rows_are_out()
        o_v[0] = jnp.concatenate(
            [acc / l for _, l, acc in heads]).astype(o_v.dtype)
        send(copy, row0)

    def attend_rows(copy, nrow):
        """A tile of ``nrow`` rows of which ``copy`` move: online softmax of
        the item's ``rows`` tokens, a kv head's group of rows at a time,
        over the key blocks turned heads-major."""
        M = n_rep * nrow
        # kv heads a step, while their score tiles stay within budget
        M_wide = n_rep * tiles[-1][1]
        hb = math.gcd(Hkv, max(_WL_SCORE_BYTES // (4 * M_wide * KB), 1))
        # the item's rows lie ``shift`` into a tile that starts early
        start = tile_start(copy, row0)
        shift = row0 - start
        pos_t = pos0 - shift
        q_copy(copy, start).wait()
        # ``q_v`` is free from here on: the next item's queries land in it
        q_t[:, :M] = (
            q_v[:nrow].transpose(1, 0, 2).reshape(Hkv, M, D)
        )
        m_scr[:, :M] = jnp.full((Hkv, M, 1), _NEG_INF, jnp.float32)
        l_scr[:, :M] = jnp.zeros((Hkv, M, 1), jnp.float32)
        acc_scr[:, :M] = jnp.zeros((Hkv, M, D), jnp.float32)
        # the tile's span of the output, read before the item's rows are
        # written into it
        o_in = pltpu.make_async_copy(
            o_hbm.at[pl.ds(start, copy)], o_v.at[pl.ds(0, copy)], sem_o.at[0]
        )

        def compute(kb, slot, carry):
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

            # needed only at the merge, and not before the rows of the item
            # before, which the span may hold, have landed: they have had
            # this block's compute to
            @pl.when(kb == kb_lo)
            def _():
                previous_rows_are_out()
                o_in.start()

            return carry

        walk(False, compute, 0)
        out = (acc_scr[:, :M] / l_scr[:, :M]).astype(o_v.dtype)
        out = out.reshape(Hq, nrow, D).transpose(1, 0, 2)  # [nrow, Hq, D]
        at = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        mine = (at >= shift) & (at < shift + rows)
        o_in.wait()
        o_v[:nrow] = jnp.where(mine, out, o_v[:nrow])
        send(copy, start)

    @pl.when(rows > 0)
    def _item():
        # the first of a run of live items fetches for itself
        @pl.when(prev_rows == 0)
        def _():
            bring(me, slot_ref[0])

        row = attend_row if as_it_lies else attend_row_by_head
        on_tile(rows, lambda copy, nrow: (
            row if copy == 1 else attend_rows)(copy, nrow))


def _key_block(page: int) -> int:
    """Keys of a key block: whole pages, ``_WL_KEY_BLOCK`` or a page."""
    return max(page, _WL_KEY_BLOCK // page * page)


def _work_list_scratch(tiles, Hq, Hkv, D, page, dtype, kv_dtype):
    """The kernel's scratch at the launch's tiles, in the kernel's order and
    None where the launch holds none: the queries' and the output's tile;
    two slots of a key block as it lies in the pool, where a page as one
    matrix is a view of it (:func:`_pages_are_matrices`: the one-row tile's,
    else that tile reads the slots below); the scalar that hands a slot from
    item to item and the copies' semaphores.  Where there are tiles of
    several rows also their queries heads-major, two slots of a key block a
    token a row of heads, the current block heads-major and the softmax's
    running state; of these the ``(lanes, 1)`` launch over a pool that has
    no such view holds the slots alone."""
    rows_t, KB = tiles[-1][1], _key_block(page)
    as_it_lies, several = _pages_are_matrices(Hkv, D), len(tiles) > 1
    tile = pltpu.VMEM((rows_t, Hq, D), dtype)
    scratch = [
        tile, tile,
        pltpu.VMEM((2, 2, KB * Hkv, D), kv_dtype) if as_it_lies else None,
        pltpu.SMEM((1,), jnp.int32), pltpu.SemaphoreType.DMA((1,)),
        pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((1,)),
    ]
    by_token = pltpu.VMEM((2, 2, KB, Hkv, D), kv_dtype)
    if several:
        M = Hq // Hkv * rows_t
        scratch += [
            pltpu.VMEM((Hkv, M, D), dtype),
            by_token,
            pltpu.VMEM((2, Hkv, KB, D), dtype),
            pltpu.VMEM((Hkv, M, 1), jnp.float32),
            pltpu.VMEM((Hkv, M, 1), jnp.float32),
            pltpu.VMEM((Hkv, M, D), jnp.float32),
        ]
    elif not as_it_lies:
        scratch += [None, by_token]
    return scratch


def _work_list_launch(
    q, kv_pages, page_table, layer, lane, row0, pos0, rows, *, s_max, window,
    interpret, name,
):
    """One launch of :func:`_work_list_kernel` over the items ``(lane, row0,
    pos0, rows)``, each ``[W]`` int32, at the tiles of ``s_max``: ``[Np, Hq,
    D]``, zeros in the rows no item owns.  The kernel is handed the pool as
    it is and, where :func:`_pages_are_matrices`, a second time with a page as
    one matrix for the one-row tile: a reshape that is a view.  Any other
    pool is handed once, and nothing is made of it in front of the launch."""
    Np, Hq, D = q.shape
    L, _, num_pages, page, Hkv, _ = kv_pages.shape
    _, tiles = _work_list_tiles(s_max, q.dtype)
    as_it_lies = _pages_are_matrices(Hkv, D)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(lane.shape[0],),
        in_specs=[hbm, hbm, hbm if as_it_lies else None, hbm],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=_work_list_scratch(
            tiles, Hq, Hkv, D, page, q.dtype, kv_pages.dtype),
    )
    return pl.pallas_call(
        functools.partial(_work_list_kernel, tiles=tiles, window=window),
        out_shape=jax.ShapeDtypeStruct((Np, Hq, D), q.dtype),
        grid_spec=grid_spec,
        # the zeroed buffer, the last operand after 6 scalars
        input_output_aliases={8 + as_it_lies: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_CAP_BYTES,
        ),
        interpret=interpret,
        name=name,
    )(
        jnp.clip(jnp.asarray(layer, jnp.int32), 0, L - 1).reshape(1),
        jnp.clip(page_table.astype(jnp.int32), 0, num_pages - 1),
        lane, row0, pos0, rows,
        # the pool as it is and, where that is a view of it, a second time
        # with a page as one matrix, its tokens' kv heads row after row (the
        # same bytes: no copy is made)
        q, kv_pages,
        kv_pages.reshape(L, 2, num_pages, page * Hkv, D) if as_it_lies else None,
        jnp.zeros((Np, Hq, D), q.dtype),
    )


def _packed_work_list_attention(
    q, kv_pages, page_table, base, seg_off, q_lens, *, s_max, layer, window,
    interpret, name_suffix="",
):
    """The packed launch over a dense pool that already holds the
    dispatch's rows (see the section comment): ``[Np, Hq, D]``.  The
    segments may lie anywhere in the packed axis and be of any length:
    ``s_max`` only sets the query block, ``min(s_max, _WL_Q_BLOCK)``."""
    from .latent_attention import packed_work_list

    qb, _ = _work_list_tiles(s_max, q.dtype)
    if s_max % qb:
        raise ValueError(f"s_max {s_max} is not a multiple of {qb}")
    if q.shape[0] < qb:
        raise ValueError(f"a tile of {qb} rows does not fit {q.shape[0]} packed")
    i32 = lambda x: x.astype(jnp.int32)  # noqa: E731
    items = packed_work_list(
        i32(base), i32(seg_off), i32(q_lens), q.shape[0], qb
    )
    return _work_list_launch(
        q, kv_pages, page_table, layer, *items, s_max=s_max, window=window,
        interpret=interpret, name="packed_ragged_attention" + name_suffix,
    )


@functools.partial(
    jax.jit, static_argnames=("window", "interpret", "name_suffix")
)
def decode_work_list_attention(
    q: jax.Array,  # [B, Hq, D] one new query token a lane
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D], that token in it
    page_table: jax.Array,  # [B, P] int32 page ids
    kv_lens: jax.Array,  # [B] tokens in the cache, the new one included
    layer: jax.Array | int = 0,
    window: int = 0,
    interpret: bool = False,
    name_suffix: str = "",  # a two-kind trunk's window layers: "_window"
) -> jax.Array:
    """The decode launch of the fused steps over a dense pool of 128-lane
    heads (:func:`_takes_work_list`): the work-list kernel at the ``(lanes,
    1)`` tile (a query head a row; the key blocks as they lie, or a token a
    row of heads where a page is no matrix) with a fixed list, one item a
    lane that holds a token, from its window's first key block to the block
    of its own position.  No step for a page group of the table's width
    (``paged_attention``'s grid), so the table may be as wide as the
    scheduler's; a lane with ``kv_lens`` 0 has no item and its row stays
    zero.  The launch the ``(lanes, 1)`` packed step makes, under the decode
    kernel's name: a device trace tells the fused steps' launches from the
    packed ones by it."""
    lane = jnp.arange(q.shape[0], dtype=jnp.int32)
    lens = kv_lens.astype(jnp.int32)
    return _work_list_launch(
        q, kv_pages, page_table, layer, lane, lane, lens - 1,
        (lens > 0).astype(jnp.int32), s_max=1, window=window,
        interpret=interpret, name="paged_decode_attention" + name_suffix,
    )


def packed_vmem_bytes(
    Np, s_max, Hq, Hkv, D, page, G, dtype, kv_dtype, quant
) -> int:
    """VMEM the packed kernel needs, from its own shapes: the packed
    q/k/v/out blocks (one copy each: their block index never moves), the
    m/l/acc scratch over the lane window (a ``[rows, 1]`` column pads to
    128 lanes), the page-group stream and one accumulate body.  Checked
    against the chip's compiler at the widest default shape (1024, 512):
    it accepts the kernel from 56 MiB at Mixtral widths and from 40 MiB at
    TinyLlama's, where this counts 69 and 68."""
    qb = min(s_max, _Q_BLOCK)
    nq = s_max // qb
    blocks = 2 * _tile_bytes((Np, Hq, D), dtype) + 2 * _tile_bytes(
        (Np, Hkv, D), dtype
    )
    scratch = nq * (
        2 * _tile_bytes((Hq * qb, 1), jnp.float32)
        + _tile_bytes((Hq * qb, D), jnp.float32)
    )
    return (
        blocks
        + scratch
        + _kv_stream_bytes(page, Hkv, D, G, kv_dtype, quant)
        + _body_bytes(Hq * qb, max(G * page, qb), Hkv, D, dtype)
        + (4 << 20)
    )


def packed_shape_fits(
    Np, s_max, Hq, Hkv, D, page, dtype, kv_dtype, quant, group: int = 4
) -> bool:
    """Whether the grid kernel (:func:`_packed_kernel`) can hold ``(Np,
    s_max)`` at these widths -- the bound the engine checks before it lets
    a mixed dispatch mint a shape (never discovered at a user's first long
    prompt).  Asked only of a launch that takes the grid
    (``engine.attention.packed_launch``): the work-list kernel holds a
    tile, whatever the packed shape."""
    return (
        packed_vmem_bytes(
            Np, s_max, Hq, Hkv, D, page, group, dtype, kv_dtype, quant
        )
        <= VMEM_CAP_BYTES
    )


@functools.partial(
    jax.jit,
    static_argnames=("s_max", "window", "group", "interpret", "name_suffix"),
)
def packed_ragged_attention(
    q: jax.Array,  # [Np, Hq, D] packed queries (lane's row i at base + i)
    k: jax.Array,  # [Np, Hkv, D] packed fresh keys
    v: jax.Array,  # [Np, Hkv, D]
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    page_table: jax.Array,  # [B, P] int32 page ids
    base: jax.Array,  # [B] committed cache length per lane
    seg_off: jax.Array,  # [B] lane's segment offset into the packed axis
    q_lens: jax.Array,  # [B] fresh rows per lane (0 = no segment)
    s_max: int,  # static per-lane window capacity (pow2 of max segment);
    # the work-list kernel takes its query block from it and holds segments
    # of any length
    layer: jax.Array | int = 0,
    window: int = 0,
    group: int = 4,
    interpret: bool = False,
    kv_scales: jax.Array | None = None,  # [L, 2, num_pages, page] int8 pool
    name_suffix: str = "",  # a two-kind trunk's window layers: "_window"
) -> jax.Array:
    """Packed-layout ragged paged attention (see the section comments):
    one flat ``[Np]`` token axis, per-lane segment offsets.  The pool
    already holds the dispatch's rows (``step`` scatters them first).  A
    dense pool of 128-lane heads takes the work-list kernel, which reads
    every key from the pool and ignores ``k``/``v``/``group``.  An int8
    pool (``kv_scales``) and narrow heads (:func:`_takes_work_list`) keep
    the page-group-streaming grid (:func:`_packed_kernel`), which
    dequantizes an int8 page in VMEM as it is read: it reads the pool below
    ``base`` and the fresh rows from ``k``/``v``, and holds the packed
    operands in VMEM for the whole launch, so ``Np`` bounds its footprint
    (:func:`packed_vmem_bytes`, :func:`packed_shape_fits`).  Chosen by the
    pool's type at trace time: one kernel an executable."""
    if _takes_work_list(q.shape[2], kv_scales is not None):
        return _packed_work_list_attention(
            q, kv_pages, page_table, base, seg_off, q_lens, s_max=s_max,
            layer=layer, window=window, interpret=interpret,
            name_suffix=name_suffix,
        )
    Np, Hq, D = q.shape
    L, _, num_pages, page, Hkv, _ = kv_pages.shape
    B, P = page_table.shape
    G = min(group, P)
    while P % G:
        G -= 1
    npg = P // G
    quant = kv_scales is not None
    qb = min(s_max, _Q_BLOCK)
    if s_max % qb:
        raise ValueError(f"s_max {s_max} is not a multiple of {qb}")
    nq = s_max // qb

    pt = jnp.clip(page_table.astype(jnp.int32), 0, num_pages - 1)
    lyr = jnp.clip(jnp.asarray(layer, jnp.int32), 0, L - 1).reshape(1)

    def kv_map(g):
        def m(b, p, layer_ref, pt_ref, base_ref, off_ref, len_ref):
            pp = jnp.minimum(p, npg - 1)
            return (layer_ref[0], 0, pt_ref[b, pp * G + g], 0, 0, 0)

        return m

    def scale_map(g):
        def m(b, p, layer_ref, pt_ref, base_ref, off_ref, len_ref):
            pp = jnp.minimum(p, npg - 1)
            return (layer_ref[0], 0, pt_ref[b, pp * G + g] // _SCALE_ROWS, 0)

        return m

    def packed_map(b, p, *_):
        # the whole packed axis is one block, revisited every grid step
        return (0, 0, 0)

    scale_specs = (
        [
            pl.BlockSpec((1, 2, _SCALE_ROWS, page), scale_map(g))
            for g in range(G)
        ]
        if quant
        else []
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, npg + 1),
        in_specs=[
            pl.BlockSpec((1, 2, 1, page, Hkv, D), kv_map(g)) for g in range(G)
        ]
        + scale_specs
        + [
            pl.BlockSpec((Np, Hq, D), packed_map),
            pl.BlockSpec((Np, Hkv, D), packed_map),
            pl.BlockSpec((Np, Hkv, D), packed_map),
        ],
        out_specs=pl.BlockSpec((Np, Hq, D), packed_map),
        scratch_shapes=[
            pltpu.VMEM((nq, Hq * qb, 1), jnp.float32),
            pltpu.VMEM((nq, Hq * qb, 1), jnp.float32),
            pltpu.VMEM((nq, Hq * qb, D), jnp.float32),
        ],
    )
    scale_ops = [kv_scales] * G if quant else []
    return pl.pallas_call(
        functools.partial(
            _packed_kernel, G=G, s_max=s_max, quant=quant, window=window
        ),
        out_shape=jax.ShapeDtypeStruct((Np, Hq, D), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                packed_vmem_bytes(
                    Np, s_max, Hq, Hkv, D, page, G, q.dtype, kv_pages.dtype,
                    quant,
                )
            ),
        ),
        interpret=interpret,
        name="packed_ragged_attention" + name_suffix,
    )(
        lyr, pt, base.astype(jnp.int32), seg_off.astype(jnp.int32),
        q_lens.astype(jnp.int32), *([kv_pages] * G), *scale_ops, q, k, v,
    )


def packed_ragged_attention_xla(
    q: jax.Array,  # [Np, Hq, D] packed queries
    k: jax.Array,  # [Np, Hkv, D] packed fresh keys
    v: jax.Array,  # [Np, Hkv, D]
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    page_table: jax.Array,  # [B, P]
    base: jax.Array,  # [B]
    seg_off: jax.Array,  # [B]
    q_lens: jax.Array,  # [B]
    lane: jax.Array,  # [Np] lane per packed token (B = padding)
    rel: jax.Array,  # [Np] row index within the lane's segment
    s_max: int,
    layer: jax.Array | int = 0,
    window: int = 0,
) -> jax.Array:
    """Pure-XLA packed reference: unpack the flat axis into the lane
    rectangle with per-lane dynamic windows, run the rectangle reference
    (:func:`ragged_paged_attention_xla`), and repack valid rows.  On this
    backend the packed layout's win is the trunk alone (the step runs
    ``Np`` rows instead of ``B*S``); the Pallas kernels above also read
    packed operands.  Rows past a lane's ``q_len`` unpack into the next
    lane's tokens -- harmless, the reference masks fresh keys by
    ``q_lens`` and the repack gather never reads an invalid row's
    output."""
    Np = q.shape[0]
    B = page_table.shape[0]
    idx = seg_off[:, None] + jnp.arange(s_max, dtype=jnp.int32)[None, :]
    idx = jnp.clip(idx, 0, Np - 1)  # [B, s_max]
    out_rect = ragged_paged_attention_xla(
        q[idx], k[idx], v[idx], kv_pages, page_table, base, q_lens,
        layer, window,
    )  # [B, s_max, Hq, D]
    lane_c = jnp.clip(lane.astype(jnp.int32), 0, B - 1)
    rel_c = jnp.clip(rel.astype(jnp.int32), 0, s_max - 1)
    out = out_rect[lane_c, rel_c]  # [Np, Hq, D]
    valid = (lane.astype(jnp.int32) < B)[:, None, None]
    return jnp.where(valid, out, jnp.zeros_like(out))
