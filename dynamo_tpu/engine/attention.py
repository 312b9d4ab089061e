"""Attention ops over the paged KV cache (reference-free JAX implementations).

Layout: the KV cache is one stacked buffer ``[layers, 2, num_pages,
page_size, kv_heads, head_dim]``; readers/writers take a scalar layer index
and scatter/gather in place, so the layer scan carries a single buffer that
XLA updates without copying.  A request owns a list of pages recorded in
its row of the page table ``[batch, pages_per_seq]``.  Page 0 is reserved
as the trash page:
inactive batch slots scatter their writes there, so dead lanes never corrupt
live state and every step runs with fully static shapes (XLA requirement).

These are the XLA-composed implementations (gather + einsum; XLA fuses the
mask/softmax chain).  On TPU the decode hot loop routes through a Pallas
kernel instead (``decode_attention_dispatch``, chosen by ``decode_backend``:
the work list of dynamo_tpu.ops.ragged_attention over a dense pool of
128-lane heads, the grid of dynamo_tpu.ops.paged_attention over narrower
ones): the XLA gather materializes [B, P*page, Hkv, D] per step, a kernel
streams pages HBM->VMEM once.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..analysis.hotpath import hot_path
from .kv_cache import (
    ConvKV,
    DeltaKV,
    KindKV,
    QuantKV,
    gather_layer_kv,
    index_kv_layer,
    kv_data,
    kv_is_latent,
    kv_is_quantized,
    quantize_kv_rows,
)

_NEG_INF = -1e30


# -- int8 pool plumbing (kv_cache.QuantKV) ----------------------------------
#
# Every reader/writer below takes the pool as one opaque value: a dense
# array for bf16/f32 pools, a QuantKV (int8 data + per-row scales) pytree
# for quantized ones.  Reads gather data+scales and dequantize after the
# page gather (kv_cache.gather_layer_kv -- XLA fuses the convert+scale
# into the consuming einsum); writes route through the shared
# quantize_kv_rows rule below and scatter both arrays.  The branch
# resolves at trace time (pytree structure is static), so each compiled
# executable embeds exactly one layout.


# -- two kinds of layer (kv_cache.KindKV) -------------------------------------


class LayerView(NamedTuple):
    """What one layer's attention is handed: its pool, its lanes' page
    table, its index in that pool, its window, how the written pool goes
    back into the carried cache, and the suffix a window layer's kernels
    carry in a device trace."""

    kv: Any
    table: Any
    layer: Any
    window: int
    put: Callable[[Any], Any]
    suffix: str


def layer_view(cfg, kv_pages, page_table, layer, kind) -> LayerView:
    """The step functions' one question about a layer.  A trunk of one kind
    (``kind is None``: every ``attn_fn`` call of such a model) is answered
    with what it was handed, so its program is the one it always was.  A
    layer of a two-kind trunk gets the pool of its kind out of the
    ``KindKV``, that kind's page table (``page_table [2, B, P]``: full,
    window), and its index among its kind's layers: whole periods before
    it times the kind's layers a period, plus its place in its own."""
    if kind is None:
        return LayerView(
            kv_pages, page_table, layer, cfg.sliding_window or 0,
            lambda kv: kv, "",
        )
    if cfg.has_linear:
        # an attention layer beside delta-rule layers, as below
        if not isinstance(kv_pages, DeltaKV):  # a step that writes no cache
            return LayerView(kv_pages, page_table, layer, 0, lambda kv: kv, "")
        return LayerView(
            kv_pages.attn, page_table, layer, 0, kv_pages.with_attn,
            # heads wider than the 128 lanes: the launches over them say so
            "_wide" if cfg.head_dim > 128 else "",
        )
    if cfg.has_conv:
        # an attention layer beside convolution layers: ``layer`` is its
        # index among the attention layers already (model.scan_layers
        # counts it), and the pair pool holds those alone
        if not isinstance(kv_pages, ConvKV):  # a step that writes no cache
            return LayerView(kv_pages, page_table, layer, 0, lambda kv: kv, "")
        return LayerView(
            kv_pages.attn, page_table, layer, 0,
            lambda pool: ConvKV(pool, kv_pages.lanes, kv_pages.pages),
            # heads narrower than the 128 lanes (config.kv_head_pack): the
            # launches over them say so in a device trace
            "_narrow" if cfg.head_dim % 128 else "",
        )
    pattern = cfg.layer_pattern
    rank, seen = [], 0
    for k in pattern:
        rank.append(seen)
        seen += k == kind
    idx = layer // len(pattern) * seen + jnp.asarray(rank, jnp.int32)[
        layer % len(pattern)
    ]
    table = page_table
    if page_table is not None and page_table.ndim == 3:
        table = page_table[0 if kind == "full" else 1]
    if not isinstance(kv_pages, KindKV):  # a step that writes no cache
        return LayerView(
            kv_pages, table, idx, cfg.kind_window(kind), lambda kv: kv, ""
        )
    return LayerView(
        kv_pages.of(kind), table, idx, cfg.kind_window(kind),
        lambda pool: kv_pages.replace(kind, pool),
        "" if kind == "full" else "_window",
    )


# -- convolution layers (kv_cache.ConvKV) -------------------------------------
#
# The two calls below are all a gated short-convolution layer asks of a step:
# mix every row with its two predecessors, and leave behind what the next
# step needs (the lane's last two rows) and what a prefix hit needs (the
# rows at a page's last two positions, under the page's id).


def _conv_predecessors(state: ConvKV, layer, page_table, first):
    """``[B, 2, H]``: the rows at positions ``first - 2`` and ``first - 1``
    of each lane's sequence.  Zeros where the segment starts the sequence;
    the snapshot of the page that ends at ``first`` where it starts a page
    (a prefix hit resumes there, and a lane walking on reads back the rows
    it wrote); the lane's own rows otherwise."""
    B, P = page_table.shape
    page = kv_data(state).shape[3]
    lanes = jax.lax.dynamic_index_in_dim(state.lanes, layer, 0, False)
    lanes = lanes.reshape(B, 2, -1)
    before = jnp.clip(first // page - 1, 0, P - 1)
    ids = jnp.take_along_axis(page_table, before[:, None], axis=1)[:, 0]
    both = 2 * ids[:, None] + jnp.arange(2)  # a page's two rows
    snap = state.pages[layer, both]  # [B, 2, H]
    prev = jnp.where((first % page == 0)[:, None, None], snap, lanes)
    return jnp.where((first == 0)[:, None, None], 0, prev), lanes


def _conv_taps(taps, z2, z1, z):
    """``w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t`` a channel, summed in float32."""
    w = taps.astype(jnp.float32)
    f = jnp.float32
    return (w[0] * z2.astype(f) + w[1] * z1.astype(f) + w[2] * z.astype(f)).astype(
        z.dtype
    )


def _conv_snapshot(state: ConvKV, layer, lanes, z, page_table, lane, pos, ok):
    """Put the lanes' new rows back, and of ``z`` the rows at a page's last
    two positions under that page's id (others to the trash page)."""
    P = page_table.shape[1]
    page = kv_data(state).shape[3]
    page_idx = pos // page
    ok = ok & (pos % page >= page - 2) & (page_idx < P)
    ids = page_table[lane, jnp.clip(page_idx, 0, P - 1)]
    row = jnp.where(ok, 2 * ids + pos % page - (page - 2), 0)
    return ConvKV(
        state.attn,
        state.lanes.at[layer].set(
            lanes.reshape(state.lanes.shape[1:]).astype(state.lanes.dtype)
        ),
        state.pages.at[layer, row].set(z.astype(state.pages.dtype)),
    )


@hot_path
def packed_conv_mix(
    z: jax.Array,  # [Np, H] packed rows of B (.) X
    taps: jax.Array,  # [3, H]
    state: ConvKV,
    layer: jax.Array,  # index among the convolution layers
    page_table: jax.Array,  # [B, P]
    base: jax.Array,  # [B] position of a lane's first row
    seg_off: jax.Array,  # [B]
    q_lens: jax.Array,  # [B] rows per lane (0 = no segment)
    lane: jax.Array,  # [Np] lane per packed row (B = padding)
    pos: jax.Array,  # [Np]
    valid: jax.Array,  # [Np]
):
    """The packed step's convolution: a row's predecessors are the rows
    before it in its segment, else what the lane carries in.  Returns the
    mixed rows and the state with each live lane's last two rows and the
    page snapshots written; padding and dead lanes write nothing read."""
    Np = z.shape[0]
    B = base.shape[0]
    prev, lanes = _conv_predecessors(state, layer, page_table, base)
    live = q_lens > 0
    drop = jnp.full((B,), Np, jnp.int32)
    at0 = jnp.where(live, seg_off, drop)
    at1 = jnp.where(q_lens > 1, seg_off + 1, drop)
    put = dict(mode="drop", unique_indices=True)
    z1 = jnp.roll(z, 1, axis=0).at[at0].set(prev[:, 1], **put)
    z2 = jnp.roll(z, 2, axis=0).at[at0].set(prev[:, 0], **put)
    z2 = z2.at[at1].set(prev[:, 1], **put)
    last = jnp.clip(seg_off + q_lens - 1, 0, Np - 1)
    new = jnp.stack(
        [
            jnp.where(
                (q_lens > 1)[:, None], z[jnp.clip(last - 1, 0, Np - 1)],
                prev[:, 1],
            ),
            z[last],
        ],
        axis=1,
    )
    lanes = jnp.where(live[:, None, None], new, lanes)
    lane_c = jnp.clip(lane.astype(jnp.int32), 0, B - 1)
    ok = valid & (lane.astype(jnp.int32) < B)
    return _conv_taps(taps, z2, z1, z), _conv_snapshot(
        state, layer, lanes, z, page_table, lane_c, pos, ok
    )


@hot_path
def decode_conv_mix(
    z: jax.Array,  # [B, H] one row a lane
    taps: jax.Array,  # [3, H]
    state: ConvKV,
    layer: jax.Array,
    page_table: jax.Array,  # [B, P]
    positions: jax.Array,  # [B] the row's position
    active: jax.Array,  # [B] bool: lanes the step advances
):
    """A decode step's convolution.  Only an ``active`` lane's rows move: a
    frozen lane runs the step over again on the same token (its K/V write
    is the same write; a shift of its two rows would not be)."""
    B = z.shape[0]
    prev, lanes = _conv_predecessors(state, layer, page_table, positions)
    new = jnp.stack([prev[:, 1], z], axis=1)
    lanes = jnp.where(active[:, None, None], new, lanes)
    return _conv_taps(taps, prev[:, 0], prev[:, 1], z), _conv_snapshot(
        state, layer, lanes, z, page_table, jnp.arange(B), positions, active
    )


# -- gated delta-rule layers (kv_cache.DeltaKV) -------------------------------
#
# The two calls below are all a linear layer asks of a step: run the
# convolution over ``[q | k | v]`` and the recurrence
#
#     S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;
#     o_t = S^T q_t
#
# over every row, from the state its lane carries in, and leave behind what
# the next step needs (the lane's state and last three rows) and what a
# prefix hit needs (a snapshot in the slot the dispatch names).

GDN_CHUNK = 64
# float32 products in float32: the chip's default rounds both sides to
# bfloat16, and the state is what every later token reads
_GDN_PRECISION = jax.lax.Precision.HIGHEST


def _gdn_dot(spec, a, b):
    return jnp.einsum(
        spec, a, b, precision=_GDN_PRECISION,
        preferred_element_type=jnp.float32,
    )


def _gdn_conv(taps, rows):
    """``silu(sum_i w_i u_{t-3+i})`` a channel, summed in float32:
    ``rows`` the four rows oldest first."""
    w = taps.astype(jnp.float32)
    acc = sum(w[i] * rows[i].astype(jnp.float32) for i in range(4))
    return jax.nn.silu(acc)


def _gdn_heads(cfg, x):
    """A convolved row ``[.., C]`` in float32 as the recurrence reads it:
    ``(q, k [.., Hv, dk], v [.., Hv, dv])``, q and k normalised over ``dk``
    (L2, eps 1e-6), q scaled by ``dk^-1/2``, each key head repeated for the
    value heads it serves."""
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    lead = x.shape[:-1]
    q, k, v = jnp.split(x, [Hk * dk, 2 * Hk * dk], axis=-1)

    def unit(a):
        a = a.reshape(*lead, Hk, dk)
        a = a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(a, Hv // Hk, axis=-2)

    return unit(q) * dk ** -0.5, unit(k), v.reshape(*lead, Hv, dv)


def gdn_chunk_terms(q, k, v, g, beta):
    """What a chunk of the delta rule computes before it meets the state
    entering it, for any leading axes: ``q, k [.., C, dk]``, ``v [.., C,
    dv]``, ``g, beta [.., C]`` (float32; a row that is padding has ``beta =
    g = 0`` and zero ``k``).  With ``G`` the running sum of ``g``, ``A_ij =
    -beta_i (k_i . k_j) exp(G_i - G_j)`` for ``j < i`` and ``T = (I - A)^-1``
    by the doubling product (``A`` is nilpotent): returns ``(V' = T (beta
    V), K' = T (beta exp(G) K), Qg = exp(G) Q, W = tril(Q K^T exp(G_i -
    G_j)), Kd = exp(G_C - G) K, exp(G_C))``.  The exponent is masked above
    the diagonal, not the product: ``exp`` of a positive sum overflows."""
    C = q.shape[-2]
    G = jnp.cumsum(g, axis=-1)
    diff = G[..., :, None] - G[..., None, :]
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    below = jnp.exp(jnp.where(i > j, diff, -jnp.inf))
    upto = jnp.exp(jnp.where(i >= j, diff, -jnp.inf))
    A = -beta[..., :, None] * _gdn_dot("...ik,...jk->...ij", k, k) * below
    T = jnp.eye(C, dtype=jnp.float32) + A
    P = A
    for _ in range(max(C - 1, 1).bit_length() - 1):
        P = _gdn_dot("...ij,...jk->...ik", P, P)
        T = T + _gdn_dot("...ij,...jk->...ik", T, P)
    eG = jnp.exp(G)
    Vp = _gdn_dot("...ij,...jv->...iv", T, beta[..., None] * v)
    Kp = _gdn_dot("...ij,...jk->...ik", T, (beta * eG)[..., None] * k)
    W = _gdn_dot("...ik,...jk->...ij", q, k) * upto
    Kd = jnp.exp(G[..., -1:] - G)[..., None] * k
    return Vp, Kp, eG[..., None] * q, W, Kd, eG[..., -1]


def gdn_chunk_apply(S, Vp, Kp, Qg, W, Kd, gC):
    """A chunk's rows from the state entering it, ``S [.., dk, dv]``:
    ``V'' = V' - K' S``, ``O = Qg S + W V''``, ``S <- exp(G_C) S + Kd^T
    V''``.  Returns ``(O [.., C, dv], S)``."""
    Vpp = Vp - _gdn_dot("...ik,...kv->...iv", Kp, S)
    O = _gdn_dot("...ik,...kv->...iv", Qg, S) + _gdn_dot(
        "...ij,...jv->...iv", W, Vpp)
    S = gC[..., None, None] * S + _gdn_dot("...ik,...iv->...kv", Kd, Vpp)
    return O, S


def _gdn_history(state: DeltaKV, layer, B):
    """``[B, 3, C]``: the lanes' last three rows, oldest first."""
    rows = jax.lax.dynamic_index_in_dim(state.conv, layer, 0, False)
    return rows.reshape(B, 3, -1)


def delta_backend() -> str:
    """What runs a packed step's chunks of the delta rule, at trace time:
    the launch ``ops.gated_delta.gated_delta_chunks`` on a TPU, the XLA
    composition (``gdn_chunk_terms``, ``gdn_chunk_apply`` in a loop)
    elsewhere."""
    return "kernel" if _on_tpu() else "xla"


def delta_runs(plan, base, seg_off, q_lens):
    """A dispatch's segments that run in chunks, as runs, a lane's two side
    by side: before, and from, the position its snapshot is taken at, so
    that a chunk never straddles it.  Every segment of more than one row
    runs so, and the rare one of one row that resumes from a slot or ends
    on a snapshot (a prompt's chunk of one row: the chunks read and write a
    slot where it lies; the one-row step knows the lanes alone).  Returns
    ``(chunked [B], snaps [B], cut [B], run_len [2 B], run_off [2 B])``."""
    restore, snap_slot, snap_pos = plan
    chunked = (q_lens > 1) | (
        (q_lens == 1) & (((restore >= 0) & (base > 0)) | (snap_slot >= 0)))
    snaps = chunked & (snap_slot >= 0)
    cut = jnp.where(snaps, jnp.clip(snap_pos - base, 0, q_lens), q_lens)
    cut = jnp.where(chunked, cut, 0)
    run_len = jnp.stack(
        [cut, jnp.where(chunked, q_lens - cut, 0)], axis=1).reshape(-1)
    run_off = jnp.stack([seg_off, seg_off + cut], axis=1).reshape(-1)
    return chunked, snaps, cut, run_len, run_off


def _gdn_packed_conv(taps, u, hist, seg_off, q_lens):
    """``silu(conv)`` of the packed rows ``u [Np, C]`` in float32.  A row's
    three predecessors are the rows before it in the packed axis, but for a
    segment's first three, which reach into the lane's history ``hist [B,
    3, C]``: those few rows are computed apart and written over the rest
    (a gather of every row's predecessors out of the history took three
    passes as long as the convolution itself)."""
    Np, C = u.shape
    B = seg_off.shape[0]
    x = _gdn_conv(taps, [jnp.roll(u, n, axis=0) for n in (3, 2, 1)] + [u])
    p = jnp.arange(3)[None, :]
    rows = seg_off[:, None] + p  # [B, 3]
    ext = jnp.concatenate([hist, u[jnp.clip(rows, 0, Np - 1)]], axis=1)
    first = _gdn_conv(taps, [ext[:, i:i + 3] for i in range(4)])  # [B, 3, C]
    to = jnp.where(p < q_lens[:, None], rows, Np).reshape(-1)
    return x.at[to].set(
        first.reshape(3 * B, C), mode="drop", unique_indices=True)


def _gdn_one_step(S0, q, k, v, g, beta):
    """The recurrence's one step a lane: ``S0 [B, Hv, dk, dv]`` and a row
    each of ``q, k [B, Hv, dk]``, ``v [B, Hv, dv]``, ``g, beta [B, Hv]``.
    Returns ``(o [B, Hv, dv], S)``."""
    S = jnp.exp(g)[..., None, None] * S0
    d = beta[..., None] * (v - _gdn_dot("bhk,bhkv->bhv", k, S))
    S = S + k[..., :, None] * d[..., None, :]
    return _gdn_dot("bhk,bhkv->bhv", q, S), S


def _gdn_chunk_loop(S0, q, k, v, g, beta, snaps, run_len, run_off):
    """The runs' chunks in packed order as an XLA loop of as many turns as
    the dispatch has chunks, each from its lane's state in ``S0 [B, ..]``
    (``q, k, v [Np, Hv, d]`` as the recurrence reads them).  Returns ``(out
    [Np, Hv, dv]``, zero in the rows of no run, the lanes' states, and the
    states at the cut of the lanes in ``snaps``)."""
    Np, Hv, dv = v.shape
    K = GDN_CHUNK
    n_chunks = -(-run_len // K)
    ends = jnp.cumsum(n_chunks)
    r = jnp.arange(K, dtype=jnp.int32)
    tail = lambda a: jnp.concatenate(  # noqa: E731  (a chunk reads K rows)
        [a, jnp.zeros((K, *a.shape[1:]), a.dtype)], axis=0)
    qp, kp, vp, gp, bp = tail(q), tail(k), tail(v), tail(g), tail(beta)

    def chunk(c, carry):
        Sw, snap, out = carry
        run = jnp.searchsorted(ends, c, side="right").astype(jnp.int32)
        b = run // 2
        j = c - (ends[run] - n_chunks[run])
        row0 = run_off[run] + K * j
        ok = r < run_len[run] - K * j  # rows past the run are not its own

        def rows(a):  # [K, Hv, ..] of this chunk, heads first
            a = jax.lax.dynamic_slice_in_dim(a, row0, K, 0)
            a = jnp.where(ok.reshape(K, *(1,) * (a.ndim - 1)), a, 0)
            return jnp.moveaxis(a, 0, 1)

        S = jax.lax.dynamic_index_in_dim(Sw, b, 0, False)
        O, S = gdn_chunk_apply(
            S, *gdn_chunk_terms(rows(qp), rows(kp), rows(vp), rows(gp), rows(bp)))
        Sw = jax.lax.dynamic_update_index_in_dim(Sw, S, b, 0)
        # a chunk that ends the run before a snapshot hands its state on
        takes = (run % 2 == 0) & (j == n_chunks[run] - 1) & snaps[b]
        snap = jax.lax.cond(
            takes,
            lambda: jax.lax.dynamic_update_index_in_dim(snap, S, b, 0),
            lambda: snap,
        )
        # rows past the run are written by their own chunk, later
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.moveaxis(O, 0, 1), row0, 0)
        return Sw, snap, out

    Sw, snap, out = jax.lax.fori_loop(
        0, ends[-1], chunk,
        (S0, jnp.zeros_like(S0), jnp.zeros((Np + K, Hv, dv), jnp.float32)),
    )
    return out[:Np], Sw, snap


@hot_path
def packed_delta_mix(
    cfg,
    u: jax.Array,  # [Np, C] packed rows of [q | k | v] before the convolution
    taps: jax.Array,  # [4, C]
    g: jax.Array,  # [Np, Hv] f32 log-decay
    beta: jax.Array,  # [Np, Hv] f32
    state: DeltaKV,
    layer: jax.Array,  # index among the linear layers
    base: jax.Array,  # [B] position of a lane's first row
    seg_off: jax.Array,  # [B]
    q_lens: jax.Array,  # [B] rows per lane (0 = no segment)
    interpret: bool = False,  # the tests': the launch through the interpreter
):
    """The packed step's delta rule.  A lane's segment of one row (a decode
    row) takes the recurrence's one step; a longer one runs in chunks of
    ``GDN_CHUNK`` of its rows, cut where the dispatch takes its snapshot so
    that a chunk never straddles it (:func:`delta_runs`).  On a TPU the
    chunks are one launch that holds a run's state in VMEM and reads and
    writes only the lanes that have one (``ops/gated_delta.py``); elsewhere
    they are an XLA loop over the lanes' states (:func:`_gdn_chunk_loop`).
    Returns ``o [Np, Hv, dv]`` float32 and the state with each live lane's
    state and last three rows written, and the snapshots the dispatch's
    plan names."""
    Np, C = u.shape
    B = base.shape[0]
    restore = state.plan[0]
    live = q_lens > 0
    S_slots = state.slots.shape[1]
    kernel = interpret or delta_backend() == "kernel"
    with jax.named_scope("gdn_chunk"):
        # -- where each lane starts from
        old_hist = _gdn_history(state, layer, B)  # [B, 3, C]
        resumes = live & (restore >= 0) & (base > 0)
        fresh = live & (base == 0)
        at = jnp.clip(restore, 0, S_slots - 1)
        hist = jax.lax.cond(
            jnp.any(resumes),
            lambda: jnp.where(
                resumes[:, None, None],
                state.slot_conv.reshape(-1, S_slots, 3, C)[layer, at], old_hist),
            lambda: old_hist,
        )
        hist = jnp.where(fresh[:, None, None], 0, hist)
        x = _gdn_packed_conv(taps, u, hist, seg_off, q_lens)  # [Np, C] f32
        chunked, snaps, cut, run_len, run_off = delta_runs(
            state.plan, base, seg_off, q_lens)
        single = live & ~chunked  # segments of one row: the one step
        row1 = jnp.clip(seg_off, 0, Np - 1)
        one = lambda S0: _gdn_one_step(  # noqa: E731
            S0, *_gdn_heads(cfg, x[row1]), g[row1], beta[row1])
        slots = state.slots
        if kernel:
            # the chunks first, over the lanes that have a run; then the
            # one-row lanes, whose states the launch left as they were
            from ..ops.gated_delta import (
                SRC_LANE, SRC_SLOT, SRC_ZERO, gated_delta_chunks)

            out, lanes_all, slots = gated_delta_chunks(
                x, g, beta, state.lanes, slots, layer, run_len, run_off,
                jnp.where(fresh, SRC_ZERO, jnp.where(resumes, SRC_SLOT, SRC_LANE)),
                restore, jnp.where(snaps, state.plan[1], -1),
                Hk=cfg.linear_num_key_heads, Hv=cfg.linear_num_value_heads,
                interpret=interpret,
            )
            Sw = jax.lax.dynamic_index_in_dim(lanes_all, layer, 0, False)
            o1, S1 = one(jnp.where(fresh[:, None, None, None], 0.0, Sw))
        else:
            lanes_all = state.lanes
            lanes = jax.lax.dynamic_index_in_dim(lanes_all, layer, 0, False)
            S0 = jax.lax.cond(  # few dispatches have a lane that resumes
                jnp.any(resumes),
                lambda: jnp.where(
                    resumes[:, None, None, None], slots[layer, at], lanes),
                lambda: lanes,
            )
            S0 = jnp.where(fresh[:, None, None, None], 0.0, S0)
            o1, S1 = one(S0)
            out, Sw, snap = _gdn_chunk_loop(
                S0, *_gdn_heads(cfg, x), g, beta, snaps, run_len, run_off)
        o = out.at[jnp.where(single, seg_off, Np)].set(o1, mode="drop")
        if kernel:
            # the launch writes its runs' rows and nothing else: zeros in
            # the rows of no lane, where whatever reads ``o`` reads it
            r = jnp.arange(Np)[:, None]
            mine = (r >= seg_off[None, :]) & (r < (seg_off + q_lens)[None, :])
            o = jnp.where(jnp.any(mine, axis=1)[:, None, None], o, 0.0)
        Sw = jnp.where(single[:, None, None, None], S1, Sw)

        # -- what the step leaves: the lanes' state and last three rows
        def row_at(p):  # [B, C]: a lane's row at segment index p (< 0: history)
            inside = u[jnp.clip(seg_off + p, 0, Np - 1)]
            return jnp.where(
                (p >= 0)[:, None], inside,
                hist[jnp.arange(B), jnp.clip(3 + p, 0, 2)])

        new_hist = jnp.stack([row_at(q_lens - 3 + n) for n in range(3)], axis=1)
        new_hist = jnp.where(live[:, None, None], new_hist, old_hist)
        snap_hist = jnp.stack([row_at(cut - 3 + n) for n in range(3)], axis=1)
        # -- the snapshots the plan names (others to no slot): the launch
        # has written the states, their rows of history go here
        to = jnp.where(snaps, state.plan[1], S_slots)
        rows3 = (3 * to[:, None] + jnp.arange(3)[None, :]).reshape(-1)

        def write(pool):  # and few take a snapshot
            slots, slot_conv = pool
            if not kernel:
                slots = slots.at[layer, to].set(snap, mode="drop")
            return (
                slots,
                slot_conv.at[layer, rows3].set(
                    snap_hist.reshape(3 * B, C).astype(slot_conv.dtype),
                    mode="drop",
                ),
            )

        slots, slot_conv = jax.lax.cond(
            jnp.any(snaps), write, lambda pool: pool, (slots, state.slot_conv))
        new = DeltaKV(
            state.attn,
            lanes_all.at[layer].set(Sw),
            state.conv.at[layer].set(
                new_hist.reshape(3 * B, C).astype(state.conv.dtype)),
            slots, slot_conv, state.plan,
        )
    return o, new


@hot_path
def decode_delta_mix(
    cfg,
    u: jax.Array,  # [B, C] one row a lane
    taps: jax.Array,  # [4, C]
    g: jax.Array,  # [B, Hv] f32
    beta: jax.Array,  # [B, Hv] f32
    state: DeltaKV,
    layer: jax.Array,
    active: jax.Array,  # [B] bool: lanes the step advances
):
    """A decode step's delta rule: one token a lane against the lane's
    state.  Only an ``active`` lane's state moves: a frozen lane runs the
    step over again on the same token, and a second update of its state
    would not be the first.  No snapshot is taken or read."""
    B, C = u.shape
    with jax.named_scope("gdn_decode"):
        hist = _gdn_history(state, layer, B)
        x = _gdn_conv(taps, [hist[:, 0], hist[:, 1], hist[:, 2], u])
        q, k, v = _gdn_heads(cfg, x)  # [B, Hv, d]
        old = jax.lax.dynamic_index_in_dim(state.lanes, layer, 0, False)
        S = jnp.exp(g)[..., None, None] * old
        d = beta[..., None] * (v - _gdn_dot("bhk,bhkv->bhv", k, S))
        S = S + k[..., :, None] * d[..., None, :]
        o = _gdn_dot("bhk,bhkv->bhv", q, S)
        keep = active[:, None, None]
        new_hist = jnp.where(
            keep, jnp.concatenate([hist[:, 1:], u[:, None]], axis=1), hist)
        new = DeltaKV(
            state.attn,
            state.lanes.at[layer].set(jnp.where(keep[..., None], S, old)),
            state.conv.at[layer].set(
                new_hist.reshape(3 * B, C).astype(state.conv.dtype)),
            state.slots, state.slot_conv, state.plan,
        )
    return o, new



def _kv_write(kv_pages, kv_idx, layer, ids, k_rows, *, slot=None):
    """Scatter one side's rows into the pool at (layer, ids[, slot]):
    quantizes on write for int8 pools.  ``ids`` (page ids) and ``slot``
    (within-page row) are pre-flattened index arrays; ``k_rows`` is
    ``[..., Hkv, D]`` aligned with them.  A latent pool has one side: its
    row is key and value at once, and the value's write is the key's."""
    if kv_is_latent(kv_pages):
        return kv_pages if kv_idx else kv_pages.write(layer, ids, k_rows, slot)
    if isinstance(kv_pages, QuantKV):
        q, s = quantize_kv_rows(k_rows)
        if slot is None:
            new_q = kv_pages.q.at[layer, kv_idx, ids].set(q)
            new_s = kv_pages.s.at[layer, kv_idx, ids].set(
                s.astype(kv_pages.s.dtype)
            )
        else:
            new_q = kv_pages.q.at[layer, kv_idx, ids, slot].set(q)
            new_s = kv_pages.s.at[layer, kv_idx, ids, slot].set(
                s.astype(kv_pages.s.dtype)
            )
        return QuantKV(q=new_q, s=new_s)
    if slot is None:
        return kv_pages.at[layer, kv_idx, ids].set(
            k_rows.astype(kv_pages.dtype)
        )
    return kv_pages.at[layer, kv_idx, ids, slot].set(
        k_rows.astype(kv_pages.dtype)
    )


def _env_flag(name: str):
    """Tri-state env override shared by every Pallas dispatch gate:
    True/False when the variable is set, None for auto."""
    env = os.environ.get(name)
    if env is None:
        return None
    return env not in ("0", "false", "")


@functools.lru_cache(maxsize=None)
def _on_tpu() -> bool:
    """Whether the process's default backend is a TPU, probed once.  A
    backend that fails to initialise raises here: a server that cannot
    reach its chip must not come up on the XLA composition instead."""
    return jax.default_backend() == "tpu"


# -- kernels on a mesh --------------------------------------------------------
#
# A Pallas (Mosaic) kernel cannot be partitioned by GSPMD: inside a jit
# over more than one device the lowering refuses it outright ("Mosaic
# kernels cannot be automatically partitioned"), whichever axis the
# operands are sharded on.  Attention has no cross-head term and the pool
# is sharded on its kv-head axis, so on a mesh every kernel below runs
# through ``jax.shard_map``: per ``tp`` shard on the head axes, replicated
# over every other axis (dp lanes are gathered around the kernel).  The
# mesh is JAX's own context mesh (``jax.set_mesh``), which is part of
# jit's cache key: the engine sets its mesh on its dispatch thread.


def _context_mesh():
    """The context mesh of the trace in progress, or None on one device."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty or mesh.size == 1 else mesh


def _tp() -> int:
    """Shards of the head axis under the trace's context mesh."""
    mesh = _context_mesh()
    return mesh.shape.get("tp", 1) if mesh is not None else 1


def _heads_shard(Hq: int, Hkv: int) -> bool:
    """The explicit tp rule of every Pallas gate: under a tp mesh the
    kernels run per shard, which needs both head counts to divide.  Where
    they do not (the pool then sits replicated, sharding._compatible_spec),
    the dispatch takes the XLA composition, which GSPMD can partition."""
    tp = _tp()
    return Hq % tp == 0 and Hkv % tp == 0


def _per_shard(kernel, args, specs, out_spec):
    """Call ``kernel(*args)`` directly on one device, or through
    ``jax.shard_map`` over the context mesh (``specs`` name the head axis
    of each operand; everything else is replicated over the mesh)."""
    if _context_mesh() is None:
        return kernel(*args)
    return jax.shard_map(
        kernel, in_specs=tuple(specs), out_specs=out_spec, check_vma=False,
    )(*args)


_POOL_SPEC = P(None, None, None, None, "tp", None)  # [L, 2, P, page, Hkv, D]


def _pallas_decode_enabled(page_size: int) -> bool:
    """Trace-time choice of the decode-attention backend.

    ``DYN_PALLAS_DECODE=1/0`` forces it; default is auto -- on when the
    backend is a TPU and the page size meets the kernel's sublane tiling
    (>= 8).  The XLA path stays as the universal fallback (CPU tests, tiny
    page sizes)."""
    forced = _env_flag("DYN_PALLAS_DECODE")
    if forced is not None:
        return forced
    return page_size >= 8 and _on_tpu()


def decode_backend(kv_pages, Hq: int, D: int, dtype) -> str:
    """What attends a decode launch (the fused steps after a dispatch's
    first, ``decode_step``, ``decode_block``) over this pool for ``Hq``
    query heads of ``D`` in ``dtype``, decided at trace time under the
    context mesh; the tick's ``dispatch`` annotation reads it here too.
    ``"latent"`` and ``"work_list"``: the pool's packed launch walks a work
    list (:func:`_packed_backend`, the one statement of that rule), and the
    decode launch is that kernel with one item a lane, so the width of the
    page table costs it nothing.  ``"grid"``: the page-group grid of
    ``ops.paged_attention`` (narrow heads).  ``"xla"``: the gather (the
    CPU, an int8 pool, narrow heads over a dense pool whose type is not the
    query's, heads that do not shard)."""
    data = kv_data(kv_pages)
    page, Hkv = data.shape[3], data.shape[4]
    packed = _packed_backend(kv_pages, Hq, Hkv, D)
    if packed in ("latent", "work_list"):
        return packed
    if (
        not kv_is_latent(kv_pages)
        and not kv_is_quantized(kv_pages)
        # the grid kernel computes directly on the pool tiles: a dense pool
        # dtype that differs from the query/compute dtype (explicit
        # --kv-dtype float32 under a bf16 model) takes the XLA gather,
        # whose dequant/cast normalizes operands
        and data.dtype == dtype
        and _heads_shard(Hq, Hkv)
        and _pallas_decode_enabled(page)
    ):
        return "grid"
    return "xla"


@hot_path
def decode_attention_dispatch(
    q: jax.Array,  # [B, Hq, D]
    kv_pages: jax.Array,  # [L, 2, num_pages, page_size, Hkv, D]
    page_table: jax.Array,  # [B, P]
    kv_lens: jax.Array,  # [B]
    layer: jax.Array,  # scalar i32
    window: int = 0,  # sliding-window width; 0 = full attention
    name_suffix: str = "",  # LayerView.suffix: a window layer's launch
) -> jax.Array:
    """Decode attention over a pool that already holds the new token's row:
    a Pallas kernel on TPU, the XLA gather elsewhere (:func:`decode_backend`).
    Resolved at trace time (static), so each compiled executable embeds
    exactly one backend.  Quantized pools take the XLA gather on this path
    (the fused steps and the classic penalized/multimodal fallback lanes);
    under ``--kv-dtype int8`` the packed dispatch's Pallas kernels fuse the
    dequant."""
    backend = decode_backend(kv_pages, q.shape[1], q.shape[2], q.dtype)
    if backend == "latent":
        from ..ops.latent_attention import latent_decode_attention

        return latent_decode_attention(q, kv_pages, page_table, kv_lens, layer)
    if backend in ("work_list", "grid"):
        if backend == "work_list":
            from ..ops.ragged_attention import (
                decode_work_list_attention as kernel,
            )

            extra = {}
        else:
            from ..ops.paged_attention import (
                paged_decode_attention_v2 as kernel,
            )

            # group-of-8 fetches: grid-step overhead dominates per-page v1
            # at serving shapes (v2 internally falls back to v1 for table
            # widths the group doesn't divide)
            extra = {"group": 8}
        return _per_shard(
            lambda q, kv, pt, lens, layer: kernel(
                q, kv, pt, lens, layer, window, name_suffix=name_suffix,
                **extra,
            ),
            (q, kv_pages, page_table, kv_lens, layer),
            (P(None, "tp", None), _POOL_SPEC, P(), P(), P()),
            P(None, "tp", None),
        )
    layer_kv = index_kv_layer(kv_pages, layer)
    return paged_decode_attention(q, layer_kv, page_table, kv_lens, window)


# -- latent pools (MLA) -------------------------------------------------------
#
# Every call below gets the model layer's absorbed operands (model.
# _latent_attention): queries already in the latent space, the fresh rows,
# and a pool whose one side is key and value at once.  The XLA compositions
# serve that as multi-query attention unchanged (gather_layer_kv and
# _kv_write know a latent pool).  On the chip the two paths serving takes --
# the packed mixed step and the fused decode steps -- have kernels of their
# own, which read everything from the pool: the dispatch's fresh rows are
# scattered first (write_packed_kv / write_decode_kv), so a kernel sees one
# source of keys and the causal mask alone tells fresh from resident.


def latent_kernels_enabled(page_size: int) -> bool:
    """Trace-time choice of a latent pool's attention backend: the Pallas
    kernels on a TPU (``DYN_PALLAS_RAGGED=1/0`` forces it, the knob of the
    pair pools' ragged kernels), the XLA composition elsewhere and on a
    mesh (a latent pool has no head axis to run per shard)."""
    if _context_mesh() is not None:
        return False
    forced = _env_flag("DYN_PALLAS_RAGGED")
    if forced is not None:
        return forced
    return page_size >= 8 and _on_tpu()


def latent_packed_path(kv_pages) -> str:
    """Which latent path a packed dispatch over this latent pool takes, for
    the tick's ``dispatch`` annotation: both are the absorbed form."""
    row = kv_data(kv_pages).shape[5]
    return (
        "absorbed_kernel" if _packed_backend(kv_pages, 1, 1, row) == "latent"
        else "absorbed_xla"
    )


def latent_packed_attention_dispatch(
    q: jax.Array,  # [Np, Hq, W] absorbed queries
    rows: jax.Array,  # [Np, 1, W] this dispatch's latent rows
    kv_pages,  # kv_cache.LatentKV
    layer, page_table, base, seg_off, q_lens, lane, rel, pos, valid,
    s_max: int,
):
    """The packed mixed step's attention AND row scatter over a latent
    pool: ``(out [Np, Hq, >= C], pool)``.  On the chip the rows are
    scattered first and one kernel reads the pool (causal by position); the
    XLA composition attends to the fresh rows beside the pool, as the pair
    pools' does, and scatters after."""
    written = write_packed_kv(
        kv_pages, rows, rows, page_table, lane, pos, valid, layer
    )
    if _packed_backend(kv_pages, q.shape[1], 1, q.shape[2]) == "latent":
        from ..ops.latent_attention import latent_packed_attention

        out = latent_packed_attention(
            q, written, page_table, base, seg_off, q_lens, s_max, layer
        )
    else:
        from ..ops.ragged_attention import packed_ragged_attention_xla

        out = packed_ragged_attention_xla(
            q, rows, rows, kv_pages, page_table, base, seg_off, q_lens,
            lane, rel, s_max, layer, 0,
        )
    return out, written


def _pallas_ragged_enabled(page_size: int, Hq: int, Hkv: int, D: int) -> bool:
    """Trace-time choice of the packed mixed-batch attention backend.

    ``DYN_PALLAS_RAGGED=1/0`` forces it; default is auto -- on when the
    backend is a TPU, the page size meets the kernels' sublane tiling
    (>= 8), and the GQA group divides cleanly.  The XLA composition
    (ops.ragged_attention.packed_ragged_attention_xla) stays as the
    universal fallback and the tier-1 (CPU) code path."""
    forced = _env_flag("DYN_PALLAS_RAGGED")
    if forced is not None:
        return forced
    if page_size < 8 or Hq % Hkv or D % 8 or not _heads_shard(Hq, Hkv):
        return False
    return _on_tpu()


def _packed_backend(kv_pages, Hq: int, Hkv: int, D: int) -> str:
    """What attends a packed launch over this pool, decided at trace time
    under the context mesh -- the ONE statement of the rule, read by both
    packed dispatches, :func:`latent_packed_path` and :func:`packed_launch`:
    ``"latent"`` (a latent pool's work-list kernel), ``"work_list"`` or
    ``"grid"`` (a pair pool's two Pallas kernels,
    ``ragged_attention._takes_work_list``), or
    ``"xla"`` (the composition: the CPU, a tiny page, heads that do not
    shard, a latent pool on a mesh)."""
    page = kv_data(kv_pages).shape[3]
    if kv_is_latent(kv_pages):
        return "latent" if latent_kernels_enabled(page) else "xla"
    if not _pallas_ragged_enabled(page, Hq, Hkv, D):
        return "xla"
    from ..ops.ragged_attention import _takes_work_list

    quant = kv_is_quantized(kv_pages)
    return "work_list" if _takes_work_list(D, quant) else "grid"


class PackedLaunch(NamedTuple):
    """What an engine has to know of its packed launch's attention."""

    # a work-list kernel has no step for a page group: the width of the
    # page table it is handed costs it nothing, and the decode launch of the
    # fused steps over the same pool is the same kernel (decode_backend)
    walks_work_list: bool
    # ``fits(Np, s_max)``: whether the launch can hold that packed shape
    fits: Callable[[int, int], bool]
    # the rows a launch may touch past an item's first, as the engine's
    # shape rule needs it (``bucketing.PackedShapeBudget``).  0: a lane's
    # whole ``s_max`` window from its offset, so ``Np`` must hold ``off_last
    # + s_max`` (the grid kernel, the XLA composition, the latent kernels).
    # ``Q > 0``: the launch moves tiles of at most ``Q`` rows and keeps them
    # inside the packed axis, so ``total <= Np`` alone binds and ``s_max``
    # means nothing beyond ``Q`` (the pair pools' work-list kernel)
    item_rows: int = 0


def packed_launch(kv_pages, Hq: int, Hkv: int, D: int, dtype) -> PackedLaunch:
    """Which kernel serves the packed launches of an engine over this pool,
    at a model of ``Hq`` query and ``Hkv`` kv heads of ``D`` in ``dtype``.
    Asked once at construction, under the engine's mesh (the gates read
    ``tp`` from the context mesh, as the step's trace will).  Only the grid
    kernel bounds the shape: it holds the packed operands of its shard of
    heads in VMEM for the whole launch.  The work-list kernels (a tile by
    DMA) and the XLA composition (no VMEM) hold any.  Only the pair pools'
    work-list kernel frees the shape of the lanes' windows."""
    kind = _packed_backend(kv_pages, Hq, Hkv, D)
    if kind == "work_list":
        from ..ops.ragged_attention import _WL_Q_BLOCK

        return PackedLaunch(True, lambda Np, s: True, _WL_Q_BLOCK)
    if kind != "grid":
        return PackedLaunch(kind == "latent", lambda Np, s: True)
    from ..ops.ragged_attention import packed_shape_fits

    # scalars only: the engine keeps ``fits``, and must not keep this pool
    data = kv_data(kv_pages)
    page, kv_dtype = data.shape[3], data.dtype
    tp = _tp()
    quant = kv_is_quantized(kv_pages)

    def fits(Np: int, s_max: int) -> bool:
        return packed_shape_fits(
            Np, s_max, Hq // tp, Hkv // tp, D, page, dtype, kv_dtype, quant
        )

    return PackedLaunch(False, fits)


def _scale_args(scales):
    """The int8 pool's row scales as (operands, specs) of a per-shard
    kernel call: they carry no head axis and ride replicated."""
    return ((), ()) if scales is None else ((scales,), (P(),))


@hot_path
def packed_ragged_attention_dispatch(
    q: jax.Array,  # [Np, Hq, D] packed queries (lane's row i at base+i)
    k: jax.Array,  # [Np, Hkv, D] packed fresh keys
    v: jax.Array,  # [Np, Hkv, D]
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    layer: jax.Array,  # scalar i32
    page_table: jax.Array,  # [B, P] (bucketed)
    base: jax.Array,  # [B] committed cache length per lane
    seg_off: jax.Array,  # [B] lane's segment offset into the packed axis
    q_lens: jax.Array,  # [B] fresh rows per lane (0 = no segment)
    lane: jax.Array,  # [Np] lane per packed token (B = padding)
    rel: jax.Array,  # [Np] row index within the lane's segment
    s_max: int,  # static per-lane window capacity
    window: int = 0,
    name_suffix: str = "",  # LayerView.suffix: a window layer's launch
) -> jax.Array:
    """Fully-packed ragged mixed-batch attention: the ONE attention call
    of ``step.packed_unified_step`` over a pair pool.  A decode lane is a
    1-row segment, a chunked-prefill lane its chunk's rows, a speculating
    lane its verify columns, all causal at token granularity against the
    resident prefix plus the dispatch's own fresh rows.  A Pallas kernel
    on TPU (work list or grid, :func:`_packed_backend`), the XLA
    unpack-reference-repack composition elsewhere -- resolved at trace
    time like every other dispatch gate (``DYN_PALLAS_RAGGED`` forces
    it).  A quantized pool passes its row scales as extra kernel operands;
    the dequant runs on each fetched page group in VMEM, never on a
    full-width pool."""
    Hq, D = q.shape[1], q.shape[2]
    Hkv = k.shape[1]
    data = kv_data(kv_pages)
    scales = kv_pages.s if kv_is_quantized(kv_pages) else None
    if _packed_backend(kv_pages, Hq, Hkv, D) in ("work_list", "grid"):
        from ..ops.ragged_attention import packed_ragged_attention

        s_ops, s_specs = _scale_args(scales)
        heads = P(None, "tp", None)
        return _per_shard(
            lambda q, k, v, data, pt, base, off, lens, layer, *sc: (
                packed_ragged_attention(
                    q, k, v, data, pt, base, off, lens, s_max, layer,
                    window, group=4, kv_scales=sc[0] if sc else None,
                    name_suffix=name_suffix,
                )
            ),
            (q, k, v, data, page_table, base, seg_off, q_lens, layer, *s_ops),
            (
                heads, heads, heads, _POOL_SPEC, P(), P(), P(), P(), P(),
                *s_specs,
            ),
            heads,
        )
    from ..ops.ragged_attention import packed_ragged_attention_xla

    return packed_ragged_attention_xla(
        q, k, v, kv_pages, page_table, base, seg_off, q_lens, lane, rel,
        s_max, layer, window,
    )


def _pallas_prefill_enabled(T: int, Hq: int, Hkv: int, D: int) -> bool:
    """Trace-time choice of the prefill-attention backend.

    ``DYN_PALLAS_PREFILL=1/0`` forces it; default is auto -- on when the
    backend is a TPU, the GQA group divides cleanly, and the sequence is
    long enough that score materialization dominates.  Measured on v5e
    (bench heads, 256-token tiles): T=512 XLA's fused chain still matches;
    T=1024 flash wins 102 vs 109 ms; T=2048 it wins 86 vs 117 ms (-26%);
    T=4096 106 vs 108 ms -- so auto engages at T >= 1024.  The XLA path
    stays as the universal fallback."""
    forced = _env_flag("DYN_PALLAS_PREFILL")
    if forced is not None:
        return forced
    if T < 1024 or Hq % Hkv or D % 8 or not _heads_shard(Hq, Hkv):
        return False
    if D > 256:  # a latent cache's rows (MLA): wider than the kernel's tiles
        return False
    return _on_tpu()


@hot_path
def prefill_attention_dispatch(
    q: jax.Array,  # [B, T, Hq, D]
    k: jax.Array,  # [B, T, Hkv, D]
    v: jax.Array,  # [B, T, Hkv, D]
    seq_lens: jax.Array,  # [B]
    window: int = 0,
) -> jax.Array:
    """Prefill attention: Pallas flash kernel on TPU, XLA einsum elsewhere.
    Resolved at trace time, so each compiled executable embeds exactly one
    backend (same pattern as decode_attention_dispatch)."""
    B, T, Hq, D = q.shape
    if _pallas_prefill_enabled(T, Hq, k.shape[2], D):
        from ..ops.flash_prefill import flash_prefill_attention

        heads = P(None, None, "tp", None)
        return _per_shard(
            lambda q, k, v, lens: flash_prefill_attention(
                q, k, v, lens, window
            ),
            (q, k, v, seq_lens),
            (heads, heads, heads, P()),
            heads,
        )
    return prefill_attention(q, k, v, seq_lens, window)


def _pallas_prefix_prefill_enabled(
    T: int, Kp: int, Hq: int, Hkv: int, D: int
) -> bool:
    """Trace-time choice for the prefix-suffix prefill backend.

    Same knob as the full-prefill dispatch (``DYN_PALLAS_PREFILL``); the
    auto threshold engages earlier than plain prefill because the score
    tensor the kernel avoids is ``[B, Hq, T, Kp+T]`` -- the resident
    prefix widens the key axis beyond what T alone suggests."""
    forced = _env_flag("DYN_PALLAS_PREFILL")
    if forced is not None:
        return forced
    if Hq % Hkv or D % 8 or D > 256 or not _heads_shard(Hq, Hkv):
        return False
    if T < 1024 and (T < 512 or Kp < 512):
        return False
    return _on_tpu()


@hot_path
def prefill_prefix_attention_dispatch(
    q: jax.Array,  # [B, T, Hq, D] suffix queries
    k: jax.Array,  # [B, T, Hkv, D] suffix keys (being prefilled)
    v: jax.Array,  # [B, T, Hkv, D]
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    layer: jax.Array,  # scalar i32
    prefix_table: jax.Array,  # [B, Pp] reused-prefix page ids (0-padded)
    offset: jax.Array,  # [B] cached prefix length in tokens
    suffix_lens: jax.Array,  # [B] valid suffix length
    window: int = 0,
) -> jax.Array:
    """Prefix-suffix prefill attention: flash-tiled on TPU, XLA gather +
    einsum elsewhere.  Resolved at trace time (same pattern as the other
    dispatches).  The flash path pre-gathers the prefix pages into
    contiguous K/V (a few MB, XLA-fused) and never materializes the
    ``[B, Hq, T, Kp+T]`` score tensor -- this is the common path under KV
    routing, where most admissions restart on a cached prefix."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    page_size = kv_data(kv_pages).shape[3]
    Kp = prefix_table.shape[1] * page_size
    if _pallas_prefix_prefill_enabled(T, Kp, Hq, Hkv, D):
        import math

        from ..ops.flash_prefill import flash_prefix_prefill_attention

        layer_kv = index_kv_layer(kv_pages, layer)
        kp = gather_layer_kv(layer_kv, 0, prefix_table, q.dtype).reshape(
            B, Kp, Hkv, D
        )
        vp = gather_layer_kv(layer_kv, 1, prefix_table, q.dtype).reshape(
            B, Kp, Hkv, D
        )
        # pad the prefix span to a key-tile multiple (BK = gcd(T, 256),
        # mirroring the kernel's tile choice): a tiny cached prefix must
        # not collapse the whole key axis to its width, and non-pow2 top
        # buckets must still tile exactly.  Pad keys are masked by
        # ``kpos < offset`` (offset <= Kp <= padded span).
        BK = math.gcd(T, 256)
        pad = (-Kp) % BK
        if pad:
            widths = [(0, 0)] * 4
            widths[1] = (0, pad)
            kp = jnp.pad(kp, widths)
            vp = jnp.pad(vp, widths)
        heads = P(None, None, "tp", None)
        return _per_shard(
            lambda q, k, v, off, lens: flash_prefix_prefill_attention(
                q, k, v, off, lens, window
            ),
            (
                q,
                jnp.concatenate([kp, k], axis=1),
                jnp.concatenate([vp, v], axis=1),
                offset,
                suffix_lens,
            ),
            (heads, heads, heads, P(), P()),
            heads,
        )
    return prefill_prefix_attention(
        q, k, v, kv_pages, layer, prefix_table, offset, suffix_lens, window
    )


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[.., kv_heads, d] -> [.., kv_heads * n_rep, d] (GQA expansion)."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


@hot_path
def prefill_attention(
    q: jax.Array,  # [B, T, Hq, D]
    k: jax.Array,  # [B, T, Hkv, D]
    v: jax.Array,  # [B, T, Hkv, D]
    seq_lens: jax.Array,  # [B] valid prompt length per slot
    window: int = 0,  # sliding-window width; 0 = full attention
) -> jax.Array:
    """Causal self-attention over the prompt being prefilled.

    Assumes the prompt starts at position 0 (no prior cache); prefix-cache
    restarts gather reused pages through the decode path instead.
    ``window`` > 0 masks keys more than ``window - 1`` positions behind the
    query (Mistral/Phi3 sliding-window semantics: the query position itself
    counts toward the window)."""
    B, T, Hq, D = q.shape
    n_rep = Hq // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
    # [B, H, T, T]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    pos = jnp.arange(T)
    causal = pos[None, :] <= pos[:, None]  # [Tq, Tk] keys <= query
    if window > 0:
        causal = causal & (pos[:, None] - pos[None, :] < window)
    valid = pos[None, :] < seq_lens[:, None]  # [B, Tk]
    mask = causal[None, None, :, :] & valid[:, None, None, :]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@hot_path
def paged_decode_attention(
    q: jax.Array,  # [B, Hq, D] one new query token per slot
    kv_pages: jax.Array,  # [2, num_pages, page_size, Hkv, D]
    page_table: jax.Array,  # [B, P] int32 page ids
    kv_lens: jax.Array,  # [B] tokens in cache (incl. the one just written)
    window: int = 0,  # sliding-window width; 0 = full attention
) -> jax.Array:
    """Decode-step attention: gather each slot's pages, mask, softmax.

    The gather materializes ``[B, P*page_size, Hkv, D]`` -- the classic
    paged-attention v1 shape.  P (pages per sequence) is static; kv_lens
    masks the tail (and, with ``window``, the head beyond the window).
    """
    B, Hq, D = q.shape
    _, _, page_size, Hkv, _ = kv_data(kv_pages).shape
    P = page_table.shape[1]
    n_rep = Hq // Hkv

    k = gather_layer_kv(kv_pages, 0, page_table, q.dtype)  # [B, P, page, Hkv, D]
    v = gather_layer_kv(kv_pages, 1, page_table, q.dtype)
    k = k.reshape(B, P * page_size, Hkv, D)
    v = v.reshape(B, P * page_size, Hkv, D)
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)

    scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
    scores = jnp.einsum("bhd,bkhd->bhk", q, k) * scale  # [B, Hq, P*page]
    idx = jnp.arange(P * page_size)
    mask = idx[None, :] < kv_lens[:, None]  # [B, P*page]
    if window > 0:
        mask = mask & (idx[None, :] >= kv_lens[:, None] - window)
    scores = jnp.where(mask[:, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhk,bkhd->bhd", probs, v)


@hot_path
def prefill_prefix_attention(
    q: jax.Array,  # [B, T, Hq, D] suffix queries
    k: jax.Array,  # [B, T, Hkv, D] suffix keys (being prefilled)
    v: jax.Array,  # [B, T, Hkv, D]
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    layer: jax.Array,  # scalar i32
    prefix_table: jax.Array,  # [B, Pp] reused-prefix page ids (0-padded)
    offset: jax.Array,  # [B] cached prefix length in tokens
    suffix_lens: jax.Array,  # [B] valid suffix length
    window: int = 0,  # sliding-window width; 0 = full attention
) -> jax.Array:
    """Suffix prefill attention with a resident prefix (prefix-cache restart).

    Queries live at absolute positions ``offset + local``; keys are the
    gathered prefix pages (positions ``0..offset``) concatenated with the
    suffix K/V computed this dispatch.  ``Pp`` is a static page-count bucket;
    pad slots point at trash page 0 and are masked by ``kpos < offset``.
    """
    B, T, Hq, D = q.shape
    page_size = kv_data(kv_pages).shape[3]
    Pp = prefix_table.shape[1]
    Hkv = k.shape[2]
    n_rep = Hq // Hkv

    layer_kv = index_kv_layer(kv_pages, layer)
    kp = gather_layer_kv(layer_kv, 0, prefix_table, q.dtype).reshape(
        B, Pp * page_size, Hkv, D
    )
    vp = gather_layer_kv(layer_kv, 1, prefix_table, q.dtype).reshape(
        B, Pp * page_size, Hkv, D
    )
    keys = repeat_kv(jnp.concatenate([kp, k], axis=1), n_rep)
    vals = repeat_kv(jnp.concatenate([vp, v], axis=1), n_rep)

    scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, keys) * scale

    local = jnp.arange(T)
    prefix_valid = jnp.arange(Pp * page_size)[None, :] < offset[:, None]  # [B, Kp]
    suffix_valid = local[None, :] < suffix_lens[:, None]  # [B, T]
    causal = local[None, :] <= local[:, None]  # [Tq, Tk]
    if window > 0:
        # absolute positions: query = offset + local_q, prefix key = kpos,
        # suffix key = offset + local_k; keep keys within the window
        q_abs = offset[:, None] + local[None, :]  # [B, Tq]
        kpos = jnp.arange(Pp * page_size)
        prefix_win = (
            kpos[None, None, :] > q_abs[:, :, None] - window
        )  # [B, Tq, Kp]
        mask_prefix = jnp.broadcast_to(
            (prefix_valid[:, None, :] & prefix_win)[:, None],
            (B, 1, T, Pp * page_size),
        )
        suffix_win = local[:, None] - local[None, :] < window  # [Tq, Tk]
        causal = causal & suffix_win
    else:
        mask_prefix = jnp.broadcast_to(
            prefix_valid[:, None, None, :], (B, 1, T, Pp * page_size)
        )
    mask_suffix = jnp.broadcast_to(
        causal[None, None, :, :] & suffix_valid[:, None, None, :], (B, 1, T, T)
    )
    mask = jnp.concatenate([mask_prefix, mask_suffix], axis=-1)
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vals)


@hot_path
def write_prefill_kv(
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    k: jax.Array,  # [B, T, Hkv, D]
    v: jax.Array,  # [B, T, Hkv, D]
    page_table: jax.Array,  # [B, P]
    layer: jax.Array,  # scalar i32
) -> jax.Array:
    """Scatter a full prompt's K/V into its pages (in place -- kv_pages is
    the scan carry).  T must be a multiple of page_size (prompts are
    bucket-padded); pad lanes land on trash page 0.  Quantized pools
    quantize on write (per-row scales scatter alongside)."""
    B, T, Hkv, D = k.shape
    page_size = kv_data(kv_pages).shape[3]
    n_pages = T // page_size
    ids = page_table[:, :n_pages].reshape(-1)  # [B*n_pages]
    kp = k.reshape(B * n_pages, page_size, Hkv, D)
    vp = v.reshape(B * n_pages, page_size, Hkv, D)
    kv_pages = _kv_write(kv_pages, 0, layer, ids, kp)
    kv_pages = _kv_write(kv_pages, 1, layer, ids, vp)
    return kv_pages


@hot_path
def write_spec_kv(
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    k: jax.Array,  # [B, S, Hkv, D] verify-column keys
    v: jax.Array,
    page_table: jax.Array,  # [B, P]
    base: jax.Array,  # [B] cache length; column j lands at base + j
    n_tokens: jax.Array,  # [B] valid columns per lane (0 = lane not verifying)
    layer: jax.Array,  # scalar i32
) -> jax.Array:
    """Scatter a speculative verify dispatch's K/V: column ``j`` of lane
    ``b`` lands at position ``base[b] + j``.  Columns past ``n_tokens``
    (rejected-draft padding, non-speculating lanes) and positions past the
    lane's page allocation route to trash page 0 -- the multi-token
    sibling of :func:`write_decode_kv`'s dead-lane handling.  Rejected
    columns' writes within a lane's pages are *garbage by design*: they
    sit beyond the committed cache length, are never attended (the read
    window is ``seq_lens``-bounded), and the next verify/decode step
    overwrites them in sequence order before the length passes them."""
    B, S, Hkv, D = k.shape
    page_size = kv_data(kv_pages).shape[3]
    P = page_table.shape[1]
    positions = base[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # [B, S]
    valid = jnp.arange(S)[None, :] < n_tokens[:, None]  # [B, S]
    page_idx = positions // page_size
    slot = jnp.where(valid, positions % page_size, 0)
    ids = jnp.take_along_axis(page_table, jnp.clip(page_idx, 0, P - 1), axis=1)
    ids = jnp.where(valid & (page_idx < P), ids, 0)
    flat_ids = ids.reshape(B * S)
    flat_slot = slot.reshape(B * S)
    kv_pages = _kv_write(
        kv_pages, 0, layer, flat_ids, k.reshape(B * S, Hkv, D),
        slot=flat_slot,
    )
    kv_pages = _kv_write(
        kv_pages, 1, layer, flat_ids, v.reshape(B * S, Hkv, D),
        slot=flat_slot,
    )
    return kv_pages


@hot_path
def write_packed_kv(
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    k: jax.Array,  # [Np, Hkv, D] packed fresh keys
    v: jax.Array,  # [Np, Hkv, D]
    page_table: jax.Array,  # [B, P]
    lane: jax.Array,  # [Np] lane per packed token (B = padding)
    pos: jax.Array,  # [Np] absolute position per token
    valid: jax.Array,  # [Np] bool (False = pad / dead row -> trash page 0)
    layer: jax.Array,  # scalar i32
) -> jax.Array:
    """Scatter a packed unified dispatch's K/V: packed token ``n`` of
    lane ``lane[n]`` lands at position ``pos[n]`` through that lane's
    page table.  The flat-axis sibling of :func:`write_spec_kv` --
    invalid rows (packed-axis padding, device-dead decode lanes) and
    positions past the lane's allocation route to trash page 0."""
    Np = k.shape[0]
    page_size = kv_data(kv_pages).shape[3]
    B, P = page_table.shape
    lane_c = jnp.clip(lane.astype(jnp.int32), 0, B - 1)
    page_idx = pos // page_size
    ok = valid & (page_idx < P) & (lane.astype(jnp.int32) < B)
    slot = jnp.where(ok, pos % page_size, 0)
    ids = page_table[lane_c, jnp.clip(page_idx, 0, P - 1)]
    ids = jnp.where(ok, ids, 0)
    kv_pages = _kv_write(kv_pages, 0, layer, ids, k, slot=slot)
    kv_pages = _kv_write(kv_pages, 1, layer, ids, v, slot=slot)
    return kv_pages


@hot_path
def write_decode_kv(
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    k: jax.Array,  # [B, Hkv, D] one token
    v: jax.Array,
    page_table: jax.Array,  # [B, P]
    positions: jax.Array,  # [B] position the token lands at
    layer: jax.Array,  # scalar i32
) -> jax.Array:
    page_size = kv_data(kv_pages).shape[3]
    P = page_table.shape[1]
    page_idx = positions // page_size
    slot = positions % page_size
    ids = jnp.take_along_axis(
        page_table, jnp.clip(page_idx, 0, P - 1)[:, None], axis=1
    )[:, 0]
    # a lane frozen at its capacity (page_idx == P) must land on trash page
    # 0, not clamp into its own last live page -- its stale write repeats
    # every step while other lanes decode
    ids = jnp.where(page_idx < P, ids, 0)
    kv_pages = _kv_write(kv_pages, 0, layer, ids, k, slot=slot)
    kv_pages = _kv_write(kv_pages, 1, layer, ids, v, slot=slot)
    return kv_pages
