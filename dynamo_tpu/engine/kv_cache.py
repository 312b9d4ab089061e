"""Paged KV cache: the device pool, its host-side page allocator, and the
one home of the cache's data format.

The G1 (HBM) tier of the multi-tier design (reference block_manager
CacheLevel G1, lib/llm/src/block_manager.rs:66-80).  Page 0 is the reserved
trash page (inactive batch lanes write there), so the usable pool is pages
``1..num_pages``.  Allocation is a host-side free list: page ids are just
ints; the device arrays are only touched by the jitted step functions
(functional update, buffer donated so XLA updates in place).

Five kinds of pool, each a pytree that rides the layer scan and jit
donation (``PagedKVCache`` builds the one a ``ModelConfig`` asks for):

    pair      one array ``[layers, 2, pages, page, Hkv, D]``; with
              ``--kv-dtype int8`` a ``QuantKV``: the int8 array and one f32
              scale a row ``[layers, 2, pages, page]``
    LatentKV  (MLA) ``[ceil(L/2), 1, pages, page, 1, 2 (C + R)]``: one row a
              token that is key and value, two layers a slab
    KindKV    (window and full layers) two pair pools, two allocators, two
              page tables a lane
    ConvKV    (convolution layers) a pair pool of the attention layers, and
              the convolution state by lane and by page beside it
    DeltaKV   (gated delta-rule layers) a pair pool of the attention layers,
              the lanes' matrix state and a pool of snapshot slots beside it

A *blob* is a block of a pair pool outside it (an evicted block, a swap
snapshot, a remote prefill's export, a donor's prefix block): a pytree of
arrays that share their first four axes ``[layers, 2, pages, page]``.
Every operation on one -- to host, to device, pad, concatenate, slice, the
jitted page movers, the stored arrays and the wire bytes -- is in the
section "the blob" below, written once over the leaves; no other module
names an axis of it or looks inside.  What a kind of pool cannot do is one
table, ``KV_REFUSALS``, read through ``kv_refusal``.

G2 (host RAM) / G3 (disk) offload tiers and the sequence-hash reuse
registry live in dynamo_tpu.offload and dynamo_tpu.block_manager.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..block_manager import OutOfPages
from .config import ModelConfig

# Declared tick-role device-touch sites (dynalint DT019): the KV blob
# coercion helpers stage device uploads/dequants for the onboard and
# external-delivery paths, which the engine runs between dispatches by
# design -- the launches batch with the page scatters they feed.
PACKED_DISPATCH_SITES = (
    "dequantize_kv_blob",
    "as_device_blob",
    "pad_page_axis",
)


# ---------------------------------------------------------------------------
# int8-quantized pool layout (ISSUE 13)
#
# The paged KV pool is the HBM ceiling at large batch (BENCH_r05: bs64
# est_hbm_util 0.28 with the chip otherwise idle), so halving its bytes is
# resident batch/context we currently cannot hold.  ``DYN_KV_DTYPE=int8`` /
# ``--kv-dtype int8`` switches the pool to symmetric per-row int8: the data
# array keeps the exact ``[L, 2, P, page, Hkv, D]`` geometry at one byte per
# element, and every (layer, k/v, page, slot) token row carries one f32
# scale in a parallel ``[L, 2, P, page]`` array.  Row granularity -- not
# per-page -- because writes are incremental appends (decode adds one row
# per page per step): a page-wide scale would need a read-rescale-write of
# the whole page whenever a new row raised the amax, while a row's scale is
# final the moment the row is written.  The scale array is
# ``4 / (Hkv * D)`` of the data -- noise next to the 2x data win.
#
# Dequantization happens at the point of use (the ragged Pallas kernels
# stream int8 pages and multiply by the prefetched row scales in VMEM; the
# XLA references dequantize after the page gather), and every KV-egress
# path (disagg export, offload tiers, swap snapshots, prefix onboard)
# moves the (data, scales) pair together so same-dtype round trips are
# byte-exact in the quantized domain.
# ---------------------------------------------------------------------------


_map = jax.tree_util.tree_map
_leaves = jax.tree_util.tree_leaves


class _PoolTree:
    """What the pool pytrees below answer like the array they stand for,
    written once over their leaves."""

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in _leaves(self))

    def block_until_ready(self):
        for a in _leaves(self):
            a.block_until_ready()
        return self

    # the commit's handles (engine._start_host_copy, _handles_ready): a leaf
    # without the call, a host array, skips it as it does there
    def copy_to_host_async(self) -> None:
        for a in _leaves(self):
            if hasattr(a, "copy_to_host_async"):
                a.copy_to_host_async()

    def is_ready(self) -> bool:
        return all(a.is_ready() for a in _leaves(self) if hasattr(a, "is_ready"))


@jax.tree_util.register_pytree_node_class
@dataclass
class QuantKV(_PoolTree):
    """An int8 KV payload + its per-row scales.

    Used both for the live device pool (``PagedKVCache.pages`` when
    ``kv_dtype=int8``) and for every blob sliced out of it (offload tier
    blocks, swap snapshots, disagg exports, chunked delivery parts) -- the
    scales always travel WITH the bytes they decode.  A registered pytree,
    so it rides ``lax.scan`` (the layer-stack carry), jit donation, and
    tree_map-based sharding harvests unchanged.

    Mirrors enough of the ndarray surface (``shape``/``dtype``/``ndim``/
    ``nbytes`` of the data, leading-axis ``__getitem__``, the host-copy
    handles) that geometry code -- shape validation, the commit's readiness
    probe -- treats it like the bf16 array it replaces.  ``q`` is int8
    ``[L, 2, n, page, Hkv, D]``; ``s`` is f32 ``[L, 2, n, page]``.
    """

    q: Any  # int8 data, full pool/blob geometry
    s: Any  # f32 per-row scales, data geometry minus (Hkv, D)

    def tree_flatten(self):
        return (self.q, self.s), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def ndim(self):
        return self.q.ndim

    def __getitem__(self, key):
        """Apply a leading-axes key to data AND scales.

        Valid keys index at most the shared ``[L, 2, pages, page]`` axes.
        Keys reaching into (Hkv, D) would desynchronize the pair and
        raise."""
        klen = len(key) if isinstance(key, tuple) else 1
        if klen > self.s.ndim:
            raise IndexError(
                f"QuantKV key {key!r} reaches past the shared scale axes"
            )
        return QuantKV(q=self.q[key], s=self.s[key])

    def copy(self) -> "QuantKV":
        """Host-side deep copy (tier ring get/demote semantics)."""
        return QuantKV(q=np.array(self.q), s=np.array(self.s))


# ---------------------------------------------------------------------------
# latent pool (MLA)
#
# A latent cache holds one row a token a layer, ``[c_kv (C) | RoPE(k_r)
# (R)]``: key and value of every head at once.  At C + R = 320 values the
# row is two and a half of the chip's 128-lane tiles, which neither XLA
# (it would lay the pool out pages-minor, and copy it for every kernel
# call) nor Mosaic (it refuses to slice such a dimension) will read a page
# of.  So two layers share a slab row of ``2 (C + R)`` values,
#
#     [c_kv of layer 2m | c_kv of layer 2m+1 | k_r of 2m | k_r of 2m+1]
#
# whose parts start on tile boundaries (C a multiple of 128, 2R = 128 at the
# published widths): the pool is ``[ceil(L/2), 1, pages, page, 1, 2(C+R)]``,
# dense, exactly ``L (C + R)`` values a token for an even L, and nothing of
# a row is stored twice.  A layer reads its C columns and the R tile (whose
# other half its queries meet with zeros).  ``LatentKV`` carries C beside
# the array, so every reader can take a row apart; like ``QuantKV`` it is a
# pytree, rides the layer scan and jit donation, and mirrors the array's
# ``shape``/``dtype``.
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclass
class LatentKV(_PoolTree):
    data: Any  # [ceil(L/2), 1, pages, page, 1, 2 * (C + R)]
    c: int  # width of c_kv (static)

    def tree_flatten(self):
        return (self.data,), self.c

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def r(self) -> int:
        return self.data.shape[-1] // 2 - self.c

    def lanes_of(self, half):
        """[2 (C + R)] bool: the slab row's lanes that belong to the layer
        in ``half`` (0 or 1, may be traced)."""
        lane = jnp.arange(self.data.shape[-1])
        c, r = self.c, self.r
        in_c = (lane >= half * c) & (lane < (half + 1) * c)
        in_r = (lane >= 2 * c + half * r) & (lane < 2 * c + (half + 1) * r)
        return in_c | in_r

    def write(self, layer, ids, rows, slot=None) -> "LatentKV":
        """Scatter ``rows [..., 1, C + R]`` of ``layer`` at ``(ids[,
        slot])``: whole slab rows are read, the layer's lanes replaced and
        written back (layers write one after another, so the neighbour's
        lanes are never in flight)."""
        c = self.c
        shape = self.data.shape
        flat = self.data.reshape(shape[0], shape[2], shape[3], shape[5])
        row = rows.reshape(*rows.shape[:-2], rows.shape[-1]).astype(flat.dtype)
        both = jnp.concatenate(
            [row[..., :c], row[..., :c], row[..., c:], row[..., c:]], axis=-1
        )
        pair, mine = layer // 2, self.lanes_of(layer % 2)
        at = (pair, ids) if slot is None else (pair, ids, slot)
        flat = flat.at[at].set(jnp.where(mine, both, flat[at]))
        return LatentKV(flat.reshape(shape), c)

    def layer_view(self, layer) -> "LatentLayer":
        return LatentLayer(
            jax.lax.dynamic_index_in_dim(
                self.data, layer // 2, 0, keepdims=False
            ),
            self.c, layer % 2,
        )


@dataclass
class LatentLayer:
    """One layer of a latent pool, for the XLA compositions: the slab
    ``[1, pages, page, 1, 2 (C + R)]`` and which half is the layer's.  Its
    one side serves as keys and as values."""

    slab: Any
    c: int
    half: Any

    @property
    def shape(self):
        return (*self.slab.shape[:-1], self.slab.shape[-1] // 2)

    def gather(self, page_table, out_dtype):
        """``[B, P, page, 1, C + R]`` rows of this layer."""
        g = self.slab[0][page_table]
        c, r = self.c, self.slab.shape[-1] // 2 - self.c
        first = self.half == 0
        return jnp.concatenate(
            [
                jnp.where(first, g[..., :c], g[..., c : 2 * c]),
                jnp.where(
                    first, g[..., 2 * c : 2 * c + r], g[..., 2 * c + r :]
                ),
            ],
            axis=-1,
        ).astype(out_dtype)


# ---------------------------------------------------------------------------
# two kinds of layer (mellum): window layers and full layers in one trunk
#
# A full layer reads every key of a sequence; a window layer reads the last
# ``sliding_window`` and nothing behind them.  One page id for all layers
# would hold a window layer's bytes for as long as a full layer needs the
# token (three quarters of a token's bytes at three window layers in four).
# So the cache is two pools with two allocators and a lane has two page
# tables, both indexed by absolute position: ``full`` holds the full layers
# ``[Lf, 2, Pf, page, Hkv, D]``, ``window`` the window layers ``[Lw, 2, Pw,
# page, Hkv, D]``.  The scheduler lets a window page go as soon as it lies
# behind the window of every row still to be computed; its entry in the
# lane's window table then points at the trash page, which the window mask
# hides.  Like ``QuantKV`` and ``LatentKV`` the pair is a pytree: it rides
# the layer scan's carry and jit donation.  A layer's view of it is taken
# at trace time (``attention.layer_view``): each pool alone is a plain pair
# pool, and every reader and writer below sees only that.
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclass
class KindKV(_PoolTree):
    full: Any  # [Lf, 2, Pf, page, Hkv, D]
    window: Any  # [Lw, 2, Pw, page, Hkv, D]

    def tree_flatten(self):
        return (self.full, self.window), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def dtype(self):
        return self.full.dtype

    def of(self, kind: str):
        return self.full if kind == "full" else self.window

    def replace(self, kind: str, pool) -> "KindKV":
        if kind == "full":
            return KindKV(pool, self.window)
        return KindKV(self.full, pool)


# ---------------------------------------------------------------------------
# convolution layers (lfm2_moe): state beside the pages
#
# A gated short-convolution layer mixes a token with its two predecessors:
# what a sequence carries past position ``t`` is ``(z_{t-1}, z_t)``, two rows
# of the hidden width a layer, exact to restore.  It touches no page, so the
# pair pool ``attn`` holds the attention layers alone, and beside it ride
#
#     lanes [conv_layers, 2 B, H]          the lane is the slot: the last two
#                                          rows lane ``b``'s sequence computed,
#                                          at rows ``2 b`` and ``2 b + 1``
#     pages [conv_layers, 2 num_pages, H]  a snapshot rides the page: the rows
#                                          at the last two positions of page
#                                          ``p`` of the PAIR POOL, at ``2 p``
#                                          and ``2 p + 1``
#
# (The pair of rows is folded into the axis before it: with an axis of 2 in
# front of ``H`` the chip tiles the array by that 2, the row scatter wants
# rows of 8, and XLA copies all of ``pages`` around every layer's write:
# three copies of 1.3 GB beside a step at LFM2's cut, compiled for a
# described v5e.)
#
# Every step writes both beside its K/V (attention.packed_conv_mix /
# decode_conv_mix).  A page's snapshot lives and dies with the page (one
# allocator, one refcount, one LRU), so whatever block the registry can hand
# out has the state at its end.  Where a segment's predecessors come from is
# read off its first position ``p``: zeros at 0; the snapshot of page ``p /
# page - 1`` where ``p`` starts a page (a prefix hit ends on a page, and a
# lane that walks on over a page boundary reads back the very rows it wrote
# there); the lane's own rows otherwise.  No dispatch carries a flag for it,
# and a lane a new request takes never reads what the last one left.  Like
# ``KindKV`` the triple is a pytree on the layer scan's carry.
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclass
class ConvKV(_PoolTree):
    attn: Any  # [La, 2, P, page, Hkv, D]
    lanes: Any  # [Lc, 2 B, H]
    pages: Any  # [Lc, 2 P, H]

    def tree_flatten(self):
        return (self.attn, self.lanes, self.pages), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def dtype(self):
        return self.attn.dtype


# ---------------------------------------------------------------------------
# gated delta-rule layers (qwen3_next): an accumulated state beside the pages
#
# A linear layer keeps, a value head, a matrix ``S [dk, dv]`` in float32 that
# every token decays and updates, behind a convolution of 4 taps whose three
# predecessor rows a sequence carries too.  What a sequence carries past a
# position is as large as the state itself (2 MB a layer at Qwen3-Next's
# widths where a page of keys and values is 32 KB), so a snapshot cannot ride
# every page as ``ConvKV.pages`` does.  The pair pool ``attn`` holds the
# attention layers alone, and beside it ride
#
#     lanes     [Ll, B, Hv, dk, dv] f32   the lane is the slot: the state
#                                         after the last row lane ``b``'s
#                                         sequence computed
#     conv      [Ll, 3 B, C]              the last three rows of ``[q|k|v]``
#                                         before their convolution (rows
#                                         ``3 b .. 3 b + 2``, oldest first)
#     slots     [Ll, S, Hv, dk, dv] f32   a pool of ``S`` snapshots, far fewer
#     slot_conv [Ll, 3 S, C]              than pages, handed out by the
#                                         scheduler (``StateSlots``)
#     plan      [3, B] i32                what the next packed step does with
#                                         the slots, a lane: the slot it
#                                         restores from, the slot it writes a
#                                         snapshot to (-1: none), and the
#                                         position the snapshot is taken at
#                                         (the state after the row before it)
#
# Where a segment's state comes from: zeros at position 0; the slot ``plan``
# names where the admission resumed from a snapshot; the lane's own
# otherwise.  The engine writes ``plan`` before a packed dispatch
# (``with_plan``); the decode steps read none of it and take no snapshot.
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclass
class DeltaKV(_PoolTree):
    attn: Any  # [La, 2, P, page, Hkv, D]
    lanes: Any  # [Ll, B, Hv, dk, dv] f32
    conv: Any  # [Ll, 3 B, C]
    slots: Any  # [Ll, S, Hv, dk, dv] f32
    slot_conv: Any  # [Ll, 3 S, C]
    plan: Any  # [3, B] i32

    def tree_flatten(self):
        return (
            self.attn, self.lanes, self.conv, self.slots, self.slot_conv,
            self.plan,
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def dtype(self):
        return self.attn.dtype

    def with_attn(self, pool) -> "DeltaKV":
        return DeltaKV(pool, *self.tree_flatten()[0][1:])

    def with_plan(self, plan) -> "DeltaKV":
        return DeltaKV(*self.tree_flatten()[0][:-1], plan)


class StateSlots:
    """The host's table of the snapshot pool: block hash -> slot, least
    recently used out first (a restore is a use), a slot held while a lane
    waits to restore from it.  One slot past the pool is the step's trash."""

    def __init__(self, num_slots: int) -> None:
        self.num_slots = num_slots
        self._free = list(range(num_slots - 1, -1, -1))
        self._slot_of: "OrderedDict[int, int]" = OrderedDict()  # LRU first
        self._held: Dict[int, int] = {}  # hash -> lanes waiting to restore
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._slot_of)

    def __contains__(self, block_hash: int) -> bool:
        return block_hash in self._slot_of

    def hold(self, block_hash: int) -> int:
        """The slot of a snapshot a lane will restore from: used now, and
        not handed out again before ``release``."""
        self._slot_of.move_to_end(block_hash)
        self._held[block_hash] = self._held.get(block_hash, 0) + 1
        return self._slot_of[block_hash]

    def release(self, block_hash: int) -> None:
        n = self._held.get(block_hash, 0) - 1
        if n > 0:
            self._held[block_hash] = n
        else:
            self._held.pop(block_hash, None)

    def slot_of(self, block_hash: int) -> int:
        return self._slot_of[block_hash]

    def take(self, block_hash: int) -> Optional[int]:
        """A slot for a new snapshot of ``block_hash``: a free one, else the
        least recently used that no lane waits on.  None where the hash has
        a snapshot already (it is used now) or every slot is waited on."""
        if block_hash in self._slot_of:
            self._slot_of.move_to_end(block_hash)
            return None
        if self._free:
            slot = self._free.pop()
        else:
            victim = next(
                (h for h in self._slot_of if h not in self._held), None)
            if victim is None:
                return None
            slot = self._slot_of.pop(victim)
            self.evictions += 1
        self._slot_of[block_hash] = slot
        return slot

    def drop(self, block_hash: int) -> None:
        """A slot dies with its block (the registry's ``on_evict``)."""
        if block_hash in self._slot_of and block_hash not in self._held:
            self._free.append(self._slot_of.pop(block_hash))


# ---------------------------------------------------------------------------
# what a kind of cache cannot do
#
# Everything that moves, rewinds or reshapes KV outside one chip's packed
# step was written for one pair pool with one page table a lane and nothing
# beside a sequence's pages.  A kind of cache that is not that refuses such
# a capability with one sentence: ``{what} is not supported over {the
# kind}: {why}``, or the capability's own where it has one to give.  The
# engine asks at configuration and at a request (``JaxEngine._refuse``), the
# pool when it is built, the step functions when they are traced.
# ---------------------------------------------------------------------------

# (kind, the ModelConfig property that tells it, what a sentence calls it,
# why), in the order a trunk of several kinds answers
_KINDS = (
    ("two_kind", "two_kind",
     "a two-kind cache (window and full layers, layer_types)",
     "its pages live in two pools with two page tables a lane, and the "
     "transfer formats and meshes carry one"),
    ("conv", "has_conv",
     "a trunk with convolution layers (layer_types 'conv')",
     "a sequence carries two rows a layer beside its pages, which only the "
     "packed step and the fused decode steps carry, snapshot and restore"),
    ("linear", "has_linear",
     "a trunk with gated delta-rule layers (layer kind 'linear')",
     "a sequence carries a matrix a value head and three rows a layer "
     "beside its pages, which only the packed step and the fused decode "
     "steps carry, snapshot and restore"),
    ("latent", "is_mla",
     "a latent cache (MLA)",
     "the transfer formats carry K/V pairs per head and per layer"),
)
_PAIR_ONLY = {"two_kind": None, "conv": None, "linear": None}
_ONE_POOL = {**_PAIR_ONLY, "latent": None}
_STATELESS = {"conv": None, "linear": None}

# capability -> (what the sentence calls it, {kind that refuses it: None
# for the kind's own reason, or the whole sentence})
KV_REFUSALS: Dict[str, Tuple[str, Dict[str, Optional[str]]]] = {
    "mesh": ("a serving mesh (tp, dp, sp or pp)", _PAIR_ONLY),
    "sp_pp": ("sp/pp meshes", {
        "latent": "sp/pp meshes are not supported over a latent cache "
        "(MLA): their prefill routes and stage pools assume K/V pairs per "
        "head and per layer"}),
    "offload": ("host/disk KV offload and swap preemption", {
        **_PAIR_ONLY,
        "latent": "host/disk KV offload is not supported over a latent "
        "cache (MLA): the tiers move [L, 2, pages, page, Hkv, D] blocks, "
        "and a latent pool holds two layers' rows a slab"}),
    "remote_tier": ("the remote KV tier (G4)", {
        **_PAIR_ONLY,
        "latent": "the remote KV tier (G4) is not supported over a latent "
        "cache (MLA): it ships the offload tiers' K/V blocks"}),
    "int8_pool": ("an int8 pool", {
        **_PAIR_ONLY,
        "latent": "kv_dtype int8 is not supported over a latent cache "
        "(MLA): one scale a row would span c_kv and the rotated key, whose "
        "ranges differ"}),
    "sharded_pool": ("a sharded pool", _PAIR_ONLY),
    "window_layers": ("a trunk of window and full layers", _STATELESS),
    "disagg_serving": (
        "disaggregated serving (a remote prefill's KV)", _ONE_POOL),
    "kv_delivery": ("a remote prefill's KV delivery", _ONE_POOL),
    "prefill_export": ("a disaggregated prefill export", _ONE_POOL),
    "block_export": ("a KV block export", _ONE_POOL),
    "unmixed": ("serving without mixed batching", _PAIR_ONLY),
    "classic_dispatch": (
        "a request with sampling penalties, a soft prompt or speculation "
        "(the classic prefill and verify dispatches)", _PAIR_ONLY),
    "scoring": (
        "a request for the prompt's log-probabilities (the scoring step)",
        _STATELESS),
    "embedding": ("pooled embeddings (the embedding step)", _STATELESS),
    "unmasked_decode_step": (
        "a decode step that is not told which lanes it advances",
        _STATELESS),
    "classic_step": (
        "a step outside the packed step and the decode steps (classic "
        "prefill, verify, scoring, embedding)", _STATELESS),
}


def kv_refusal(cfg: ModelConfig, capability: str) -> Optional[str]:
    """The sentence with which ``cfg``'s kind of cache refuses
    ``capability`` (a key of ``KV_REFUSALS``), or None where it serves it."""
    what, kinds = KV_REFUSALS[capability]
    for kind, told_by, over, why in _KINDS:
        if kind in kinds and getattr(cfg, told_by):
            return kinds[kind] or f"{what} is not supported over {over}: {why}"
    return None


def refuse(cfg: ModelConfig, capability: str) -> None:
    sentence = kv_refusal(cfg, capability)
    if sentence is not None:
        raise ValueError(sentence)


def kv_data(kv_pages):
    """The dense data array of any pool form (shape/dtype queries, Pallas
    operand plumbing).  A two-kind cache answers with its full pool: the
    two differ in layers and pages only.  A trunk with convolution layers
    answers with its attention layers' pool."""
    if isinstance(kv_pages, (ConvKV, DeltaKV)):
        return kv_pages.attn
    if isinstance(kv_pages, KindKV):
        return kv_pages.full
    if isinstance(kv_pages, QuantKV):
        return kv_pages.q
    return kv_pages.data if isinstance(kv_pages, LatentKV) else kv_pages


def kv_num_layers(kv_pages) -> int:
    """Layers a pool holds (a latent pool: two a slab)."""
    n = kv_data(kv_pages).shape[0]
    return 2 * n if isinstance(kv_pages, LatentKV) else n


def kv_is_quantized(kv_pages) -> bool:
    return isinstance(kv_pages, QuantKV)


def kv_is_latent(kv_pages) -> bool:
    """A latent pool (MLA): one row a token that is key and value at once.
    Told by its type, at trace time."""
    return isinstance(kv_pages, (LatentKV, LatentLayer))


def index_kv_layer(kv_pages, layer):
    """``dynamic_index_in_dim(pool, layer, 0)`` for any pool form."""
    if isinstance(kv_pages, LatentKV):
        return kv_pages.layer_view(layer)
    if isinstance(kv_pages, QuantKV):
        return QuantKV(
            q=jax.lax.dynamic_index_in_dim(
                kv_pages.q, layer, 0, keepdims=False
            ),
            s=jax.lax.dynamic_index_in_dim(
                kv_pages.s, layer, 0, keepdims=False
            ),
        )
    return jax.lax.dynamic_index_in_dim(kv_pages, layer, 0, keepdims=False)


def gather_layer_kv(layer_kv, kv_idx, page_table, out_dtype):
    """Gather one side (k=0 / v=1) of a layer's pages: ``[B, P, page,
    Hkv, D]`` in ``out_dtype``, dequantized when the pool is int8.  The
    dequant runs on the GATHERED pages (a few MB), never the pool.  A
    latent pool's one side is both."""
    if isinstance(layer_kv, LatentLayer):
        return layer_kv.gather(page_table, out_dtype)
    if isinstance(layer_kv, QuantKV):
        pages = layer_kv.q[kv_idx][page_table]  # [B, P, page, Hkv, D] int8
        scales = layer_kv.s[kv_idx][page_table]  # [B, P, page]
        return (
            pages.astype(jnp.float32) * scales[..., None, None]
        ).astype(out_dtype)
    return layer_kv[kv_idx][page_table].astype(out_dtype)


def parse_kv_dtype(spec: Optional[str]) -> Optional[str]:
    """Normalize a ``--kv-dtype`` / ``DYN_KV_DTYPE`` value: ``int8`` is
    the quantized layout, ``bf16``/``bfloat16``/``f32``/``float32`` pass
    through as plain pool dtypes, empty/None defers to the model dtype."""
    if spec is None:
        return None
    s = str(spec).strip().lower()
    if not s or s in ("auto", "default", "model"):
        return None
    aliases = {
        "bf16": "bfloat16",
        "f32": "float32",
        "fp32": "float32",
        "f16": "float16",
        "fp16": "float16",
    }
    s = aliases.get(s, s)
    if s not in ("int8", "bfloat16", "float32", "float16"):
        raise ValueError(f"unsupported kv dtype {spec!r}")
    return s


def quantize_kv_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-row int8 over the trailing (heads, head_dim) axes.

    ``x`` is ``[..., Hkv, D]``; returns ``(q int8 [..., Hkv, D],
    s f32 [...])``.  The ONE quantization rule shared by the jitted write
    paths (engine/attention.py) and the host-side blob conversion below,
    so device-quantized and host-quantized bytes can never disagree."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(
        jnp.round(xf / s[..., None, None]), -127, 127
    ).astype(jnp.int8)
    return q, s


def quantize_kv_blob(blob: Any) -> QuantKV:
    """Host-side blob conversion (cross-dtype delivery into an int8 pool):
    a dense ``[L, 2, n, page, Hkv, D]`` array becomes a :class:`QuantKV`
    pair under the same per-row rule as the device writes."""
    arr = np.asarray(blob, np.float32)
    amax = np.max(np.abs(arr), axis=(-2, -1))
    s = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(
        np.rint(arr / s[..., None, None]), -127, 127
    ).astype(np.int8)
    return QuantKV(q=q, s=s)


def dequantize_kv_blob(blob: QuantKV, dtype: Any = np.float32) -> Any:
    """The inverse direction (int8 blob delivered into a full-width pool)."""
    q, s = blob.q, blob.s
    if isinstance(q, jax.Array):
        return (q.astype(jnp.float32) * s[..., None, None]).astype(
            jnp.dtype(dtype)
        )
    return (
        np.asarray(q, np.float32) * np.asarray(s, np.float32)[..., None, None]
    ).astype(dtype)


# ---------------------------------------------------------------------------
# the blob: a block of a pair pool outside it
#
# A pytree of arrays that share their first four axes ``[layers, 2, pages,
# page]``: a dense pool's blob is its one array ``[.., Hkv, D]``, an int8
# pool's the ``QuantKV`` of that array in int8 and its row scales.  Every
# function here is written once over the leaves.  The stored and the wire
# forms, which a block written by any commit must keep loading from:
#
#   arrays  ``{"blob": data[, "blob_scales": scales]}``: the disk tier's
#           ``.npz`` keys and the host tier's rings
#   bytes   each leaf's C-order bytes in that order (int8 data, then f32
#           scales).  A frame carries the data's ``(shape, dtype)``; dtype
#           ``int8`` means the pair, and both extents follow from the shape.
#           The G4 frame says it once more as kind ``quant`` | ``dense``
# ---------------------------------------------------------------------------

LAYER_AXIS = 0
PAGE_AXIS = 2
_SHARED_AXES = 4  # [layers, 2, pages, page]
_ARRAY_NAMES = ("blob", "blob_scales")  # in leaf order


def _at_pages(key) -> tuple:
    return (slice(None),) * PAGE_AXIS + (key,)


def as_device_blob(blob: Any) -> Any:
    """``jnp.asarray`` of every leaf (scatter-site upload)."""
    return _map(jnp.asarray, blob)


def blob_to_host(blob: Any) -> Any:
    """``np.asarray`` of every leaf (tier materialize)."""
    return _map(np.asarray, blob)


def assemble_blob(blob: Any) -> Any:
    """Materialize a slice of the pool on host, each leaf reassembled from
    its per-shard head slices where the pool is sharded
    (``parallel.sharding.assemble_shards``; a plain ``device_get``
    otherwise): the wire and offload forms are full-width whatever the
    serving mesh."""
    from ..parallel.sharding import assemble_shards

    return _map(assemble_shards, blob)


def _concat_blobs(blobs: List[Any], axis: int) -> Any:
    return _map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis), *blobs
    )


def concat_blob_pages(blobs: List[Any]) -> Any:
    """Host blobs end to end on the page axis (an admission's tier hits)."""
    return _concat_blobs(blobs, PAGE_AXIS)


def concat_blob_layers(blobs: List[Any]) -> Any:
    """Host blobs of consecutive layer spans, stacked to the whole."""
    return _concat_blobs(blobs, LAYER_AXIS)


def blob_layers(blob: Any, lo: int, hi: int) -> Any:
    """Layers ``[lo, hi)`` of a blob (a view)."""
    return _map(lambda a: a[lo:hi], blob)


def blob_pages(blob: Any, lo: int, hi: int) -> Any:
    """Pages ``[lo, hi)`` of a blob (a view): one request's of a group's."""
    return _map(lambda a: a[_at_pages(slice(lo, hi))], blob)


def blob_num_pages(shape) -> int:
    return int(shape[PAGE_AXIS])


def blob_num_layers(shape) -> int:
    return int(shape[LAYER_AXIS])


def blob_tokens(shape) -> int:
    """Token rows a blob of ``shape`` holds (pages x page)."""
    return int(shape[PAGE_AXIS]) * int(shape[PAGE_AXIS + 1])


def blob_shape(
    shape, num_pages: Optional[int] = None, num_layers: Optional[int] = None
) -> Tuple[int, ...]:
    """``shape`` (a pool's, a blob's) at ``num_pages`` pages and
    ``num_layers`` layers, each as it is by default."""
    shape = list(int(x) for x in shape)
    if num_pages is not None:
        shape[PAGE_AXIS] = int(num_pages)
    if num_layers is not None:
        shape[LAYER_AXIS] = int(num_layers)
    return tuple(shape)


def coerce_kv_blob(blob: Any, pool_quantized: bool, compute_dtype) -> Any:
    """Bring a delivered blob into the receiving pool's dtype domain.

    Same-domain blobs pass through untouched (byte-exact round trip);
    cross-geometry deliveries -- a bf16 exporter feeding an int8 pool, or
    an int8 tier blob restoring into a full-width pool -- convert through
    the shared quantization rule, so delivery stays exact up to the int8
    rounding the pool itself applies."""
    is_quant = isinstance(blob, QuantKV)
    if pool_quantized and not is_quant:
        return quantize_kv_blob(blob)
    if not pool_quantized and is_quant:
        return dequantize_kv_blob(blob, compute_dtype)
    return blob


def pad_page_axis(blob, bucket: int):
    """Pad a blob with zero pages up to ``bucket`` pages -- the shared
    shape-normalization for every bucketed page scatter (external KV
    delivery, chunked delivery, tier onboard, swap-in restore).  Pad
    entries target trash page 0 with zero content (a zero scale row decodes
    to zero), so one executable per page bucket serves every blob size.
    Device-resident leaves pad on device (``np.pad`` would silently pull
    them to host and re-upload)."""

    def pad_leaf(a):
        n = a.shape[PAGE_AXIS]
        if bucket <= n:
            return a
        pad = [(0, 0)] * a.ndim
        pad[PAGE_AXIS] = (0, bucket - n)
        return jnp.pad(a, pad) if isinstance(a, jax.Array) else np.pad(a, pad)

    return _map(pad_leaf, blob)


# -- the stored form ---------------------------------------------------------


def blob_to_arrays(blob: Any) -> Dict[str, Any]:
    """The blob's leaves under their stored names."""
    return dict(zip(_ARRAY_NAMES, _leaves(blob)))


def blob_from_arrays(arrays) -> Any:
    """Inverse of :func:`blob_to_arrays` over any mapping that holds the
    names (other keys, a block's meta beside them, are not read)."""
    leaves = [arrays[name] for name in _ARRAY_NAMES if name in arrays]
    return QuantKV(*leaves) if len(leaves) > 1 else leaves[0]


# -- the wire form -----------------------------------------------------------


def _wire_leaves(shape, dtype) -> List[Tuple[Tuple[int, ...], Any]]:
    """``(shape, dtype)`` of each leaf of the blob a frame describes."""
    shape = tuple(int(x) for x in shape)
    if blob_kind(dtype) == "quant":
        return [
            (shape, jnp.dtype(jnp.int8)),
            (shape[:_SHARED_AXES], jnp.dtype(jnp.float32)),
        ]
    return [(shape, jnp.dtype(dtype))]


def blob_kind(dtype) -> str:
    """What a G4 frame calls a blob of this dtype."""
    return "quant" if jnp.dtype(dtype) == jnp.int8 else "dense"


def blob_nbytes(shape, dtype) -> int:
    """Wire size of the blob a frame describes."""
    return sum(
        int(np.prod(s)) * d.itemsize for s, d in _wire_leaves(shape, dtype)
    )


def blob_byte_views(blob: Any) -> List[np.ndarray]:
    """The wire bytes as flat uint8 views, one a leaf: a sender chunks them
    in order and no buffer of the whole ever materializes.  A leaf that is
    not C-contiguous (a request's pages of a group's transfer) is copied
    once."""
    return [
        np.ascontiguousarray(a).reshape(-1).view(np.uint8)
        for a in _leaves(blob)
    ]


def blob_to_bytes(blob: Any) -> bytes:
    return b"".join(blob_byte_views(blob))


def blob_from_bytes(buf, shape, dtype) -> Any:
    """Inverse of :func:`blob_to_bytes` for the blob a frame describes.

    ``buf`` is anything exposing the buffer protocol (bytes, a uint8
    ndarray, a memoryview) -- the returned leaves ALIAS it, so a
    staging-buffer caller gets a zero-copy unpack (the refcount keeps the
    backing buffer alive)."""
    leaves, off = [], 0
    for s, d in _wire_leaves(shape, dtype):
        n = int(np.prod(s))
        leaves.append(np.frombuffer(buf, d, count=n, offset=off).reshape(s))
        off += n * d.itemsize
    return QuantKV(*leaves) if len(leaves) > 1 else leaves[0]


# -- the jitted page movers --------------------------------------------------
#
# ``ids`` are page ids; pad entries of a bucketed scatter target trash page
# 0.  The layer-range pair take the layer ids as an ARRAY (one executable per
# (group size, page count), not one per layer range) and three adjacent
# advanced indices, which keep a chunk in blob layout.  The serving mesh
# re-jits the raw bodies with the pool's shardings pinned
# (parallel/sharding.make_sharded_steps).


def _slice_block_pages(kv_pages, ids: jax.Array):
    """Read a block's pages (pre-eviction snapshot for G1 -> G2 demotion).
    Dispatched before the free-list reuses the pages, so device program
    order guarantees it reads the pre-reuse contents."""
    return _map(lambda a: a[_at_pages(ids)], kv_pages)


slice_block_pages = jax.jit(_slice_block_pages)


def _scatter_block_pages(kv_pages, ids: jax.Array, blob):
    """Write an offloaded block's contents back into fresh pages (G2/G3 ->
    G1 onboarding).  Donated so the cache updates in place."""
    return _map(
        lambda a, b: a.at[_at_pages(ids)].set(b.astype(a.dtype)),
        kv_pages, blob,
    )


scatter_block_pages = partial(jax.jit, donate_argnames=("kv_pages",))(
    _scatter_block_pages
)


def _layer_page_index(layer_ids: jax.Array, page_ids: jax.Array) -> tuple:
    return (
        layer_ids[:, None, None],
        jnp.arange(2)[None, :, None],
        page_ids[None, None, :],
    )


def _gather_layer_pages(kv_pages, layer_ids: jax.Array, page_ids: jax.Array):
    """Slice one layer-group chunk out of the pool: a device-resident copy,
    so the scratch pages can be freed as soon as the gather is dispatched
    (device program order, as in ``_slice_block_pages``)."""
    at = _layer_page_index(layer_ids, page_ids)
    return _map(lambda a: a[at], kv_pages)


gather_layer_pages = jax.jit(_gather_layer_pages)


def _scatter_layer_pages(
    kv_pages, layer_ids: jax.Array, page_ids: jax.Array, blob
):
    """Write one layer-group chunk into its reserved pages (the incremental
    decode-side onboard; donated so the pool updates in place)."""
    at = _layer_page_index(layer_ids, page_ids)
    return _map(lambda a, b: a.at[at].set(b.astype(a.dtype)), kv_pages, blob)


scatter_layer_pages = partial(jax.jit, donate_argnames=("kv_pages",))(
    _scatter_layer_pages
)


def place_pool(pages: Any, sharding) -> Any:
    """``device_put`` a pool: the leaves with a head axis take ``sharding``
    (kv heads over tp); an int8 pool's row scales have none and replicate --
    they are 4/(Hkv*D) of the data, so replication costs ~nothing."""
    mesh = getattr(sharding, "mesh", None)

    def place(a):
        if a.ndim > _SHARED_AXES:
            return jax.device_put(a, sharding)
        if mesh is None:
            return a
        return jax.device_put(
            a, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        )

    return _map(place, pages)


class PageAllocator:
    """LIFO free-list over page ids 1..num_pages-1 (0 is the trash page).

    alloc/free are locked: the scheduler allocates on the tick-loop thread
    while ``JaxEngine._prefill_export`` (the disagg prefill-worker path)
    allocates scratch pages on the engine executor thread."""

    def __init__(self, num_pages: int) -> None:
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._lock = threading.Lock()

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def alloc(self, n: int) -> List[int]:
        if n <= 0:
            return []
        with self._lock:
            if n > len(self._free):
                raise OutOfPages(f"requested {n} pages, {len(self._free)} free")
            out = self._free[-n:][::-1]
            del self._free[len(self._free) - n :]
            return out

    def free(self, pages: List[int]) -> None:
        with self._lock:
            self._free.extend(pages)


class PagedKVCache:
    """Owns the device KV array and its allocator."""

    def __init__(
        self,
        cfg: ModelConfig,
        num_pages: int,
        page_size: int = 16,
        dtype: Any = None,
        sharding: Optional[jax.sharding.Sharding] = None,
        allocator: Optional[Any] = None,
        num_window_pages: int = 0,
        window_allocator: Optional[Any] = None,
        max_lanes: int = 0,
        state_slots: int = 0,
    ) -> None:
        self.cfg = cfg
        self.num_pages = num_pages
        self.page_size = page_size
        # a two-kind cache (KindKV): ``num_pages``/``allocator`` are the full
        # layers' pool, these the window layers'
        self.num_window_pages = num_window_pages if cfg.two_kind else 0
        self.window_allocator = None
        # "int8" selects the quantized layout (see module section comment);
        # anything else is a plain dense pool of that dtype
        self.quantized = dtype is not None and (
            (isinstance(dtype, str) and dtype.strip().lower() == "int8")
            or (not isinstance(dtype, str) and jnp.dtype(dtype) == jnp.int8)
        )
        self.dtype = (
            jnp.dtype(jnp.int8)
            if self.quantized
            else jnp.dtype(dtype or cfg.dtype)
        )
        # default is the plain free list; the engine passes a PagePool
        # (block_manager) to get the sequence-hash reuse registry
        self.allocator = allocator if allocator is not None else PageAllocator(num_pages)
        slabs, sides, heads, width = cfg.kv_geometry
        shape = (slabs, sides, num_pages, page_size, heads, width)
        for asked, capability in (
            (self.quantized, "int8_pool"),
            (sharding is not None, "sharded_pool"),
            (cfg.two_kind, "window_layers"),
        ):
            if asked:
                refuse(cfg, capability)
        if cfg.two_kind:
            if self.num_window_pages < 2:
                raise ValueError(
                    "a trunk of window and full layers needs num_window_pages"
                    " (the window layers' pool) beside num_pages"
                )
            self.window_allocator = (
                window_allocator if window_allocator is not None
                else PageAllocator(self.num_window_pages)
            )
            self.pages = KindKV(
                jnp.zeros((cfg.kind_layers("full"), *shape[1:]), self.dtype),
                jnp.zeros(
                    (cfg.kind_layers("sliding"), sides, self.num_window_pages,
                     page_size, heads, width),
                    self.dtype,
                ),
            )
        elif self.quantized:
            self.pages: Any = QuantKV(
                q=jnp.zeros(shape, jnp.int8),
                s=jnp.zeros(shape[:_SHARED_AXES], jnp.float32),
            )
        else:
            arr = jnp.zeros(shape, self.dtype)
            self.pages = LatentKV(arr, cfg.kv_lora_rank) if cfg.is_mla else arr
        if sharding is not None:
            self.pages = place_pool(self.pages, sharding)
        if cfg.has_conv:
            self.pages = ConvKV(self.pages, *self._conv_state(max_lanes))
        if cfg.has_linear:
            self.pages = DeltaKV(
                self.pages, *self._delta_state(max_lanes, state_slots))

    def _delta_state(self, max_lanes: int, slots: int):
        """The zeroed state of the delta-rule layers: lanes and slots."""
        if max_lanes < 1 or slots < 1:
            raise ValueError(
                "a trunk with gated delta-rule layers needs max_lanes (the "
                "engine's max_batch_size: the lane is the state's slot) and "
                "state_slots (the snapshots a prefix hit can resume from)"
            )
        c = self.cfg
        Ll, C = c.kind_layers("linear"), c.linear_conv_width
        mat = (c.linear_num_value_heads, c.linear_key_head_dim,
               c.linear_value_head_dim)
        return (
            jnp.zeros((Ll, max_lanes, *mat), jnp.float32),
            jnp.zeros((Ll, 3 * max_lanes, C), self.dtype),
            jnp.zeros((Ll, slots, *mat), jnp.float32),
            jnp.zeros((Ll, 3 * slots, C), self.dtype),
            jnp.full((3, max_lanes), -1, jnp.int32),
        )

    def _conv_state(self, max_lanes: int):
        """The zeroed state of the convolution layers: ``(lanes, pages)``."""
        if max_lanes < 1:
            raise ValueError(
                "a trunk with convolution layers needs max_lanes (the "
                "engine's max_batch_size): the lane is the state's slot"
            )
        Lc, H = self.cfg.kind_layers("conv"), self.cfg.hidden_size
        return (
            jnp.zeros((Lc, 2 * max_lanes, H), self.dtype),
            jnp.zeros((Lc, 2 * self.num_pages, H), self.dtype),
        )

    @property
    def state_bytes(self) -> dict:
        """Bytes of the state layers' state by part (empty without)."""
        if isinstance(self.pages, DeltaKV):
            p = self.pages
            return {
                "lanes": int(p.lanes.nbytes) + int(p.conv.nbytes),
                "slots": int(p.slots.nbytes) + int(p.slot_conv.nbytes),
            }
        if not isinstance(self.pages, ConvKV):
            return {}
        return {
            "lanes": int(self.pages.lanes.nbytes),
            "pages": int(self.pages.pages.nbytes),
        }

    @property
    def bytes_per_page(self) -> int:
        """HBM bytes per pool page -- dtype-true, so the bench's
        ``est_hbm_util`` and ``kv_pool_gb`` lines report the actual
        footprint.  Quantized pages count their scale rows too."""
        c = self.cfg
        data = c.kv_values_per_token * self.page_size * self.dtype.itemsize
        if self.quantized:
            data += c.num_layers * 2 * self.page_size * 4  # f32 row scales
        return data

    @property
    def bytes_per_token(self) -> float:
        """Pool bytes over pool tokens (``dynamo_engine_kv_bytes_per_token``).
        A two-kind cache: the bytes of both pools over the tokens the full
        pool holds, which are the tokens of context it can keep."""
        if self.cfg.two_kind:
            return self.pool_bytes / (self.num_pages * self.page_size)
        return self.bytes_per_page / self.page_size

    def kind_bytes_per_page(self, kind: str) -> int:
        """Bytes of one page of the ``full`` or the ``window`` pool."""
        _, sides, heads, width = self.cfg.kv_geometry
        return (
            self.cfg.kind_layers(kind) * sides * heads * width
            * self.page_size * self.dtype.itemsize
        )

    @property
    def pool_bytes(self) -> int:
        """Total pool footprint (every page, trash page included)."""
        if self.cfg.two_kind:
            return (
                self.kind_bytes_per_page("full") * self.num_pages
                + self.kind_bytes_per_page("sliding") * self.num_window_pages
            )
        return self.bytes_per_page * self.num_pages

    def pages_for_tokens(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    @property
    def usage(self) -> float:
        total = self.num_pages - 1
        return self.allocator.used_pages / total if total else 0.0

    @property
    def shard_geometry(self):
        """``{"axis": i, "parts": n}`` when the pool is sharded (tp: kv
        heads on axis 4), else None.  Every KV blob leaving the device
        (disagg export, offload tiers, swap snapshots) records this so
        restore sites can assert pool compatibility."""
        from ..parallel.sharding import kv_shard_geometry

        return kv_shard_geometry(kv_data(self.pages))

    def read_pages(self, ids) -> Any:
        """The blob of pages ``ids``, read outside jit (the export paths:
        a copy on the device, placed like the pool)."""
        return _slice_block_pages(self.pages, ids)


def layer_chunk_spans(
    num_layers: int,
    layers_per_chunk: Optional[int] = None,
    target_chunks: int = 8,
) -> List[tuple]:
    """Split the layer stack into contiguous [lo, hi) spans -- the chunk
    granularity of the pipelined KV export (engine.prefill_export_batch_stream)
    and the unit the decode side scatters incrementally.  ``layers_per_chunk``
    pins the group size; None aims for ``target_chunks`` groups.  Lives with
    the cache geometry so export and onboard can never disagree on what one
    chunk spans."""
    if num_layers <= 0:
        raise ValueError(f"num_layers must be positive, got {num_layers}")
    if layers_per_chunk is not None and layers_per_chunk <= 0:
        # fail at configuration time: a negative value would yield zero
        # spans (every export delivering 0 of L layers), and 0 would
        # silently mean "default"
        raise ValueError(
            f"layers_per_chunk must be positive, got {layers_per_chunk}"
        )
    g = layers_per_chunk or max(1, -(-num_layers // target_chunks))
    return [
        (lo, min(lo + g, num_layers)) for lo in range(0, num_layers, g)
    ]


def choose_num_pages(
    cfg: ModelConfig,
    page_size: int,
    hbm_bytes: int,
    param_bytes: int,
    mem_fraction: float = 0.9,
    kv_dtype_size: int = 2,
) -> int:
    """Size the G1 pool from available HBM after weights (reference vLLM-style
    gpu_memory_utilization accounting)."""
    per_page = cfg.kv_values_per_token * page_size * kv_dtype_size
    budget = int(hbm_bytes * mem_fraction) - param_bytes
    return max(2, budget // per_page)
