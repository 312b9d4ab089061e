"""Grouped matrix product for the dropless expert MLP.

``grouped_matmul(lhs [M, K], rhs [E, K, N], group_sizes [E])`` multiplies
the rows of group ``g`` -- ``lhs[off[g]:off[g+1]]``, ``off`` the running
sum of ``group_sizes`` -- by ``rhs[g]``: each routed row meets its own
expert's matrix and no other.  Rows past the last group are not computed:
what comes back there is unspecified (the kernel leaves the buffer as it
found it, ``ragged_dot`` writes zeros), and a caller that sorts rows behind
the groups masks them.  f32 accumulation, result in ``lhs.dtype``.

On the chip it is one Pallas kernel, ``moe_grouped_matmul``.  The rows are
cut into aligned tiles of ``tm``; a *visit* is one (group, row tile) pair
with at least one row of the group in the tile, so a tile that holds a
group boundary is visited once per group and each visit stores only its
group's rows (the scheme of ``jax.experimental.pallas.ops.tpu.megablox``).
The grid is (column tiles of ``N``, visits) and a weight block spans all of
``K``: consecutive visits of one group use one weight block, copied in
once, so every weight byte of a group that has rows is read once per call,
and a group with no row is not read at all.  The kernel copies the weight
blocks itself, two buffers in turn: a group's first visit starts the next
group's copy, which then has all of this group's visits to arrive in (the
pipeline's own double buffering would start it at the last visit only, and
one visit of 128 rows is half the time a 16 MB block takes: 2.6 ms a
product against 2.2 ms at 2048 rows, PERF.md section 6, PR 27).  The row
tile is what is read again, once per column tile, which is why ``tn`` is as
wide as VMEM allows.
Tiles past the last group are never visited (the visit axis has a dynamic
bound): rows sorted behind the groups cost nothing.

Off the chip (tier-1 runs on the CPU) the same function is
``jax.lax.ragged_dot``; ``interpret=True`` runs the kernel itself through
the Pallas interpreter, which is how the tests hold the two to each other.
On the chip ``ragged_dot`` is not used: its default lowering may expand
to one dense product per group.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ragged_attention import _tile_bytes, _vmem_limit

KERNEL_NAME = "moe_grouped_matmul"

# Rows of a tile.  A visit multiplies all tm rows whatever share of them is
# the group's, so a tile wider than a group's rows wastes the MXU (eight
# experts over 2048 routed rows leave about 256 a group) and one narrower
# than the MXU's 128 rows starves it.
_ROW_TILE = 128

# VMEM the double-buffered weight block [K, tn] may take; the rest of the
# footprint (row tile, result tile, the f32 product) is small beside it.
_WEIGHT_BLOCK_BYTES = 36 << 20


def _col_tile(K: int, N: int, itemsize: int) -> int:
    """The widest column tile, a multiple of 128 that divides ``N``, whose
    weight block fits the budget twice over (the pipeline holds two)."""
    best = 128
    for tn in range(128, N + 1, 128):
        if N % tn == 0 and 2 * K * tn * itemsize <= _WEIGHT_BLOCK_BYTES:
            best = tn
    return best


def kernel_fits(K: int, N: int) -> bool:
    """Whether the chip kernel takes a product of these widths: both tile
    to 128 lanes.  (A [K, 128] block of any width this repo serves fits.)"""
    return K % 128 == 0 and N % 128 == 0


def group_visits(
    group_sizes: jax.Array, tiles_m: int, tm: int
) -> Tuple[jax.Array, ...]:
    """The kernel's scalar operands.  By group ([E]; the groups lie end to
    end): its first row and the row after its last.  By visit, in arrays of
    the static length ``tiles_m + E - 1`` (the most visits there can be;
    entries past the number of visits are never run): its group, its row
    tile, whether it is its group's first visit, its group's rank among the
    groups that have rows, and the next such group (after the last, the
    first again).  Then the number of groups that have rows ([1]) and the
    number of visits."""
    E = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tile0 = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - tile0 + 1, 0)
    stop = jnp.cumsum(tiles)  # visits up to and with each group
    visits = stop[-1]
    v = jnp.arange(tiles_m + E - 1, dtype=jnp.int32)
    gid = jnp.minimum(jnp.searchsorted(stop, v, side="right"), E - 1)
    begin = (stop - tiles)[gid]  # the first visit of this visit's group
    mid = jnp.clip(tile0[gid] + v - begin, 0, tiles_m - 1)
    first = (v == begin) & (v < visits)
    nxt = gid[stop[gid] % jnp.maximum(visits, 1)]
    out = (starts, ends, gid, mid, first, jnp.cumsum(first) - 1, nxt,
           jnp.sum(group_sizes > 0)[None], visits)
    return tuple(a.astype(jnp.int32) for a in out)


def _kernel(
    starts_ref, ends_ref, gid_ref, mid_ref, first_ref, rank_ref, nxt_ref,
    groups_ref, layer_ref, lhs_ref, rhs_hbm, out_ref, wbuf, sem, *, tm, tn,
):
    n, v = pl.program_id(0), pl.program_id(1)
    g = gid_ref[v]
    # weight blocks are numbered in the order they are used; block b sits in
    # buffer b % 2.  A group's block is waited for at the group's first
    # visit, where the next block's copy is also started: it then has all of
    # this group's visits to arrive in, not only the last one
    block = n * groups_ref[0] + rank_ref[v]
    slot = block % 2

    def copy(group, col, slot):
        return pltpu.make_async_copy(
            rhs_hbm.at[layer_ref[0], group, :, pl.ds(col * tn, tn)],
            wbuf.at[slot], sem.at[slot],
        )

    @pl.when(first_ref[v] == 1)
    def _():
        @pl.when(block == 0)
        def _():
            copy(g, n, slot).start()

        wraps = rank_ref[v] == groups_ref[0] - 1
        col = jnp.where(wraps, n + 1, n)

        @pl.when(col < pl.num_programs(0))
        def _():
            copy(nxt_ref[v], col, 1 - slot).start()

        copy(g, n, slot).wait()

    acc = jnp.dot(lhs_ref[...], wbuf[slot], preferred_element_type=jnp.float32)
    rows = mid_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    mine = (rows >= starts_ref[g]) & (rows < ends_ref[g])
    # a tile on a group boundary is visited by each of its groups in turn:
    # keep what the earlier visit stored in the rows that are not this one's
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _grouped_matmul_pallas(
    lhs: jax.Array,
    rhs: jax.Array,  # [L, E, K, N]
    group_sizes: jax.Array,
    layer: jax.Array,  # scalar int32
    tm: int = _ROW_TILE,
    tn: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    M, K = lhs.shape
    _, E, _, N = rhs.shape
    tn = tn or _col_tile(K, N, rhs.dtype.itemsize)
    if M % tm or N % tn:
        raise ValueError(f"rows {M} and columns {N} must tile by ({tm}, {tn})")
    *visit, visits = group_visits(group_sizes, M // tm, tm)
    need = (
        2 * _tile_bytes((K, tn), rhs.dtype)
        + 2 * _tile_bytes((tm, K), lhs.dtype)
        + 2 * _tile_bytes((tm, tn), lhs.dtype)
        + 2 * _tile_bytes((tm, tn), jnp.float32)
    )
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    row_tile = lambda n, v, s, e, gid, mid, *_: (mid[v], 0)
    out_tile = lambda n, v, s, e, gid, mid, *_: (mid[v], n)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=9,
            grid=(N // tn, visits),
            in_specs=[
                pl.BlockSpec((tm, K), row_tile),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_tile),
            scratch_shapes=[
                pltpu.VMEM((2, K, tn), rhs.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(need),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=KERNEL_NAME,
    )(*visit, layer, lhs, rhs)


def grouped_matmul(
    lhs: jax.Array,  # [M, K] rows sorted by group
    rhs: jax.Array,  # [E, K, N], or the layers' stack [L, E, K, N]
    group_sizes: jax.Array,  # [E] int32, sum <= M
    layer: Optional[jax.Array] = None,  # scalar index into a stack
    *,
    kernel: bool,
    interpret: bool = False,
) -> jax.Array:
    """``kernel`` is the caller's trace-time choice of backend: the Pallas
    kernel (on a TPU, or through the interpreter with ``interpret``), or
    ``jax.lax.ragged_dot``.  The kernel wants ``M`` in whole row tiles
    (``_ROW_TILE``): the caller gathers its rows to that length.

    Inside a scan over layers pass the whole stack and the layer's index:
    the kernel copies its weight blocks straight out of the stack.  A
    custom call cannot fuse the slice XLA would take for it, so a sliced
    operand is a copy of all ``E`` matrices before every launch (measured:
    2.9 ms beside a 1.3 ms launch at Mixtral widths)."""
    K, N = lhs.shape[1], rhs.shape[-1]
    group_sizes = group_sizes.astype(jnp.int32)
    if rhs.ndim == 3:
        rhs, layer = rhs[None], 0
    if not (kernel or interpret):
        return jax.lax.ragged_dot(
            lhs, rhs[layer], group_sizes, preferred_element_type=jnp.float32
        ).astype(lhs.dtype)
    tn = None if kernel_fits(K, N) else N  # interpreter only: any width
    return _grouped_matmul_pallas(
        lhs, rhs, group_sizes, layer, tn=tn, interpret=interpret
    )
