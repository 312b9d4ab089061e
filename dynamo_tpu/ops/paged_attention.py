"""Pallas paged-attention decode kernel (TPU): a grid over the page table.

Which pools still reach it (``engine.attention.decode_backend``): a dense
pool whose heads are narrower than a 128-lane tile (TinyLlama's 64), of the
query's type.  A dense pool of 128-lane heads takes the work-list kernel
(``ragged_attention.decode_work_list_attention``: one item a lane over its
live key blocks, where this grid pays a step for every page group of the
table's width on every lane), a latent pool its own, an int8 pool the XLA
gather.

Replaces the XLA gather path (engine/attention.py paged_decode_attention,
the classic paged-attention "v1" shape) on the decode hot loop.  The XLA
path materializes ``[B, P*page, Hkv, D]`` in HBM every step -- gather write
+ attention read, twice the KV traffic.  This kernel instead streams each
lane's pages HBM->VMEM directly, guided by the page table, and keeps the
softmax accumulation (flash-style online max/sum) in f32 VMEM scratch; KV
is read from HBM exactly once and nothing is written back but the [B, Hq,
D] output.

Mechanics: the grid is ``(B, P/G)`` -- each step covers a GROUP of ``G``
pages fetched as ``G`` independently-pipelined block operands (all
aliasing the one HBM pool; a block spans a page's K and V in one fetch).
The page table + kv lengths + layer index ride as scalar prefetch, so the
BlockSpec index maps dereference ``page_table[b, p*G+g]`` and Pallas
double-buffers the group fetches against the attention math.  Grouping
matters because grid-step overhead, not bandwidth, dominates at serving
shapes (measured ~2x attention-time reduction at G=8 vs per-page).

Numerics match the XLA path: f32 scores/softmax, bf16 (input dtype)
probs @ V accumulation per page chunk, f32 running rescale.  Inactive
lanes (kv_len == 0) produce zeros.  Capability parity: vLLM's CUDA
paged_attention v1 (the engine the reference shells out to --
lib/llm/src/engines.rs MultiNodeConfig vllm path); built TPU-native here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _decode_kernel_v2(
    # scalar prefetch
    layer_ref,  # [1] layer index (SMEM)
    pt_ref,  # [B, P] page table (SMEM)
    len_ref,  # [B] kv lengths (SMEM)
    *refs,  # G kv blocks [1, 2, 1, page, Hkv, D], then q_ref, o_ref, scratch
    G: int,
    window: int = 0,
):
    """Group-of-pages variant: each grid step covers ``G`` pages fetched as
    ``G`` independently-pipelined block operands (one [2, page, ...] block
    per page -- K and V of a page ride ONE fetch), so the grid shrinks by
    ``G``x and the per-step attention math runs on ``G*page`` keys at once.
    Grid-step overhead -- not bandwidth -- dominates the per-page v1 kernel
    at serving shapes, so fewer, fatter steps are the win."""
    kv_refs = refs[:G]
    q_ref, o_ref, m_scr, l_scr, acc_scr = refs[G:]
    b = pl.program_id(0)
    p = pl.program_id(1)
    page = kv_refs[0].shape[3]
    Hkv = kv_refs[0].shape[4]
    D = kv_refs[0].shape[5]
    Hq = q_ref.shape[1]
    n_rep = Hq // Hkv

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[b]
    base = p * G * page  # first position this group covers
    live = base < kv_len
    if window > 0:
        live = live & (base + G * page > kv_len - window)

    @pl.when(live)
    def _attend():
        q = q_ref[0].reshape(Hkv, n_rep, D)
        # [Hkv, G*page, D] keys/values for the whole group
        k = jnp.concatenate(
            [r[0, 0, 0].transpose(1, 0, 2) for r in kv_refs], axis=1
        )
        v = jnp.concatenate(
            [r[0, 1, 0].transpose(1, 0, 2) for r in kv_refs], axis=1
        )
        scale = 1.0 / (D ** 0.5)
        s = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [Hkv, n_rep, G*page]
        pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (Hkv, n_rep, G * page), dimension=2
        )
        keep = pos < kv_len
        if window > 0:
            keep = keep & (pos >= kv_len - window)
        s = jnp.where(keep, s, _NEG_INF)

        s2 = s.reshape(Hq, G * page)
        m_prev = m_scr[:]
        m_cur = jnp.max(s2, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(s2 - m_new)
        pv = jax.lax.dot_general(
            probs.reshape(Hkv, n_rep, G * page).astype(v.dtype), v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + pv.reshape(Hq, D)

    @pl.when(p == pl.num_programs(1) - 1)
    def _finish():
        l = l_scr[:]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_scr[:] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "group", "interpret", "name_suffix")
)
def paged_decode_attention_v2(
    q: jax.Array,  # [B, Hq, D]
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    page_table: jax.Array,  # [B, P] int32 page ids
    kv_lens: jax.Array,  # [B]
    layer: jax.Array | int = 0,
    window: int = 0,
    group: int = 4,  # pages per grid step
    interpret: bool = False,
    name_suffix: str = "",  # a two-kind trunk's window layers: "_window"
) -> jax.Array:
    """Group-fetch paged decode attention (see _decode_kernel_v2).  When
    the table width doesn't divide by ``group``, the group degrades to the
    largest divisor of the width (callers pass power-of-two widths >= 8,
    so the full group applies; G=1 is the per-page degenerate case)."""
    B, Hq, D = q.shape
    L, _, num_pages, page, Hkv, _ = kv_pages.shape
    P = page_table.shape[1]
    G = min(group, P)
    while P % G:
        G -= 1

    pt = jnp.clip(page_table.astype(jnp.int32), 0, num_pages - 1)
    lens = kv_lens.astype(jnp.int32)
    lyr = jnp.clip(jnp.asarray(layer, jnp.int32), 0, L - 1).reshape(1)

    def kv_map(g):
        def m(b, p, layer_ref, pt_ref, len_ref):
            return (layer_ref[0], 0, pt_ref[b, p * G + g], 0, 0, 0)

        return m

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, P // G),
        in_specs=[
            pl.BlockSpec((1, 2, 1, page, Hkv, D), kv_map(g)) for g in range(G)
        ]
        + [pl.BlockSpec((1, Hq, D), lambda b, p, *_: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, p, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel_v2, G=G, window=window),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        # a one-kind trunk's launch keeps the name it always had
        **(
            {"name": "paged_decode_attention" + name_suffix}
            if name_suffix else {}
        ),
    )(lyr, pt, lens, *([kv_pages] * G), q)


def paged_decode_attention(
    q: jax.Array,  # [B, Hq, D] one new query token per lane
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    page_table: jax.Array,  # [B, P] int32 page ids
    kv_lens: jax.Array,  # [B] tokens in cache (incl. the one just written)
    layer: jax.Array | int = 0,  # scalar layer index into kv_pages
    window: int = 0,  # sliding-window width; 0 = full attention
    interpret: bool = False,
) -> jax.Array:
    """TPU replacement for the XLA gather path (same math as
    engine.attention.paged_decode_attention run on ``kv_pages[layer]`` --
    note the interface difference: this takes the FULL stacked buffer plus
    a (possibly traced) layer index, so the engine's layer scan never
    slices the cache).  This is the per-page (G=1) degenerate case of the
    group-fetch kernel -- ONE online-softmax kernel body serves both, so
    the masking/rescale math cannot diverge between paths."""
    return paged_decode_attention_v2(
        q, kv_pages, page_table, kv_lens, layer, window,
        group=1, interpret=interpret,
    )
