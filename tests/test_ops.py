"""Pallas kernel validation: dynamo_tpu.ops vs the XLA-composed references.

Runs in interpret mode on the CPU test mesh (conftest pins JAX_PLATFORMS=cpu
and matmul precision "highest" -- the comparisons here are only meaningful
at full f32 accumulation).  Real-TPU execution of the same kernel is
exercised by bench.py on hardware.

The kernel takes the FULL stacked KV buffer [L, 2, N, page, Hkv, D] plus a
layer index (scalar prefetch), so the engine's layer scan never slices the
cache; every test here compares against the per-layer XLA reference run on
the indexed slice, at a nonzero layer to prove the index map actually
dereferences it.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import attention as att
from dynamo_tpu.ops.paged_attention import paged_decode_attention


def _mk(B, Hq, Hkv, D, page, N, P, L=3, seed=0):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, Hq, D), jnp.float32)
    kv = jnp.asarray(rs.randn(L, 2, N, page, Hkv, D), jnp.float32)
    pt = jnp.asarray(
        np.stack([rs.permutation(N - 1)[:P] + 1 for _ in range(B)]).astype(np.int32)
    )
    return q, kv, pt


@pytest.mark.parametrize(
    "B,Hq,Hkv,D,page,N,P,lens",
    [
        (2, 4, 4, 16, 8, 16, 2, [16, 9]),  # MHA (n_rep=1)
        (2, 8, 2, 64, 8, 32, 4, [32, 5]),  # GQA n_rep=4
        (4, 32, 4, 64, 16, 64, 4, [64, 33, 16, 1]),  # TinyLlama head geometry
        (1, 4, 2, 32, 8, 8, 1, [3]),  # single partial page
    ],
)
def test_matches_xla_reference(B, Hq, Hkv, D, page, N, P, lens):
    q, kv, pt = _mk(B, Hq, Hkv, D, page, N, P)
    kv_lens = jnp.asarray(lens, jnp.int32)
    for layer in (0, 2):
        ref = att.paged_decode_attention(q, kv[layer], pt, kv_lens)
        got = paged_decode_attention(q, kv, pt, kv_lens, layer, interpret=True)
        assert float(jnp.max(jnp.abs(ref - got))) < 1e-5


def test_traced_layer_index():
    """The layer index arrives traced (the engine scans it); the kernel must
    still fetch the right slice."""
    q, kv, pt = _mk(2, 8, 2, 32, 8, 16, 2)
    kv_lens = jnp.asarray([16, 10], jnp.int32)

    @jax.jit
    def per_layer(layer):
        return paged_decode_attention(q, kv, pt, kv_lens, layer, interpret=True)

    for layer in (0, 1, 2):
        ref = att.paged_decode_attention(q, kv[layer], pt, kv_lens)
        got = per_layer(jnp.asarray(layer, jnp.int32))
        assert float(jnp.max(jnp.abs(ref - got))) < 1e-5


def test_dead_lane_emits_zeros_not_garbage():
    """kv_len == 0 lanes: the XLA path softmaxes over an all-masked row
    (uniform garbage, discarded by the engine); the kernel defines the
    output as zeros.  Live lanes must still match the reference exactly."""
    q, kv, pt = _mk(3, 8, 2, 32, 8, 16, 2)
    kv_lens = jnp.asarray([16, 0, 7], jnp.int32)
    ref = att.paged_decode_attention(q, kv[1], pt, kv_lens)
    got = paged_decode_attention(q, kv, pt, kv_lens, 1, interpret=True)
    assert float(jnp.max(jnp.abs(ref[0] - got[0]))) < 1e-5
    assert float(jnp.max(jnp.abs(ref[2] - got[2]))) < 1e-5
    assert float(jnp.max(jnp.abs(got[1]))) == 0.0


def test_bf16_inputs():
    q, kv, pt = _mk(2, 8, 2, 64, 16, 32, 2)
    q = q.astype(jnp.bfloat16)
    kv = kv.astype(jnp.bfloat16)
    kv_lens = jnp.asarray([32, 20], jnp.int32)
    ref = att.paged_decode_attention(q, kv[1], pt, kv_lens).astype(jnp.float32)
    got = paged_decode_attention(q, kv, pt, kv_lens, 1, interpret=True).astype(
        jnp.float32
    )
    assert float(jnp.max(jnp.abs(ref - got))) < 0.05
    assert got.dtype == jnp.float32  # cast back above; kernel out was bf16


def test_repeated_pages_in_table():
    """A page id appearing twice in one lane's table contributes at both
    positions (both paths must agree -- the mask is positional)."""
    q, kv, _ = _mk(1, 4, 2, 16, 8, 8, 3)
    pt = jnp.asarray([[2, 2, 5]], jnp.int32)
    kv_lens = jnp.asarray([24], jnp.int32)
    ref = att.paged_decode_attention(q, kv[0], pt, kv_lens)
    got = paged_decode_attention(q, kv, pt, kv_lens, 0, interpret=True)
    assert float(jnp.max(jnp.abs(ref - got))) < 1e-5


def test_dispatch_uses_xla_on_cpu():
    """On the CPU test platform the dispatcher must pick the XLA path (the
    kernel itself is TPU-only outside interpret mode)."""
    q, kv, pt = _mk(1, 4, 2, 16, 8, 8, 1)
    kv_lens = jnp.asarray([8], jnp.int32)
    out = att.decode_attention_dispatch(q, kv, pt, kv_lens, jnp.asarray(1, jnp.int32))
    ref = att.paged_decode_attention(q, kv[1], pt, kv_lens)
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0


def test_sliding_window_matches_xla_reference():
    """Window masking parity between the kernel and the XLA path, including
    the page-skip fast path (pages wholly behind the window)."""
    q, kv, pt = _mk(2, 8, 2, 32, 8, 32, 4)
    kv_lens = jnp.asarray([30, 12], jnp.int32)
    for window in (5, 8, 17):
        ref = att.paged_decode_attention(q, kv[1], pt, kv_lens, window)
        got = paged_decode_attention(
            q, kv, pt, kv_lens, 1, window, interpret=True
        )
        assert float(jnp.max(jnp.abs(ref - got))) < 1e-5, f"window={window}"


@pytest.mark.parametrize("group", [2, 4, 8])
def test_v2_group_kernel_matches_xla_reference(group):
    """The group-fetch v2 kernel (G pages per grid step, K+V per page in
    one block) against the XLA reference, across fill levels and windows."""
    from dynamo_tpu.ops.paged_attention import paged_decode_attention_v2

    q, kv, pt = _mk(2, 8, 2, 32, 8, 32, 8)
    for lens in ([64, 5], [33, 12], [8, 3]):
        kv_lens = jnp.asarray(lens, jnp.int32)
        for window in (0, 7, 20):
            ref = att.paged_decode_attention(q, kv[1], pt, kv_lens, window)
            got = paged_decode_attention_v2(
                q, kv, pt, kv_lens, 1, window, group, interpret=True
            )
            err = float(jnp.max(jnp.abs(ref - got)))
            assert err < 1e-5, f"lens={lens} window={window} group={group}"


def test_v2_falls_back_when_group_indivisible():
    from dynamo_tpu.ops.paged_attention import paged_decode_attention_v2

    q, kv, pt = _mk(1, 4, 2, 16, 8, 16, 3)  # P=3 not divisible by 2
    kv_lens = jnp.asarray([20], jnp.int32)
    ref = att.paged_decode_attention(q, kv[0], pt, kv_lens)
    got = paged_decode_attention_v2(
        q, kv, pt, kv_lens, 0, 0, 2, interpret=True
    )
    assert float(jnp.max(jnp.abs(ref - got))) < 1e-5


# -- flash prefill kernel ----------------------------------------------------

from dynamo_tpu.ops.flash_prefill import flash_prefill_attention


def _mk_prefill(B, T, Hq, Hkv, D, seed=0, dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, T, Hq, D), dtype)
    k = jnp.asarray(rs.randn(B, T, Hkv, D), dtype)
    v = jnp.asarray(rs.randn(B, T, Hkv, D), dtype)
    return q, k, v


def _valid_mask(T, lens):
    """[B, T, 1, 1] -- only rows below seq_len carry defined outputs (the
    kernel zeroes fully-masked rows; the XLA path averages -inf scores)."""
    return (np.arange(T)[None, :] < np.asarray(lens)[:, None])[:, :, None, None]


@pytest.mark.parametrize(
    "B,T,Hq,Hkv,D,lens,bq,bk",
    [
        (2, 16, 4, 4, 16, [16, 9], 8, 8),      # MHA, partial lane
        (2, 32, 8, 2, 64, [32, 5], 16, 16),    # GQA n_rep=4
        (1, 64, 32, 4, 64, [64], 32, 32),      # TinyLlama heads
        (3, 16, 4, 2, 32, [16, 1, 0], 16, 16), # single block + dead lane
        (1, 32, 4, 2, 32, [20], 8, 16),        # BQ != BK
    ],
)
def test_flash_prefill_matches_xla(B, T, Hq, Hkv, D, lens, bq, bk):
    q, k, v = _mk_prefill(B, T, Hq, Hkv, D)
    seq_lens = jnp.asarray(lens, jnp.int32)
    ref = att.prefill_attention(q, k, v, seq_lens)
    got = flash_prefill_attention(
        q, k, v, seq_lens, block_q=bq, block_k=bk, interpret=True
    )
    m = _valid_mask(T, lens)
    diff = np.abs(np.asarray(ref) - np.asarray(got)) * m
    assert float(diff.max()) < 1e-5


@pytest.mark.parametrize("window", [4, 8, 16])
def test_flash_prefill_sliding_window(window):
    B, T, Hq, Hkv, D = 2, 32, 8, 2, 32
    q, k, v = _mk_prefill(B, T, Hq, Hkv, D, seed=3)
    seq_lens = jnp.asarray([32, 17], jnp.int32)
    ref = att.prefill_attention(q, k, v, seq_lens, window)
    got = flash_prefill_attention(
        q, k, v, seq_lens, window, block_q=8, block_k=8, interpret=True
    )
    diff = np.abs(np.asarray(ref) - np.asarray(got)) * _valid_mask(T, [32, 17])
    assert float(diff.max()) < 1e-5


def test_flash_prefill_bf16():
    B, T, Hq, Hkv, D = 2, 32, 4, 2, 32
    q, k, v = _mk_prefill(B, T, Hq, Hkv, D, seed=5, dtype=jnp.bfloat16)
    seq_lens = jnp.asarray([32, 11], jnp.int32)
    ref = att.prefill_attention(q, k, v, seq_lens).astype(jnp.float32)
    got = flash_prefill_attention(
        q, k, v, seq_lens, block_q=16, block_k=16, interpret=True
    ).astype(jnp.float32)
    diff = np.abs(np.asarray(ref) - np.asarray(got)) * _valid_mask(T, [32, 11])
    assert float(diff.max()) < 0.06  # bf16 probs @ V accumulation


def test_flash_prefill_indivisible_T_degrades_to_single_block():
    B, T, Hq, Hkv, D = 1, 24, 4, 4, 16  # 24 % 16 != 0 -> one T-block
    q, k, v = _mk_prefill(B, T, Hq, Hkv, D, seed=7)
    seq_lens = jnp.asarray([24], jnp.int32)
    ref = att.prefill_attention(q, k, v, seq_lens)
    got = flash_prefill_attention(
        q, k, v, seq_lens, block_q=16, block_k=16, interpret=True
    )
    diff = np.abs(np.asarray(ref) - np.asarray(got)) * _valid_mask(T, [24])
    assert float(diff.max()) < 1e-5


def test_prefill_dispatch_uses_xla_on_cpu():
    """On the CPU test platform the dispatch must pick the XLA path (the
    kernel is TPU-only outside interpret mode)."""
    B, T, Hq, Hkv, D = 1, 16, 4, 2, 16
    q, k, v = _mk_prefill(B, T, Hq, Hkv, D)
    seq_lens = jnp.asarray([16], jnp.int32)
    got = att.prefill_attention_dispatch(q, k, v, seq_lens)
    ref = att.prefill_attention(q, k, v, seq_lens)
    assert float(jnp.max(jnp.abs(ref - got))) == 0.0


# -- flash prefix-suffix prefill kernel --------------------------------------

from dynamo_tpu.ops.flash_prefill import flash_prefix_prefill_attention


def _mk_prefix_case(B, T, Pp, page, Hq, Hkv, D, offsets, slens, seed=0,
                    L=2, layer=1, dtype=jnp.float32):
    """Build a paged prefix + suffix K/V pair and both paths' inputs.

    The XLA reference (att.prefill_prefix_attention) reads the prefix from
    the paged cache; the flash kernel takes the same pages pre-gathered and
    concatenated with the suffix, exactly as the dispatch wrapper does."""
    rs = np.random.RandomState(seed)
    num_pages = 1 + B * Pp
    kv_pages = jnp.asarray(
        rs.randn(L, 2, num_pages, page, Hkv, D), dtype
    )
    # zero the trash page so 0-padded table slots carry no content
    kv_pages = kv_pages.at[:, :, 0].set(0.0)
    prefix_table = np.zeros((B, Pp), np.int32)
    for b in range(B):
        used = -(-offsets[b] // page)
        prefix_table[b, :used] = 1 + b * Pp + np.arange(used)
    prefix_table = jnp.asarray(prefix_table)
    q = jnp.asarray(rs.randn(B, T, Hq, D), dtype)
    k = jnp.asarray(rs.randn(B, T, Hkv, D), dtype)
    v = jnp.asarray(rs.randn(B, T, Hkv, D), dtype)
    offset = jnp.asarray(offsets, jnp.int32)
    suffix_lens = jnp.asarray(slens, jnp.int32)
    layer_kv = kv_pages[layer]
    Kp = Pp * page
    kp = layer_kv[0][prefix_table].reshape(B, Kp, Hkv, D)
    vp = layer_kv[1][prefix_table].reshape(B, Kp, Hkv, D)
    k_cat = jnp.concatenate([kp, k], axis=1)
    v_cat = jnp.concatenate([vp, v], axis=1)
    return kv_pages, prefix_table, q, k, v, offset, suffix_lens, k_cat, v_cat


@pytest.mark.parametrize(
    "B,T,Pp,page,Hq,Hkv,D,offsets,slens,bq,bk",
    [
        (2, 16, 2, 8, 4, 4, 16, [16, 8], [16, 9], 8, 8),    # MHA, partial
        (2, 32, 4, 8, 8, 2, 64, [32, 0], [32, 5], 16, 16),  # GQA + no prefix
        (1, 32, 2, 16, 32, 4, 64, [24], [32], 16, 16),      # partial page
        (3, 16, 1, 16, 4, 2, 32, [16, 16, 0], [16, 1, 16], 16, 16),
    ],
)
def test_flash_prefix_prefill_matches_xla(
    B, T, Pp, page, Hq, Hkv, D, offsets, slens, bq, bk
):
    kv_pages, pt, q, k, v, offset, slen, k_cat, v_cat = _mk_prefix_case(
        B, T, Pp, page, Hq, Hkv, D, offsets, slens
    )
    ref = att.prefill_prefix_attention(
        q, k, v, kv_pages, 1, pt, offset, slen
    )
    got = flash_prefix_prefill_attention(
        q, k_cat, v_cat, offset, slen, block_q=bq, block_k=bk, interpret=True
    )
    m = _valid_mask(T, slens)
    diff = np.abs(np.asarray(ref) - np.asarray(got)) * m
    assert float(diff.max()) < 1e-5


@pytest.mark.parametrize("window", [4, 12, 24])
def test_flash_prefix_prefill_sliding_window(window):
    B, T, Pp, page, Hq, Hkv, D = 2, 16, 2, 8, 4, 2, 32
    offsets, slens = [16, 8], [16, 11]
    kv_pages, pt, q, k, v, offset, slen, k_cat, v_cat = _mk_prefix_case(
        B, T, Pp, page, Hq, Hkv, D, offsets, slens, seed=3
    )
    ref = att.prefill_prefix_attention(
        q, k, v, kv_pages, 1, pt, offset, slen, window
    )
    got = flash_prefix_prefill_attention(
        q, k_cat, v_cat, offset, slen, window,
        block_q=8, block_k=8, interpret=True,
    )
    diff = np.abs(np.asarray(ref) - np.asarray(got)) * _valid_mask(T, slens)
    assert float(diff.max()) < 1e-5


def test_flash_prefix_prefill_bf16():
    B, T, Pp, page, Hq, Hkv, D = 2, 16, 2, 8, 4, 2, 32
    offsets, slens = [12, 16], [16, 7]
    kv_pages, pt, q, k, v, offset, slen, k_cat, v_cat = _mk_prefix_case(
        B, T, Pp, page, Hq, Hkv, D, offsets, slens, seed=5, dtype=jnp.bfloat16
    )
    ref = att.prefill_prefix_attention(
        q, k, v, kv_pages, 1, pt, offset, slen
    ).astype(jnp.float32)
    got = flash_prefix_prefill_attention(
        q, k_cat, v_cat, offset, slen, block_q=8, block_k=8, interpret=True
    ).astype(jnp.float32)
    diff = np.abs(np.asarray(ref) - np.asarray(got)) * _valid_mask(T, slens)
    assert float(diff.max()) < 0.06


def test_prefix_prefill_dispatch_uses_xla_on_cpu():
    """On the CPU test platform the prefix dispatch must pick the XLA path
    (the kernel is TPU-only outside interpret mode)."""
    B, T, Pp, page, Hq, Hkv, D = 1, 16, 1, 16, 4, 2, 16
    kv_pages, pt, q, k, v, offset, slen, _, _ = _mk_prefix_case(
        B, T, Pp, page, Hq, Hkv, D, [16], [16]
    )
    got = att.prefill_prefix_attention_dispatch(
        q, k, v, kv_pages, 1, pt, offset, slen
    )
    ref = att.prefill_prefix_attention(q, k, v, kv_pages, 1, pt, offset, slen)
    assert float(jnp.max(jnp.abs(ref - got))) == 0.0


# -- ragged paged attention (mixed prefill+decode) ---------------------------
#
# The reference is ``ragged_paged_attention_xla`` over the lane rectangle;
# the kernel under test is the packed launch's page-group GRID kernel
# (``packed_ragged_attention`` at D < 128, where ``_takes_work_list`` is
# false), the same lanes packed onto one flat token axis.

from dynamo_tpu.ops.ragged_attention import (
    _takes_work_list,
    packed_ragged_attention,
    ragged_paged_attention_xla,
)
from tests.test_long_context import _mk_packed_case


def _mk_ragged_case(B, S, Pp, page, Hq, Hkv, D, bases, qlens, seed=0,
                    L=2, dtype=jnp.float32):
    """Ragged mixed-batch inputs over a paged pool: lane ``b`` holds a
    resident prefix of ``bases[b]`` tokens in its page table and
    contributes ``qlens[b]`` fresh query rows (1 = decode lane, >1 =
    chunked-prefill lane, 0 = inactive)."""
    rs = np.random.RandomState(seed)
    num_pages = 1 + B * Pp
    kv_pages = jnp.asarray(rs.randn(L, 2, num_pages, page, Hkv, D), dtype)
    kv_pages = kv_pages.at[:, :, 0].set(0.0)  # trash page
    pt = np.zeros((B, Pp), np.int32)
    for b in range(B):
        used = -(-bases[b] // page) if bases[b] else 0
        pt[b, :used] = 1 + b * Pp + np.arange(used)
    q = jnp.asarray(rs.randn(B, S, Hq, D), dtype)
    k = jnp.asarray(rs.randn(B, S, Hkv, D), dtype)
    v = jnp.asarray(rs.randn(B, S, Hkv, D), dtype)
    return (
        q, k, v, kv_pages, jnp.asarray(pt),
        jnp.asarray(bases, jnp.int32), jnp.asarray(qlens, jnp.int32),
    )


def _packed_lanes(B, Pp, page, Hq, Hkv, D, bases, qlens, seed=0,
                  dtype=jnp.float32):
    """The lanes both ways from one draw: ``(packed, rect, rows)`` --
    the packed kernel's operands up to ``s_max``, the rectangle reference's
    operands, and ``rows(out_packed, out_rect)`` pairing each live packed
    row with its rectangle cell."""
    (qp, kp, vp, qr, kr, vr, kv_pages, pt, base, seg_off, qn, lane, rel,
     s_max, total) = _mk_packed_case(
        B, page, Pp, Hq, Hkv, D, bases, qlens, seed=seed)
    qp, kp, vp, qr, kr, vr, kv_pages = (
        x.astype(dtype) for x in (qp, kp, vp, qr, kr, vr, kv_pages))
    lane_np, rel_np = np.asarray(lane)[:total], np.asarray(rel)[:total]

    def rows(out_packed, out_rect):
        return (
            np.asarray(out_packed.astype(jnp.float32))[:total],
            np.asarray(out_rect.astype(jnp.float32))[lane_np, rel_np],
        )

    return (
        (qp, kp, vp, kv_pages, pt, base, seg_off, qn, lane, rel, s_max),
        (qr, kr, vr, kv_pages, pt, base, qn),
        rows,
    )


def _grid_kernel_err(case, group, window=0):
    """Max abs error of the grid kernel (interpret mode) against the
    rectangle reference over the live rows of ``case``."""
    (qp, kp, vp, kv_pages, pt, base, seg_off, qn, _lane, _rel, s_max), rect, rows = case
    assert not _takes_work_list(qp.shape[2], False)
    ref = ragged_paged_attention_xla(*rect, 1, window)
    got = packed_ragged_attention(
        qp, kp, vp, kv_pages, pt, base, seg_off, qn, s_max, 1, window,
        group=group, interpret=True,
    )
    got, ref = rows(got, ref)
    return float(np.abs(got - ref).max())


@pytest.mark.parametrize(
    "B,Pp,page,Hq,Hkv,D,bases,qlens,group",
    [
        # pure decode batch (every lane one row)
        (3, 4, 8, 4, 4, 16, [9, 32, 17], [1, 1, 1], 2),
        # mixed: decode lane + chunked-prefill lanes + a dead lane
        (4, 4, 8, 8, 2, 32, [16, 0, 11, 24], [1, 8, 5, 0], 2),
        # prefill continuation from a non-page-aligned base
        (2, 8, 4, 4, 2, 16, [7, 0], [16, 13], 4),
        # group doesn't divide the table: degrades to a divisor
        (2, 6, 8, 4, 4, 16, [48, 3], [4, 1], 4),
    ],
)
def test_ragged_kernel_matches_xla(B, Pp, page, Hq, Hkv, D, bases, qlens,
                                   group):
    case = _packed_lanes(B, Pp, page, Hq, Hkv, D, bases, qlens)
    assert _grid_kernel_err(case, group) < 1e-5


@pytest.mark.parametrize("window", [4, 12])
def test_ragged_kernel_sliding_window(window):
    case = _packed_lanes(2, 4, 8, 4, 2, 16, [24, 0], [8, 6], seed=3)
    assert _grid_kernel_err(case, 2, window) < 1e-5


def test_ragged_xla_matches_prefix_prefill():
    """The ragged XLA reference must agree with the existing prefix-suffix
    attention (its independent oracle) when every lane is a prefill
    continuation."""
    B, S, Pp, page, Hq, Hkv, D = 2, 8, 4, 8, 4, 2, 16
    bases, qlens = [16, 8], [8, 5]
    q, k, v, kv_pages, pt, base, qn = _mk_ragged_case(
        B, S, Pp, page, Hq, Hkv, D, bases, qlens, seed=7
    )
    ref = att.prefill_prefix_attention(q, k, v, kv_pages, 1, pt, base, qn)
    got = ragged_paged_attention_xla(q, k, v, kv_pages, pt, base, qn, 1)
    diff = np.abs(np.asarray(ref) - np.asarray(got)) * _valid_mask(S, qlens)
    assert float(diff.max()) < 1e-5


def test_ragged_kernel_bf16():
    case = _packed_lanes(
        2, 4, 8, 4, 2, 32, [16, 9], [8, 1], seed=5, dtype=jnp.bfloat16)
    assert _grid_kernel_err(case, 2) < 0.06


def test_ragged_dispatch_uses_xla_on_cpu():
    """On the CPU test platform the packed dispatch must pick the XLA
    composition (the kernels are TPU-only outside interpret mode), which
    runs the rectangle reference's exact math on the unpacked lanes."""
    (qp, kp, vp, kv_pages, pt, base, seg_off, qn, lane, rel, s_max), rect, rows = (
        _packed_lanes(2, 4, 8, 4, 2, 16, [8, 0], [1, 4]))
    got = att.packed_ragged_attention_dispatch(
        qp, kp, vp, kv_pages, 1, pt, base, seg_off, qn, lane, rel, s_max
    )
    got, ref = rows(got, ragged_paged_attention_xla(*rect, 1))
    np.testing.assert_array_equal(got, ref)
