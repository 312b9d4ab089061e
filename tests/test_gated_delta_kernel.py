"""The launch ``ops.gated_delta.gated_delta_chunks`` through the Pallas
interpreter, at toy widths (2 key heads serving 4 value heads of 8 x 8),
against the XLA composition that a CPU serves (``attention._gdn_chunk_loop``
over ``gdn_chunk_terms`` and ``gdn_chunk_apply``) and against the recurrence
one token at a time: a packed step's rows, the lanes' states and last three
rows, and the snapshots the plan names.

Tolerance.  All three are float32 at ``highest`` precision and differ in the
order of their sums: the launch from the composition by what a product of 128
rows in place of two of 64 moves (1e-6 read here), either from the
recurrence by what chunks of 64 move (2e-5, ``tests/test_qwen3next.py``).  A
state started from zeros, from another lane's or from a snapshot taken a row
late reads 1e-2 and more.

Each case waits for its launch (``jax.block_until_ready``) before anything
else is traced: the interpreter's callbacks run JAX operations of their own,
and a launch still in flight can deadlock with a main thread that goes on to
trace (PERF.md section 7).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import attention as att
from dynamo_tpu.engine.kv_cache import DeltaKV
from dynamo_tpu.ops import gated_delta as gd
from tests.test_qwen3next import model_config, tiny

B, NP, SLOTS = 4, 256, 5
HV, DK, DV = 4, 8, 8


def _plan(restore=(), snap=()):
    plan = np.full((3, B), -1, np.int32)
    for lane, slot in restore:
        plan[0, lane] = slot
    for lane, slot, pos in snap:
        plan[1, lane], plan[2, lane] = slot, pos
    return plan


CASES = {
    # two whole chunks from the lane's state
    "one-whole-segment": ([128, 0, 0, 0], [64, 0, 0, 0], _plan()),
    # three whole chunks and 8 rows; a run shorter than a chunk
    "partial-last-chunk": ([200, 0, 37, 0], [16, 0, 5, 0], _plan()),
    # the cut at row 80 of 100: runs of 64 + 16 and 20 rows
    "snapshot-mid-segment": (
        [100, 0, 0, 0], [16, 0, 0, 0], _plan(snap=[(0, 4, 96)])),
    # the cut at the segment's end: one run, its state to lane and slot
    "snapshot-at-the-end": (
        [96, 0, 0, 0], [0, 0, 0, 0], _plan(snap=[(0, 2, 96)])),
    # lane 0 from slot 3, lane 1 fresh, lane 2 idle, lane 3 from its state
    "restored-fresh-idle": (
        [70, 90, 0, 40], [32, 0, 0, 7], _plan(restore=[(0, 3)])),
    # a chunk beside decode rows, one of them resuming from a slot
    "beside-decode-rows": (
        [100, 1, 1, 70], [16, 300, 7, 0], _plan(restore=[(2, 1)])),
    # segments that leave most of the packed axis padding, the last one
    # ending on the axis' last row
    "padding-rows": ([3, 0, 65, 0], [9, 0, 0, 0], _plan(), 188),
    # a prompt's chunk of one row that ends on a block: its state is the
    # snapshot (the one-row step would have left the slot as it was)
    "one-row-snapshot": (
        [1, 1, 0, 40], [15, 9, 0, 0], _plan(snap=[(0, 2, 16)])),
    # decode rows alone: the launch has no run
    "decode-only": ([1, 1, 1, 1], [5, 6, 7, 8], _plan()),
    # a packed axis shorter than the rows a chunk copies (the 16-row step)
    "short-axis": ([5, 1, 0, 3], [40, 9, 0, 0], _plan(), 2, 16),
}


def _operands(q_lens, base, plan, first=0, NP=NP, seed=0):
    mc = model_config(tiny())
    C = mc.linear_conv_width
    rng = np.random.RandomState(seed)
    u = jnp.asarray(rng.standard_normal((NP, C)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((4, C)) / 2, jnp.float32)
    g = -jnp.asarray(rng.uniform(1e-3, 0.2, (NP, HV)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.9, (NP, HV)), jnp.float32)
    state = DeltaKV(
        jnp.zeros((1,)),
        jnp.asarray(rng.standard_normal((2, B, HV, DK, DV)) * 0.3, jnp.float32),
        jnp.asarray(rng.standard_normal((2, 3 * B, C)), jnp.float32),
        jnp.asarray(rng.standard_normal((2, SLOTS, HV, DK, DV)) * 0.3, jnp.float32),
        jnp.asarray(rng.standard_normal((2, 3 * SLOTS, C)), jnp.float32),
        jnp.asarray(plan, jnp.int32),
    )
    q_lens = np.asarray(q_lens, np.int32)
    seg_off = first + np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
    padding = np.ones((NP,), bool)
    for b in range(B):
        padding[seg_off[b]:seg_off[b] + q_lens[b]] = False
    ops = tuple(jnp.asarray(a, jnp.int32) for a in (base, seg_off, q_lens))
    return mc, u, taps, g, beta, state, seg_off, padding, ops


def _recurrence(mc, u, taps, g, beta, S, hist, off, n):
    """``n`` rows from ``off`` one token at a time from ``(S, hist)``:
    the rows, and the state and history after each."""
    rows = jnp.concatenate([hist, u[off:off + n]])
    x = jax.nn.silu(sum(taps[i] * rows[i:i + n] for i in range(4)))
    q, k, v = att._gdn_heads(mc, x)
    out, states = [], []
    for t in range(n):
        S = jnp.exp(g[off + t])[:, None, None] * S
        d = beta[off + t][:, None] * (v[t] - jnp.einsum("hk,hkv->hv", k[t], S))
        S = S + k[t][:, :, None] * d[:, None, :]
        out.append(jnp.einsum("hk,hkv->hv", q[t], S))
        states.append(S)
    return jnp.stack(out), states, rows


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_the_launch_is_the_composition_and_the_recurrence(case):
    q_lens, base, plan, *first = case
    layer = 1
    mc, u, taps, g, beta, state, seg_off, padding, ops = _operands(
        q_lens, base, plan, *first)
    mix = jax.jit(att.packed_delta_mix, static_argnums=(0, 10))
    args = (mc, u, taps, g, beta, state, jnp.int32(layer), *ops)
    o, new = jax.block_until_ready(mix(*args, True))  # the launch
    o_x, new_x = jax.block_until_ready(mix(*args, False))  # the composition
    close = dict(rtol=2e-6, atol=2e-6)
    for got, want in zip(jax.tree.leaves((o, new)), jax.tree.leaves((o_x, new_x))):
        np.testing.assert_allclose(got, want, **close)
    # the other layer's state and every slot the plan does not name are
    # the bytes they were
    np.testing.assert_array_equal(new.lanes[0], state.lanes[0])
    named = [int(s) for s in plan[1] if s >= 0]
    others = [s for s in range(SLOTS) if s not in named]
    np.testing.assert_array_equal(new.slots[:, others], state.slots[:, others])
    np.testing.assert_array_equal(new.slots[0], state.slots[0])
    # rows of no lane are zero
    np.testing.assert_array_equal(np.asarray(o)[padding], 0.0)
    loose = dict(rtol=2e-5, atol=2e-5)
    for b, n in enumerate(q_lens):
        if not n:  # an idle lane keeps what it had, bit for bit
            np.testing.assert_array_equal(new.lanes[layer, b], state.lanes[layer, b])
            np.testing.assert_array_equal(
                new.conv[layer, 3 * b:3 * b + 3], state.conv[layer, 3 * b:3 * b + 3])
            continue
        S0 = state.lanes[layer, b]
        hist = state.conv[layer].reshape(B, 3, -1)[b]
        if plan[0][b] >= 0 and base[b] > 0:
            S0 = state.slots[layer, plan[0][b]]
            hist = state.slot_conv[layer].reshape(SLOTS, 3, -1)[plan[0][b]]
        if base[b] == 0:
            S0, hist = jnp.zeros_like(S0), jnp.zeros_like(hist)
        want, states, rows = _recurrence(
            mc, u, taps, g, beta, S0, hist, int(seg_off[b]), n)
        np.testing.assert_allclose(o[seg_off[b]:seg_off[b] + n], want, **loose)
        np.testing.assert_allclose(new.lanes[layer, b], states[-1], **loose)
        np.testing.assert_allclose(
            new.conv[layer].reshape(B, 3, -1)[b], rows[-3:], rtol=1e-6, atol=1e-6)
        if plan[1][b] >= 0:  # the snapshot: the state after the cut's last row
            cut = int(plan[2][b]) - base[b]
            np.testing.assert_allclose(
                new.slots[layer, plan[1][b]], states[cut - 1], **loose)
            np.testing.assert_allclose(
                new.slot_conv[layer].reshape(SLOTS, 3, -1)[plan[1][b]],
                rows[cut:cut + 3], rtol=1e-6, atol=1e-6)


def test_chunks_of_counts_what_the_device_cuts():
    """The host's count of a dispatch's chunks (the ``/metrics`` counter) is
    the device's: ``delta_runs`` over the same plan, in the one chunk length
    the launch and the composition share."""
    assert gd.CHUNK == att.GDN_CHUNK
    for q_lens, base, plan, *_ in CASES.values():
        *_, run_len, _off = att.delta_runs(
            jnp.asarray(plan), jnp.asarray(base), jnp.zeros((B,), jnp.int32),
            jnp.asarray(q_lens))
        want = int(np.sum(-(-np.asarray(run_len) // gd.CHUNK)))
        assert gd.chunks_of(q_lens, base, plan) == want


def test_the_engine_counts_the_chunks_its_launches_run():
    """``dynamo_engine_gdn_chunks_total``: nothing where the XLA composition
    runs the chunks (here), and a launch's chunks a linear layer where the
    engine has read the kernel as its backend (set by hand: the step still
    traces the composition on a CPU).  A prompt of 100 tokens goes in
    chunks of 48, 48 and 4 rows, each one run of one chunk."""
    from tests.test_qwen3next import PROMPT, serve, served

    def chunks(engine):
        return int(engine.obs.registry.sample("dynamo_engine_gdn_chunks") or 0)

    async def body(engine):
        assert engine._delta_backend == "xla"
        await served(engine, PROMPT[:100], 2)
        assert chunks(engine) == 0
        engine._delta_backend = "kernel"
        await served(engine, PROMPT[100:200], 2)
        return chunks(engine), engine.model_cfg.kind_layers("linear")

    got, linear_layers = serve(body)
    assert linear_layers == 6 and got == 3 * linear_layers
