"""Gated delta-rule layers beside gated attention layers (the ``qwen3_next``
family) on the served path, at toy widths, float32, seeded weights, against
the plain reference of the benchmark (``benchmark/reference_qwen3next.py``: no
cache, no state between calls, the linear layers by the token-by-token
recurrence, nothing of the program); the chunked form of the recurrence
against the recurrence itself; the state a lane carries and the snapshots a
prefix hit resumes from; the chip's share of the experts; and what this trunk
refuses by name.

Tolerance.  Everything runs in float32 with ``highest`` matmul precision
(``conftest.py``); engine and reference differ in the order of their sums
(chunks of 64 against one token at a time), which over 8 layers of width 64
reads 1e-5 to 2e-5 on a log-probability.  ``TOL`` = 1e-4 leaves five times
that and is far under what it has to refuse: a layer written wrongly, a
state started from zeros or from another sequence's snapshot read above 1e-3
(tested).
"""

import asyncio
import dataclasses
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
from dynamo_tpu.engine import attention as att
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.kv_cache import (
    KV_REFUSALS, DeltaKV, PagedKVCache, StateSlots, kv_refusal,
)
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Annotated, Context
from dynamo_tpu.runtime.metrics import MetricsRegistry

W = importlib.import_module("benchmark.weights_qwen3next")
REF = importlib.import_module("benchmark.reference_qwen3next")

TOL = 1e-4
SEED = 11

# the catalog's ``config`` of Qwen3-Next-80B-A3B-Instruct, verbatim
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


def tiny(**over):
    """Two periods at toy widths: 4 of 16 experts held, top-4, heads of 16
    of which 4 columns turn, 2 key heads serving 4 value heads of 8 x 8."""
    cfg = dict(
        PUBLISHED, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8, num_experts=4,
        router_experts=16, expert_offset=0, num_experts_per_tok=4,
        vocab_size=256, num_hidden_layers=8, max_position_embeddings=4096,
        torch_dtype="float32",
    )
    cfg.update(over)
    return cfg


def model_config(cfg, **over):
    mc = ModelConfig.from_hf_config(cfg)
    return dataclasses.replace(
        mc, dtype=cfg["torch_dtype"],
        moe_capacity_factor=mc.num_experts / mc.num_experts_per_tok, **over)


def engine_config(**over):
    settings = dict(max_batch_size=2, max_seq_len=512, page_size=16,
                    num_pages=80, mixed_token_budget=48, state_snapshot_slots=6)
    settings.update(over)
    return EngineConfig(**settings)


def request(tokens, max_tokens, **sampling):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0, logprobs=5, **sampling),
    )


async def served(engine, tokens, max_tokens, **sampling):
    """(token ids, per-token [[id, logprob] x 5]) as the engine streams them."""
    stream = await engine.generate(
        Context.new(request(tokens, max_tokens, **sampling)))
    ids, tops = [], []
    async for item in stream:
        ann = item if isinstance(item, Annotated) else Annotated.from_dict(item)
        assert not ann.is_error(), ann.error_message()
        ids.extend(ann.data.get("token_ids") or [])
        tops.extend(ann.data.get("top_logprobs") or [])
    return ids, tops


_REFS = {}


def worst_gap(cfg, prompt, ids, tops):
    """Largest |served - reference| log-probability over every position's
    five listed tokens."""
    key = json.dumps(cfg, sort_keys=True)
    ref = _REFS.setdefault(key, REF.Reference(cfg))
    listed = [[int(t) for t, _lp in top] for top in tops]
    rows = [len(prompt) - 1 + i for i in range(len(ids))]
    want = ref.logprobs(SEED, list(prompt) + ids[:-1], rows, listed)
    got = np.array([[lp for _t, lp in top] for top in tops])
    return float(np.max(np.abs(got - want)))


def serve(body, cfg=None, mc=None, params=None, **settings):
    cfg = cfg or tiny()
    mc = mc or model_config(cfg)
    params = params if params is not None else W.build_params(cfg, SEED)

    async def main():
        engine = JaxEngine(mc, params, engine_config(**settings),
                           metrics_registry=MetricsRegistry())
        try:
            return await body(engine)
        finally:
            await engine.stop()

    return asyncio.run(main())


def counters(engine):
    reg = engine.obs.registry
    return {
        n: int(reg.sample(f"dynamo_engine_state_{n}") or 0)
        for n in ("restores", "resets", "walkbacks", "snapshots",
                  "snapshot_recompute_tokens", "snapshot_evictions")}


RNG = np.random.RandomState(5)
PROMPT = RNG.randint(3, 256, 200).tolist()  # five chunks of 48, not whole pages
OTHER = RNG.randint(3, 256, 200).tolist()


# -- the trunk against the reference -------------------------------------------


def test_chunks_fused_decode_restore_walk_back_and_a_pair_of_lanes():
    """A prompt in five chunks and twelve decoded tokens; the same again,
    resumed from the snapshot at its last whole block; a prompt that parts
    from it mid-chunk, walked back to the chunk end that has a snapshot and
    computed again from there; two prompts side by side: all within
    float32's rounding of the reference's full forward pass."""
    async def body(engine):
        first = await served(engine, PROMPT, 12)
        assert worst_gap(tiny(), PROMPT, *first) < TOL
        assert counters(engine)["resets"] == 1
        # chunk ends 48, 96, 144, 192; the last whole block of 200 is 192
        assert counters(engine)["snapshots"] == 4
        again = await served(engine, PROMPT, 12)
        assert again[0] == first[0]
        assert worst_gap(tiny(), PROMPT, *again) < TOL
        c = counters(engine)
        assert (c["restores"], c["snapshot_recompute_tokens"]) == (1, 0)
        # 160 tokens shared: blocks to 160 match, the deepest snapshot is 144
        branch = PROMPT[:160] + OTHER[:37]
        got = await served(engine, branch, 12)
        assert worst_gap(tiny(), branch, *got) < TOL
        c = counters(engine)
        assert (c["restores"], c["walkbacks"], c["snapshot_recompute_tokens"]) == (
            2, 1, 16)
        a, b = await asyncio.gather(
            served(engine, OTHER[:77], 10), served(engine, OTHER[100:171], 10))
        assert worst_gap(tiny(), OTHER[:77], *a) < TOL
        assert worst_gap(tiny(), OTHER[100:171], *b) < TOL

    serve(body)


def test_a_zeroed_or_a_stale_snapshot_is_outside_the_tolerance():
    """The check can see a lost state: with the snapshot pool zeroed, or the
    slots of two sequences exchanged, a request resumed from its snapshot
    reads far outside the tolerance; left alone it reads inside."""
    async def body(engine):
        await served(engine, PROMPT, 4)
        await served(engine, OTHER, 4)
        good = await served(engine, PROMPT, 8)
        assert worst_gap(tiny(), PROMPT, *good) < TOL
        kv = engine.kv.pages  # (every step donates it: read it anew each time)
        slots = np.asarray(kv.slots)
        engine.kv.pages = DeltaKV(
            kv.attn, kv.lanes, kv.conv, jnp.zeros_like(kv.slots),
            jnp.zeros_like(kv.slot_conv), kv.plan)
        zeroed = await served(engine, PROMPT, 8)
        assert worst_gap(tiny(), PROMPT, *zeroed) > 10 * TOL
        kv = engine.kv.pages
        engine.kv.pages = DeltaKV(
            kv.attn, kv.lanes, kv.conv, jnp.asarray(slots[:, ::-1]),
            kv.slot_conv, kv.plan)
        stale = await served(engine, PROMPT, 8)
        assert worst_gap(tiny(), PROMPT, *stale) > 10 * TOL

    serve(body, state_snapshot_slots=12)


def _full_rope(cfg, mc, params):
    return dataclasses.replace(mc, partial_rotary_factor=1.0), params


def _no_output_gate(cfg, mc, params):
    attn = dict(params["layers"]["attn"])
    attn["wq"] = attn["wq"][..., : attn["wq"].shape[-1] // 2]
    layers = dict(params["layers"], attn=attn)
    return dataclasses.replace(mc, attn_output_gate=False), dict(params, layers=layers)


def _taps_reversed(cfg, mc, params):
    lin = dict(params["layers"]["linear"])
    lin["gdn_taps"] = lin["gdn_taps"][:, ::-1]
    return mc, dict(params, layers=dict(params["layers"], linear=lin))


def _plain_norm_weights(cfg, mc, params):
    return dataclasses.replace(mc, rms_norm_offset=False), params


def _ungated_shared_expert(cfg, mc, params):
    layers = {k: v for k, v in params["layers"].items() if k != "ws_router"}
    return mc, dict(params, layers=layers)


def _published_column_order(cfg, mc, params):
    """The projection as published, a key head at a time, where the tree
    wants every head's q first."""
    s = W.sizes(cfg)
    key = W.seed_key(SEED)
    raw = jnp.stack([
        W.operator_weights(s, key, l, "linear")["gdn_in"]
        for l in range(s["L"]) if s["kinds"][l] == "linear"])
    lin = dict(params["layers"]["linear"], gdn_in=raw)
    return mc, dict(params, layers=dict(params["layers"], linear=lin))


@pytest.mark.parametrize("broken", [
    _full_rope, _no_output_gate, _taps_reversed, _plain_norm_weights,
    _ungated_shared_expert, _published_column_order,
], ids=lambda f: f.__name__.strip("_"))
def test_the_comparison_refuses_a_wrong_layer(broken):
    """Each part of the two operators and of the expert layer is held by the
    reference: written wrongly, the served engine reads far outside."""
    cfg = tiny()
    mc, params = broken(cfg, model_config(cfg), W.build_params(cfg, SEED))

    async def body(engine):
        return await served(engine, PROMPT[:70], 6)

    got = serve(body, cfg=cfg, mc=mc, params=params)
    assert worst_gap(cfg, PROMPT[:70], *got) > 10 * TOL


# -- the chunked form against the recurrence -------------------------------------


def _delta_case(q_lens, base, plan=None, B=4, Np=256, seed=0, state=None):
    """One linear layer's packed rows through ``packed_delta_mix`` and, lane
    by lane, through the recurrence one token at a time."""
    mc = model_config(tiny())
    Hv, dk, dv = 4, 8, 8
    C = mc.linear_conv_width
    rng = np.random.RandomState(seed)
    u = jnp.asarray(rng.standard_normal((Np, C)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((4, C)) / 2, jnp.float32)
    g = -jnp.asarray(rng.uniform(1e-3, 0.2, (Np, Hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.9, (Np, Hv)), jnp.float32)
    if state is None:
        state = DeltaKV(
            jnp.zeros((1,)),
            jnp.asarray(rng.standard_normal((1, B, Hv, dk, dv)) * 0.3, jnp.float32),
            jnp.asarray(rng.standard_normal((1, 3 * B, C)), jnp.float32),
            jnp.asarray(rng.standard_normal((1, 5, Hv, dk, dv)) * 0.3, jnp.float32),
            jnp.asarray(rng.standard_normal((1, 15, C)), jnp.float32),
            jnp.full((3, B), -1, jnp.int32),
        )
    if plan is not None:
        state = state.with_plan(jnp.asarray(plan, jnp.int32))
    q_lens = np.asarray(q_lens, np.int32)
    seg_off = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
    o, new = jax.jit(att.packed_delta_mix, static_argnums=0)(
        mc, u, taps, g, beta, state, jnp.int32(0),
        *(jnp.asarray(a, jnp.int32) for a in (base, seg_off, q_lens)))

    def recurrence(b, S, hist):
        n, off = int(q_lens[b]), int(seg_off[b])
        rows = jnp.concatenate([hist, u[off:off + n]])
        x = jax.nn.silu(sum(taps[i] * rows[i:i + n] for i in range(4)))
        q, k, v = att._gdn_heads(mc, x)
        out = []
        for t in range(n):
            S = jnp.exp(g[off + t])[:, None, None] * S
            d = beta[off + t][:, None] * (v[t] - jnp.einsum("hk,hkv->hv", k[t], S))
            S = S + k[t][:, :, None] * d[:, None, :]
            out.append(jnp.einsum("hk,hkv->hv", q[t], S))
        return jnp.stack(out), S, rows[-3:]

    return o, new, state, seg_off, recurrence


@pytest.mark.parametrize("q_lens,base", [
    ([200, 0, 0, 0], [0, 0, 0, 0]),  # three whole chunks and 8 rows
    ([37, 0, 90, 0], [64, 0, 0, 0]),  # shorter than a chunk; from position 0
    ([100, 1, 1, 70], [16, 300, 7, 0]),  # beside decode rows, mid-chunk starts
    ([1, 1, 1, 1], [5, 6, 7, 8]),  # decode rows alone: the loop turns no time
], ids=["one-long", "short-and-fresh", "beside-decode-rows", "decode-only"])
def test_the_chunked_form_is_the_recurrence(q_lens, base):
    """Segments that start mid-chunk of the packed axis, are shorter than a
    chunk, start a sequence (zeros) or go on from the lane's state, and sit
    beside decode rows in one packed step: rows, states and the three rows of
    history as the token-by-token recurrence leaves them."""
    o, new, state, seg_off, recurrence = _delta_case(q_lens, base)
    for b, n in enumerate(q_lens):
        S0 = state.lanes[0, b] * (base[b] > 0)
        hist = state.conv[0].reshape(4, 3, -1)[b] * (base[b] > 0)
        if not n:  # a lane without rows keeps what it had
            np.testing.assert_array_equal(new.lanes[0, b], state.lanes[0, b])
            continue
        want, S, rows = recurrence(b, S0, hist)
        np.testing.assert_allclose(
            o[seg_off[b]:seg_off[b] + n], want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(new.lanes[0, b], S, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            new.conv[0].reshape(4, 3, -1)[b], rows, rtol=1e-6, atol=1e-6)


def test_a_restore_gives_the_bits_of_the_lane_that_went_on():
    """A lane takes a snapshot where its chunk ends (position 96) and goes
    on; another lane restores from that slot and computes the same rows: the
    same chunks from the same state, bit for bit.  A snapshot taken
    mid-chunk (the last whole block of a prompt) is the state a chunk that
    ends there leaves."""
    plan = np.full((3, 4), -1, np.int32)
    plan[1, 0], plan[2, 0] = 2, 96
    o1, mid, *_ = _delta_case([96, 0, 0, 0], [0, 0, 0, 0], plan)
    went_on, end, *_ = _delta_case([64, 0, 0, 0], [96, 0, 0, 0], state=mid, seed=1)
    plan = np.full((3, 4), -1, np.int32)
    plan[0, 3] = 2
    restored, end2, *_ = _delta_case(
        [0, 0, 0, 64], [0, 0, 0, 96], plan, state=mid, seed=1)
    np.testing.assert_array_equal(went_on[:64], restored[:64])
    np.testing.assert_array_equal(end.lanes[0, 0], end2.lanes[0, 3])
    np.testing.assert_array_equal(end.conv[0, :3], end2.conv[0, 9:12])
    # mid-chunk: rows [0, 100) with the snapshot at 80
    plan = np.full((3, 4), -1, np.int32)
    plan[1, 0], plan[2, 0] = 4, 80
    _o, cut, before, *_ = _delta_case([100, 0, 0, 0], [0, 0, 0, 0], plan)
    _o, whole, *_ = _delta_case([80, 0, 0, 0], [0, 0, 0, 0])
    np.testing.assert_array_equal(cut.slots[0, 4], whole.lanes[0, 0])
    np.testing.assert_array_equal(cut.slot_conv[0, 12:15], whole.conv[0, :3])
    # and no other slot moved
    np.testing.assert_array_equal(cut.slots[0, :4], before.slots[0, :4])


def test_a_frozen_lanes_state_does_not_move():
    mc = model_config(tiny())
    rng = np.random.RandomState(3)
    C = mc.linear_conv_width
    state = DeltaKV(
        jnp.zeros((1,)),
        jnp.asarray(rng.standard_normal((1, 2, 4, 8, 8)), jnp.float32),
        jnp.asarray(rng.standard_normal((1, 6, C)), jnp.float32),
        jnp.zeros((1, 1, 4, 8, 8)), jnp.zeros((1, 3, C)),
        jnp.full((3, 2), -1, jnp.int32))
    u = jnp.asarray(rng.standard_normal((2, C)), jnp.float32)
    g = -jnp.full((2, 4), 0.1)
    beta = jnp.full((2, 4), 0.5)
    _o, new = att.decode_delta_mix(
        mc, u, jnp.ones((4, C)) / 2, g, beta, state, jnp.int32(0),
        jnp.asarray([True, False]))
    assert not np.array_equal(new.lanes[0, 0], state.lanes[0, 0])
    np.testing.assert_array_equal(new.lanes[0, 1], state.lanes[0, 1])
    np.testing.assert_array_equal(new.conv[0, 3:], state.conv[0, 3:])
    np.testing.assert_array_equal(new.conv[0, 2], u[0])


# -- the chip's share of the experts ----------------------------------------------


def test_four_ranks_partial_sums_with_the_shared_expert_once_are_the_uncut_layer():
    """Four chips share a layer, 4 of 16 experts each: every rank routes
    over all 16 and computes its own experts' part; the parts, with what
    every rank computes alike (the gated shared expert) counted once, add up
    to the layer with all 16 held."""
    whole_cfg = tiny(num_experts=16, router_experts=16)
    s = W.sizes(whole_cfg)
    key = W.seed_key(SEED)
    lp = dict(W.layer_weights(s, key, 2))
    experts = [W.expert_weights(s, key, 2, e) for e in range(16)]
    stack = lambda es: {  # noqa: E731
        k: jnp.stack([e[k] for e in es]) for k in ("w_gate", "w_up", "w_down")}
    x = jnp.asarray(np.random.RandomState(2).standard_normal((24, 64)), jnp.float32)
    whole = M._moe_mlp({**lp, **stack(experts)}, x, model_config(whole_cfg))
    shared = M._shared_experts(lp, x, model_config(whole_cfg))
    parts = 0
    for rank in range(4):
        mc = model_config(tiny(expert_offset=4 * rank))
        assert (mc.num_experts, mc.experts_held, mc.local_expert_offset) == (
            16, 4, 4 * rank)
        held = stack(experts[4 * rank:4 * rank + 4])
        parts = parts + M._moe_mlp({**lp, **held}, x, mc) - shared
    np.testing.assert_allclose(parts + shared, whole, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(shared).max()) > 0.01  # the gate leaves it something


# -- the configuration ------------------------------------------------------------


def test_from_hf_config_reads_the_catalogs_config_whole():
    mc = ModelConfig.from_hf_config(PUBLISHED)
    assert mc.layer_pattern == ("linear", "linear", "linear", "full")
    assert (mc.kind_layers("linear"), mc.kind_layers("full")) == (36, 12)
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim, mc.rope_dim) == (16, 2, 256, 64)
    assert (mc.linear_num_key_heads, mc.linear_num_value_heads,
            mc.linear_key_head_dim, mc.linear_value_head_dim,
            mc.linear_conv_width) == (16, 32, 128, 128, 8192)
    assert (mc.num_experts, mc.experts_held, mc.num_experts_per_tok,
            mc.intermediate_size, mc.num_shared_experts) == (512, 512, 10, 512, 1)
    assert mc.rms_norm_offset and mc.qk_norm and mc.attn_output_gate
    assert mc.shared_expert_gate and not mc.tie_word_embeddings
    assert (mc.rope_theta, mc.rms_norm_eps, mc.vocab_size) == (1e7, 1e-6, 151936)
    assert mc.kv_geometry == (12, 2, 2, 256) and mc.state_kind == "linear"


@pytest.mark.parametrize("change,match", [
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step=2"),
    ({"linear_conv_kernel_dim": 3}, "linear_conv_kernel_dim=3"),
    ({"num_hidden_layers": 46}, "not whole periods"),
    ({"layer_types": ["full_attention"] * 48}, "layer_types differs"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"use_sliding_window": True}, "sliding window"),
    ({"shared_expert_intermediate_size": 300}, "not a multiple"),
    ({"linear_num_value_heads": 24}, "not a multiple"),
    ({"router_experts": 256}, "lie outside"),
])
def test_what_qwen3_next_cannot_serve_fails_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(dict(PUBLISHED, **change))


def test_a_checkpoint_of_this_family_is_refused_by_name():
    from dynamo_tpu.engine.weights import assemble_params

    with pytest.raises(ValueError, match="qwen3_next.*not implemented"):
        assemble_params({}, model_config(tiny()), jnp.float32)


def test_the_loaders_column_order_is_the_published_one_part_by_part():
    """``W_qkvz`` reads a key head at a time ``[q | k | v | z]``; the tree
    keeps every head's q, then every head's k, ..."""
    cols = M.delta_columns(2, (3, 3, 4, 4))
    assert cols.tolist() == [
        0, 1, 2, 14, 15, 16, 3, 4, 5, 17, 18, 19,
        6, 7, 8, 9, 20, 21, 22, 23, 10, 11, 12, 13, 24, 25, 26, 27]
    s = W.sizes(tiny())
    raw = W.operator_weights(s, W.seed_key(SEED), 0, "linear")
    got = W.engine_order(s, "linear", raw)["gdn_in"]
    np.testing.assert_array_equal(
        got, raw["gdn_in"][:, M.delta_columns(2, (8, 8, 16, 16))])


# -- what moves or rewinds KV refuses by one sentence ----------------------------


def test_every_capability_a_convolution_trunk_refuses_this_trunk_refuses():
    mc, conv = model_config(tiny()), {"conv"}
    for capability, (_what, kinds) in KV_REFUSALS.items():
        if conv & set(kinds):
            sentence = kv_refusal(mc, capability)
            assert sentence and "gated delta-rule layers" in sentence, capability


@pytest.mark.parametrize("settings", [
    {"host_offload_blocks": 4}, {"kv_dtype": "int8"}, {"kv_remote": "on"},
    {"tp": 2}, {"mixed_batching": False},
], ids=lambda s: next(iter(s)))
def test_what_moves_kv_refuses_delta_rule_layers_at_configuration(settings):
    cfg = tiny()
    with pytest.raises(ValueError, match="gated delta-rule layers"):
        JaxEngine(model_config(cfg), W.build_params(cfg, SEED),
                  engine_config(**settings))


def test_requests_and_steps_that_leave_the_packed_step_are_refused():
    async def body(engine):
        stream = await engine.generate(
            Context.new(request(PROMPT, 2, frequency_penalty=0.5)))
        items = [i async for i in stream]
        ann = items[0] if isinstance(items[0], Annotated) else Annotated.from_dict(items[0])
        assert ann.is_error() and "gated delta-rule layers" in ann.error_message()
        with pytest.raises(ValueError, match="gated delta-rule layers"):
            await engine.generate_external(Context.new(request(PROMPT, 2)))
        with pytest.raises(ValueError, match="gated delta-rule layers"):
            await engine.embed([PROMPT[:8]])

    serve(body)
    with pytest.raises(ValueError, match="max_lanes"):
        PagedKVCache(model_config(tiny()), num_pages=8, page_size=16)


# -- the cache and its table ------------------------------------------------------


def test_the_pool_holds_the_full_layers_and_the_state_rides_beside_it():
    mc = model_config(tiny())
    kv = PagedKVCache(mc, num_pages=8, page_size=16, max_lanes=2, state_slots=3)
    p = kv.pages
    assert isinstance(p, DeltaKV)
    assert p.attn.shape == (2, 2, 8, 16, 2, 16)  # 2 of 8 layers hold pages
    assert p.lanes.shape == (6, 2, 4, 8, 8) and p.lanes.dtype == jnp.float32
    assert p.conv.shape == (6, 6, 64)
    assert p.slots.shape == (6, 3, 4, 8, 8) and p.slots.dtype == jnp.float32
    assert p.slot_conv.shape == (6, 9, 64) and p.plan.shape == (3, 2)
    assert kv.state_bytes == {
        "lanes": 6 * 2 * 256 * 4 + 6 * 6 * 64 * 4,
        "slots": 6 * 3 * 256 * 4 + 6 * 9 * 64 * 4}
    assert len(jax.tree_util.tree_leaves(p)) == 6


def test_the_slot_table_is_lru_holds_what_a_lane_waits_on_and_dies_with_its_block():
    t = StateSlots(2)
    assert t.take(10) == 0 and t.take(11) == 1 and len(t) == 2
    assert t.take(10) is None  # has one: used now, so 11 is the older
    assert t.take(12) == 1 and 11 not in t and t.evictions == 1
    assert t.hold(10) == 0  # a lane will restore from it
    assert t.take(13) == 1 and 12 not in t  # 10 is older but waited on
    t.hold(13)
    assert t.take(14) is None  # every slot is waited on
    t.release(10)
    t.release(13)
    assert t.take(14) == 0 and 10 not in t
    t.drop(14)  # the registry let the block go
    assert 14 not in t and t.take(15) == 0 and t.evictions == 3


def test_the_dispatch_annotation_and_the_request_span_say_what_was_restored():
    async def body(engine):
        await served(engine, PROMPT, 2)
        plans = []
        plan = engine.sched.state_plan

        def spy(chunks):
            out = plan(chunks)
            plans.append(out.copy())
            return out

        engine.sched.state_plan = spy
        await served(engine, PROMPT[:160] + OTHER[:20], 2)
        first = plans[0]
        # resumed from the snapshot at 144, a snapshot at the chunk's end
        assert (first[0] >= 0).sum() == 1 and (first[1] >= 0).sum() == 1
        assert all((p[0] < 0).all() for p in plans[1:])
        return counters(engine)

    c = serve(body)
    assert c["restores"] == 1 and c["snapshot_recompute_tokens"] == 16


def test_int8_weights_cover_the_delta_rules_projections():
    from dynamo_tpu.engine.quant import QUANT_KEYS, QuantizedTensor, quantize_params

    assert {"gdn_in", "gdn_out"} <= set(QUANT_KEYS)
    cfg = tiny()
    q = quantize_params(W.build_params(cfg, SEED), model_config(cfg))
    lin = q["layers"]["linear"]
    assert isinstance(lin["gdn_in"], QuantizedTensor)
    assert isinstance(lin["gdn_out"], QuantizedTensor)
    assert isinstance(q["layers"]["attn"]["wq"], QuantizedTensor)
    assert not isinstance(lin["gdn_a_log"], QuantizedTensor)

    async def body(engine):
        return await served(engine, PROMPT, 6)

    got = serve(body, quantize="int8")
    assert 10 * TOL < worst_gap(tiny(), PROMPT, *got) < 0.5
