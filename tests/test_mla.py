"""Latent attention (MLA) on the served path: the ``mistral4`` family at tiny
widths, float32, seeded weights, against the plain reference of the
benchmark (``benchmark/reference_mistral4.py``: no cache, no kernels, the
up-projected form, nothing of the program).

Tolerances.  Everything here runs in float32 with ``highest`` matmul
precision (``conftest.py``); engine and reference differ in the order of
their sums only.  Over 2-3 layers of width 64 that reads 1e-6 to 4e-6 on a
log-probability; ``TOL`` = 2e-4 leaves fifty times that, and is a tenth of
the smallest thing it has to refuse: bfloat16 arithmetic reads 2e-2 or more
(tested), a dropped query factor, a missing shared expert and routing over
the held experts alone each read above 1e-2 (tested).
"""

import asyncio
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
from dynamo_tpu.engine import attention as att
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.kv_cache import LatentKV, PagedKVCache
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Annotated, Context

W = importlib.import_module("benchmark.weights_mistral4")
REF = importlib.import_module("benchmark.reference_mistral4")

TOL = 2e-4
SEED = 11

# the published keys of Mistral-Small-4-119B-2603's config.json
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 12288,
    "kv_lora_rank": 256, "max_position_embeddings": 1048576,
    "mlp_bias": False, "model_type": "mistral4",
    "moe_intermediate_size": 2048, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 36,
    "num_key_value_heads": 32, "q_lora_rank": 1024, "qk_head_dim": 128,
    "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True,
    "rope_parameters": {
        "beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn",
    },
    "routed_scaling_factor": 1, "sliding_window": None,
    "tie_word_embeddings": False, "topk_group": 1, "v_head_dim": 128,
    "vocab_size": 131072,
}


def tiny(**over):
    """The family at tiny widths: this chip holds experts 4-7 of 16;
    positions cross ``original_max_position_embeddings`` = 32, so YaRN's
    blend and the query factor both act."""
    cfg = dict(
        PUBLISHED, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, qk_head_dim=16, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=4, router_experts=16, expert_offset=4,
        num_experts_per_tok=2, vocab_size=256, torch_dtype="float32",
        rope_parameters=dict(
            PUBLISHED["rope_parameters"], original_max_position_embeddings=32
        ),
    )
    cfg.update(over)
    return cfg


def model_config(cfg, **over):
    mc = ModelConfig.from_hf_config(cfg)
    return dataclasses.replace(
        mc, dtype=cfg["torch_dtype"],
        moe_capacity_factor=mc.num_experts / mc.num_experts_per_tok, **over,
    )


def request(tokens, max_tokens):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0, logprobs=5),
    )


async def served(engine, tokens, max_tokens):
    """(token ids, per-token [[id, logprob] x 5]) as the engine streams them."""
    stream = await engine.generate(Context.new(request(tokens, max_tokens)))
    ids, tops = [], []
    async for item in stream:
        ann = item if isinstance(item, Annotated) else Annotated.from_dict(item)
        assert not ann.is_error(), ann.error_message()
        ids.extend(ann.data.get("token_ids") or [])
        tops.extend(ann.data.get("top_logprobs") or [])
    return ids, tops


def worst_gap(cfg, prompt, ids, tops):
    """Largest |served - reference| log-probability over every position's
    five listed tokens."""
    listed = [[int(t) for t, _lp in top] for top in tops]
    rows = [len(prompt) - 1 + i for i in range(len(ids))]
    ref = REF.Reference(cfg).logprobs(SEED, list(prompt) + ids[:-1], rows, listed)
    got = np.array([[lp for _t, lp in top] for top in tops])
    return float(np.max(np.abs(got - ref)))


def serve_twice(cfg, mc, params, prompt, n=8):
    """Serve ``prompt`` twice through one engine: three chunks of prefill
    then decode through the latent cache, then again with its pages in the
    prefix cache.  Returns the two gaps and the prefix-hit tokens."""

    async def body():
        engine = JaxEngine(mc, params, EngineConfig(
            max_batch_size=2, max_seq_len=192, page_size=16, num_pages=40,
            mixed_token_budget=40,
        ))
        hits = engine.obs.prefix_hits._value
        try:
            before = hits.get()  # the registry outlives an engine
            first = await served(engine, prompt, n)
            again = await served(engine, prompt, n)
            assert engine.obs.kv_bytes_per_token._value.get() == (
                engine.kv.bytes_per_token)
            return first, again, hits.get() - before
        finally:
            await engine.stop()

    first, again, hits = asyncio.run(body())
    assert first[0] == again[0]
    return worst_gap(cfg, prompt, *first), worst_gap(cfg, prompt, *again), hits


PROMPT = list(np.random.RandomState(5).randint(0, 256, 100))


def test_served_chunks_decode_and_prefix_hit_match_the_reference():
    cfg = tiny()
    first, again, hits = serve_twice(
        cfg, model_config(cfg), W.build_params(cfg, SEED), PROMPT)
    assert hits >= 96  # the second ask found the document's six whole pages
    assert first < TOL and again < TOL, (first, again)


def _without_shared(cfg, mc, params):
    layers = {k: v for k, v in params["layers"].items() if not k.startswith("ws_")}
    return dataclasses.replace(mc, num_shared_experts=0), dict(params, layers=layers)


def _without_query_factor(cfg, mc, params):
    return dataclasses.replace(mc, query_pos_scaling=None), params


def _routing_over_the_held(cfg, mc, params):
    lo, n = cfg["expert_offset"], cfg["n_routed_experts"]
    layers = dict(params["layers"], router=params["layers"]["router"][..., lo:lo + n])
    mc = dataclasses.replace(mc, num_experts=n, num_local_experts=0,
                             local_expert_offset=0, moe_capacity_factor=n / 2)
    return mc, dict(params, layers=layers)


def _in_bfloat16(cfg, mc, params):
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    return dataclasses.replace(mc, dtype="bfloat16"), half


@pytest.mark.parametrize("broken", [
    _without_shared, _without_query_factor, _routing_over_the_held, _in_bfloat16,
], ids=lambda f: f.__name__.strip("_"))
def test_the_comparison_refuses_a_wrong_layer(broken):
    """What the tolerance has to tell apart, each served the same way."""
    cfg = tiny()
    mc, params = broken(cfg, model_config(cfg), W.build_params(cfg, SEED))
    first, again, _hits = serve_twice(cfg, mc, params, PROMPT, n=4)
    assert min(first, again) > 10 * TOL, (first, again)


def test_absorbed_attention_equals_the_up_projected_form():
    """One layer's attention: the program carries the queries into the
    latent space and up-projects after (``model._latent_attention``); here
    every head's keys and values are materialised.  Same numbers."""
    cfg = tiny()
    mc = model_config(cfg)
    lp = jax.tree.map(lambda a: a[1], W.build_params(cfg, SEED)["layers"])
    T, Hq, C = 48, mc.num_heads, mc.kv_lora_rank
    N, R, V = mc.qk_nope_head_dim, mc.qk_rope_head_dim, mc.v_head_dim
    h = jax.random.normal(jax.random.PRNGKey(0), (1, T, mc.hidden_size))
    pos = jnp.arange(T)[None]
    cos, sin = M.rope_cos_sin(pos, R, mc.rope_theta, mc.rope_scaling)
    beta, orig = mc.query_pos_scaling
    q_factor = 1.0 + beta * jnp.log1p((pos // orig).astype(jnp.float32))

    def attend(q, k, v, kv, layer):
        return att.prefill_attention(q, k, v, jnp.array([T])), kv

    got, _ = M._latent_attention(lp, h, cos, sin, mc, attend, None, 0, q_factor)

    c_q = M.rms_norm(h @ lp["wq_a"], lp["q_a_norm"], mc.rms_norm_eps)
    q = (c_q @ lp["wq_b"]).reshape(1, T, Hq, N + R)
    kv_a = h @ lp["wkv_a"]
    c_kv = M.rms_norm(kv_a[..., :C], lp["kv_a_norm"], mc.rms_norm_eps)
    kv = (c_kv @ lp["wkv_b"]).reshape(1, T, Hq, N + V)
    k_r = M.apply_rope_interleaved(kv_a[..., None, C:], cos, sin)
    k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(k_r, (1, T, Hq, R))], -1)
    q = jnp.concatenate([q[..., :N], M.apply_rope_interleaved(q[..., N:], cos, sin)], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q * q_factor[..., None, None], k)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s * mc.attn_softmax_scale, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., N:])
    assert float(jnp.max(jnp.abs(got - want.reshape(1, T, Hq * V)))) < 1e-5


@pytest.mark.parametrize("rows", [24, 256], ids=["capacity", "grouped"])
def test_four_expert_shares_and_the_shared_expert_add_up_to_the_uncut_layer(rows):
    """Each of four chips holds 4 of the 16 experts and routes over all 16:
    their routed parts, and the shared expert counted once, are the layer
    with every expert local; and that layer, served, is the reference's
    (both layouts of the expert product: 24 rows take the capacity buffers,
    256 the grouped product)."""
    whole = tiny(n_routed_experts=16, router_experts=16, expert_offset=0,
                 num_hidden_layers=1)
    mc_whole = model_config(whole)
    lp = jax.tree.map(lambda a: a[0], W.build_params(whole, SEED)["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, rows, 64))
    uncut = M._moe_mlp(lp, x, mc_whole)
    shared = M._shared_experts(lp, x.reshape(-1, 64), mc_whole).reshape(x.shape)
    routed = 0
    for rank in range(4):
        share = tiny(expert_offset=4 * rank, num_hidden_layers=1)
        lp_s = jax.tree.map(lambda a: a[0], W.build_params(share, SEED)["layers"])
        np.testing.assert_array_equal(
            lp_s["w_up"], lp["w_up"][4 * rank:4 * rank + 4])  # the same experts
        routed = routed + M._moe_mlp(lp_s, x, model_config(share)) - shared
    assert float(jnp.max(jnp.abs(routed + shared - uncut))) < 1e-5
    # and the uncut layer, on the served path, is the reference's
    prompt = PROMPT[:40]
    first, again, _ = serve_twice(
        whole, mc_whole, W.build_params(whole, SEED), prompt, n=3)
    assert first < TOL and again < TOL


def test_latent_pool_geometry_and_bytes():
    """320 values a token a layer at the published widths, two layers a
    slab row of 640: 6 layers are 3840 B a token in bfloat16, and no array
    of the pool holds a second copy of anything."""
    cfg = dict(PUBLISHED, num_hidden_layers=6, n_routed_experts=32,
               router_experts=128, vocab_size=32768)
    mc = ModelConfig.from_hf_config(cfg)
    assert mc.kv_geometry == (3, 1, 1, 640)
    kv = PagedKVCache(mc, num_pages=4, page_size=16)
    assert isinstance(kv.pages, LatentKV) and kv.pages.c == 256
    assert kv.pages.shape == (3, 1, 4, 16, 1, 640)
    assert len(jax.tree.leaves(kv.pages)) == 1
    assert kv.bytes_per_page == 16 * 3840 and kv.bytes_per_token == 3840
    assert kv.pool_bytes == kv.pages.nbytes
    # an odd depth leaves half a slab row unused, and says so in its bytes
    odd = PagedKVCache(dataclasses.replace(mc, num_layers=5), 4, 16)
    assert odd.pages.shape[0] == 3 and odd.bytes_per_token == 3840
    # the pair pools are as they were
    pair = PagedKVCache(ModelConfig.tiny(), num_pages=4, page_size=16)
    assert pair.pages.shape == (2, 2, 4, 16, 2, 16)
    assert pair.bytes_per_page == 2 * 2 * 16 * 2 * 16 * 4


def test_a_slab_row_keeps_both_layers():
    """Writing layer 1's rows leaves layer 0's in place, and each reads
    back its own."""
    pool = LatentKV(jnp.zeros((1, 1, 4, 16, 1, 48)), 16)
    rows = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 1, 24))
    ids, slot = jnp.array([1, 1, 2, 3, 3]), jnp.array([0, 7, 3, 15, 2])
    for layer in (0, 1):
        pool = pool.write(layer, ids, rows[layer], slot)
    table = jnp.arange(4)[None]
    for layer in (0, 1):
        got = pool.layer_view(layer).gather(table, jnp.float32)[0]
        np.testing.assert_array_equal(got[ids, slot], rows[layer])


def test_from_hf_config_reads_the_published_keys():
    mc = ModelConfig.from_hf_config(PUBLISHED)
    assert mc.is_mla and mc.is_moe
    assert (mc.q_lora_rank, mc.kv_lora_rank) == (1024, 256)
    assert (mc.qk_nope_head_dim, mc.qk_rope_head_dim, mc.v_head_dim) == (64, 64, 128)
    assert mc.head_dim == 128 and mc.rope_dim == 64 and mc.rope_interleave
    assert mc.intermediate_size == 2048  # the experts' width, not the dense 12288
    assert (mc.num_experts, mc.experts_held, mc.num_experts_per_tok) == (128, 128, 4)
    assert mc.num_shared_experts == 1 and mc.routed_scaling_factor == 1.0
    assert mc.rope_scaling == ("yarn", 128.0, 8192, 32.0, 1.0, 1.0, 1.0)
    assert mc.query_pos_scaling == (0.1, 8192)
    m = 0.1 * np.log(128) + 1
    assert mc.attn_softmax_scale == pytest.approx(128 ** -0.5 * m * m)
    held = ModelConfig.from_hf_config(
        dict(PUBLISHED, n_routed_experts=32, router_experts=128, expert_offset=32))
    assert (held.num_experts, held.experts_held, held.local_expert_offset) == (128, 32, 32)


@pytest.mark.parametrize("change,match", [
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
    ({"n_group": 2}, "grouped routing"),
    ({"rope_parameters": {"rope_type": "longrope"}}, "longrope"),
    ({"n_routed_experts": 32, "router_experts": 128, "expert_offset": 100}, "outside the router"),
])
def test_what_mistral4_cannot_serve_fails_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(dict(PUBLISHED, **change))


def _engine(**over):
    cfg = tiny()
    return JaxEngine(
        model_config(cfg), W.build_params(cfg, SEED),
        EngineConfig(**dict(dict(max_batch_size=2, max_seq_len=64, page_size=16,
                                 num_pages=16), **over)))


@pytest.mark.parametrize("settings", [
    {"host_offload_blocks": 4},
    {"kv_remote": "on"},
    {"kv_dtype": "int8"},
], ids=["offload", "g4", "int8_kv"])
def test_what_moves_kv_pairs_refuses_a_latent_cache_at_configuration(settings):
    with pytest.raises(ValueError, match="latent cache"):
        _engine(**settings)


def test_tp_and_disaggregation_refuse_a_latent_cache():
    mc = model_config(tiny())
    with pytest.raises(ValueError, match="latent cache"):
        mc.validate_tp(2)
    mc.validate_tp(1)
    engine = _engine()
    for call in (
        lambda: engine.deliver_external("r", np.zeros(1), 0),
        lambda: engine.deliver_external_chunk("r", 0, 1, np.zeros(1)),
        lambda: engine._refuse("kv_delivery"),
    ):
        with pytest.raises(ValueError, match="latent cache"):
            call()
    for coro in (engine.generate_external(Context.new(request([1, 2], 1))),
                 engine.prefill_export(request([1, 2], 1))):
        with pytest.raises(ValueError, match="latent cache"):
            asyncio.run(coro)


def test_kv_bytes_per_token_gauge():
    """pool bytes / pool tokens, for every model."""
    engine = _engine()
    # two layers x (16 + 8) values x float32; serve_twice reads the gauge
    assert engine.kv.bytes_per_token == 2 * 24 * 4
    pair = JaxEngine.random_init(ModelConfig.tiny(), EngineConfig(
        max_batch_size=2, max_seq_len=64, page_size=16, num_pages=16))
    assert pair.kv.bytes_per_token == 2 * 2 * 2 * 16 * 4


def test_checkpoint_tensor_names():
    """``weights.assemble_params`` finds a mistral4 checkpoint's tensors by
    their published names and holds the experts this process holds."""
    from dynamo_tpu.engine.weights import assemble_params

    cfg = tiny(num_hidden_layers=1)
    mc = model_config(cfg)
    want = jax.tree.map(np.asarray, W.build_params(cfg, SEED))
    lw = {k: v[0] for k, v in want["layers"].items()}
    t = lambda a: np.ascontiguousarray(a.T)  # noqa: E731  torch stores [out, in]
    pre = "model.layers.0."
    raw = {
        "model.embed_tokens.weight": want["embed"],
        "model.norm.weight": want["final_norm"],
        "lm_head.weight": t(want["lm_head"]),
        pre + "input_layernorm.weight": lw["input_norm"],
        pre + "post_attention_layernorm.weight": lw["post_norm"],
        pre + "self_attn.q_a_proj.weight": t(lw["wq_a"]),
        pre + "self_attn.q_a_layernorm.weight": lw["q_a_norm"],
        pre + "self_attn.q_b_proj.weight": t(lw["wq_b"]),
        pre + "self_attn.kv_a_proj_with_mqa.weight": t(lw["wkv_a"]),
        pre + "self_attn.kv_a_layernorm.weight": lw["kv_a_norm"],
        pre + "self_attn.kv_b_proj.weight": t(lw["wkv_b"]),
        pre + "self_attn.o_proj.weight": t(lw["wo"]),
        pre + "mlp.gate.weight": t(lw["router"]),
    }
    for name, key in (("gate_proj", "gate"), ("up_proj", "up"), ("down_proj", "down")):
        raw[pre + f"mlp.shared_experts.{name}.weight"] = t(lw["ws_" + key])
        for e in range(16):  # a whole checkpoint: every published expert
            held = e - cfg["expert_offset"]
            raw[pre + f"mlp.experts.{e}.{name}.weight"] = (
                t(lw["w_" + key][held]) if 0 <= held < 4
                else np.full(lw["w_" + key][0].T.shape, np.nan, np.float32))
    got = assemble_params(raw, mc, jnp.float32)
    for k, v in want["layers"].items():
        np.testing.assert_array_equal(np.asarray(got["layers"][k]), v, err_msg=k)
    for k in ("embed", "final_norm", "lm_head"):
        np.testing.assert_array_equal(np.asarray(got[k]), want[k])


# (fresh rows, first position) a lane, s_max, Np, then the decode step's
# contexts; a key block is 512 keys, a page 16, a wide item's sub-tile 32
# tokens and a loop step two of them
_KERNEL_CASES = {
    # a decode row, two prefill chunks of which one starts at position 0,
    # an idle lane
    "mixed": ([(1, 37), (20, 16), (0, 0), (5, 0)], 32, 64, [38, 36, 1, 5]),
    # rows that are no multiple of a sub-tile or of a step, a lane of no
    # rows between two live ones, first positions that are no multiple of
    # the key block, contexts that end inside a page and inside a key block
    "edges": ([(1, 700), (9, 513), (0, 0), (65, 37), (200, 1000), (256, 300)],
              256, 1024, [701, 522, 1, 102, 1200, 556]),
    # a 2048-token chunk of a document beside decode rows, one launch
    "chunk": ([(1, 1500), (1, 30), (2048, 600), (1, 511)], 2048, 4096,
              [1501, 31, 2648, 512]),
}


@pytest.mark.parametrize("layer,case", [
    pytest.param(0, "mixed", id="0"), pytest.param(1, "mixed", id="1"),
    pytest.param(2, "mixed", id="2"),
    pytest.param(2, "edges", id="edges-even"),
    pytest.param(3, "edges", id="edges-odd"),
    pytest.param(0, "chunk", id="chunk-even"),
    pytest.param(1, "chunk", id="chunk-odd"),
])
def test_latent_kernels_match_the_xla_composition(layer, case):
    """Both Pallas kernels through the interpreter, on either half of a
    slab row: a mixed dispatch and a decode step (``_KERNEL_CASES``)."""
    from dynamo_tpu.ops.latent_attention import (
        latent_decode_attention, latent_packed_attention)
    from dynamo_tpu.ops.ragged_attention import packed_ragged_attention_xla

    lanes, s_max, Np, lens = _KERNEL_CASES[case]
    page, Wd, C, Hq, B = 16, 24, 16, 4, len(lanes)
    q_lens, base = (np.array(x) for x in zip(*lanes))
    Pw = -(-max(int((q_lens + base).max()), max(lens)) // page)
    P = B * Pw + 1
    rng = np.random.RandomState(layer)
    kv = LatentKV(jnp.asarray(rng.randn(2, 1, P, page, 1, 2 * Wd), jnp.float32), C)
    pt = jnp.asarray(rng.permutation(np.arange(1, P))[: B * Pw].reshape(B, Pw))
    seg_off = np.concatenate([[0], np.cumsum(q_lens)[:-1]])
    lane, rel = np.full(Np, B), np.zeros(Np, np.int32)
    for b in range(B):
        at = slice(seg_off[b], seg_off[b] + q_lens[b])
        lane[at], rel[at] = b, np.arange(q_lens[b])
    q = jnp.asarray(rng.randn(Np, Hq, Wd), jnp.float32)
    rows = jnp.asarray(rng.randn(Np, 1, Wd), jnp.float32)
    valid = lane < B
    pos = base[np.clip(lane, 0, B - 1)] + rel
    args = [jnp.asarray(a) for a in (base, seg_off, q_lens)]
    want = packed_ragged_attention_xla(
        q, rows, rows, kv, pt, *args, jnp.asarray(lane), jnp.asarray(rel),
        s_max, layer, 0)
    kv = att.write_packed_kv(kv, rows, rows, pt, jnp.asarray(lane),
                             jnp.asarray(pos), jnp.asarray(valid), layer)
    got = latent_packed_attention(q, kv, pt, *args, s_max, layer, interpret=True)
    assert float(jnp.max(jnp.abs(got[valid] - want[valid][..., :C]))) < 1e-5
    qd = jnp.asarray(rng.randn(B, Hq, Wd), jnp.float32)
    lens = jnp.asarray(lens)
    want = att.paged_decode_attention(qd, kv.layer_view(layer), pt, lens, 0)
    got = latent_decode_attention(qd, kv, pt, lens, layer, interpret=True)
    assert float(jnp.max(jnp.abs(got - want[..., :C]))) < 1e-5


def test_the_dispatch_annotation_names_the_latent_path():
    """Which latent path a packed dispatch takes is read off the backend at
    trace time (no flag): the XLA composition here, the kernel on a chip."""
    pool = LatentKV(jnp.zeros((1, 1, 3, 16, 1, 384), jnp.bfloat16), 128)
    assert att.latent_packed_path(pool) == "absorbed_xla"
    assert not att.latent_kernels_enabled(16)
