"""Window and full attention layers in one trunk (the ``mellum`` family) on
the served path, at toy widths, float32, seeded weights, against the plain
reference of the benchmark (``benchmark/reference_mellum.py``: no cache, no
kernels, the window as a mask, nothing of the program); and the cache's two
pools: what a lane holds of the window pool, what a prefix hit needs of it,
and that the pages add up.

Tolerance.  Everything runs in float32 with ``highest`` matmul precision
(``conftest.py``); engine and reference differ in the order of their sums
only, which over 8 layers of width 64 reads 2e-6 to 5e-6 on a
log-probability.  ``TOL`` = 1e-4 leaves twenty times that and is far under
what it has to refuse: a window layer served without its window, or a full
layer's rotation on a window layer, reads above 1e-2 (tested).
"""

import asyncio
import dataclasses
import importlib

import numpy as np
import pytest

from dynamo_tpu.block_manager import PagePool
from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
from dynamo_tpu.engine.kv_cache import KindKV, PagedKVCache
from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, SeqState
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Annotated, Context

W = importlib.import_module("benchmark.weights_mellum")
REF = importlib.import_module("benchmark.reference_mellum")

TOL = 1e-4
SEED = 11
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]

# the catalog's ``config`` of Mellum2-12B-A2.5B-Instruct, verbatim
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    },
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}


def tiny(**over):
    """Two periods at toy widths: window 32 (two pages), 8 experts top-2;
    positions cross the full layers' ``original_max_position_embeddings`` =
    64, so YaRN's blend and its attention factor both act."""
    rope = dict(PUBLISHED["rope_parameters"])
    rope["full_attention"] = dict(
        rope["full_attention"], original_max_position_embeddings=64)
    cfg = dict(
        PUBLISHED, hidden_size=64, num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        vocab_size=256, sliding_window=32, torch_dtype="float32",
        rope_parameters=rope, layer_types=PERIOD * 2,
        mlp_layer_types=["sparse"] * 8,
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
                    num_pages=80, num_window_pages=48, mixed_token_budget=48)
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


def worst_gap(cfg, prompt, ids, tops):
    """Largest |served - reference| log-probability over every position's
    five listed tokens."""
    listed = [[int(t) for t, _lp in top] for top in tops]
    rows = [len(prompt) - 1 + i for i in range(len(ids))]
    ref = REF.Reference(cfg).logprobs(SEED, list(prompt) + ids[:-1], rows, listed)
    got = np.array([[lp for _t, lp in top] for top in tops])
    return float(np.max(np.abs(got - ref)))


def run(body):
    """``body(engine)`` over a fresh toy engine; returns what it returns."""

    async def main(mc, params, settings):
        engine = JaxEngine(mc, params, settings)
        try:
            return await body(engine)
        finally:
            await engine.stop()

    return main


def serve(body, cfg=None, mc=None, params=None, **settings):
    cfg = cfg or tiny()
    mc = mc or model_config(cfg)
    params = params if params is not None else W.build_params(cfg, SEED)
    return asyncio.run(run(body)(mc, params, engine_config(**settings)))


RNG = np.random.RandomState(5)
PROMPT = RNG.randint(3, 256, 200).tolist()  # six windows, five chunks of 48
OTHER = PROMPT[:100] + RNG.randint(3, 256, 60).tolist()


def pool_adds_up(pool):
    """free + used + reusable is the pool, and nothing is counted twice."""
    inactive = pool.num_inactive * pool.pages_per_block
    assert len(pool._free) + inactive + pool.used_pages == pool.num_pages - 1
    held = [p for b in pool._registered.values() for p in b.pages]
    assert len(set(held) | set(pool._free)) == len(held) + len(pool._free)


# -- the trunk against the reference -------------------------------------------


def test_served_chunks_decode_prefix_hit_and_walk_back_match_the_reference():
    """Chunked prefill over a prompt several windows and several chunks
    long, decode through both pools; the same prompt again from the prefix
    cache; and again after the window pool has taken back the blocks behind
    its tail, where the match walks back to the longest boundary whose
    window tail is still there."""
    cfg = tiny()

    async def body(engine):
        hits = engine.obs.prefix_hits._value
        wpool = engine.kv.window_allocator
        cold = await served(engine, PROMPT, 40)
        released = engine.sched.window_released
        h0 = hits.get()
        warm = await served(engine, PROMPT, 40)
        h1 = hits.get()
        # another prompt shares the first 96 tokens: its hit takes the window
        # blocks 4 and 5 and lets them go last, so they are the youngest
        await served(engine, OTHER, 4)
        # the window pool takes back its 12 oldest reusable blocks: PROMPT's
        # 0-3, 6-9, 12, 13 and then 10, 11 (the tail of a 192-token hit,
        # which the warm ask let go last); 4 and 5 are younger
        pages = wpool.alloc(len(wpool._free) + 12)
        wpool.free(pages)
        h2 = hits.get()
        back = await served(engine, PROMPT, 40)
        h3 = hits.get()
        for pool in (engine.kv.allocator, wpool):
            pool_adds_up(pool)
            assert pool.used_pages == 0
        return cold, warm, back, released, h1 - h0, h3 - h2

    cold, warm, back, released, hit_warm, hit_back = serve(body, cfg)
    assert cold[0] == warm[0] == back[0]
    # 200 + 40 tokens: the blocks behind the window of the last rows were
    # let go (the host releases at a tick's start, a block of steps behind)
    assert released >= (239 - 16 - 31) // 16
    assert hit_warm == 192  # twelve whole blocks, the tail 10, 11 resident
    assert hit_back == 96  # walked back to block 6: its tail 4, 5 is there
    gaps = [worst_gap(cfg, PROMPT, *r) for r in (cold, warm, back)]
    assert max(gaps) < TOL, gaps


def _wider_window(cfg, mc):
    return dataclasses.replace(mc, sliding_window=64)


def _one_rope(cfg, mc):
    return dataclasses.replace(mc, rope_by_kind=tuple(
        (kind, theta, None) for kind, theta, _s in mc.rope_by_kind))


def _bfloat16_weights(cfg, mc):
    return dataclasses.replace(mc, dtype="bfloat16")


@pytest.mark.parametrize("broken", [_wider_window, _one_rope, _bfloat16_weights],
                         ids=lambda f: f.__name__.strip("_"))
def test_the_comparison_refuses_a_wrong_layer(broken):
    """What the tolerance has to tell apart, each served the same way."""
    cfg = tiny()
    mc = broken(cfg, model_config(cfg))
    params = W.build_params(cfg, SEED)
    if mc.dtype == "bfloat16":
        import jax
        import jax.numpy as jnp

        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)

    async def body(engine):
        return await served(engine, PROMPT, 4)

    got = serve(body, cfg, mc, params)
    assert worst_gap(cfg, PROMPT, *got) > 10 * TOL


# -- the configuration ---------------------------------------------------------


def test_from_hf_config_reads_the_catalogs_config():
    mc = ModelConfig.from_hf_config(PUBLISHED)
    assert (mc.num_layers, mc.hidden_size, mc.num_heads, mc.num_kv_heads,
            mc.head_dim, mc.vocab_size) == (28, 2304, 32, 4, 128, 98304)
    assert (mc.num_experts, mc.num_experts_per_tok, mc.intermediate_size) == (64, 8, 896)
    assert mc.num_shared_experts == 0 and not mc.tie_word_embeddings
    assert mc.layer_pattern == ("sliding", "sliding", "sliding", "full")
    assert mc.two_kind and mc.sliding_window == 1024
    assert (mc.kind_layers("sliding"), mc.kind_layers("full")) == (21, 7)
    assert (mc.kind_window("sliding"), mc.kind_window("full")) == (1024, 0)
    assert mc.kind_rope("sliding") == (500000.0, None)
    theta, yarn = mc.kind_rope("full")
    assert theta == 500000.0 and yarn[:5] == ("yarn", 16.0, 8192, 32.0, 1.0)
    # attention_factor rides as the mscale that yields it, on cos and sin
    from dynamo_tpu.engine.config import _yarn_mscale

    assert abs(_yarn_mscale(yarn[1], yarn[5]) - 1.2772588722239782) < 1e-12
    assert yarn[6] == 0.0 and mc.rope_scaling is None
    # the cut the benchmark serves: three whole periods
    cut = dict(PUBLISHED, num_hidden_layers=12, layer_types=PERIOD * 3,
               mlp_layer_types=["sparse"] * 12)
    assert ModelConfig.from_hf_config(cut).kind_layers("full") == 3
    # one kind of layer is a trunk of one kind, whatever spells it
    full = ModelConfig.from_hf_config(dict(
        PUBLISHED, layer_types=["full_attention"] * 28))
    assert full.layer_pattern is None and full.sliding_window is None
    assert full.rope_scaling[0] == "yarn" and not full.two_kind
    slide = ModelConfig.from_hf_config(dict(
        PUBLISHED, layer_types=["sliding_attention"] * 28))
    assert slide.layer_pattern is None and slide.sliding_window == 1024


@pytest.mark.parametrize("change,match", [
    ({"layer_types": PERIOD * 6 + PERIOD[:2], "num_hidden_layers": 26},
     "not whole periods"),
    ({"layer_types": PERIOD * 6 + ["chunked_attention"] * 4}, "chunked_attention"),
    ({"layer_types": PERIOD * 6}, "24 entries"),
    ({"rope_parameters": {"rope_type": "longrope", "rope_theta": 1e4}}, "longrope"),
    ({"mlp_layer_types": ["sparse"] * 27 + ["dense"]}, "dense entry"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"sliding_window": None}, "no sliding_window"),
])
def test_what_mellum_cannot_serve_fails_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(dict(PUBLISHED, **change))


def test_a_window_mix_of_another_family_points_at_layer_types():
    with pytest.raises(ValueError, match="layer_types"):
        ModelConfig.from_hf_config({
            "model_type": "qwen2", "hidden_size": 64, "num_attention_heads": 4,
            "num_hidden_layers": 4, "intermediate_size": 128, "vocab_size": 256,
            "sliding_window": 32, "use_sliding_window": True,
            "max_window_layers": 2})


def test_checkpoint_tensor_names():
    """``weights.assemble_params`` finds a mellum checkpoint's tensors: plain
    q/k/v/o projections, the router under ``mlp.gate``, the experts under
    ``mlp.experts.N``."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.weights import assemble_params

    cfg = tiny(num_hidden_layers=4, layer_types=PERIOD, mlp_layer_types=["sparse"] * 4)
    mc = model_config(cfg)
    want = jax.tree.map(np.asarray, W.build_params(cfg, SEED))
    t = lambda a: np.ascontiguousarray(a.T)  # noqa: E731  torch stores [out, in]
    raw = {
        "model.embed_tokens.weight": want["embed"],
        "model.norm.weight": want["final_norm"],
        "lm_head.weight": t(want["lm_head"]),
    }
    for i in range(4):
        lw = {k: v[i] for k, v in want["layers"].items()}
        pre = f"model.layers.{i}."
        raw[pre + "input_layernorm.weight"] = lw["input_norm"]
        raw[pre + "post_attention_layernorm.weight"] = lw["post_norm"]
        for name, key in (("q_proj", "wq"), ("k_proj", "wk"), ("v_proj", "wv"),
                          ("o_proj", "wo")):
            raw[pre + f"self_attn.{name}.weight"] = t(lw[key])
        raw[pre + "mlp.gate.weight"] = t(lw["router"])
        for name, key in (("gate_proj", "w_gate"), ("up_proj", "w_up"),
                          ("down_proj", "w_down")):
            for e in range(8):
                raw[pre + f"mlp.experts.{e}.{name}.weight"] = t(lw[key][e])
    got = assemble_params(raw, mc, jnp.float32)
    for k, v in want["layers"].items():
        np.testing.assert_array_equal(np.asarray(got["layers"][k]), v, err_msg=k)
    for k in ("embed", "final_norm", "lm_head"):
        np.testing.assert_array_equal(np.asarray(got[k]), want[k])


# -- what moves KV beyond one chip's hot path refuses by one sentence ----------


@pytest.mark.parametrize("settings", [
    {"host_offload_blocks": 4}, {"kv_dtype": "int8"}, {"kv_remote": "on"},
    {"tp": 2}, {"mixed_batching": False}, {"num_window_pages": 0},
    {"num_window_pages": 10},
], ids=lambda s: next(iter(s)))
def test_what_moves_kv_refuses_a_two_kind_cache_at_configuration(settings):
    cfg = tiny()
    want = "num_window_pages" if "num_window_pages" in settings else "two-kind cache"
    with pytest.raises(ValueError, match=want):
        JaxEngine(model_config(cfg), W.build_params(cfg, SEED),
                  engine_config(**settings))


def test_disaggregation_and_classic_dispatches_refuse_a_two_kind_cache():
    async def body(engine):
        with pytest.raises(ValueError, match="two-kind cache"):
            await engine.generate_external(Context.new(request(PROMPT, 2)))
        with pytest.raises(ValueError, match="two-kind cache"):
            await engine.prefill_export(request(PROMPT, 2))
        stream = await engine.generate(
            Context.new(request(PROMPT, 2, frequency_penalty=0.5)))
        items = [i async for i in stream]
        ann = items[0] if isinstance(items[0], Annotated) else Annotated.from_dict(items[0])
        assert ann.is_error() and "two-kind cache" in ann.error_message()

    serve(body)


# -- the cache -------------------------------------------------------------------


def test_two_pools_geometry_bytes_and_gauges():
    cfg = tiny()
    mc = model_config(cfg)
    kv = PagedKVCache(mc, num_pages=80, page_size=16, num_window_pages=48)
    assert isinstance(kv.pages, KindKV)
    assert kv.pages.full.shape == (2, 2, 80, 16, 2, 16)
    assert kv.pages.window.shape == (6, 2, 48, 16, 2, 16)
    per_layer = 2 * 2 * 16 * 4  # K and V, 2 heads of 16, float32
    assert kv.kind_bytes_per_page("full") == 2 * per_layer * 16
    assert kv.kind_bytes_per_page("sliding") == 6 * per_layer * 16
    assert kv.pool_bytes == kv.pages.nbytes
    # all pools' bytes over the full pool's tokens: one answer for
    # /bench/state, engine.kv.dtype and dynamo_engine_kv_bytes_per_token
    assert kv.bytes_per_token == kv.pool_bytes / (80 * 16)
    assert str(kv.dtype) == "float32"

    async def body(engine):
        await served(engine, PROMPT, 24)
        reg = engine.obs.registry
        assert engine.obs.kv_bytes_per_token._value.get() == engine.kv.bytes_per_token
        for kind, pool in (("full", engine.kv.allocator),
                           ("window", engine.kv.window_allocator)):
            for state, want in (("used", 0), ("resident", pool.resident_pages)):
                got = reg.sample("dynamo_engine_kv_kind_pages",
                                 {"kind": kind, "state": state})
                assert got == want, (kind, state, got, want)
        assert reg.sample("dynamo_engine_kv_resident_context_tokens") == (
            engine.kv.allocator.num_inactive * 16)
        assert reg.sample("dynamo_engine_kv_window_pages_released") >= 10

    serve(body)


def test_interleaved_lanes_hold_a_window_and_a_chunk_and_reuse_each_others_pages(
    monkeypatch,
):
    """Two long sequences prefill and decode side by side over a window
    pool far smaller than their prompts: neither ever holds more window
    pages than window + chunk + one page (beside the few its decode growth
    asks for ahead), pages one let go are handed to the other, and both
    agree with the reference, which never heard of pages."""
    cfg = tiny()
    a = RNG.randint(3, 256, 330).tolist()
    b = RNG.randint(3, 256, 290).tolist()
    budget, ps = 48, 16
    seen = {}  # request -> most pages held of its prompt's span
    owners = {}  # window page -> requests that held it

    def note(sched):
        for seq in sched.slots:
            if seq is None:
                continue
            n_prompt = -(-len(seq.prompt) // ps)
            held = [p for p in seq.wpages[:n_prompt] if p]
            ahead = [p for p in seq.wpages[n_prompt:] if p]
            seen[seq.request_id] = max(seen.get(seq.request_id, 0), len(held))
            assert len(held) <= sched.window_lane_pages(budget)
            assert len(ahead) <= 10
            for p in held + ahead:
                owners.setdefault(p, set()).add(seq.request_id)

    form = Scheduler.form_mixed_chunks
    grow = Scheduler.ensure_decode_capacity

    def form_and_note(self, *args, **kw):
        out = form(self, *args, **kw)
        note(self)
        return out

    def grow_and_note(self, *args, **kw):
        out = grow(self, *args, **kw)
        note(self)
        return out

    monkeypatch.setattr(Scheduler, "form_mixed_chunks", form_and_note)
    monkeypatch.setattr(Scheduler, "ensure_decode_capacity", grow_and_note)

    async def body(engine):
        both = await asyncio.gather(served(engine, a, 40), served(engine, b, 40))
        for pool in (engine.kv.allocator, engine.kv.window_allocator):
            pool_adds_up(pool)
        return both

    got_a, got_b = serve(body, cfg, num_window_pages=40)
    assert len(seen) == 2 and max(seen.values()) >= 3  # a window and a chunk
    assert any(len(who) == 2 for who in owners.values())  # a page changed hands
    assert worst_gap(cfg, a, *got_a) < TOL and worst_gap(cfg, b, *got_b) < TOL


def _sched(pages=64, wpages=24, lanes=2, window=32):
    cfg = SchedulerConfig(max_batch_size=lanes, max_seq_len=512, page_size=16)
    return Scheduler(cfg, PagePool(pages), PagePool(wpages), window)


def _seq(rid, n, max_tokens=8):
    return SeqState.from_request(rid, request(list(range(3, 3 + n)), max_tokens), 16)


def _prefill(sched, budget=48):
    """Form and 'dispatch' chunks until no prompt owes any."""
    while sched.mix_pending:
        for ch in sched.form_mixed_chunks(budget):
            ch.seq.prefilled_tokens = ch.start + ch.length
            if ch.final:
                ch.seq.prefilling = False


def test_pages_add_up_in_both_pools_after_preemption_and_cancel():
    sched = _sched()
    one, two = _seq("one", 200, max_tokens=40), _seq("two", 150)
    for s in (one, two):
        sched.enqueue(s)
    for s, _n in sched.plan().prefills:
        sched.queue_mixed_prefill(s, s.cached_prompt_tokens)
    assert sched.page_table.shape == (2, 2, 32)  # kind, lane, page
    for ch in sched.form_mixed_chunks(48):  # one chunk each side of a budget
        ch.seq.prefilled_tokens = ch.start + ch.length
    assert sum(1 for p in one.wpages if p) == 3  # a chunk of 48 tokens
    _prefill(sched)
    # a prefilled lane holds the window behind its last chunk, not its prompt
    assert sum(1 for p in one.wpages if p) <= sched.window_lane_pages(48)
    assert one.w_lo >= (200 - 48 - 31) // 16 and sched.window_released > 0
    assert len(one.pages) == len(one.wpages) == 13
    full, window = sched.allocator, sched.window_allocator
    used = (full.used_pages, window.used_pages)
    assert used[0] >= 13 + 10 and 0 < used[1] <= 2 * sched.window_lane_pages(48)
    sched.ensure_decode_capacity(lookahead=20)
    assert len(one.pages) == len(one.wpages) > 13  # both pools, in step
    assert (sched.page_table[1, one.slot, :len(one.wpages)] == one.wpages).all()
    # preemption lets every page of the lane go, in both pools
    sched._preempt(two)
    assert two.slot == -1 and not two.wpages and not two.pages and not two.w_held
    assert sched.waiting[0] is two
    for pool in (full, window):
        pool_adds_up(pool)
    assert window.used_pages == sum(1 for p in one.wpages if p)
    # re-admitted, it finds its registered blocks: the full layers' for the
    # whole hit, the window layers' for the tail of it alone
    for s, _n in sched.plan().prefills:
        sched.queue_mixed_prefill(s, s.cached_prompt_tokens)
    assert two.cached_prompt_tokens == 144 and sorted(two.w_held) == [7, 8]
    assert [bool(p) for p in two.wpages[:9]] == [False] * 7 + [True] * 2
    _prefill(sched)
    sched.cancel(two)
    sched.cancel(one)
    for pool in (full, window):
        pool_adds_up(pool)
        assert pool.used_pages == 0
    assert not sched.page_table.any()


def test_a_dry_window_pool_makes_a_lane_wait_and_nothing_is_lost():
    """Where the window pool cannot give a chunk its pages the lane waits a
    tick (its pages so far intact); it goes on when pages come back."""
    sched = _sched(wpages=10)  # 9 pages
    one, two = _seq("one", 120), _seq("two", 120)
    sched.enqueue(one)
    sched.enqueue(two)
    for s, _n in sched.plan().prefills:
        sched.queue_mixed_prefill(s, s.cached_prompt_tokens)
    chunks = sched.form_mixed_chunks(96, chunk_cap=48)  # 3 pages each of 9
    assert [c.seq.request_id for c in chunks] == ["one", "two"]
    for ch in chunks:
        ch.seq.prefilled_tokens = ch.start + ch.length
    chunks = sched.form_mixed_chunks(96, chunk_cap=48)
    # each lets one page go behind its window; one's next three fit in the
    # four then free, two's do not in the two left, and it waits
    assert [c.seq.request_id for c in chunks] == ["one"]
    assert two in sched.mix_pending and sum(1 for p in two.wpages if p) == 2
    sched.cancel(one)
    chunks = sched.form_mixed_chunks(96, chunk_cap=48)
    assert [c.seq.request_id for c in chunks] == ["two"] and chunks[0].start == 48
    sched.cancel(two)
    for pool in (sched.allocator, sched.window_allocator):
        pool_adds_up(pool)
        assert pool.used_pages == 0
