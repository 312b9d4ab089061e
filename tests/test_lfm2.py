"""Gated short-convolution layers beside attention layers (the ``lfm2_moe``
family) on the served path, at toy widths, float32, seeded weights, against
the plain reference of the benchmark (``benchmark/reference_lfm2.py``: no
cache, no state, the convolution as two shifts of the whole sequence, nothing
of the program); the state a lane carries and the snapshot that rides a page;
the sigmoid router whose bias decides the choice alone; and what this trunk
refuses by name.

Tolerance.  Everything runs in float32 with ``highest`` matmul precision
(``conftest.py``); engine and reference differ in the order of their sums
only, which over 9 layers of width 64 reads 2e-6 to 5e-6 on a
log-probability.  ``TOL`` = 1e-4 leaves twenty times that and is far under
what it has to refuse: reversed taps, the bias in the weights or the norm
after the rotation read above 1e-3 (tested).
"""

import asyncio
import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine import step as S
from dynamo_tpu.engine.kv_cache import ConvKV, PagedKVCache
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import Scheduler
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Annotated, Context
from dynamo_tpu.runtime.metrics import EngineMetrics, MetricsRegistry

W = importlib.import_module("benchmark.weights_lfm2")
REF = importlib.import_module("benchmark.reference_lfm2")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOL = 1e-4
SEED = 11

PERIOD = ["full_attention", "conv", "conv", "conv"]

# the catalog's ``config`` of LFM2-8B-A1B, verbatim
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": (
        ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 4
        + ["full_attention", "conv", "conv"] * 2
    ),
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}


def tiny(**over):
    """One leading dense layer and two periods at toy widths: 8 experts
    top-2, heads of 16, pages of 16."""
    cfg = dict(
        PUBLISHED, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
        vocab_size=256, num_hidden_layers=9, num_dense_layers=1,
        layer_types=["conv"] + PERIOD * 2, torch_dtype="float32",
        max_position_embeddings=1024,
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
                    num_pages=80, mixed_token_budget=48)
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
        # a registry of its own: the counters below start at zero
        engine = JaxEngine(mc, params, engine_config(**settings),
                           metrics_registry=MetricsRegistry())
        try:
            return await body(engine)
        finally:
            await engine.stop()

    return asyncio.run(main())


def counters(engine):
    reg = engine.obs.registry
    return tuple(
        int(reg.sample(f"dynamo_engine_state_{n}") or 0)
        for n in ("restores", "resets", "walkbacks"))


RNG = np.random.RandomState(5)
PROMPT = RNG.randint(3, 256, 200).tolist()  # five chunks of 48, not whole pages
WHOLE = RNG.randint(3, 256, 192).tolist()  # twelve whole pages


# -- the trunk against the reference -------------------------------------------


def test_chunks_fused_decode_snapshot_resume_walk_back_and_decode_written_pages():
    """Chunked prefill and decode through the fused steps; the same prompt
    again, resumed from the snapshot of the page its hit ends on; a prompt
    of whole pages asked twice, whose second hit walks back a page; and a
    prompt that continues into what the first request decoded, so that its
    hit ends on a page a decode step wrote."""
    cfg = tiny()

    async def body(engine):
        hits = engine.obs.prefix_hits._value
        cold = await served(engine, PROMPT, 40)
        c0, h0 = counters(engine), hits.get()
        warm = await served(engine, PROMPT, 40)
        c1, h1 = counters(engine), hits.get()
        whole = await served(engine, WHOLE, 8)
        again = await served(engine, WHOLE, 8)
        c2, h2 = counters(engine), hits.get()
        # 200 prompt + 40 decoded tokens: pages 12..13 hold decoded rows
        longer = PROMPT + cold[0][:30] + [7, 8, 9]
        cont = await served(engine, longer, 8)
        c3, h3 = counters(engine), hits.get()
        assert engine.kv.allocator.used_pages == 0
        return (cold, warm, whole, again, (longer, cont),
                (c0, c1, c2, c3), (h1 - h0, h2 - h1, h3 - h2))

    cold, warm, whole, again, (longer, cont), cs, hs = serve(body, cfg)
    assert cold[0] == warm[0] and whole[0] == again[0]
    # (restores, resets, walkbacks) after each phase
    assert cs[0] == (0, 1, 0)
    assert cs[1] == (1, 1, 0)
    assert cs[2] == (2, 2, 1)  # WHOLE: a reset, then a walked-back restore
    assert cs[3] == (3, 2, 1)
    # 192 of 200; 176 of 192 (not 191: no z_{189} anywhere); 224 of 233
    assert hs == (192, 176, 224)
    gaps = [worst_gap(cfg, PROMPT, *cold), worst_gap(cfg, PROMPT, *warm),
            worst_gap(cfg, WHOLE, *whole), worst_gap(cfg, WHOLE, *again),
            worst_gap(cfg, longer, *cont)]
    assert max(gaps) < TOL, gaps


def test_recompute_preemption_in_mid_decode_resumes_from_its_own_pages(monkeypatch):
    """A lane preempted after it has decoded for a while is admitted again
    through the prefix match, hits the pages it wrote (its prompt's and its
    decode steps') and goes on from the snapshot of the last whole one."""
    cfg = tiny()
    done = []
    grow = Scheduler.ensure_decode_capacity

    def preempt_once(self, *a, **k):
        out = grow(self, *a, **k)
        for seq in self.slots:
            if (seq is not None and not done and not seq.prefilling
                    and seq.num_generated >= 20):
                done.append(seq.num_generated)
                self._preempt(seq)
                out.append(seq)
        return out

    monkeypatch.setattr(Scheduler, "ensure_decode_capacity", preempt_once)

    async def body(engine):
        got = await served(engine, PROMPT, 48)
        return got, counters(engine), engine.obs.preemptions._value.get()

    got, c, preemptions = serve(body, cfg)
    assert done and preemptions == 1
    assert c[0] == 1 and c[1] == 1  # a reset, then the restore of the re-admission
    assert len(got[0]) == 48
    assert worst_gap(cfg, PROMPT, *got) < TOL


def test_interleaved_lanes_a_finished_lanes_successor_and_a_lane_left_dead():
    """Two requests whose chunks share dispatches and whose decode steps
    share fused blocks; one finishes early and a third takes its lane while
    the other still decodes; then the engine idles, and a fourth request
    takes a lane that stood dead for a while.  Each agrees with the
    reference, which has no lanes."""
    cfg = tiny()
    a = RNG.randint(3, 256, 150).tolist()
    b = RNG.randint(3, 256, 90).tolist()
    c = RNG.randint(3, 256, 70).tolist()
    d = b[:64] + RNG.randint(3, 256, 21).tolist()  # hits b's first four pages

    async def body(engine):
        ta = asyncio.ensure_future(served(engine, a, 60))
        tb = asyncio.ensure_future(served(engine, b, 6))
        rb = await tb
        rc = await served(engine, c, 30)  # b's lane, while a decodes
        ra = await ta
        for _ in range(20):  # both lanes dead for some ticks
            await asyncio.sleep(0.01)
        rd = await served(engine, d, 12)
        return ra, rb, rc, rd, counters(engine)

    ra, rb, rc, rd, cs = serve(body, cfg)
    assert cs == (1, 3, 0)
    gaps = [worst_gap(cfg, p, *r) for p, r in ((a, ra), (b, rb), (c, rc), (d, rd))]
    assert max(gaps) < TOL, gaps


def _taps_reversed(cfg, mc, params):
    flip = lambda a: a[..., ::-1, :]  # noqa: E731
    layers = dict(params["layers"])
    layers["conv"] = dict(layers["conv"], conv_taps=flip(layers["conv"]["conv_taps"]))
    lead = tuple(dict(lp, conv_taps=flip(lp["conv_taps"])) for lp in params["lead"])
    return mc, dict(params, layers=layers, lead=lead)


def _bias_in_the_weights(cfg, mc, params):
    """The scores the weights are gathered from carry the bias too."""
    return mc, params, "bias"


def _norm_after_rope(cfg, mc, params):
    return mc, params, "norm"


@pytest.mark.parametrize(
    "broken", [_taps_reversed, _bias_in_the_weights, _norm_after_rope],
    ids=lambda f: f.__name__.strip("_"))
def test_the_comparison_refuses_a_wrong_layer(broken, monkeypatch):
    """What the tolerance has to tell apart, each served the same way."""
    cfg = tiny()
    # another static argument than any other test's: the step is traced anew
    mc = model_config(cfg, max_position=1025 + len(broken.__name__))
    mc, params, *how = broken(cfg, mc, W.build_params(cfg, SEED))
    if how == ["bias"]:
        def biased(lp, xf, c):
            logits = jnp.dot(xf, lp["router"], preferred_element_type=jnp.float32)
            scores = jax.nn.sigmoid(logits) + lp["router_bias"].astype(jnp.float32)
            topw, topi = jax.lax.top_k(scores, c.num_experts_per_tok)
            topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-6)
            return topw.astype(xf.dtype), topi

        monkeypatch.setattr(M, "_route", biased)
    if how == ["norm"]:
        late_norm, late_rope = _the_norm_after_the_rotation(M.apply_rope, M.rms_norm)
        monkeypatch.setattr(M, "rms_norm", late_norm)
        monkeypatch.setattr(M, "apply_rope", late_rope)

    async def body(engine):
        return await served(engine, PROMPT, 4)

    got = serve(body, cfg, mc, params)
    assert worst_gap(cfg, PROMPT, *got) > 10 * TOL


def _the_norm_after_the_rotation(rope, norm):
    """The layer's own calls in the other order: the norm over a head's
    values waits for the rotation of the same rows (q, then k)."""
    waiting = []

    def late_norm(x, w, eps, *rest):
        if x.ndim != 4:  # the layer's norms over the hidden state
            return norm(x, w, eps, *rest)
        waiting.append((w, eps))
        return x

    def late_rope(x, cos, sin):
        w, eps = waiting.pop(0)
        return norm(rope(x, cos, sin), w, eps)

    return late_norm, late_rope


# -- the router ------------------------------------------------------------------


def test_the_bias_decides_the_choice_and_no_weight():
    """The choice is the largest of ``s + b``, the weights are ``s`` of the
    chosen over their sum: a bias that lifts one expert into the chosen
    changes which experts run, and the weight of every expert is its
    unbiased score over the sum of the chosen's."""
    mc = model_config(tiny())
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 64), jnp.float32)
    router = jnp.asarray(rng.randn(64, 8) / 8, jnp.float32)
    zero = jnp.zeros((8,), jnp.float32)
    w0, i0 = M._route({"router": router, "router_bias": zero}, x, mc)
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    order = np.argsort(-scores, axis=1)
    np.testing.assert_array_equal(np.sort(np.asarray(i0), 1), np.sort(order[:, :2], 1))
    # lift each row's third expert over its second and no further
    for row in range(16):
        third, second, first = order[row, 2], order[row, 1], order[row, 0]
        gap = scores[row, second] - scores[row, third]
        lift = zero.at[third].set(gap + 1e-3)
        w1, i1 = M._route({"router": router, "router_bias": lift}, x, mc)
        chosen = set(np.asarray(i1)[row].tolist())
        if scores[row, first] - scores[row, third] > gap + 1e-3:
            assert chosen == {first, third}, (row, chosen)
        s = scores[row, np.asarray(i1)[row]]
        np.testing.assert_allclose(
            np.asarray(w1)[row], s / (s.sum() + 1e-6), rtol=1e-5)
    # no bias at all is the same router as a zero bias
    w2, i2 = M._route({"router": router}, x, dataclasses.replace(mc, router_bias=False))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w2))


# -- the configuration ---------------------------------------------------------


def test_from_hf_config_reads_the_catalogs_config_whole():
    mc = ModelConfig.from_hf_config(
        dict(PUBLISHED, num_hidden_layers=18, layer_types=PUBLISHED["layer_types"][:18]))
    assert (mc.num_layers, mc.hidden_size, mc.num_heads, mc.num_kv_heads,
            mc.head_dim, mc.vocab_size) == (18, 2048, 32, 8, 64, 65536)
    assert (mc.num_experts, mc.num_experts_per_tok, mc.intermediate_size,
            mc.lead_intermediate_size) == (32, 4, 1792, 7168)
    assert mc.layer_pattern == ("full", "conv", "conv", "conv")
    assert mc.lead_pattern == ("conv", "conv")
    assert mc.has_conv and not mc.two_kind and mc.qk_norm
    assert (mc.kind_layers("full"), mc.kind_layers("conv")) == (4, 14)
    # two 64-wide KV heads a 128-lane row of the pool
    assert (mc.kv_head_pack, mc.pool_kv_heads, mc.pool_head_dim) == (2, 4, 128)
    assert mc.kv_geometry == (4, 2, 4, 128) and mc.kv_values_per_token == 4096
    assert (mc.router_score, mc.router_bias) == ("sigmoid", True)
    assert mc.tie_word_embeddings and mc.rope_theta == 1e6
    assert mc.rms_norm_eps == 1e-5 and mc.routed_scaling_factor == 1.0


def test_the_benchmarks_cut_is_the_published_rows_it_says():
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    rows = [0] + list(range(2, 14))
    assert cfg["layer_types"] == [PUBLISHED["layer_types"][i] for i in rows]
    for key, value in PUBLISHED.items():
        if key not in ("num_hidden_layers", "num_dense_layers", "layer_types"):
            assert cfg[key] == value, key
    mc = ModelConfig.from_hf_config(
        {k: v for k, v in cfg.items() if k not in ("engine", "rehearse")})
    assert (mc.kind_layers("full"), mc.kind_layers("conv")) == (3, 10)
    assert mc.kv_geometry == (3, 2, 4, 128)


@pytest.mark.parametrize("change,match", [
    ({}, "not whole periods"),  # the published list, uncut: A c c A c c at its end
    ({"conv_bias": True}, "conv_bias"),
    ({"conv_L_cache": 4}, "conv_L_cache=4"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"n_group": 4}, "grouped routing"),
    ({"num_dense_layers": 24}, "no expert layer"),
    ({"layer_types": ["conv"] * 24}, "without a full_attention layer"),
    ({"layer_types": ["linear_attention"] * 24}, "'linear_attention' is not supported"),
    ({"num_hidden_layers": 20}, "24 entries"),
])
def test_what_lfm2_cannot_serve_fails_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(dict(PUBLISHED, **change))


def test_a_checkpoint_of_this_family_is_refused_by_name():
    from dynamo_tpu.engine.weights import assemble_params

    with pytest.raises(ValueError, match="lfm2_moe.*not implemented"):
        assemble_params({}, model_config(tiny()), jnp.float32)


# -- what moves or rewinds KV refuses by one sentence ----------------------------


@pytest.mark.parametrize("settings", [
    {"host_offload_blocks": 4}, {"kv_dtype": "int8"}, {"kv_remote": "on"},
    {"tp": 2}, {"mixed_batching": False},
], ids=lambda s: next(iter(s)))
def test_what_moves_kv_refuses_convolution_layers_at_configuration(settings):
    cfg = tiny()
    with pytest.raises(ValueError, match="convolution layers"):
        JaxEngine(model_config(cfg), W.build_params(cfg, SEED),
                  engine_config(**settings))


@pytest.mark.parametrize("sampling,extra", [
    ({"frequency_penalty": 0.5}, {}),
    ({}, {"prompt_logprobs": 1}),
], ids=["penalised", "prompt_logprobs"])
def test_requests_that_leave_the_packed_step_are_refused(sampling, extra):
    async def body(engine):
        req = request(PROMPT, 2, **sampling)
        for key, value in extra.items():
            setattr(req, key, value)
        stream = await engine.generate(Context.new(req))
        items = [i async for i in stream]
        ann = items[0] if isinstance(items[0], Annotated) else Annotated.from_dict(items[0])
        assert ann.is_error() and "convolution layers" in ann.error_message()

    serve(body)


def test_disaggregation_embedding_and_the_classic_steps_refuse():
    async def body(engine):
        with pytest.raises(ValueError, match="convolution layers"):
            await engine.generate_external(Context.new(request(PROMPT, 2)))
        with pytest.raises(ValueError, match="convolution layers"):
            await engine.prefill_export(request(PROMPT, 2))
        with pytest.raises(ValueError, match="convolution layers"):
            await engine.embed([PROMPT[:8]])

    serve(body)
    # the five step functions that carry no state say so when traced
    mc = model_config(tiny())
    params = W.build_params(tiny(), SEED)
    kv = PagedKVCache(mc, num_pages=8, page_size=16, max_lanes=2).pages
    toks = jnp.zeros((2, 16), jnp.int32)
    lens = jnp.full((2,), 16, jnp.int32)
    table = jnp.zeros((2, 1), jnp.int32)
    for call in (
        lambda: S.prefill_step(params, mc, kv, toks, lens, table),
        lambda: S.decode_step(params, mc, kv, toks[:, 0], lens, table),
        lambda: S.score_prompt_step(params, mc, kv, toks, lens),
        lambda: S.embed_step(params, mc, kv, toks, lens),
        lambda: S._verify_and_sample(
            params, mc, kv, toks[:, :2], lens, lens, table,
            jax.random.PRNGKey(0), None),
    ):
        with pytest.raises(ValueError, match="convolution layers"):
            call()


# -- the cache ---------------------------------------------------------------------


def test_the_pool_holds_the_attention_layers_and_the_state_rides_beside_it():
    cfg = tiny()
    mc = model_config(cfg)
    kv = PagedKVCache(mc, num_pages=80, page_size=16, max_lanes=2)
    assert isinstance(kv.pages, ConvKV)
    assert kv.pages.attn.shape == (2, 2, 80, 16, 1, 32)  # two heads of 16 a row
    assert kv.pages.lanes.shape == (7, 2 * 2, 64)
    assert kv.pages.pages.shape == (7, 2 * 80, 64)
    assert kv.bytes_per_page == 2 * 2 * 2 * 16 * 16 * 4  # attention layers only
    assert kv.state_bytes == {"lanes": 7 * 2 * 2 * 64 * 4, "pages": 7 * 80 * 2 * 64 * 4}
    with pytest.raises(ValueError, match="max_lanes"):
        PagedKVCache(mc, num_pages=80, page_size=16)

    async def body(engine):
        from dynamo_tpu.runtime import tracing

        was = tracing.collector.enabled
        tracing.collector.clear()
        tracing.collector.enable()
        try:
            await served(engine, PROMPT, 4)
            await served(engine, PROMPT, 4)
        finally:
            spans = [s for s in tracing.collector.dump() if s["name"] == "engine.request"]
            tracing.collector.enabled = was
        reg = engine.obs.registry
        for part, want in engine.kv.state_bytes.items():
            assert reg.sample("dynamo_engine_state_bytes", {"part": part}) == want
        snapshot = np.asarray(engine.kv.pages.pages)
        lanes = np.asarray(engine.kv.pages.lanes)
        return snapshot, lanes, [s["attrs"] for s in spans]

    snapshot, lanes, attrs = serve(body)
    # every page a prompt filled has its state; the trash page is trash
    assert np.abs(snapshot[:, 2:26]).min(axis=(0, 2)).all()
    assert np.abs(lanes).max() > 0
    assert [a["state_restored"] for a in attrs] == [False, True]
    assert attrs[0]["state_page"] is None and attrs[1]["state_page"] > 0


def test_the_dispatch_annotation_says_which_kernels_and_how_many_lanes_restored(
        monkeypatch):
    """While a profiler trace is taken, a dispatch of this trunk says what
    its packed launch takes (``attn``, beside ``decode`` for the fused
    steps) and how many of its lanes resumed from a page's snapshot."""
    from dynamo_tpu.runtime import profiling

    marks = []
    mark = profiling._Tick.mark

    def spy_mark(self, phase, **meta):
        if phase == "dispatch" and "pt" in meta:
            marks.append(meta)
        return mark(self, phase, **meta)

    class Open:
        def set_metadata(self, **meta):
            pass

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(profiling._Tick, "mark", spy_mark)
    monkeypatch.setattr(profiling, "annotate", lambda name: Open())
    monkeypatch.setattr(profiling.profiler, "enabled", True)

    async def body(engine):
        await served(engine, PROMPT, 4)
        del marks[:]
        await served(engine, PROMPT, 4)  # resumes from a snapshot
        return engine._packed_attn

    backend = serve(body)
    assert backend in ("grid", "work_list", "xla")
    assert marks and all(m["attn"] == backend for m in marks)
    assert [m["restored"] for m in marks][0] == 1
    assert sum(m["restored"] for m in marks) == 1


def test_int8_weights_cover_the_convolution_projections_and_every_level_of_the_tree():
    """``quantize="int8"`` reaches each kind's operator under its own key
    and the layers in front of the periods, the convolution's two
    projections among them, and the engine serves close to the reference
    (which has the weights as drawn) and not exactly."""
    from dynamo_tpu.engine.quant import QuantizedTensor

    async def body(engine):
        p = engine.params
        quantized = [
            p["layers"]["conv"]["conv_in"], p["layers"]["conv"]["conv_out"],
            p["layers"]["attn"]["wq"], p["layers"]["attn"]["wo"],
            p["layers"]["w_gate"], p["lead"][0]["conv_in"], p["lead"][0]["w_down"],
        ]
        assert all(isinstance(w, QuantizedTensor) for w in quantized)
        assert not isinstance(p["layers"]["conv"]["conv_taps"], QuantizedTensor)
        return await served(engine, PROMPT, 4)

    got = serve(body, quantize="int8")
    assert 10 * TOL < worst_gap(tiny(), PROMPT, *got) < 0.5


# -- guards for the cells that are there -----------------------------------------


def _packed_operands(mc, B=4, Np=32, P=8, pages=16):
    from dynamo_tpu.engine.kv_cache import KindKV, LatentKV

    spec = jax.ShapeDtypeStruct
    shapes = jax.eval_shape(lambda: M.init_params(mc, jax.random.PRNGKey(0)))
    slabs, sides, heads, width = mc.kv_geometry
    dt = jnp.dtype(mc.dtype)
    pool = spec((slabs, sides, pages, 16, heads, width), dt)
    table = (B, P)
    if mc.is_mla:
        pool = LatentKV(pool, mc.kv_lora_rank)
    elif mc.two_kind:
        pool = KindKV(
            spec((mc.kind_layers("full"), sides, pages, 16, heads, width), dt),
            spec((mc.kind_layers("sliding"), sides, pages, 16, heads, width), dt))
        table = (2, B, P)
    elif mc.has_conv:
        Lc, H = mc.kind_layers("conv"), mc.hidden_size
        pool = ConvKV(pool, spec((Lc, 2 * B, H), dt), spec((Lc, 2 * pages, H), dt))
    i32 = lambda *d: spec(d, jnp.int32)  # noqa: E731
    b1 = lambda *d: spec(d, jnp.bool_)  # noqa: E731
    f32 = lambda *d: spec(d, jnp.float32)  # noqa: E731
    sampling = SamplingParams(
        f32(B), f32(B), i32(B), spec((B,), jnp.uint32), f32(B), f32(B), f32(B))
    return (shapes, pool, i32(B), i32(B), i32(B), b1(B), i32(B, 4), i32(*table),
            i32(Np), i32(Np), i32(Np), b1(Np), i32(B), i32(B), b1(B), b1(B), b1(B),
            i32(B), i32(B), spec((2,), jnp.uint32), sampling)


# counted on the parent commit (83a4fa9) with the function below: the packed
# step and one fused decode step of each configuration at its rehearsal sizes
PARENT_EQUATIONS = {
    "mistral-7b": 913, "mixtral-8x7b": 1043,
    "mistral-small-4-119b": 1427, "mellum2-12b-a2.5b": 3667,
    # this family's own, counted on the parent of PR 53 (003b1f2), which
    # brought a second kind of layer that holds state
    "lfm2-8b-a1b": 4213,
}


@pytest.mark.parametrize("name", sorted(PARENT_EQUATIONS))
def test_the_configurations_that_are_there_trace_the_step_they_traced(name):
    """Nothing this family brought is in the step of a trunk without
    convolution layers: each benchmark configuration's packed step with a
    fused decode step behind it has the equations it had on the parent."""
    from tests.test_packed_work_list import _eqns

    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg.update(cfg.pop("rehearse"))
    mc = ModelConfig.from_hf_config({k: v for k, v in cfg.items() if k != "engine"})
    extra = {"dtype": cfg.get("torch_dtype", "bfloat16")}
    if mc.is_moe:
        extra["moe_capacity_factor"] = mc.num_experts / mc.num_experts_per_tok
    mc = dataclasses.replace(mc, **extra)
    ops = _packed_operands(mc)
    jaxpr = jax.make_jaxpr(
        lambda *a: S._packed_unified_multistep(a[0], mc, *a[1:], s_max=16, num_steps=2)
    )(*ops)
    n = sum(1 for _ in _eqns(jaxpr.jaxpr))
    assert n == PARENT_EQUATIONS[name], n


def test_a_one_kind_engines_dispatch_takes_the_arguments_it_took():
    """No new leaf: a trunk without convolution layers hands its step one
    pool array and the twenty-two operands it handed it on the parent; the
    convolution layers' state is leaves of the cache operand of a trunk that
    has them, and no argument of its own."""
    import inspect

    names = list(inspect.signature(S._packed_unified_step).parameters)
    assert names == [
        "params", "cfg", "kv_pages", "tokens", "seq_lens", "limit_lens",
        "active", "stop_ids", "page_table", "t_tokens", "t_lane", "t_rel",
        "t_dec", "p_start", "p_lens", "p_sample", "p_activate", "dec_cap",
        "seg_off", "v_lens", "rng", "sampling", "s_max", "s_spec", "top_n",
        "use_filters",
    ]
    plain = ModelConfig.tiny()
    kv = PagedKVCache(plain, num_pages=8, page_size=16)
    assert len(jax.tree_util.tree_leaves(kv.pages)) == 1 and kv.state_bytes == {}
    conv = PagedKVCache(model_config(tiny()), num_pages=8, page_size=16, max_lanes=2)
    assert len(jax.tree_util.tree_leaves(conv.pages)) == 3
    # and nothing of it in /metrics where no such trunk is served
    obs = EngineMetrics(MetricsRegistry(), max_slots=2)
    assert obs.state_restores is None
    assert b"dynamo_engine_state_" not in obs.registry.render()[0]
