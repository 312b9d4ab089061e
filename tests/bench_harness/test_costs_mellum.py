"""The mellum family's counts (``benchmark/costs_mellum.py``): what the
three new readers and ``step.weight_stream_pct`` divide by, at the published
widths, checked against the arithmetic of ISSUE 36."""
import importlib.util
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import costs, costs_attn, costs_mellum, stats

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs", "mellum2-12b-a2.5b.json")) as f:
    CFG = json.load(f)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = os.path.join(ROOT, "benchmark", "layer_metrics")
S = 1_000_000_000


def test_a_layer_and_the_weights_a_step_streams():
    parts = costs_mellum.layer_params(CFG)
    assert parts == {"attention": 21233664, "router": 147456, "experts": 396361728}
    # 12 layers and the head; the embedding is a gather
    assert costs_mellum.weight_bytes(CFG) == 2.0 * (
        12 * (21233664 + 147456 + 396361728) + 2304 * 98304)
    assert 10.4e9 < costs_mellum.weight_bytes(CFG) < 10.6e9


def test_kv_bytes_by_pool():
    assert (costs_mellum.layers_of(CFG, "full"), costs_mellum.layers_of(CFG, "window")) == (3, 9)
    assert costs_mellum.kind_kv_bytes_per_token(CFG, "full") == 6144
    assert costs_mellum.kind_kv_bytes_per_token(CFG, "window") == 18432
    assert costs_mellum.kv_bytes_per_token(CFG) == 24576
    # full pages of 262k tokens and window pages of 65k: the two pools' bytes
    eng = CFG["engine"]
    pools = costs_mellum.resident_bytes(
        {"full": eng["num_pages"], "window": eng["num_window_pages"]}, CFG, eng["page_size"])
    assert pools == 16384 * 16 * 6144 + 4096 * 16 * 18432
    # a document of 20000 tokens kept with its 1024-token tail: 6144 B a
    # token and the tail's share, against 24576 for one pool of all layers
    kept = costs_mellum.resident_bytes({"full": 1250, "window": 64}, CFG, 16) / 20000
    assert 6144 < kept < 7200


@pytest.mark.parametrize("q,ctx", [(1, 5000), (992, 992), (992, 20000), (64, 3000)])
def test_attention_launches_by_kind(q, ctx):
    full = costs_mellum.attn_launch([q], [ctx], CFG, "full")
    window = costs_mellum.attn_launch([q], [ctx], CFG, "window")
    # a full layer is the Mistral family's count without a window, a window
    # layer the same count at this family's window
    like = dict(CFG, sliding_window=None)
    assert full == pytest.approx(costs_attn.lane(q, ctx, like))
    assert window == pytest.approx(costs_attn.lane(q, ctx, CFG))
    assert window[0] <= full[0] and window[1] <= full[1]
    if ctx - q >= 1024:  # every row sees a whole window, no more
        assert window[0] == 4.0 * 32 * 128 * q * 1024
        assert window[1] == 2.0 * 128 * (2 * 4 * (1024 + q - 1) + 2 * 32 * q)


def test_the_grouped_product_at_a_chunks_rows():
    # a 1024-row chunk on a 2048-row packed shape: 16384 result rows
    flops, nbytes = costs_mellum.grouped_matmul(16384, CFG)
    assert flops == 2.0 * 16384 * 2304 * 896
    assert nbytes == 2.0 * (64 * 2304 * 896 + 16384 * 2304 + 16384 * 896)
    least, bound = costs.roofline_seconds(flops, nbytes, PEAK)
    assert 0.3e-3 < least < 0.5e-3 and bound in ("compute", "memory")
    # a question's 100 rows: the experts' bytes alone
    _least, bound = costs.roofline_seconds(*costs_mellum.grouped_matmul(800, CFG), PEAK)
    assert bound == "memory"


def test_forward_passes_count_every_kind_of_attention_event():
    counts = {"_packed_ragged_attention_window.1___bf16_32_32_128_": 90,
              "_packed_ragged_attention.2___bf16_32_32_128_": 30,
              "_fusion.3___bf16_32_2304_": 500}
    assert costs_mellum.forward_passes(counts, CFG) == 10.0


# -- the readers on a hand-made trace -------------------------------------------


def _reader(file):
    spec = importlib.util.spec_from_file_location("r", os.path.join(READERS, file))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ev(name, start_s, dur_s, **stats_):
    return NS(name=name, start_ns=int(start_s * S), duration_ns=int(dur_s * S),
              stats=list(stats_.items()))


Q, CTX = [1, 1000], [5000, 20000]  # a decode lane beside a chunk, on 2048 packed rows


def _ctx(ops):
    """ops: label -> (instruction text, events, seconds); one packed dispatch
    of 1001 real rows on the 2048-row shape is annotated."""
    loop = [_ev("dyn.tick", 0.0, 1.0, phase="dispatch", q="1|1000", ctx="5000|20000",
                k=1, np=2048)]
    planes = [
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
            _ev("%fusion.1 = bf16[8]{0} fusion()", 0.0, 1.0)])]),
        NS(name="/host:CPU", lines=[NS(name="loop", events=loop)]),
    ]
    return {"planes": planes, "trace_window_s": 1.0, "cfg": CFG, "costs": costs,
            "model_costs": costs_mellum, "peaks": PEAK,
            "trace": {"device_planes": 1, "busy_s": 1.0,
                      "ops": {k: v[2] for k, v in ops.items()},
                      "op_counts": {k: v[1] for k, v in ops.items()},
                      "op_text": {k: v[0] for k, v in ops.items()}}}


WINDOW = "%packed_ragged_attention_window.5 = bf16[2048,32,128]{2,1,0} custom-call(%q, %kv)"
FULL = "%packed_ragged_attention.6 = bf16[2048,32,128]{2,1,0} custom-call(%q, %kv)"
GROUPED = ("%moe_grouped_matmul.3 = bf16[16384,896]{1,0} custom-call(bf16[16384,2304]{1,0} %x, "
           "bf16[12,64,2304,896]{3,2,1,0} %w)")


@pytest.mark.parametrize("kind,label,events", [("window", "w", 9), ("full", "f", 3)])
def test_attention_readers_tell_the_kinds_by_the_kernels_name(kind, label, events):
    mod = _reader("kernel.window_attn_roofline.py")
    least, _ = costs.roofline_seconds(*costs_mellum.attn_launch(Q, CTX, CFG, kind), PEAK)
    ops = {"w": (WINDOW, 9, 1.0), "f": (FULL, 3, 1.0)}
    ops[label] = (ops[label][0], events, 4 * least * events)  # a quarter of its roofline
    ctx = _ctx(ops)
    assert mod.launches(ctx, kind) == {2048: [events, 4 * least * events]}
    assert getattr(mod, kind)(ctx) == pytest.approx(25.0)
    # a program that names no window launch (the parent, a one-kind trunk): nothing
    assert getattr(mod, kind)(_ctx({"f": (FULL, 3, 1.0)})) is None


def test_grouped_reader_counts_the_routed_rows_not_the_packed_shapes():
    """A chunk of 1001 rows rides the 2048-row shape: the result has 16384
    rows, 8008 of them routed.  Counting all 16384 read 110.6 on the chip."""
    mod = _reader("kernel.small_expert_grouped_roofline.py")
    least, bound = costs.roofline_seconds(*costs_mellum.grouped_matmul(8 * 1001, CFG), PEAK)
    padded, _ = costs.roofline_seconds(*costs_mellum.grouped_matmul(16384, CFG), PEAK)
    assert bound == "memory" and least < padded
    ctx = _ctx({"g": (GROUPED, 36, 36 * least * 2), "w": (WINDOW, 9, 1.0)})
    assert mod.launches(ctx) == {16384: [36, 36 * least * 2]}
    assert mod.read(ctx) == pytest.approx(50.0)
    # events of a width no annotated dispatch has are left out, not guessed
    other = GROUPED.replace("16384", "8192")
    assert mod.read(_ctx({"g": (other, 36, 1.0)})) is None
    mixtral = {"hidden_size": 4096, "intermediate_size": 14336, "num_local_experts": 8}
    assert mod.read(dict(ctx, cfg=mixtral)) is None  # not this family's keys


def test_resident_bytes_reader_takes_the_mean_of_the_windows_ends():
    mod = _reader("cache.kv_bytes_per_resident_token.py")

    def text(full, window, tokens):
        return (f'dynamo_engine_kv_kind_pages{{kind="full",state="resident"}} {full}\n'
                f'dynamo_engine_kv_kind_pages{{kind="window",state="resident"}} {window}\n'
                f'dynamo_engine_kv_kind_pages{{kind="window",state="used"}} 7\n'
                f"dynamo_engine_kv_resident_context_tokens {tokens}\n")

    ctx = {"cfg": CFG, "model_costs": costs_mellum,
           "counters": stats.Counters(text(1000, 100, 16000), text(2000, 400, 32000))}
    # 16 tokens a page: 6144 B a token of full pages; a window page is 294912 B
    want = ((1000 * 98304 + 100 * 294912) / 16000 + (2000 * 98304 + 400 * 294912) / 32000) / 2
    assert mod.read(ctx) == pytest.approx(want)
    # the parent has no such gauges: nothing to read, and no error
    none = stats.Counters("", "dynamo_engine_kv_pages_total 9\n")
    assert mod.read({"cfg": CFG, "model_costs": costs_mellum, "counters": none}) is None
