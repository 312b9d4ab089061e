"""``costs_mla.py`` against hand counts at the published widths, and each
new per-layer reader on a hand-made trace."""
import importlib.util
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import costs, costs_attn, costs_mla, stats

READERS = os.path.join(os.path.dirname(os.path.abspath(costs.__file__)), "layer_metrics")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CFG = {"num_attention_heads": 32, "kv_lora_rank": 256, "qk_rope_head_dim": 64,
       "hidden_size": 4096, "moe_intermediate_size": 2048, "n_routed_experts": 32,
       "router_experts": 128, "num_experts_per_tok": 4, "num_hidden_layers": 6}
S = 1_000_000_000


def _reader(file):
    spec = importlib.util.spec_from_file_location("r", os.path.join(READERS, file))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_decode_row_reads_640_bytes_a_key_and_is_memory_bound():
    flops, nbytes = costs_mla.absorbed_lane(1, 16384, CFG)
    # 32 heads x (2 x 320 for the score + 2 x 256 for the values) a key
    assert flops == 32 * 16384 * 1152 == 36864 * 16384
    # the rows, and one query row in (32 x 320) and out (32 x 256)
    assert nbytes == 16384 * 640 + 2 * 32 * (320 + 256)
    assert 57 < flops / nbytes < 58  # against the chip's ridge of 240
    assert costs.roofline_seconds(flops, nbytes, PEAK)[1] == "memory"
    assert costs_mla.decode_launch([16384, 8192], CFG) == tuple(
        a + b for a, b in zip(costs_mla.absorbed_lane(1, 16384, CFG),
                              costs_mla.absorbed_lane(1, 8192, CFG)))


def test_a_prefill_chunk_is_compute_bound_and_causal():
    q, ctx = 2048, 16384
    flops, nbytes = costs_mla.absorbed_lane(q, ctx, CFG)
    pairs = sum(range(ctx - q + 1, ctx + 1))  # row at position p reads p + 1 keys
    assert pairs == costs_attn.pairs(q, ctx) == 2048 * 16384 - 2047 * 2048 // 2
    assert flops == 32.0 * pairs * 1152
    assert nbytes == 2.0 * (ctx * 320 + 32 * q * 576)
    least, bound = costs.roofline_seconds(flops, nbytes, PEAK)
    assert bound == "compute" and 0.005 < least < 0.007  # 5.9 ms a layer


def test_the_held_experts_grouped_product():
    """2048 tokens x 4 assignments = 8192 rows, a quarter of them to the 32
    held of 128 experts: 2048 rows multiply, all 32 experts are read."""
    flops, nbytes = costs_mla.held_grouped_launch(2048, CFG)
    assert flops == 2.0 * 2048 * 4096 * 2048
    assert nbytes == pytest.approx(2.0 * (32 * 4096 * 2048 + 2048 * 4096 + 2048 * 2048))
    assert costs.roofline_seconds(flops, nbytes, PEAK)[1] == "memory"
    whole = dict(CFG, n_routed_experts=128)  # every expert local: every row counts
    assert costs_mla.held_grouped_launch(2048, whole)[0] == 2.0 * 8192 * 4096 * 2048


def _ev(name, start_s, dur_s, **stats_):
    return NS(name=name, start_ns=int(start_s * S), duration_ns=int(dur_s * S),
              stats=list(stats_.items()))


def _ctx(ops):
    """ops: label -> (instruction text, events, seconds); one mixed dispatch
    and one fused decode dispatch of 8 steps are annotated."""
    loop = [
        _ev("dyn.tick", 0.0, 1.0, phase="dispatch", q="1|2040", ctx="9000|16384",
            k=1, np=4096, latent="absorbed_kernel"),
        _ev("dyn.tick", 1.0, 1.0, phase="dispatch", q="1|1", ctx="9001|16385",
            k=8, np=16, latent="absorbed_kernel"),
    ]
    planes = [
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
            _ev("%fusion.1 = bf16[8]{0} fusion()", 0.0, 2.0)])]),
        NS(name="/host:CPU", lines=[NS(name="loop", events=loop)]),
    ]
    return {"planes": planes, "trace_window_s": 2.0, "cfg": CFG, "costs": costs,
            "peaks": PEAK,
            "trace": {"device_planes": 1, "busy_s": 2.0,
                      "ops": {k: v[2] for k, v in ops.items()},
                      "op_counts": {k: v[1] for k, v in ops.items()},
                      "op_text": {k: v[0] for k, v in ops.items()}}}


PACKED = "%latent_packed_attention.7 = bf16[131072,256]{1,0:T(8,128)(2,1)} custom-call(%q, %kv)"
PACKED_DECODE = "%latent_packed_attention.9 = bf16[512,256]{1,0:T(8,128)(2,1)} custom-call(%q, %kv)"
DECODE = "%latent_decode_attention.8 = bf16[512,256]{1,0:T(8,128)(2,1)} custom-call(%q, %kv)"
GROUPED = ("%moe_grouped_matmul.3 = bf16[16384,2048]{1,0} custom-call(bf16[16384,4096]{1,0} %x, "
           "bf16[6,32,4096,2048]{3,2,1,0} %w)")


def test_latent_attn_reader_sets_events_against_the_annotated_dispatches():
    mod = _reader("kernel.latent_attn_roofline.py")
    mixed, _ = costs.roofline_seconds(
        *costs_mla.absorbed_launch([1, 2040], [9000, 16384], CFG), PEAK)
    first, _ = costs.roofline_seconds(
        *costs_mla.absorbed_launch([1, 1], [9001, 16385], CFG), PEAK)
    ctx = _ctx({"a": (PACKED, 6, 4 * 6 * mixed), "b": (PACKED_DECODE, 6, 2 * 6 * first),
                "d": (DECODE, 42, 1.0)})
    # told by name and by packed rows: result rows / 32 heads
    assert mod.launches(ctx) == {131072: [6, 4 * 6 * mixed], 512: [6, 2 * 6 * first]}
    want = 100.0 * (6 * mixed + 6 * first) / (4 * 6 * mixed + 2 * 6 * first)
    assert mod.read(ctx) == pytest.approx(want)
    # not capped, and nothing to read where the program has no such kernel
    fast = _ctx({"a": (PACKED, 6, 3 * mixed)})
    assert mod.read(fast) == pytest.approx(200.0)
    assert mod.read(_ctx({"d": (DECODE, 42, 1.0)})) is None


def test_latent_decode_reader_counts_the_fused_steps():
    mod = _reader("kernel.latent_decode_roofline.py")
    least, bound = costs.roofline_seconds(
        *costs_mla.decode_launch([9001, 16385], CFG), PEAK)
    assert bound == "memory"
    assert least == pytest.approx(((9001 + 16385) * 640 + 2 * 2 * 32 * 576) / 819e9)
    ctx = _ctx({"d": (DECODE, 42, 42 * least * 5), "a": (PACKED, 6, 1.0)})
    assert mod.read(ctx) == pytest.approx(20.0)
    assert mod.read(_ctx({"a": (PACKED, 6, 1.0)})) is None


def _decode_least():
    return costs.roofline_seconds(*costs_mla.decode_launch([9001, 16385], CFG), PEAK)[0]


@pytest.mark.parametrize("ops, want", [
    # the packed kernel alone, in the executable of one row a lane: a
    # decode-only dispatch of one step, as nearly all are since PR 42
    ({"b": (PACKED_DECODE, 6, 6 * 4.0)}, 25.0),
    # the fused steps' kernel alone: 7 steps annotated, 42 events
    ({"d": (DECODE, 42, 42 * 5.0)}, 20.0),
    # both: (6 + 42) least times over 6 x 4 + 42 x 5
    ({"b": (PACKED_DECODE, 6, 6 * 4.0), "d": (DECODE, 42, 42 * 5.0)}, 100.0 * 48 / 234),
    # a chunk's and a question's launches are the other metric's: not this one's
    ({"b": (PACKED_DECODE, 6, 6 * 4.0), "a": (PACKED, 6, 1.0),
      "q": (PACKED.replace("131072", "8192"), 6, 1.0)}, 25.0),
    # faster than the least time reads over 100: no cap
    ({"b": (PACKED_DECODE, 6, 6 * 0.5)}, 200.0),
], ids=["packed", "fused", "both", "beside_chunk_and_question", "no_cap"])
def test_latent_decode_reader_reads_whichever_kernel_served(ops, want):
    """Seconds are given in least times of the decode launch, the same count
    for either kernel: one query row a lane against its context."""
    least = _decode_least()
    ops = {k: (text, n, sec * least) for k, (text, n, sec) in ops.items()}
    mod = _reader("kernel.latent_decode_roofline.py")
    assert mod.read(_ctx(ops)) == pytest.approx(want)


def test_latent_decode_reader_counts_one_least_time_for_both_kernels():
    first, _ = costs.roofline_seconds(
        *costs_mla.absorbed_launch([1, 1], [9001, 16385], CFG), PEAK)
    assert first == _decode_least()


def test_latent_decode_reader_takes_the_class_the_program_states():
    """A dispatch the program calls a chunk step (a prefill row of one
    token in the one-row executable) is not decode attention, and leaves
    nothing to read where it is the only one."""
    ctx = _ctx({"b": (PACKED_DECODE, 6, 1.0)})
    for ev in ctx["planes"][1].lines[0].events:
        ev.stats.append(("step", "chunk"))
    mod = _reader("kernel.latent_decode_roofline.py")
    assert mod.read(ctx) is None
    ctx = _ctx({"b": (PACKED_DECODE, 6, 6 * 4.0 * _decode_least())})
    for ev in ctx["planes"][1].lines[0].events:
        ev.stats.append(("step", "decode" if dict(ev.stats)["k"] == 8 else "chunk"))
    assert mod.read(ctx) == pytest.approx(25.0)


def test_held_experts_reached_by_a_question_and_by_a_chunk():
    rows, experts = costs_mla.held_rows_and_experts(60, CFG)
    assert rows == 60 * 4 * 32 / 128 == 60.0
    assert experts == pytest.approx(32 * (1 - (127 / 128) ** 240)) and 27 < experts < 28
    rows, experts = costs_mla.held_rows_and_experts(2048, CFG)
    assert rows == 2048.0 and experts == pytest.approx(32.0)


def test_held_expert_reader_counts_what_the_annotated_steps_routed():
    """The mixed dispatch of _ctx routed 2041 tokens through a 4096-row
    step (result rows 16384); the reader takes its count from the
    annotation, not from the rows."""
    mod = _reader("kernel.held_expert_grouped_roofline.py")
    least, bound = costs.roofline_seconds(*costs_mla.held_grouped_launch(2041, CFG), PEAK)
    assert bound == "memory"
    ctx = _ctx({"g": (GROUPED, 18, 18 * least * 2), "a": (PACKED, 6, 1.0)})
    assert mod.launches(ctx) == {16384: [18, 18 * least * 2]}
    assert mod.read(ctx) == pytest.approx(50.0)
    assert mod.read(_ctx({"a": (PACKED, 6, 1.0)})) is None
    mixtral = {"hidden_size": 4096, "intermediate_size": 14336, "num_local_experts": 8}
    assert mod.read(dict(ctx, cfg=mixtral)) is None  # not this family's keys


def test_kv_bytes_per_token_reader_reads_the_gauge():
    mod = _reader("cache.kv_bytes_per_token.py")
    after = "dynamo_engine_kv_pages_total 32767\ndynamo_engine_kv_bytes_per_token 3840.0\n"
    assert mod.read({"counters": stats.Counters("", after)}) == 3840.0
    # the parent has no such gauge: nothing to read, and no error
    assert mod.read({"counters": stats.Counters("", "dynamo_engine_kv_pages_total 9\n")}) is None
