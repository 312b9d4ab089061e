"""The reduction from a profiler trace to busy time and time by operation."""
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_does_not_count_a_nanosecond_twice():
    assert tr.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.union_seconds([(0, 10), (2, 3)]) == 10
    assert tr.union_seconds([]) == 0


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


FUSION = "%fusion.7 = bf16[8,32,14336]{2,1,0:T(8,128)(2,1)} fusion(bf16[8,32,4096]{2,1,0} %x), kind=kOutput"


def test_reduce_hand_made_planes():
    ops = NS(name="XLA Ops", events=[
        # spans the two below: union only
        _ev("%while.3 = (s32[]{:T(128)}, bf16[1,1024,4096]{2,1,0}) while(%tuple.2), condition=%c, body=%b", 0, 1000),
        _ev(FUSION, 0, 400),
        _ev(FUSION, 500, 300),
        _ev("%copy.1 = s32[1024]{0:T(1024)} copy(%p)", 2000, 100),
    ])
    planes = [
        NS(name="/device:TPU:0", lines=[ops, NS(name="Steps", events=[_ev("x", 0, 9999)])]),
        NS(name="/host:CPU", lines=[NS(name="XLA Ops", events=[_ev("y", 0, 5000)])]),
    ]
    out = tr.reduce_planes(planes)
    assert out["device_planes"] == 1
    assert out["busy_s"] == pytest.approx(1100e-9)
    assert out["span_s"] == pytest.approx(2100e-9)
    assert out["ops"]["_fusion.7___bf16_8_32_14336_"] == pytest.approx(700e-9)
    assert out["op_counts"]["_fusion.7___bf16_8_32_14336_"] == 2
    assert "_copy.1___s32_1024_" in out["ops"]
    assert not any("while" in k for k in out["ops"])
    # the instruction's text is kept: readers tell an operation by its operands
    assert out["op_text"]["_fusion.7___bf16_8_32_14336_"] == FUSION


def test_recorded_trace_from_the_chip():
    path = os.path.join(DATA, "tiny_tpu.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    out = tr.reduce_file(path)
    assert out["device_planes"] >= 1
    assert 0 < out["busy_s"] <= out["span_s"]
    assert sum(out["ops"].values()) > 0


# -- the readers that take their numbers from the trace ----------------------------

import importlib.util  # noqa: E402

from benchmark import costs  # noqa: E402

READERS = os.path.join(os.path.dirname(os.path.dirname(DATA)), "..", "benchmark", "layer_metrics")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MIXTRAL = {"num_local_experts": 8, "hidden_size": 4096, "intermediate_size": 14336,
           "num_hidden_layers": 4}


def _reader(file):
    spec = importlib.util.spec_from_file_location("r", os.path.join(READERS, file))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _expert_trace(product_seconds):
    """Two decode-width expert products (weights sliced outside the fusion
    and inside it), an elementwise fusion over a buffer of the same shape,
    and a dense product that is no expert's."""
    L = "{2,1,0:T(8,128)(2,1)}"
    text = {
        "up": f"%fusion.5 = bf16[8,32,14336]{L} fusion(bf16[8,32,4096]{L} %buf, "
              f"bf16[8,4096,14336]{L} %w), kind=kOutput, calls=%fc.5",
        "down": f"%fusion.6 = bf16[8,32,4096]{L} fusion(bf16[8,32,14336]{L} %mid, "
                f"bf16[4,8,14336,4096]{{3,2,1,0}} %stack, s32[] %i), kind=kOutput, calls=%fc.6",
        "silu": f"%fusion.9 = bf16[8,32,14336]{L} fusion(bf16[8,32,14336]{L} %g, "
                f"bf16[8,32,14336]{L} %u), kind=kLoop, calls=%fc.9",
        "dense": f"%fusion.2 = bf16[1,32,4096]{L} fusion(bf16[1,32,4096]{L} %h, "
                 f"bf16[4096,4096]{{1,0}} %wq), kind=kOutput, calls=%fc.2",
        "attn": "%_packed_ragged_attention.10 = bf16[32,32,128]{2,1,0} custom-call(%q)",
    }
    seconds = {"up": product_seconds, "down": product_seconds, "silu": 1e-5,
               "dense": 1e-4, "attn": 1e-4}
    return {"ops": seconds, "op_counts": {k: 1 for k in seconds}, "op_text": text,
            "busy_s": sum(seconds.values()), "device_planes": 1}


def test_expert_products_are_told_by_their_operands_not_their_speed():
    mod = _reader("kernel.expert_mlp_roofline.py")
    flops, nbytes = costs.expert_matmul(8, 32, 4096, 14336)
    least, bound = costs.roofline_seconds(flops, nbytes, PEAK)
    assert bound == "memory"
    ctx = {"cfg": MIXTRAL, "costs": costs, "peaks": PEAK, "trace": _expert_trace(2 * least)}
    assert sorted(p[0] for p in mod.products(ctx)) == ["down", "up"]
    assert mod.read(ctx) == pytest.approx(50.0)


def test_roofline_share_is_not_capped():
    # a product that ran faster than its count of bytes allows reads over
    # 100%: the count is at fault and has to show, not be filtered away
    mod = _reader("kernel.expert_mlp_roofline.py")
    flops, nbytes = costs.expert_matmul(8, 32, 4096, 14336)
    least, _ = costs.roofline_seconds(flops, nbytes, PEAK)
    ctx = {"cfg": MIXTRAL, "costs": costs, "peaks": PEAK, "trace": _expert_trace(least / 2)}
    assert mod.read(ctx) == pytest.approx(200.0)
    assert mod.read(dict(ctx, cfg={"hidden_size": 4096, "intermediate_size": 14336})) is None


@pytest.mark.parametrize("busy,window,want", ((1.0, 4.0, 75.0), (4.2, 4.0, -5.0)))
def test_idle_share_is_not_clamped(busy, window, want):
    mod = _reader("device.idle_pct.py")
    ctx = {"trace": {"device_planes": 1, "busy_s": busy}, "trace_window_s": window}
    assert mod.read(ctx) == pytest.approx(want)
    assert mod.read({"trace": {"device_planes": 0}, "trace_window_s": window}) is None
