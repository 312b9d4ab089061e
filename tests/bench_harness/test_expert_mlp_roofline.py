"""``kernel.expert_mlp_roofline`` tells an expert product by its operands:
the experts' weights and a buffer ``bf16[E, C, .]``.  The instruction texts
are the chip's, from the traces of PR 27 (PERF.md section 6): the decode
steps' three products, and the copy of one layer's expert weights out of the
layers' stack that its first version made beside every grouped launch, which
the rule without the buffer took for a product of capacity 14336 or 4096."""
import importlib.util
import os

import pytest

from benchmark import costs

READERS = os.path.join(os.path.dirname(os.path.abspath(costs.__file__)), "layer_metrics")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MIXTRAL = {"num_local_experts": 8, "hidden_size": 4096, "intermediate_size": 14336,
           "num_hidden_layers": 4}
T = "{2,1,0:T(8,128)(2,1)S(1)}"
W = "{3,2,1,0:T(8,128)(2,1)}"
TEXT = {
    "gate": f"%fusion.505 = bf16[8,32,14336]{T} fusion(bf16[8,32,4096]{T} %bitcast.1064, "
            f"bf16[4,8,4096,14336]{W} %get-tuple-element.1971, s32[]{{:T(128)}} "
            "%get-tuple-element.1937), kind=kOutput, calls=%fused_computation.145.clone.clone",
    "down": f"%fusion.506 = bf16[8,32,4096]{T} fusion(bf16[4,8,14336,4096]{W} "
            f"%get-tuple-element.2159, s32[]{{:T(128)}} %get-tuple-element.2127, "
            f"bf16[8,32,14336]{T} %fusion.504, bf16[8,32,14336]{T} %fusion.505), "
            "kind=kOutput, calls=%fused_computation.143.clone.clone",
    "copy_down": "%dynamic-slice_bitcast_fusion.7 = bf16[8,14336,4096]{2,1,0:T(8,128)(2,1)} "
                 f"fusion(bf16[4,8,14336,4096]{W} %get-tuple-element.1302, s32[]{{:T(128)}} "
                 "%get-tuple-element.1243), kind=kLoop, calls=%fused_computation.61.clone",
    "copy_up": "%dynamic-slice_bitcast_fusion.5 = bf16[8,4096,14336]{2,1,0:T(8,128)(2,1)} "
               f"fusion(bf16[4,8,4096,14336]{W} %get-tuple-element.1300, s32[]{{:T(128)}} "
               "%get-tuple-element.1243), kind=kLoop, calls=%fused_computation.59.clone",
    "copy_of_a_copy": "%copy_fusion.2 = bf16[8,4096,14336]{2,1,0:T(8,128)(2,1)} "
                      "fusion(bf16[8,4096,14336]{2,1,0} %dynamic-slice_bitcast_fusion.5), "
                      "kind=kLoop, calls=%fused_computation.9",
    "elementwise": f"%multiply_fusion.3 = bf16[8,32,14336]{T} fusion(bf16[8,32,14336]{T} "
                   f"%fusion.504, bf16[8,32,14336]{T} %fusion.505), kind=kLoop, calls=%fc.3",
    "grouped": "%moe_grouped_matmul.19 = bf16[2048,14336]{1,0:T(8,128)(2,1)} custom-call("
               f"bf16[2048,4096]{{1,0}} %fusion.281, bf16[4,8,4096,14336]{W} %gte.1300), "
               "custom_call_target=\"tpu_custom_call\"",
    "decode_attention": "%paged_decode_attention_v2.11 = bf16[32,32,128]{2,1,0} custom-call(%q)",
}


def _reader():
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(READERS, "kernel.expert_mlp_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ctx(seconds):
    counts = {k: 4 for k in TEXT}
    return {"cfg": MIXTRAL, "costs": costs, "peaks": PEAK,
            "trace": {"ops": {k: seconds.get(k, 2.86e-3 * 4) for k in TEXT},
                      "op_counts": counts, "op_text": TEXT}}


@pytest.mark.parametrize("label,capacity", (
    ("gate", 32), ("down", 32), ("copy_down", None), ("copy_up", None),
    ("copy_of_a_copy", None), ("elementwise", None), ("grouped", None), ("decode_attention", None)))
def test_a_product_has_the_weights_and_a_buffer_among_its_operands(label, capacity):
    found = {p[0]: p[1] for p in _reader().products(_ctx({}))}
    assert found.get(label) == capacity


def test_a_copy_of_a_layers_weights_does_not_move_the_share(capsys):
    least, bound = costs.roofline_seconds(*costs.expert_matmul(8, 32, 4096, 14336), PEAK)
    assert bound == "memory"
    # the two products at their least time; the copies 2.86 ms each, as met
    got = _reader().read(_ctx({"gate": 4 * least, "down": 4 * least}))
    assert got == pytest.approx(100.0)
    assert "8 product events for 4 attention events" in capsys.readouterr().err
    dense = {"hidden_size": 4096, "intermediate_size": 14336}
    assert _reader().read(dict(_ctx({}), cfg=dense)) is None
