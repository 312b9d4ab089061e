"""The count of the grouped expert product and the reader that uses it."""
import importlib.util
import os

import pytest

from benchmark import costs, costs_moe

READERS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..",
                       "benchmark", "layer_metrics")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MIXTRAL = {"num_local_experts": 8, "hidden_size": 4096, "intermediate_size": 14336,
           "num_hidden_layers": 4}
L = "{1,0:T(8,128)(2,1)}"


def _reader():
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(READERS, "kernel.expert_grouped_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_grouped_count_by_hand():
    flops, nbytes = costs_moe.grouped_matmul(2048, 8, 4096, 14336)
    assert flops == 2 * 2048 * 4096 * 14336
    assert nbytes == 2 * (8 * 4096 * 14336 + 2048 * 4096 + 2048 * 14336)
    # 2048 routed rows sit on the ridge: 1.22 ms of products, 1.24 ms of bytes
    least, bound = costs.roofline_seconds(flops, nbytes, PEAK)
    assert bound == "memory" and least == pytest.approx(1.2403e-3, rel=1e-3)
    # a quarter of the buffers' operations at C = N = 1024, and the same weights
    dense, _ = costs.expert_matmul(8, 1024, 4096, 14336)
    assert dense == 4 * flops


@pytest.mark.parametrize("rows,bound", ((64, "memory"), (2048, "memory"), (4096, "compute")))
def test_grouped_bound_by_rows(rows, bound):
    got = costs.roofline_seconds(*costs_moe.grouped_matmul(rows, 8, 4096, 14336), PEAK)
    assert got[1] == bound


def _trace(seconds_each):
    text = {
        "up": f"%moe_grouped_matmul.3 = bf16[2048,14336]{L} custom-call(s32[9]{{0}} %off, "
              f"s32[23]{{0}} %gid, s32[23]{{0}} %mid, bf16[2048,4096]{L} %rows, "
              f"bf16[8,4096,14336]{{2,1,0}} %w), custom_call_target=\"tpu_custom_call\"",
        "down": f"%moe_grouped_matmul.5 = bf16[2048,4096]{L} custom-call(s32[9]{{0}} %off, "
                f"s32[23]{{0}} %gid, s32[23]{{0}} %mid, bf16[2048,14336]{L} %act, "
                f"bf16[8,14336,4096]{{2,1,0}} %w), custom_call_target=\"tpu_custom_call\"",
        "decode": "%fusion.5 = bf16[8,32,14336]{2,1,0} fusion(bf16[8,32,4096]{2,1,0} %buf, "
                  "bf16[8,4096,14336]{2,1,0} %w), kind=kOutput, calls=%fc.5",
        "attn": "%_packed_ragged_attention.10 = bf16[1024,32,128]{2,1,0} custom-call(%q)",
    }
    seconds = {"up": 8 * seconds_each, "down": 4 * seconds_each, "decode": 1e-3, "attn": 1e-4}
    counts = {"up": 8, "down": 4, "decode": 6, "attn": 4}
    return {"ops": seconds, "op_counts": counts, "op_text": text,
            "busy_s": sum(seconds.values()), "device_planes": 1}


def test_grouped_reader_finds_the_kernel_by_name_and_rows(capsys):
    mod = _reader()
    least, _ = costs.roofline_seconds(*costs_moe.grouped_matmul(2048, 8, 4096, 14336), PEAK)
    ctx = {"cfg": MIXTRAL, "costs": costs, "peaks": PEAK, "trace": _trace(2 * least)}
    assert mod.launches(ctx) == {2048: [12, pytest.approx(24 * least)]}
    assert mod.read(ctx) == pytest.approx(50.0)
    assert "12 grouped product events beside 6 product events" in capsys.readouterr().err
    # not capped: a launch faster than its count allows has to show
    assert mod.read(dict(ctx, trace=_trace(least / 2))) == pytest.approx(200.0)


def test_grouped_reader_reads_nothing_where_the_kernel_is_not():
    mod = _reader()
    trace = _trace(1e-3)
    for k in ("ops", "op_counts", "op_text"):
        trace[k] = {n: v for n, v in trace[k].items() if n in ("decode", "attn")}
    ctx = {"cfg": MIXTRAL, "costs": costs, "peaks": PEAK, "trace": trace}
    assert mod.read(ctx) is None  # the parent of the PR that brought the kernel
    dense = {"hidden_size": 4096, "intermediate_size": 14336}
    assert mod.read(dict(ctx, cfg=dense)) is None
