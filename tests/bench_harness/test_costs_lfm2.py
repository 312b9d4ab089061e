"""The lfm2_moe family's counts (``benchmark/costs_lfm2.py``): what the new
readers and ``step.weight_stream_pct`` divide by, at the published widths,
checked by hand against the arithmetic of ISSUE 45; and the readers over
made-up traces and counters."""
import importlib.util
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import costs, costs_attn, costs_lfm2, stats

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b.json")) as f:
    CFG = json.load(f)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = os.path.join(ROOT, "benchmark", "layer_metrics")


def _reader(name, func="read"):
    spec = importlib.util.spec_from_file_location("r", os.path.join(READERS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, func)


def test_the_layers_and_the_weights_a_step_streams():
    assert (costs_lfm2.layers_of(CFG, "conv"), costs_lfm2.layers_of(CFG, "attention")) == (10, 3)
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 128
    assert costs_lfm2.operator_params(CFG, "conv") == conv == 16783360
    assert costs_lfm2.operator_params(CFG, "attention") == attn == 10485888
    experts = 32 * 3 * 2048 * 1792 + 2048 * 32 + 32
    assert costs_lfm2.mlp_params(CFG, False) == experts == 352387104
    assert costs_lfm2.mlp_params(CFG, True) == 3 * 2048 * 7168 == 44040192
    # a dense conv layer 60.8M, a conv expert layer 369.2M, an attention
    # expert layer 362.9M, the tied embedding 134.2M read once as the head
    total = (conv + 44040192) + 9 * (conv + experts) + 3 * (attn + experts) + 2048 * 65536
    assert costs_lfm2.weight_bytes(CFG) == 2.0 * total
    assert 9.15e9 < costs_lfm2.weight_bytes(CFG) < 9.25e9  # the issue's 9.21 GB


def test_a_page_holds_the_attention_layers_and_a_snapshot_rides_it():
    assert costs_lfm2.kv_bytes_per_token(CFG) == 3 * 2 * 8 * 64 * 2 == 6144
    assert costs_lfm2.state_bytes_per_page(CFG) == 10 * 2 * 2048 * 2 == 81920
    eng = CFG["engine"]
    pool = eng["num_pages"] * (eng["page_size"] * 6144 + 81920)
    assert 2.9e9 < pool < 3.0e9  # 16384 pages of both: the issue's 2.9 GB
    assert eng["max_batch_size"] * 81920 == 2621440  # the lanes' 2.6 MB


@pytest.mark.parametrize("q,ctx", [(1, 5000), (496, 496), (496, 4000), (64, 1700)])
def test_an_attention_launch_at_64_wide_heads(q, ctx):
    got = costs_lfm2.attn_launch([q], [ctx], CFG)
    like = dict(CFG, head_dim=64, sliding_window=None)
    assert got == pytest.approx(costs_attn.lane(q, ctx, like))
    if q == 1:  # a decode row: every key once a KV head, 4 D operations a key a head
        assert got[0] == 4.0 * 32 * 64 * ctx
        assert got[1] == 2.0 * 64 * (2 * 8 * ctx + 2 * 32)


def test_forward_passes_count_the_attention_layers_alone():
    counts = {"packed_ragged_attention_narrow.1": 30, "paged_decode_attention_narrow.2": 60,
              "fusion.7": 999}
    assert costs_lfm2.forward_passes(counts, CFG) == 30.0


def test_the_grouped_product_over_routed_rows():
    flops, nbytes = costs_lfm2.grouped_matmul(2048, CFG)
    assert flops == 2.0 * 2048 * 2048 * 1792
    assert nbytes == 2.0 * (32 * 2048 * 1792 + 2048 * 2048 + 2048 * 1792)
    # 32 experts' matrices are 235 MB: a launch is memory-bound under 7700 rows
    least, bound = costs.roofline_seconds(flops, nbytes, PEAK)
    assert bound == "memory" and 2.8e-4 < least < 3.2e-4


def _counters(before, after):
    return stats.Counters(before, after)


def test_the_state_readers_read_the_programs_counters_and_gauge():
    before = ("dynamo_engine_state_restores_total 10\ndynamo_engine_state_resets_total 5\n")
    after = (
        "dynamo_engine_state_restores_total 40\ndynamo_engine_state_resets_total 15\n"
        'dynamo_engine_state_bytes{part="lanes"} 2621440\n'
        'dynamo_engine_state_bytes{part="pages"} 1342177280\n')
    ctx = {"counters": _counters(before, after), "cfg": CFG}
    assert _reader("sched.state_restore_pct")(ctx) == 75.0
    assert _reader("cache.state_bytes_per_page")(ctx) == 81920.0
    # a program without the families (the parent, any other trunk) reads nothing
    none = {"counters": _counters("", "dynamo_engine_tokens_generated_total 5\n"), "cfg": CFG}
    assert _reader("sched.state_restore_pct")(none) is None
    assert _reader("cache.state_bytes_per_page")(none) is None


def _trace(ops):
    """``ops``: {label: (instruction text, events, seconds)}."""
    return {"busy_s": 1.0,
            "ops": {k: v[2] for k, v in ops.items()},
            "op_counts": {k: v[1] for k, v in ops.items()},
            "op_text": {k: v[0] for k, v in ops.items()}}


def _ctx(ops, dispatches, monkeypatch):
    from benchmark import trace_host

    monkeypatch.setattr(trace_host, "table", lambda ctx: {"dispatches": dispatches})
    return {"trace": _trace(ops), "cfg": CFG, "model_costs": costs_lfm2, "costs": costs,
            "peaks": PEAK}


def test_the_roofline_readers_divide_the_least_time_by_the_time_taken(monkeypatch):
    chunk = {"q": [496] + [1] * 15, "ctx": [2000] + [900] * 15, "np": 512, "k": 1,
             "step": "chunk"}
    fused = {"q": [1] * 16, "ctx": [900] * 16, "np": 32, "k": 4, "step": "decode"}
    least_chunk, _ = costs.roofline_seconds(
        *costs_lfm2.attn_launch(chunk["q"], chunk["ctx"], CFG), PEAK)
    least_first, _ = costs.roofline_seconds(
        *costs_lfm2.attn_launch(fused["q"], fused["ctx"], CFG), PEAK)
    least_group, _ = costs.roofline_seconds(
        *costs_lfm2.grouped_matmul(4 * 511, CFG), PEAK)
    ops = {
        "a": ("%packed_ragged_attention_narrow.3 = bf16[512,32,64]{2,1,0} custom-call(...)",
              3, 3 * 4 * least_chunk),
        "b": ("%packed_ragged_attention_narrow.4 = bf16[32,32,64]{2,1,0} custom-call(...)",
              3, 3 * 2 * least_first),
        "c": ("%paged_decode_attention_narrow.5 = bf16[32,32,64]{2,1,0} custom-call(...)",
              9, 9 * 10 * least_first),
        "d": ("%moe_grouped_matmul.6 = bf16[2048,1792]{1,0} custom-call(...)",
              12, 12 * 5 * least_group),
        "e": ("%packed_ragged_attention.9 = bf16[512,32,128]{2,1,0} custom-call(...)",
              3, 1.0),  # another family's launch: not read
    }
    ctx = _ctx(ops, [chunk, fused], monkeypatch)
    # (3 x least_chunk + 3 x least_first) / (12 least_chunk + 6 least_first)
    want = 100.0 * (3 * least_chunk + 3 * least_first) / (12 * least_chunk + 6 * least_first)
    assert _reader("kernel.narrow_attn_roofline")(ctx) == pytest.approx(want)
    assert _reader("kernel.narrow_decode_roofline")(ctx) == pytest.approx(10.0)
    assert _reader("kernel.lfm2_expert_grouped_roofline")(ctx) == pytest.approx(20.0)
    # a program whose launches carry no such name (the parent) reads nothing
    bare = _ctx({"e": ops["e"]}, [chunk, fused], monkeypatch)
    assert _reader("kernel.narrow_attn_roofline")(bare) is None
    assert _reader("kernel.narrow_decode_roofline")(bare) is None
    assert _reader("kernel.lfm2_expert_grouped_roofline")(bare) is None


def test_the_new_metrics_list_the_new_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "lfm2-8b-a1b.sessions-open"
    new = {"sched.state_restore_pct", "cache.state_bytes_per_page",
           "kernel.narrow_attn_roofline", "kernel.narrow_decode_roofline",
           "kernel.lfm2_expert_grouped_roofline"}
    found = {m["name"]: m for m in bench["per_layer"] if m["name"] in new}
    assert set(found) == new
    for name, m in found.items():
        assert m["workloads"] == [cell], name
        with open(os.path.join(READERS, name + ".json")) as f:
            own = json.load(f)
        assert own["workloads"] == [cell] and own["moves"] == m["moves"]
        assert os.path.exists(os.path.join(READERS, own["reader"].partition(":")[0]))
    # no reader of another family is borrowed
    borrowed = [m["name"] for m in bench["per_layer"]
                if cell in m.get("workloads", []) and m["name"].startswith("kernel.")
                and m["name"] not in new and m["name"] != "kernel.attn_share_pct"]
    assert borrowed == []
