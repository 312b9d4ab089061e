"""``benchmark/costs_qwen3next.py`` against hand arithmetic at the
configuration's sizes: the parameters the configuration's ``reduced`` states,
a chunk launch and a decode launch of the delta rule, the experts a step's
rows reach, and the readers' behaviour on a program that lacks what they
read."""
import importlib.util
import os

import pytest

from benchmark import costs, costs_qwen3next as C, server, stats

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CFG = server.load_config("qwen3-next-80b-a3b", False)


def _reader(file):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", file)
    spec = importlib.util.spec_from_file_location("r_" + file.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_parameters_are_the_ones_the_cut_states():
    assert C.layers_of(CFG, "linear") == 9 and C.layers_of(CFG, "attention") == 3
    assert C.conv_width(CFG) == 8192 and C.state_values(CFG) == 32 * 128 * 128
    # 2048x12288 + 2048x64 + 8192x4 + 4096x2048 + 192
    assert C.operator_params(CFG, "linear") == 25165824 + 131072 + 32768 + 8388608 + 192
    # 2048x8192 + 2x2048x512 + 4096x2048 + 512
    assert C.operator_params(CFG, "attention") == 16777216 + 2097152 + 8388608 + 512
    assert C.expert_params(CFG) == 3 * 2048 * 512
    assert C.shared_params(CFG) == 3 * 2048 * 512 + 2048
    # 10.84 GB of weights on the chip
    assert C.resident_bytes(CFG) == pytest.approx(10.84e9, rel=0.01)


def test_a_pass_streams_the_experts_its_rows_reach_never_all():
    rows, reached = C.held_rows_and_experts(16, CFG)
    assert rows == 16 * 10 * 128 / 512 == 40.0
    assert reached == pytest.approx(128 * (1 - (511 / 512) ** 160)) and 34 < reached < 35
    rows, reached = C.held_rows_and_experts(2048, CFG)
    assert rows == 5120.0 and reached == pytest.approx(128.0, abs=1e-6)
    dense = 2 * (2048 * 37984 + 9 * C.operator_params(CFG, "linear")
                 + 3 * C.operator_params(CFG, "attention")
                 + 12 * (2048 * 512 + C.shared_params(CFG)))
    want = dense + 2 * 12 * C.held_rows_and_experts(16, CFG)[1] * C.expert_params(CFG)
    assert C.weight_bytes(CFG) == pytest.approx(want)
    # a third of what a pass over every held expert would stream
    assert C.weight_bytes(CFG) < 0.4 * C.resident_bytes(CFG)


def test_a_chunk_launch_and_a_decode_launch_by_hand():
    # one chunk a value head: K K^T, Q K^T, T(beta e^G K): 2 x 64 x 64 x 128 each;
    # T(beta V), W V'': the same; ten 64^3 products; K'S, QS, Kd^T V'': 2 x 64 x 128 x 128
    chunk = 5 * 2 * 64 * 64 * 128 + 10 * 2 * 64 ** 3 + 3 * 2 * 64 * 128 * 128
    assert chunk == 16777216
    flops, nbytes = C.gdn_chunk_launch([2048], CFG)
    assert flops == 32 * 32 * chunk
    assert nbytes == 2048 * (2 * 8192 + 4 * 32 * 128) + 2 * 4 * 32 * 128 * 128
    # 2033 rows are 32 chunks too; a decode row beside them is one step
    flops2, _ = C.gdn_chunk_launch([2033] + [1] * 15, CFG)
    assert flops2 == 32 * 32 * chunk + 15 * 32 * 6 * 128 * 128
    flops, nbytes = C.gdn_decode_launch(16, CFG)
    assert flops == 16 * 32 * 6 * 128 * 128
    assert nbytes == 16 * (2 * 8192 + 4 * 4096 + 8 * 32 * 128 * 128)
    peaks = costs.peaks("TPU v5 lite")
    least, bound = costs.roofline_seconds(flops, nbytes, peaks)
    assert bound == "memory" and least == pytest.approx(nbytes / peaks["hbm_bytes_per_s"])


def test_the_cache_and_a_snapshot_by_hand():
    assert C.kv_bytes_per_token(CFG) == 3 * 2 * 2 * 256 * 2 == 6144
    assert C.state_bytes_per_snapshot(CFG) == 9 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert C.forward_passes({"packed_ragged_attention_wide.1": 6, "fusion.3": 9}, CFG) == 2.0
    flops, nbytes = C.attn_launch([1], [16384], CFG)
    assert flops == 4 * 16 * 256 * 16384
    assert nbytes == 2 * 256 * (2 * 2 * 16384 + 2 * 16)
    flops, nbytes = C.held_grouped_launch(2048, CFG)
    assert flops == 2 * 5120 * 2048 * 512
    assert nbytes == pytest.approx(2 * (128 * 2048 * 512 + 5120 * 2560))


def test_the_new_readers_read_nothing_from_a_program_that_lacks_the_pool():
    """The parent, and every other family: no counter, no gauge part, no
    scope, no launch of that name: None, and no exception."""
    ctx = {"counters": stats.Counters("", ""), "cfg": CFG, "model_costs": costs,
           "costs": costs, "trace": {"ops": {}, "op_counts": {}, "op_text": {}},
           "peaks": costs.peaks("TPU v5 lite"), "xplane": None}
    snap = _reader("sched.snapshot.py")
    assert snap.restore_pct(ctx) is None and snap.recompute_mean(ctx) is None
    assert _reader("cache.state_bytes_per_snapshot.py").read(ctx) is None
    assert _reader("kernel.wide_head_attn_roofline.py").read(ctx) is None
    assert _reader("kernel.qwen3next_expert_grouped_roofline.py").read(ctx) is None
    assert _reader("kernel.gdn_chunk_roofline.py").read(ctx) is None


def test_the_counter_readers_by_hand():
    before = "\n".join([
        "dynamo_engine_state_snapshots_total 0", "dynamo_engine_state_restores_total 0",
        "dynamo_engine_state_resets_total 0",
        "dynamo_engine_state_snapshot_recompute_tokens_total 0"])
    after = "\n".join([
        "dynamo_engine_state_snapshots_total 40", "dynamo_engine_state_restores_total 30",
        "dynamo_engine_state_resets_total 10",
        "dynamo_engine_state_snapshot_recompute_tokens_total 24000",
        'dynamo_engine_state_bytes{part="slots"} 1236271104',
        'dynamo_engine_state_bytes{part="lanes"} 309067776'])
    ctx = {"counters": stats.Counters(before, after), "cfg": CFG}
    snap = _reader("sched.snapshot.py")
    assert snap.restore_pct(ctx) == 75.0 and snap.recompute_mean(ctx) == 800.0
    assert _reader("cache.state_bytes_per_snapshot.py").read(ctx) == 19316736.0
