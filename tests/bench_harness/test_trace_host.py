"""Idle time by what the host was doing, the attention kernel's cost
function, and the readers PR 26 added, each on a hand-made context."""
import importlib.util
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import costs, costs_attn, stats, trace_host

READERS = os.path.join(os.path.dirname(os.path.abspath(costs.__file__)), "layer_metrics")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MISTRAL = {"num_attention_heads": 32, "num_key_value_heads": 8, "hidden_size": 4096,
           "sliding_window": 4096, "num_hidden_layers": 16}
S = 1_000_000_000  # ns in a second


def _reader(file):
    spec = importlib.util.spec_from_file_location("r", os.path.join(READERS, file))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ev(name, start_s, dur_s, **stats_):
    return NS(name=name, start_ns=int(start_s * S), duration_ns=int(dur_s * S),
              stats=list(stats_.items()))


def _tick(start_s, dur_s, phase, **stats_):
    return _ev("dyn.tick", start_s, dur_s, phase=phase, **stats_)


def _planes(ops, loop, executor=()):
    return [
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=ops), NS(name="Steps", events=[_ev("s", 0, 99)])]),
        NS(name="/host:CPU", lines=[NS(name="loop", events=list(loop)),
                                    NS(name="executor", events=list(executor))]),
    ]


def test_interval_arithmetic():
    assert trace_host.merge([(3, 4), (0, 2), (1, 2.5), (4, 4)]) == [(0, 2.5), (3, 4)]
    assert trace_host.complement([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert trace_host.complement([(0, 5)], 1, 4) == []
    assert trace_host.overlap([(0, 2), (3, 6)], [(1, 4), (5, 9)]) == pytest.approx(3)


def test_gaps_go_to_what_the_host_was_doing():
    """Busy 0-1, 2-3, 5-6, 8-9 of a window 0-10: a gap wholly under one
    phase, one split across two, one parked, one under nothing."""
    ops = [_ev("%fusion.1 = bf16[8]{0} fusion()", t, 1.0) for t in (0, 2, 5, 8)]
    loop = [
        _tick(0.0, 1.0, "device_wait"),          # device busy: no idle under it
        _tick(1.0, 1.0, "plan"),                 # gap 1-2, wholly under plan
        _tick(3.0, 0.5, "commit"),               # gap 3-5 split: commit 0.5,
        _ev("dyn.parked", 6.0, 2.0),             # gap 6-8: parked
    ]
    executor = [_tick(3.5, 1.5, "device_wait", q="1|512", ctx="900|4608", k=1, np=1024)]
    # gap 9-10: 0.75 s under nothing, then the window's last event
    loop.append(_tick(9.75, 0.25, "other"))
    t = trace_host.idle_by_host(_planes(ops, loop, executor))
    assert t["window_s"] == pytest.approx(10.0) and t["busy_s"] == pytest.approx(4.0)
    assert t["idle_s"] == pytest.approx(6.0)
    assert t["idle_by_phase_s"]["plan"] == pytest.approx(1.0)
    assert t["idle_by_phase_s"]["commit"] == pytest.approx(0.5)
    assert t["idle_in_wait_s"] == pytest.approx(1.5)
    assert t["idle_host_work_s"] == pytest.approx(1.75)
    assert t["idle_parked_s"] == pytest.approx(2.0)
    assert t["idle_no_annotation_s"] == pytest.approx(0.75)
    assert t["busy_while_parked_s"] == 0.0
    assert t["dispatches"] == [
        {"q": [1, 512], "ctx": [900, 4608], "k": 1, "np": 1024, "step": "chunk",
             "start_s": 3.5}]
    parts = (t["idle_host_work_s"] + t["idle_in_wait_s"] + t["idle_parked_s"]
             + t["idle_no_annotation_s"])
    assert parts == pytest.approx(t["idle_s"])


def test_shares_add_up_to_the_idle_metric():
    ops = [_ev("%fusion.1 = bf16[8]{0} fusion()", t, 1.0) for t in (0, 2)]
    loop = [_tick(1.0, 0.25, "plan"), _tick(1.25, 0.75, "device_wait"),
            _ev("dyn.parked", 3.0, 0.5)]
    # the run's own window is longer than the trace's events (4.0 against 3.5)
    ctx = {"planes": _planes(ops, loop), "trace_window_s": 4.0,
           "trace": {"device_planes": 1, "busy_s": 2.0}}
    t = trace_host.table(ctx)
    shares = t["shares_pct"]
    assert shares["host_work"] == pytest.approx(6.25)
    assert shares["in_wait"] == pytest.approx(18.75)
    assert shares["parked"] == pytest.approx(12.5)
    assert shares["unattributed"] == pytest.approx(12.5)
    idle = _reader("device.idle_pct.py").read(ctx)
    assert sum(shares[k] for k in ("host_work", "in_wait", "parked", "unattributed")
               ) == pytest.approx(idle) == pytest.approx(shares["idle"])
    mod = _reader("device.idle_by_host.py")
    assert mod.host_work(ctx) == pytest.approx(6.25)
    assert mod.in_wait(ctx) == pytest.approx(18.75)


def test_idle_gaps_are_the_devices_idle_seconds_by_host_phase():
    """The result line's ``breakdown.idle_gaps``: seconds the device ran
    nothing, longest first, adding up to the slice's idle seconds; not the
    tick phases' totals (``device_wait`` spans 2.5 s here, 1.5 s of it idle)."""
    ops = [_ev("%fusion.1 = bf16[8]{0} fusion()", t, 1.0) for t in (0, 2, 5, 8)]
    loop = [_tick(0.0, 1.0, "device_wait"), _tick(1.0, 1.0, "plan"),
            _tick(3.0, 0.5, "commit"), _tick(3.5, 1.5, "device_wait"),
            _ev("dyn.parked", 6.0, 2.0), _tick(9.75, 0.25, "other")]
    t = trace_host.idle_by_host(_planes(ops, loop))
    gaps = trace_host.idle_gaps(t)
    assert [k for k, _v in gaps] == [
        "dyn.parked", "device_wait", "plan", "no_annotation", "commit", "other"]
    assert dict(gaps) == pytest.approx({"dyn.parked": 2.0, "device_wait": 1.5, "plan": 1.0,
                                        "no_annotation": 0.75, "commit": 0.5, "other": 0.25})
    assert sum(v for _k, v in gaps) == pytest.approx(t["idle_s"]) == pytest.approx(6.0)
    assert trace_host.idle_gaps(None) == []  # a program without the annotations


def test_a_program_without_the_annotations_reads_nothing():
    ops = [_ev("%fusion.1 = bf16[8]{0} fusion()", 0, 1.0)]
    ctx = {"planes": _planes(ops, [_ev("other", 0, 1.0)]), "trace_window_s": 2.0,
           "trace": {"device_planes": 1, "busy_s": 1.0, "ops": {}, "op_counts": {},
                     "op_text": {}},
           "cfg": MISTRAL, "costs": costs, "peaks": PEAK}
    assert trace_host.table(ctx) is None
    mod = _reader("device.idle_by_host.py")
    assert mod.host_work(ctx) is None and mod.in_wait(ctx) is None
    assert _reader("kernel.packed_attn_roofline.py").read(ctx) is None
    no_device = {"planes": [NS(name="/host:CPU", lines=[NS(name="l", events=[
        _tick(0, 1, "plan")])])]}
    assert trace_host.table(no_device) is None


# -- the attention kernel's cost function: hand-worked cases ---------------------


def test_pairs_and_keys():
    # a decode row at context 100 reads 100 keys
    assert costs_attn.pairs(1, 100) == 100 and costs_attn.keys_read(1, 100) == 100
    # a causal block from an empty cache: 1 + 2 + 3 + 4
    assert costs_attn.pairs(4, 4) == 10 and costs_attn.keys_read(4, 4) == 4
    # a block after 6 cached keys: 7 + 8 + 9
    assert costs_attn.pairs(3, 9) == 24
    # window 4, rows at positions 8 and 9: 4 keys each, keys 5..9 read
    assert costs_attn.pairs(2, 10, 4) == 8 and costs_attn.keys_read(2, 10, 4) == 5
    # window 4, rows at positions 1..5 see 2, 3, 4, 4, 4 keys; keys 0..5 read
    assert costs_attn.pairs(5, 6, 4) == 17 and costs_attn.keys_read(5, 6, 4) == 6
    # a window wider than the context changes nothing
    assert costs_attn.pairs(3, 9, 4096) == 24 and costs_attn.keys_read(3, 9, 4096) == 9
    for q, ctx, w in ((7, 40, 16), (16, 16, 16), (5, 21, 16), (1, 17, 16)):
        rows = range(ctx - q, ctx)
        assert costs_attn.pairs(q, ctx, w) == sum(min(p + 1, w) for p in rows)


def test_lane_counts_query_heads_for_work_and_kv_heads_for_bytes():
    flops, nbytes = costs_attn.lane(1, 100, dict(MISTRAL, sliding_window=None))
    assert flops == 4 * 32 * 128 * 100
    # keys and values of 8 KV heads (GQA), the query read and the output written
    assert nbytes == 2 * 128 * (2 * 8 * 100 + 2 * 32 * 1)
    # 512 fresh rows ending at 6144 of context under a 4096 window
    flops, nbytes = costs_attn.lane(512, 6144, MISTRAL)
    assert flops == 4 * 32 * 128 * 512 * 4096
    assert nbytes == 2 * 128 * (2 * 8 * (4096 + 511) + 2 * 32 * 512)
    total = costs_attn.launch([1, 512], [100, 6144], MISTRAL)
    assert total == tuple(a + b for a, b in zip(
        costs_attn.lane(1, 100, MISTRAL), costs_attn.lane(512, 6144, MISTRAL)))


def _attn_ctx(seconds_mixed, seconds_decode):
    """Two mixed dispatches (one cut off from its events by the slice: 16
    events, one dispatch's worth) and three decode dispatches, 16 layers."""
    L = "{2,1,0:T(8,128)(2,1)}"
    text = {
        "mixed": f"%_packed_ragged_attention.3 = bf16[1024,32,128]{L} custom-call(%q, %k)",
        "decode": f"%_packed_ragged_attention.9 = bf16[16,32,128]{L} custom-call(%q, %k)",
        "paged": f"%paged_decode_attention.4 = bf16[16,32,128]{L} custom-call(%q)",
    }
    seconds = {"mixed": seconds_mixed, "decode": seconds_decode, "paged": 1.0}
    counts = {"mixed": 16, "decode": 48, "paged": 999}
    loop = [_tick(0.0, 0.001, "dispatch", q="1|512", ctx="900|6144", k=1, np=1024),
            _tick(1.0, 0.001, "dispatch", q="1|512", ctx="900|6144", k=1, np=1024)]
    loop += [_tick(2.0 + i, 0.001, "dispatch", q="1|1", ctx="901|6145", k=8, np=16)
             for i in range(3)]
    loop.append(_tick(6.0, 0.001, "dispatch"))  # the loop's own hop: no shapes
    ops = [_ev(text["mixed"], 0.0, 0.5)]
    return {"planes": _planes(ops, loop), "trace_window_s": 7.0, "cfg": MISTRAL,
            "costs": costs, "peaks": PEAK,
            "trace": {"device_planes": 1, "busy_s": 0.5, "ops": seconds,
                      "op_counts": counts, "op_text": text}}


def test_attention_roofline_reader():
    mod = _reader("kernel.packed_attn_roofline.py")
    mixed, how = costs.roofline_seconds(
        *costs_attn.launch([1, 512], [900, 6144], MISTRAL), PEAK)
    decode, how_d = costs.roofline_seconds(
        *costs_attn.launch([1, 1], [901, 6145], MISTRAL), PEAK)
    assert (how, how_d) == ("compute", "memory")
    ctx = _attn_ctx(seconds_mixed=4 * 16 * mixed, seconds_decode=4 * 48 * decode)
    assert mod.launches(ctx) == {1024: [16, 4 * 16 * mixed], 16: [48, 4 * 48 * decode]}
    assert mod.read(ctx) == pytest.approx(25.0)
    # not capped: a kernel faster than its count allows has to show
    fast = _attn_ctx(seconds_mixed=8 * mixed, seconds_decode=24 * decode)
    assert mod.read(fast) == pytest.approx(200.0)


def test_stage_readers_on_counters():
    before = "\n".join(
        f"dynamo_engine_{s}_seconds_sum 1.0\ndynamo_engine_{s}_seconds_count 10"
        for s in ("ingress", "queue_wait", "first_token_service"))
    after = ("dynamo_engine_ingress_seconds_sum 1.05\ndynamo_engine_ingress_seconds_count 20\n"
             "dynamo_engine_queue_wait_seconds_sum 2.0\ndynamo_engine_queue_wait_seconds_count 20\n"
             "dynamo_engine_first_token_service_seconds_sum 7.0\n"
             "dynamo_engine_first_token_service_seconds_count 20\n")
    mod = _reader("engine.stage_mean_ms.py")
    ctx = {"counters": stats.Counters(before, after)}
    assert mod.ingress(ctx) == pytest.approx(5.0)
    assert mod.queue_wait(ctx) == pytest.approx(100.0)
    assert mod.first_token_service(ctx) == pytest.approx(600.0)
    # a program without the histograms, or a window in which no request
    # passed the stage, reads nothing
    empty = {"counters": stats.Counters("x_total 1\n", "x_total 2\n")}
    assert mod.ingress(empty) is None and mod.queue_wait(empty) is None
    assert mod.first_token_service({"counters": stats.Counters(after, after)}) is None
