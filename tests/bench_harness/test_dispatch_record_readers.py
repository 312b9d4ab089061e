"""The nine readers of the tick loop's dispatch record
(``benchmark/layer_metrics/engine.dispatch_record.py``) on canned
``/metrics`` bodies: a number from a program that keeps the record, nothing
from one that does not (the parent) or that mints the families and observes
none (the mocker)."""
import importlib.util
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import costs, stats

READER = os.path.join(os.path.dirname(os.path.abspath(costs.__file__)),
                      "layer_metrics", "engine.dispatch_record.py")
S = 1_000_000_000


def _reader():
    spec = importlib.util.spec_from_file_location("r", READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _body(svc_chunk, n_chunk, svc_decode, n_decode, steps_decode, lanes_chunk,
          lanes_decode, parked, clock, wait, first, rows, mixed):
    """A change's exposition: the record's families beside the two the
    parent already had (first-token service, mixed tokens)."""
    p = "dynamo_engine_"
    lines = [
        f'{p}dispatch_service_seconds_sum{{np="512",step="chunk"}} {svc_chunk}',
        f'{p}dispatch_service_seconds_count{{np="512",step="chunk"}} {n_chunk}',
        f'{p}dispatch_service_seconds_bucket{{le="+Inf",np="512",step="chunk"}} {n_chunk}',
        f'{p}dispatch_service_seconds_sum{{np="16",step="decode"}} {svc_decode}',
        f'{p}dispatch_service_seconds_count{{np="16",step="decode"}} {n_decode}',
        f'{p}dispatch_steps_total{{np="512",step="chunk"}} {n_chunk}',
        f'{p}dispatch_steps_total{{np="16",step="decode"}} {steps_decode}',
        f'{p}decode_lane_steps_total{{step="chunk"}} {lanes_chunk}',
        f'{p}decode_lane_steps_total{{step="decode"}} {lanes_decode}',
        f"{p}parked_seconds_total {parked}",
        f"{p}clock_seconds {clock}",
    ]
    for behind, v in zip(("chunk_steps", "decode_steps", "no_dispatch"), wait):
        lines += [f'{p}first_token_wait_seconds_sum{{behind="{behind}"}} {v}',
                  f'{p}first_token_wait_seconds_count{{behind="{behind}"}} {first[1]}']
    lines += [f"{p}first_token_service_seconds_sum {first[0]}",
              f"{p}first_token_service_seconds_count {first[1]}",
              f'{p}first_token_chunk_rows_total{{whose="own"}} {rows[0]}',
              f'{p}first_token_chunk_rows_total{{whose="all"}} {rows[1]}',
              f'{p}mixed_tokens_total{{kind="used"}} {mixed[0]}',
              f'{p}mixed_tokens_total{{kind="dispatched"}} {mixed[1]}']
    return "# HELP x y\n" + "\n".join(lines) + "\n"


CHANGE = (
    _body(1.0, 40, 2.0, 100, 400, 100, 900, 5.0, 1000.0, (2.0, 1.0, 0.5),
          (3.5, 10), (1000, 4000), (6000, 10000)),
    _body(3.5, 140, 12.0, 350, 1400, 400, 1800, 25.0, 1051.0, (8.0, 4.0, 1.5),
          (13.5, 30), (3000, 12000), (12500, 20000)),
)
PARENT = tuple(
    "\n".join(line for line in body.splitlines()
              if "first_token_service" in line or "mixed_tokens" in line) + "\n"
    for body in CHANGE)
MOCKER = tuple(
    "# TYPE dynamo_engine_dispatch_service_seconds histogram\n"
    "# TYPE dynamo_engine_first_token_wait_seconds histogram\n"
    "dynamo_engine_parked_seconds_total 0.0\n"
    f"dynamo_engine_clock_seconds {clock}\n" for clock in (7.0, 58.0))

EXPECTED = {
    "chunk_step_mean_ms": 25.0,           # 2.5 s over 100 chunk steps
    "decode_step_mean_ms": 10.0,          # 10 s over 1000 forward passes
    "decode_steps_per_dispatch": 4.0,     # 1000 forward passes in 250 dispatches
    "first_token_in_chunk_steps": 60.0,   # 6 of 10 s
    "first_token_in_decode_steps": 30.0,
    "first_token_own_rows": 25.0,         # 2000 of 8000 rows
    "decode_rows_in_chunk_steps": 25.0,   # 300 of 1200 lane steps
    "packed_rows_used": 65.0,             # 6500 of 10000 rows
    # the program's clock says 51 s between the scrapes, not the harness's
    # 50: (51 - 12.5 served - 20 parked) / 51
    "loop_held_device": 100.0 * 18.5 / 51.0,
}


def _ctx(bodies, **more):
    return {"counters": stats.Counters(*bodies), "window_s": 50.0, **more}


@pytest.mark.parametrize("func", sorted(EXPECTED))
def test_reader_on_a_program_that_keeps_the_record(func, capsys):
    assert getattr(_reader(), func)(_ctx(CHANGE)) == pytest.approx(EXPECTED[func])


@pytest.mark.parametrize("func", sorted(EXPECTED))
def test_reader_on_the_parent(func):
    """No family of the record: nothing, but for the counter the parent
    already had without a reader."""
    value = getattr(_reader(), func)(_ctx(PARENT))
    assert value == (pytest.approx(65.0) if func == "packed_rows_used" else None)


@pytest.mark.parametrize("func", sorted(EXPECTED))
def test_reader_on_families_without_samples(func):
    assert getattr(_reader(), func)(_ctx(MOCKER)) is None


def test_every_metric_of_the_file_is_in_benchmark_json():
    root = os.path.dirname(os.path.dirname(os.path.dirname(READER)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    funcs, served = set(), set()
    for name in names:
        with open(os.path.join(os.path.dirname(READER), name + ".json")) as f:
            file, _, func = json.load(f)["reader"].partition(":")
        if file == os.path.basename(READER):
            funcs.add(func)
            served.add(name)
    assert funcs == set(EXPECTED)
    # the set the file's readers serve, not the tail of the list: a later PR
    # appends entries of its own behind them
    assert served == {
        "step.chunk_step_mean_ms", "step.decode_step_mean_ms",
        "tick.first_token_in_chunk_steps_pct", "tick.first_token_in_decode_steps_pct",
        "sched.first_token_own_rows_pct", "sched.decode_rows_in_chunk_steps_pct",
        "tick.packed_rows_used_pct", "tick.loop_held_device_pct",
        "tick.decode_steps_per_dispatch"}


def test_without_the_programs_clock_the_harness_window_stands():
    bodies = tuple(
        "\n".join(l for l in b.splitlines() if "clock_seconds" not in l) + "\n"
        for b in CHANGE)
    assert _reader().loop_held_device(_ctx(bodies)) == pytest.approx(
        100.0 * 17.5 / 50.0)


def _tick(start_s, dur_s, **stats_):
    return NS(name="dyn.tick", start_ns=int(start_s * S), duration_ns=int(dur_s * S),
              stats=list(stats_.items()))


def test_the_traced_slice_sets_service_beside_busy(capsys):
    """The ``device_wait`` annotations' ``svc_us``, summed, on the reader's
    stderr line beside the trace's busy seconds."""
    planes = [
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
            NS(name="%f = bf16[8]{0} fusion()", start_ns=0, duration_ns=3 * S, stats=[])])]),
        NS(name="/host:CPU", lines=[NS(name="executor", events=[
            _tick(0.0, 0.1, phase="dispatch", q="1", ctx="9", k=1, np=16, step="decode", d=1),
            _tick(0.1, 1.0, phase="device_wait", d="1", svc_us=1_100_000),
            _tick(1.2, 1.7, phase="device_wait", d="2", svc_us=1_800_000),
            _tick(3.0, 0.1, phase="commit"),
        ])]),
    ]
    ctx = _ctx(CHANGE, planes=planes, trace={"busy_s": 3.0}, trace_window_s=4.0)
    assert _reader().loop_held_device(ctx) == pytest.approx(EXPECTED["loop_held_device"])
    line = [l for l in capsys.readouterr().err.splitlines() if "dispatch_record" in l][-1]
    info = json.loads(line[len("info "):])["dispatch_record"]
    assert info["traced_slice"] == {
        "svc_s": pytest.approx(2.9), "fetches": 2, "busy_s": 3.0, "trace_window_s": 4.0}
    assert info["served_pct"] == pytest.approx(100.0 * 12.5 / 51.0)
    assert info["parked_pct"] == pytest.approx(100.0 * 20.0 / 51.0)
    # a trace without the stat (the parent's) adds nothing to the line
    for ev in planes[1].lines[0].events:
        ev.stats = [kv for kv in ev.stats if kv[0] != "svc_us"]
    _reader().loop_held_device(ctx)
    line = [l for l in capsys.readouterr().err.splitlines() if "dispatch_record" in l][-1]
    assert "traced_slice" not in json.loads(line[len("info "):])["dispatch_record"]


def test_the_engines_own_exposition_has_the_names_the_readers_ask_for():
    """Rendered by the program's ``EngineMetrics``: every family the reader
    file names is there under that name once observed."""
    from dynamo_tpu.runtime import metrics as rtm

    reg = rtm.MetricsRegistry()
    obs = rtm.EngineMetrics(reg)
    before = reg.render()[0].decode()
    obs.observe_service("chunk", 512, 0.025, 1, 3)
    obs.observe_service("decode", 16, 0.080, 8, 16)
    obs.observe_first_token_wait(0.2, 0.1, 0.05, 100, 400)
    obs.first_token_service.observe(0.35)
    obs.observe_mixed_tokens(13, 20)
    obs.parked_seconds.inc(1.5)
    after = reg.render()[0].decode()
    ctx = _ctx((before, after))
    r = _reader()
    assert r.chunk_step_mean_ms(ctx) == pytest.approx(25.0)
    assert r.decode_step_mean_ms(ctx) == pytest.approx(10.0)
    assert r.decode_steps_per_dispatch(ctx) == pytest.approx(8.0)
    assert r.first_token_in_chunk_steps(ctx) == pytest.approx(100 * 0.2 / 0.35)
    assert r.first_token_in_decode_steps(ctx) == pytest.approx(100 * 0.1 / 0.35)
    assert r.first_token_own_rows(ctx) == pytest.approx(25.0)
    assert r.decode_rows_in_chunk_steps(ctx) == pytest.approx(100 * 3 / 19)
    assert r.packed_rows_used(ctx) == pytest.approx(65.0)
    held = r.loop_held_device(ctx)
    assert held is not None and held < 100.0  # its window is the program's clock
