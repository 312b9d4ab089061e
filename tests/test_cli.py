"""CLI tests: endpoint-id parsing, one-shot text mode through the full
pipeline, and the http frontend+worker combo launched via cli entrypoints
(reference launch/dynamo-run/src/opt.rs:23,83)."""

import asyncio
import json
import urllib.request

import pytest

from dynamo_tpu.cli import build_parser, main, parse_endpoint_id


def test_parse_endpoint_id():
    assert parse_endpoint_id("dyn://ns.comp.ep") == ("ns", "comp", "ep")
    with pytest.raises(ValueError):
        parse_endpoint_id("ns.comp.ep")
    with pytest.raises(ValueError):
        parse_endpoint_id("dyn://ns.comp")
    with pytest.raises(ValueError):
        parse_endpoint_id("dyn://a.b.c.d")


def test_text_one_shot_mocker(model_dir, capsys):
    rc = main(
        [
            "run", "in=text", "out=mocker",
            "--model-path", model_dir,
            "--prompt", "hello",
            "--max-tokens", "4",
        ]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert out.strip()  # generated some text


def test_http_frontend_plus_worker(model_dir, run):
    """Worker (in=dyn out=mocker) + frontend (in=http out=dyn) over a hub:
    a chat request flows through discovery-built pipeline to the worker."""

    async def body():
        from dynamo_tpu.cli import build_parser as bp

        from dynamo_tpu.http.service import HttpService, ModelManager
        from dynamo_tpu.llm.discovery import ModelWatcher
        from dynamo_tpu.llm.kv_router.publisher import (
            KvEventPublisher,
            WorkerMetricsPublisher,
        )
        from dynamo_tpu.llm.model_card import register_llm
        from dynamo_tpu.mocker import MockerConfig, MockerEngine
        from dynamo_tpu.runtime.component import DistributedRuntime
        from dynamo_tpu.runtime.transports.hub import HubServer

        hub = HubServer()
        host, port = await hub.start()
        addr = f"{host}:{port}"
        # worker leg (what run_worker does)
        wrt = await DistributedRuntime.detached(addr)
        engine = MockerEngine(MockerConfig(block_size=4, vocab_size=300))
        ep = wrt.namespace("dynamo").component("backend").endpoint("generate")
        await ep.serve(engine)
        pub = KvEventPublisher(wrt.namespace("dynamo"), worker_id=wrt.primary_lease)
        pub.hook(engine)
        mp = WorkerMetricsPublisher(engine.metrics)
        await mp.attach(wrt.namespace("dynamo").component("backend"))
        await register_llm(wrt, ep, model_dir, model_name="cli-model")
        # frontend leg (what run_http_frontend does)
        frt = await DistributedRuntime.detached(addr)
        manager = ModelManager()
        watcher = ModelWatcher(frt, manager)
        await watcher.start()
        service = HttpService(manager)
        await service.start()
        try:
            def chat():
                req = urllib.request.Request(
                    service.url + "/v1/chat/completions",
                    data=json.dumps(
                        {
                            "model": "cli-model",
                            "messages": [{"role": "user", "content": "ping"}],
                            "max_tokens": 4,
                        }
                    ).encode(),
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=10) as r:
                    return r.status, json.loads(r.read())

            loop = asyncio.get_running_loop()
            status, body = await loop.run_in_executor(None, chat)
            assert status == 200
            assert body["choices"][0]["message"]["content"]
        finally:
            await service.stop()
            await watcher.stop()
            await pub.close()
            await engine.stop()
            await wrt.shutdown()
            await frt.shutdown()
            await hub.stop()

    run(body())


def test_parser_flags():
    p = build_parser()
    a = p.parse_args(
        ["run", "in=http", "out=jax", "--model-path", "/m", "--tp", "4",
         "--page-size", "32", "--num-pages", "1024",
         "--num-window-pages", "256"]
    )
    assert a.tp == 4 and a.page_size == 32 and a.num_pages == 1024
    assert a.num_window_pages == 256


def test_llmctl_list_and_remove(run, capsys, model_dir):
    """llmctl lists registered models with instance counts and removes a
    model's entries + card from the hub."""
    import argparse

    from dynamo_tpu.cli import run_llmctl
    from dynamo_tpu.llm.model_card import register_llm
    from dynamo_tpu.runtime.component import DistributedRuntime
    from dynamo_tpu.runtime.transports.hub import HubServer

    def ctl(addr, *argv):
        ns = argparse.Namespace(hub=addr, llmcmd=argv[0])
        if argv[0] == "remove":
            ns.name = argv[1]
        return run_llmctl(ns)

    async def body():
        hub_server = HubServer()
        host, port = await hub_server.start()
        addr = f"{host}:{port}"
        rt = await DistributedRuntime.detached(addr)
        try:
            ep = rt.namespace("ns").component("backend").endpoint("generate")
            await register_llm(rt, ep, model_dir, model_name="tiny-model")

            assert await ctl(addr, "list") == 0
            out = capsys.readouterr().out
            assert "tiny-model" in out and "instances=1" in out
            assert "dyn://ns.backend.generate" in out

            assert await ctl(addr, "remove", "tiny-model") == 0
            assert "removed 1" in capsys.readouterr().out

            assert await ctl(addr, "list") == 0
            assert "no models registered" in capsys.readouterr().out
            assert await ctl(addr, "remove", "tiny-model") == 1
        finally:
            await rt.shutdown()
            await hub_server.stop()

    run(body())


def test_tracing_spans_collected():
    from dynamo_tpu.runtime import tracing

    tracing.collector.clear()
    tracing.collector.enable()
    try:
        with tracing.span("unit.op", "req-1", size=3) as sp:
            sp.set(extra=True)
        spans = tracing.collector.get("req-1")
        assert len(spans) == 1
        s = spans[0].to_dict()
        assert s["name"] == "unit.op"
        assert s["attrs"]["size"] == 3 and s["attrs"]["extra"] is True
        assert s["duration_ms"] >= 0.0
    finally:
        tracing.collector.disable()
        tracing.collector.clear()


def test_tracing_disabled_is_noop():
    from dynamo_tpu.runtime import tracing

    tracing.collector.clear()
    assert not tracing.collector.enabled
    with tracing.span("x", "req-2"):
        pass
    assert tracing.collector.get("req-2") == []


def test_trace_cli_assembles_timeline(run, tmp_path, capsys):
    """`dynamo-tpu trace <rid>`: discovers components from the hub,
    scrapes their _trace endpoints, prints an offset-ordered timeline, and
    writes Chrome-trace JSON."""
    from dynamo_tpu.cli import run_trace
    from dynamo_tpu.runtime import tracing
    from tests.test_tracing import _two_component_stack, req

    from dynamo_tpu.runtime.component import (
        Context,
        DistributedRuntime,
        PushRouter,
    )
    from dynamo_tpu.runtime.transports.hub import HubServer

    prev_component = tracing.collector.component
    tracing.collector.clear()
    tracing.collector.enable()

    async def body():
        hub = HubServer()
        host, port = await hub.start()
        addr = f"{host}:{port}"
        _rt_a, _rt_b, shutdown = await _two_component_stack(addr, "clit")
        caller = await DistributedRuntime.detached(addr)
        try:
            client = await (
                caller.namespace("clit").component("relay")
                .endpoint("generate").client()
            )
            await client.wait_for_instances()
            request = Context.new(req([1, 2, 3, 4]))
            stream = await PushRouter(client).generate(request)
            async for _ in stream:
                pass
            await client.close()

            class Args:
                hub = addr
                namespace = "clit"
                request_id = request.id
                json_out = str(tmp_path / "trace.json")
                timeout = 2.0

            rc = await run_trace(Args())
            return rc
        finally:
            await caller.shutdown()
            await shutdown()
            await hub.stop()

    try:
        rc = run(body())
    finally:
        tracing.collector.disable()
        tracing.collector.clear()
        tracing.collector.component = prev_component
    assert rc == 0
    out = capsys.readouterr().out
    assert "spans across" in out and "ingress" in out
    doc = json.loads((tmp_path / "trace.json").read_text())
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(events) >= 4
    # the CLI deduplicates spans that colocated components both returned
    span_ids = [e["args"]["span_id"] for e in events]
    assert len(span_ids) == len(set(span_ids))


def test_batch_mode_runs_prompt_file(run, tmp_path, model_dir, capsys):
    """in=batch: a JSONL prompt file runs through the full pipeline and
    produces one in-order JSON result per line."""
    import json

    from dynamo_tpu.cli import build_parser, run_batch

    inp = tmp_path / "prompts.jsonl"
    inp.write_text(
        json.dumps({"text": "hello world", "max_tokens": 3}) + "\n"
        + json.dumps({"prompt": "the quick brown fox"}) + "\n"
    )
    out = tmp_path / "results.jsonl"
    args = build_parser().parse_args(
        ["run", "in=batch", "out=mocker", "--model-path", model_dir,
         "--input-file", str(inp), "--output-file", str(out),
         "--max-tokens", "4"]
    )
    args.inp, args.out = "batch", "mocker"
    run(run_batch(args))
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert [l["index"] for l in lines] == [0, 1]
    assert lines[0]["text"] == "hello world"
    assert all(l["response"] for l in lines)
    assert all("error" not in l for l in lines)


def test_resolve_model_path(tmp_path, monkeypatch):
    """Local dirs pass through; org/repo ids resolve via the HF hub;
    anything else fails loudly (reference local_model.rs:27)."""
    import pytest

    from dynamo_tpu.llm.local_model import resolve_model_path

    assert resolve_model_path(str(tmp_path)) == str(tmp_path)

    # a .gguf FILE is a valid local model path (GGUF checkpoints)
    gguf = tmp_path / "model.gguf"
    gguf.write_bytes(b"GGUF")
    assert resolve_model_path(str(gguf)) == str(gguf)

    with pytest.raises(SystemExit, match="neither a local path"):
        resolve_model_path("/no/such/dir")

    calls = {}

    def fake_snapshot(repo_id, allow_patterns=None):
        calls["repo"] = repo_id
        calls["patterns"] = allow_patterns
        return str(tmp_path / "snap")

    import huggingface_hub

    monkeypatch.setattr(huggingface_hub, "snapshot_download", fake_snapshot)
    got = resolve_model_path("org/some-model")
    assert got == str(tmp_path / "snap")
    assert calls["repo"] == "org/some-model"
    assert "*.safetensors" in calls["patterns"]

    def failing_snapshot(repo_id, allow_patterns=None):
        raise ConnectionError("no egress")

    monkeypatch.setattr(huggingface_hub, "snapshot_download", failing_snapshot)
    with pytest.raises(SystemExit, match="could not resolve"):
        resolve_model_path("org/other-model")


def test_fleet_table_plan_column_and_quarantine_flag():
    """`dynamo-tpu fleet --plan` renders the planner's last decision per
    pool, and quarantined workers are flagged over plain stragglers."""
    from dynamo_tpu.cli import format_fleet_table

    summary = {
        "totals": {
            "workers_by_role": {"decode": 2},
            "kv_pressure": 0.4,
            "queue_depth": 1,
        },
        "workers": [
            {"worker_id": 1, "role": "decode", "tokens_per_s": 10.0,
             "step_ms": 1.0, "kv_pages_used": 4, "kv_pages_total": 10,
             "queue_depth": 0, "batch_occupancy": 1, "batch_slots": 8},
            {"worker_id": 2, "role": "decode", "tokens_per_s": 0.5,
             "step_ms": 9.0, "kv_pages_used": 9, "kv_pages_total": 10,
             "queue_depth": 1, "batch_occupancy": 2, "batch_slots": 8,
             "straggler": True, "quarantined": True},
        ],
        "plan": {
            "decode": {"action": "up", "count_before": 2,
                       "reason": "itl attainment 0.71 < floor 0.90"},
        },
    }
    out = format_fleet_table(summary, show_plan=True)
    assert "QUARANTINED" in out
    assert "plan:  decode: up from 2 -- itl attainment" in out
    # without --plan the column stays off
    assert "plan:" not in format_fleet_table(summary)
    # and an empty ledger says so rather than rendering nothing
    empty = dict(summary, plan={})
    assert "(no planner adjustments yet)" in format_fleet_table(
        empty, show_plan=True
    )


def _hub_and_frontend(tmp_path):
    """Start ``hub`` and an ``in=http out=dyn`` frontend as a user would;
    returns (hub, front, health_url) once the frontend answers."""
    import os
    import socket
    import subprocess
    import sys
    import time

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ports = []
    for _ in range(2):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            ports.append(s.getsockname()[1])
    hub_port, http_port = ports
    env = dict(os.environ, PYTHONPATH=root, DYN_LOG="info")
    cmd = [sys.executable, "-m", "dynamo_tpu"]
    hub = subprocess.Popen(
        cmd + ["hub", "--host", "127.0.0.1", "--port", str(hub_port)],
        env=env, cwd=tmp_path, stderr=subprocess.PIPE, text=True,
    )
    front = None
    try:
        for _ in range(200):
            try:
                socket.create_connection(("127.0.0.1", hub_port), 1).close()
                break
            except OSError:
                time.sleep(0.05)
        front = subprocess.Popen(
            cmd + ["run", "in=http", "out=dyn", "--hub",
                   f"127.0.0.1:{hub_port}", "--port", str(http_port)],
            env=env, cwd=tmp_path, stderr=subprocess.PIPE, text=True,
        )
        url = f"http://127.0.0.1:{http_port}/health"
        for _ in range(400):
            assert front.poll() is None, front.stderr.read()[-2000:]
            try:
                if urllib.request.urlopen(url, timeout=2).status == 200:
                    return hub, front, url
            except OSError:
                time.sleep(0.05)
        raise AssertionError("frontend never answered /health")
    except BaseException:
        _reap(hub, front)
        raise


def _reap(*procs):
    for proc in procs:
        if proc is not None and proc.poll() is None:
            proc.kill()
        if proc is not None:
            proc.wait()
            proc.stderr.close()


def test_frontend_that_loses_its_hub_exits_nonzero(tmp_path):
    """A frontend (or worker) that loses the hub's connection or its lease
    stops, and a supervisor must see that as a failure: on the chip a
    frontend whose lease the hub had expired exited with code 0 (PR 22)."""
    import signal

    hub, front, _url = _hub_and_frontend(tmp_path)
    try:
        hub.send_signal(signal.SIGKILL)
        assert front.wait(timeout=30) != 0
        assert "lost" in front.stderr.read()
    finally:
        _reap(hub, front)


def test_frontend_keeps_its_lease_through_a_freeze_of_the_machine(tmp_path):
    """A TPU runtime starting in a worker stops every process of a v5e
    host for seconds (6.8 s measured, PR 22).  Stopped together for longer
    than the lease's 10 s, hub and frontend thaw together: the frontend
    keeps its lease and goes on serving."""
    import signal
    import time

    hub, front, url = _hub_and_frontend(tmp_path)
    try:
        for proc in (hub, front):
            proc.send_signal(signal.SIGSTOP)
        time.sleep(11.0)
        for proc in (front, hub):
            proc.send_signal(signal.SIGCONT)
        time.sleep(5.0)  # a keepalive interval (10 s / 3) and some
        assert front.poll() is None, front.stderr.read()[-2000:]
        assert urllib.request.urlopen(url, timeout=5).status == 200
        hub.send_signal(signal.SIGINT)
        hub.wait(timeout=30)
        assert "hub did not run for" in hub.stderr.read()
    finally:
        _reap(hub, front)
