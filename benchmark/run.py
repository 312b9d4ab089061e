"""Run one cell of the benchmark once.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX.  It reads the cell's files, starts the one
child that holds the chip (``benchmark/server.py``), generates the load over
HTTP, and prints the result as the last line of its output.  ``--rehearse``
runs the whole command at a tiny size on the CPU and always reports
``correct: false``; it is never a fallback.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import importlib
import importlib.util
import json
import os
import random
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import client, costs, stats, traffic  # noqa: E402

WORKDIR = os.path.join(ROOT, ".bench_cache")
READY_TIMEOUT_S = 1100.0
TRACE_SECONDS = 4.0


def log(*a: Any) -> None:
    print(*a, flush=True)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def shrink(spec: Dict[str, Any]) -> Dict[str, Any]:
    """Rehearsal traffic: token lengths a sixteenth of the cell's."""
    out = json.loads(json.dumps(spec))
    for key in ("prompt", "output", "document", "question"):
        if key in out:
            for k in ("median", "min", "max"):
                if k in out[key]:
                    out[key][k] = max(4, out[key][k] // 16)
    if "clients" in out:
        out["clients"] = out["block"] = 4
        out["pair_stride"] = 1
    if "warm" in out and "seconds" in out["warm"]:
        out["warm"]["seconds"] = 2
    return out


# -- the child -----------------------------------------------------------------


class Child:
    def __init__(self, argv: List[str], env: Dict[str, str]) -> None:
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=subprocess.PIPE, text=True
        )
        self.ready: Optional[Dict[str, Any]] = None
        self._event = threading.Event()
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            if line.startswith("BENCH_READY "):
                self.ready = json.loads(line[len("BENCH_READY "):])
                self._event.set()
            else:
                sys.stderr.write("[server] " + line)
        self._event.set()

    def wait_ready(self) -> Dict[str, Any]:
        deadline = time.monotonic() + READY_TIMEOUT_S
        while time.monotonic() < deadline:
            if self._event.wait(1.0):
                break
        if self.ready is None:
            code = self.proc.poll()
            self.stop()
            raise SystemExit(code if code else 4)
        return self.ready

    def stop(self) -> None:
        if self.proc.poll() is None:
            with contextlib.suppress(Exception):
                self.proc.wait(timeout=30)
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


class StallMonitor:
    """A thread that sleeps 50 ms at a time and keeps the longest oversleep
    and when it ended: a stall of this whole process (or of the machine), as
    distinct from a generator that cannot keep its schedule."""

    def __init__(self) -> None:
        self.worst: List[float] = [0.0, 0.0]  # seconds lost, when it ended
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def _watch(self) -> None:
        last = time.monotonic()
        while not self._stop.wait(0.05):
            now = time.monotonic()
            if now - last - 0.05 > self.worst[0]:
                self.worst = [now - last - 0.05, now]
            last = now

    def reset(self) -> None:
        self.worst = [0.0, 0.0]

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()


# -- load ------------------------------------------------------------------------


class Load:
    """Sends requests and keeps every result, finished or not."""

    def __init__(self, host: str, port: int) -> None:
        self.host, self.port = host, port
        self.results: List[Dict[str, Any]] = []
        self.tasks: List[asyncio.Task] = []

    def begin(self, req: Dict[str, Any], due: Optional[float] = None,
              logprobs: Optional[int] = None):
        """Start one request now; returns (its result, its task)."""
        now = time.monotonic()
        res: Dict[str, Any] = {
            "id": req.get("id"), "due": now if due is None else due,
            "sent": now, "chunks": [], "finished": None, "ok": None, "error": "",
        }
        res["late"] = now - res["due"]
        self.results.append(res)
        return res, self.spawn(
            client.complete(self.host, self.port, req, res, logprobs))

    async def one(self, req: Dict[str, Any], due: Optional[float] = None,
                  logprobs: Optional[int] = None) -> Dict[str, Any]:
        res, task = self.begin(req, due, logprobs)
        await task
        return res

    def spawn(self, coro) -> asyncio.Task:
        t = asyncio.ensure_future(coro)
        self.tasks.append(t)
        return t

    async def cancel_all(self) -> None:
        for t in self.tasks:
            t.cancel()
        await asyncio.gather(*self.tasks, return_exceptions=True)
        self.tasks.clear()


async def _wait_idle(host, port, limit_s: float = 90.0) -> None:
    deadline = time.monotonic() + limit_s
    while time.monotonic() < deadline:
        m = stats.parse_prometheus((await client.call(host, port, "GET", "/metrics"))[1].decode())
        busy = sum(v for (n, _l), v in m.items()
                   if n in ("dynamo_engine_batch_occupancy", "dynamo_engine_prefill_queue_depth"))
        if not busy:
            return
        await asyncio.sleep(0.5)


def _filler(rng: random.Random, n: int, vocab: int) -> List[int]:
    return [rng.randrange(traffic.FIRST_TOKEN_ID, vocab) for _ in range(n)]


async def warm_shapes(load: Load, cfg: Dict[str, Any], seed: int) -> None:
    """Run every packed executable the configuration fixes, at every width
    of page table the cell's lanes can reach, before any of it is timed: an
    anchor request of each length decodes alone (the fused decode steps ramp
    through K = 1, 2, 4, 8), and beside it one prompt of each chunk width."""
    eng = cfg["engine"]
    rng = random.Random(f"warm/{seed}")
    vocab = cfg["vocab_size"]
    budget = eng["mixed_token_budget"]
    widths = sorted({s for _np, s in eng.get("packed_shapes", []) if s > 1})
    for anchor_tokens in eng.get("warm_anchor_tokens", []):
        # the anchor outlives its probes (it is cancelled after them): its
        # pages set the page-table width of every dispatch in between
        anchor, anchor_task = load.begin({
            "prompt": _filler(rng, anchor_tokens, vocab), "id": "warm.anchor",
            "max_tokens": 200})
        while not anchor_task.done() and sum(n for _t, n in anchor["chunks"]) < 17:
            await asyncio.sleep(0.01)
        for s in widths:
            probe = await load.one({"prompt": _filler(rng, min(s, budget - 1), vocab),
                                    "max_tokens": 2, "id": "warm.probe"})
            if not probe["ok"] or anchor_task.done():
                raise SystemExit(
                    f"warm-up failed: probe {probe['error']!r}, anchor {anchor['error']!r}")
        await load.cancel_all()
        await _wait_idle(load.host, load.port)
    load.results.clear()
    load.tasks.clear()


async def run_closed(load: Load, spec, seed, vocab, seconds, on_window):
    """Closed loop: each client sends its next request when its last ends.
    The window opens when every client has finished one request."""
    finished_one = [False] * spec["clients"]
    opened = asyncio.Event()
    blocks: Dict[int, List[Dict[str, Any]]] = {}  # built once, not per client

    async def client_loop(i: int) -> None:
        # client i sends the i-th request of block after block: which lane
        # gets which request does not depend on who finishes first
        block = 0
        while True:
            if block not in blocks:
                blocks[block] = traffic.closed_block(spec, seed, block, vocab)
            await load.one(blocks[block][i])
            block += 1
            finished_one[i] = True
            if all(finished_one):
                opened.set()

    for i in range(spec["clients"]):
        load.spawn(client_loop(i))
    await opened.wait()
    start = time.monotonic()
    await on_window(start)
    await asyncio.sleep(max(0.0, start + seconds - time.monotonic()))
    end = time.monotonic()
    await load.cancel_all()
    return start, end


async def run_open(load: Load, spec, seed, vocab, seconds, on_window):
    """Open loop: every request is sent when it is due, whatever the server
    is doing.  The window is [warm, warm + seconds) of the schedule."""
    warm = float(spec["warm"]["seconds"])
    sched = traffic.open_schedule(spec, seed, vocab, warm + seconds)
    t0 = time.monotonic()
    start = t0 + warm

    async def sender() -> None:
        for r in sched:
            delay = t0 + r["due"] - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            load.begin(r, due=t0 + r["due"])

    send = asyncio.ensure_future(sender())
    await asyncio.sleep(max(0.0, start - time.monotonic()))
    await on_window(start)
    await send
    await asyncio.sleep(max(0.0, start + seconds - time.monotonic()))
    end = time.monotonic()
    # a request due in the window still owes its first token: wait for it,
    # within reason, so that a slow answer is a long time and not a lost one
    grace = time.monotonic() + 30.0
    while time.monotonic() < grace and any(
        start <= r["due"] < end and r["ok"] is None and not r["chunks"]
        for r in load.results
    ):
        await asyncio.sleep(0.05)
    await load.cancel_all()
    return start, end


# -- correct ---------------------------------------------------------------------


def _ids_of(top: Dict[str, float]) -> List[int]:
    return [int(k[1:]) for k in top]


async def check_logits(load: Load, cfg, spec, seed, vocab) -> Dict[str, Any]:
    """Prefill-then-decode log-probabilities through the served engine
    against the plain reference, on a seeded sample of the cell's own
    requests (the longest of the first block, and another; in a mix with
    sharing the longest again, answered from the prefix cache).

    The number compared, ``logprob_err`` (``stats.compared_error``): for
    every compared position the RMS difference over its top five tokens;
    then the 90th percentile over the positions of all the sample's
    requests together.  ``position_errs`` holds every position's number."""
    if spec["loop"] == "closed":
        block = traffic.closed_block(spec, seed, 0, vocab)
    else:
        block = traffic.open_block(spec, seed, 0, vocab)
    n_tok = spec["check"]["decode_tokens"]
    longest = max(block, key=lambda r: len(r["prompt"]))
    others = [r for r in block if r is not longest]
    sample = [longest] + random.Random("check").sample(
        others, spec["check"]["requests"] - 1)
    if spec["check"].get("repeat_for_prefix_hit"):
        sample.append(longest)  # again: now its document is in the cache

    errs: List[float] = []
    for req in sample:
        res = await load.one(
            {"prompt": req["prompt"], "max_tokens": n_tok, "id": "check"}, logprobs=5)
        if not res["ok"]:
            return {"error": res["error"]}
        lp = res["lp"]
        tokens = [int(t[1:]) for t in lp["tokens"]]
        width = min(len(top) for top in lp["top_logprobs"])
        ids = [_ids_of(top)[:width] for top in lp["top_logprobs"]]
        served = [list(top.values())[:width] for top in lp["top_logprobs"]]
        body = {"seed": seed, "tokens": req["prompt"] + tokens[:-1], "ids": ids,
                "rows": [len(req["prompt"]) - 1 + i for i in range(len(tokens))]}
        ref = await client.call_json(load.host, load.port, "POST", "/bench/reference", body)
        errs += stats.position_errors(served, ref["logprobs"])
    return {"logprob_err": stats.compared_error(errs), "positions": len(errs),
            "position_err_p50": stats.percentile(errs, 50),
            "position_err_max": max(errs),
            "prompt_tokens": [len(r["prompt"]) for r in sample],
            "position_errs": errs}


# -- per-layer readers -------------------------------------------------------------


def read_layer_metrics(bench, cell_name: str, ctx: Dict[str, Any],
                       source: Optional[str] = None) -> Dict[str, Any]:
    """The cell's per-layer metrics; with ``source``, those of that source."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        if source is not None and m["source"] != source:
            continue
        # the metric's own file names its reader, "<file>.py:<function>"
        file, _, func = load_json("benchmark", "layer_metrics", m["name"] + ".json")[
            "reader"].partition(":")
        spec = importlib.util.spec_from_file_location(
            "reader_" + file.replace(".", "_"), os.path.join(HERE, "layer_metrics", file))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = getattr(mod, func)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """The device operations that took most of the traced slice, and its
    idle seconds by what the host was doing in them."""
    from benchmark import trace_host  # not at import: it parses a trace

    ops = sorted(ctx["trace"].get("ops", {}).items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": trace_host.idle_gaps(trace_host.table(ctx))}


def layer_context(cfg: Dict[str, Any], spec: Dict[str, Any], cell: Dict[str, Any],
                  **measured: Any) -> Dict[str, Any]:
    """What a per-layer reader is handed (README, "A per-layer metric"):
    the cell's files, the arithmetic every family shares (``costs``), the
    counts of the configuration's own family (``model_costs``: the module
    its file names under ``"costs"``), and what the run measured."""
    return {"cfg": cfg, "traffic": spec, "cell": cell, "costs": costs,
            "model_costs": importlib.import_module(cfg["costs"]), **measured}


# -- main --------------------------------------------------------------------------


async def calibrate(args, cfg, spec, ready) -> Dict[str, Any]:
    """Not a measurement: the comparison with the reference on several
    seeds in one process (new weights in the live engine for each), to set
    or to test a tolerance.  Prints no result line."""
    host, port = ready["host"], ready["port"]
    load = Load(host, port)
    readings = {}
    for i, seed in enumerate(args.calibrate):
        if i:
            await client.call_json(host, port, "POST", "/bench/reseed", {"seed": seed})
        readings[seed] = await check_logits(load, cfg, spec, seed, cfg["vocab_size"])
        log("calibrate " + json.dumps({"seed": seed, "control": args.control, **readings[seed]}))
    return readings


async def sweep(args, cfg, spec, ready) -> None:
    """Not a measurement: the open loop at each of several rates in one
    process, to find the knee once.  Prints one line per rate."""
    host, port = ready["host"], ready["port"]
    load = Load(host, port)
    await warm_shapes(load, cfg, args.seed)

    async def nothing(_start: float) -> None:
        return None

    for i, rate in enumerate(args.sweep):
        s = dict(spec, rate_per_s=rate)
        before = await client.call_json(host, port, "GET", "/bench/state")
        start, end = await run_open(load, s, args.seed + i, cfg["vocab_size"],
                                    args.seconds, nothing)
        exit_time = time.monotonic()
        after = await client.call_json(host, port, "GET", "/bench/state")
        e2e = stats.end_to_end(load.results, start, end, exit_time)
        due = [r for r in load.results if start <= r["due"] < end]
        half = start + (end - start) / 2
        ttft = lambda rs: [  # noqa: E731
            (r["chunks"][0][0] - r["due"]) * 1e3 for r in rs if r["chunks"]]
        first, second = ttft([r for r in due if r["due"] < half]), ttft(
            [r for r in due if r["due"] >= half])
        log("sweep " + json.dumps({
            "rate_per_s": rate, "due_in_window": len(due),
            "no_first_token": sum(1 for r in due if not r["chunks"]),
            "finished": sum(1 for r in due if r["ok"]),
            "failed": sum(1 for r in due if r["ok"] is False),
            "ttft_p50_first_half_ms": stats.percentile(first, 50) if first else None,
            "ttft_p50_second_half_ms": stats.percentile(second, 50) if second else None,
            "compiles": sum(after["compiles"].values()) - sum(before["compiles"].values()),
            **e2e}))
        load.results.clear()
        await _wait_idle(host, port)


async def drive(args, bench, cell, cfg, spec, ready) -> Dict[str, Any]:
    host, port = ready["host"], ready["port"]
    vocab = cfg["vocab_size"]
    load = Load(host, port)
    t = time.monotonic()
    await warm_shapes(load, cfg, args.seed)
    warm_shapes_s = time.monotonic() - t
    marks: Dict[str, Any] = {}
    stalls = StallMonitor()

    async def on_window(start: float) -> None:
        # set-up ends here: everything before the window is counted
        marks["setup_s"] = start - T_START
        stalls.reset()
        if args.trace:
            # the tick profiler goes on as the window opens, so that its
            # totals are the window's; the engine behaves the same with it
            # on or off (PERF.md section 6, PR 26)
            await client.call_json(host, port, "POST", "/profile/ticks",
                                   {"enabled": True, "clear": True})
        await client.call_json(host, port, "POST", "/bench/log_compiles", {"on": True})
        marks["state0"] = await client.call_json(host, port, "GET", "/bench/state")
        marks["metrics0"] = (await client.call(host, port, "GET", "/metrics"))[1].decode()
        if args.trace:
            async def tracer() -> None:
                await asyncio.sleep(args.seconds * 0.4)
                await client.call_json(host, port, "POST", "/bench/trace", {"action": "start"})
                await asyncio.sleep(min(TRACE_SECONDS, args.seconds * 0.4))
                marks["trace"] = await client.call_json(
                    host, port, "POST", "/bench/trace", {"action": "stop"})
            marks["tracer"] = asyncio.ensure_future(tracer())

    runner = run_closed if spec["loop"] == "closed" else run_open
    t_traffic = time.monotonic()
    start, end = await runner(load, spec, args.seed, vocab, args.seconds, on_window)
    exit_time = time.monotonic()
    stalls.stop()
    await client.call_json(host, port, "POST", "/bench/log_compiles", {"on": False})
    if "tracer" in marks:
        await marks["tracer"]
    metrics1 = (await client.call(host, port, "GET", "/metrics"))[1].decode()
    state1 = await client.call_json(host, port, "GET", "/bench/state")
    results = load.results
    e2e = stats.end_to_end(results, start, end, exit_time)
    e2e["setup_s"] = marks["setup_s"]

    in_window = [r for r in results
                 if r["sent"] < end and (r["finished"] is None or r["finished"] >= start)]
    failed = [r for r in in_window if r["ok"] is False]
    lates = [r["late"] for r in results if start <= r["due"] < end]
    late_p50 = stats.percentile(lates, 50) if lates else 0.0
    late_p99 = stats.percentile(lates, 99) if lates else 0.0
    compiles0 = marks["state0"]["compiles"]
    compiles = {k: v - compiles0.get(k, 0) for k, v in state1["compiles"].items()
                if v - compiles0.get(k, 0)}

    checks: List[Dict[str, Any]] = []

    def check(name: str, value: Any, limit: Any, ok: bool) -> None:
        checks.append({"check": name, "value": value, "limit": limit, "ok": bool(ok)})

    check("failed_requests", len(failed), 0, not failed)
    check("compiles_in_window", sum(compiles.values()), 0, not compiles)
    if spec["loop"] == "open":
        # a generator that runs behind its schedule by more than the mean
        # gap between arrivals measured a slower schedule.  Judged by the
        # median request: a stall of the whole machine makes a few sends
        # late and the run a far-off one, which the times from the due time
        # show and the reader of several runs sets aside (PERF.md section 2);
        # the p99 and the longest stall of this process are printed below
        gap = traffic.mean_gap_s(spec)
        check("generator_late_p50_s", late_p50, gap, late_p50 <= gap)
    for what, stated in cfg["guarantees"].items():
        if what in state1["dtypes"]:  # the types the engine says it serves in
            check(what, state1["dtypes"][what], stated, state1["dtypes"][what] == stated)
    tol = cfg.get("tolerance", {})
    logits = await check_logits(load, cfg, spec, args.seed, vocab)
    if "error" in logits:
        check("logits_request", logits["error"], "ok", False)
    else:
        limit = tol.get("logprob_err")
        check("logprob_err_vs_reference", logits["logprob_err"], limit,
              limit is not None and logits["logprob_err"] <= limit)
        log("info " + json.dumps({"logits": {
            k: v for k, v in logits.items() if k != "position_errs"}}))
    for c in checks:
        log("check " + json.dumps(c))
        if not c["ok"]:  # and where a reader keeps only the end of stderr
            print("check failed " + json.dumps(c), file=sys.stderr, flush=True)
    correct = all(c["ok"] for c in checks) and not args.rehearse

    setup_parts = dict(state1["setup_parts"], warm_shapes_s=warm_shapes_s,
                       warm_traffic_s=start - t_traffic)
    log("info " + json.dumps({
        "setup_parts": setup_parts, "requests_sent": len(results),
        "in_window": len(in_window), "due_in_window": len(lates),
        "finished_in_window": sum(
            1 for r in results if r["finished"] and start <= r["finished"] < end),
        "compiles_in_window": compiles, "generator_late_p99_s": late_p99,
        "process_stall_max_s": stalls.worst[0],
        "process_stall_at_s": stalls.worst[1] - start if stalls.worst[0] else None,
        "window_s": end - start, "packed_shapes": state1.get("packed_shapes"),
        "errors": sorted({r["error"] for r in failed})[:5],
    }))

    device = dict(state1["device"], memory_peak_bytes=state1["memory_peak_bytes"])
    wanted = [m["name"] for m in bench["end_to_end"]
              if "workloads" not in m or cell["name"] in m["workloads"]]
    counters = stats.Counters(marks["metrics0"], metrics1)
    if not args.trace:
        metrics = {}
        for m in bench["end_to_end"]:
            if m["name"] in wanted and m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        # what tells two runs of one tree apart (a stall of the machine, the
        # fused block's ceiling, one request more at the window's edge) is in
        # the window's counters, which an untraced run has too: printed, not
        # reported.  ``planes``: no trace of an earlier run is read for it
        ctx = layer_context(
            cfg, spec, cell, counters=counters,
            window_s=end - start, compiles=compiles, planes=[], end_to_end=e2e)
        log("info " + json.dumps({"window_counters": {
            k: v["value"] for k, v in read_layer_metrics(
                bench, cell["name"], ctx, "program_counter").items()}}))
        out = {"correct": correct, "attempted": len(in_window), "failed": len(failed),
               "metrics": metrics, "device": device}
    else:
        trace = await client.call_json(
            host, port, "POST", "/bench/trace_reduce",
            {"dump": args.dump_trace or None}, timeout=300)
        ctx = layer_context(
            cfg, spec, cell,
            counters=counters, profiler=state1.get("profiler"), trace=trace,
            # the trace runs on until stop_trace returns: where the device
            # was busy at both ends, its own span is the window
            trace_window_s=max(marks.get("trace", {}).get("window_s", 0.0),
                               trace.get("span_s", 0.0)),
            window_s=end - start, compiles=compiles,
            device_kind=device["kind"], end_to_end=e2e)
        if not args.rehearse:
            ctx["peaks"] = costs.peaks(device["kind"])
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = ctx["trace_window_s"]
        metrics = read_layer_metrics(bench, cell["name"], ctx) if "peaks" in ctx else {}
        log("info " + json.dumps({"end_to_end_of_traced_run": e2e}))
        out = {"correct": correct, "attempted": len(in_window), "failed": len(failed),
               "metrics": metrics, "device": device, "breakdown": breakdown(ctx)}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; always correct: false")
    ap.add_argument("--control", default="",
                    help="int8_weights | int8_kv: the program serving through its "
                         "own lower-precision path, which correct has to refuse")
    ap.add_argument("--calibrate", default="", type=lambda s: [int(v) for v in s.split(",") if v],
                    help="seeds: compare with the reference on each, and stop")
    ap.add_argument("--sweep", default="", type=lambda s: [float(v) for v in s.split(",") if v],
                    help="rates: run the open loop at each, and stop")
    ap.add_argument("--dump-trace", default="",
                    help="write a description of the trace to this file")
    args = ap.parse_args()

    bench = load_json("BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    from benchmark.server import load_config  # no JAX at import

    cfg = load_config(cell["config"], args.rehearse)
    spec = traffic.load(cell["traffic"])
    if args.rehearse:
        spec = shrink(spec)

    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(WORKDIR, "xla"))
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["DYN_XLA_CACHE_DIR"] = "off"
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    os.makedirs(WORKDIR, exist_ok=True)
    argv = [sys.executable, "-m", "benchmark.server", "--config", cell["config"],
            "--seed", str(args.calibrate[0] if args.calibrate else args.seed), "--chips", str(cell["chips"]),
            "--workdir", WORKDIR]
    if args.rehearse:
        argv.append("--rehearse")
    if args.control:
        argv += ["--control", args.control]
    child = Child(argv, env)
    try:
        ready = child.wait_ready()
        try:
            if args.calibrate:
                asyncio.run(calibrate(args, cfg, spec, ready))
                return 0
            if args.sweep:
                asyncio.run(sweep(args, cfg, spec, ready))
                return 0
            out = asyncio.run(drive(args, bench, cell, cfg, spec, ready))
        finally:
            with contextlib.suppress(Exception):
                asyncio.run(client.call(ready["host"], ready["port"], "POST",
                                        "/bench/exit", timeout=10))
    finally:
        child.stop()
    log(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
