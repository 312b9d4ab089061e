#!/usr/bin/env python3
"""Start the served path on one TPU chip and check what comes out.

    python chip_smoke.py              # one chip: kernels, serve, distributed
    python chip_smoke.py --chips 4    # four chips: --tp 4 against tp=1, only
    python chip_smoke.py --rehearse   # CPU, tiny model, kernels interpreted

The parent process never imports JAX: a chip belongs to one process at a
time, so everything that needs it runs in a child, one child at a time.
The children are the entry points a user runs (``python -m dynamo_tpu run
...``); only the kernel table and the sharding evidence come from children
of this file (``--child``), which build an engine in-process.

Every failure is fatal: a child that dies, hangs past its limit or answers
wrongly ends the run non-zero with that child's last lines, and no child
that needs the chip carries on on the CPU.  The last line of stdout is
``{"ok": true, "device": {...}}`` only when every phase passed; with
``--rehearse`` the device it names is the CPU, so a rehearsal can never be
read as a chip pass.  The timings printed are smoke timings (one cold run
each), not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")  # git-ignored: model dir and logs
# where the children keep their compile cache: JAX_COMPILATION_CACHE_DIR if
# set, else the engine's own default (dynamo_tpu.engine.engine.XLA_CACHE_DIR)
XLA_CACHE = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
    ROOT, ".xla_cache"
)
# a rehearsal's CPU executables stay out of the cache the chip run reads
REHEARSE_CACHE = os.path.join(WORK, "xla_cache_cpu")

# TinyLlama-1.1B, whole (the dense Llama-family shape of bench.py)
TINYLLAMA = dict(
    vocab_size=32000, hidden_size=2048, intermediate_size=5632,
    num_hidden_layers=22, num_attention_heads=32, num_key_value_heads=4,
    head_dim=64, max_position_embeddings=2048,
)
# ModelConfig.tiny, for --rehearse
TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, max_position_embeddings=512,
)
# prompt lengths of the served requests: short; the s_max 512 executable;
# several mixed-budget chunks beside running decodes; the classic path
PROMPTS = {"short": 16, "mid": 300, "long": 900, "longest": 1500,
           "penalised": 1100}
REHEARSE_PROMPTS = {"short": 8, "mid": 40, "long": 100, "longest": 180,
                    "penalised": 130}
# engine flags of a rehearsal (the chip run passes none: default flags)
REHEARSE_FLAGS = ["--max-seq-len", "256", "--num-pages", "128",
                  "--mixed-token-budget", "64"]
MAX_TOKENS = 64
# max |kernel - XLA twin| over outputs of magnitude <= 1: both round
# probabilities and values to bf16 (2**-8 relative) but in a different order
TOLERANCE = {"bfloat16": 3e-2, "float32": 2e-4}
# first-token logprob, tp=4 against tp=1: bf16 partial sums reduce in a
# different order across shards
TP_LOGPROB_TOLERANCE = 0.15

_children: list = []  # every process started, for stop_all
_servers: dict = {}  # name -> (proc, log) of the servers that should be up


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


class Failed(Exception):
    pass


def fail(msg: str, log: str | None = None) -> None:
    lines = ""
    if log and os.path.exists(log):
        with open(log, errors="replace") as f:
            lines = "".join(f.readlines()[-40:])
    raise Failed(f"{msg}\n{lines}" if lines else msg)


def stop_all() -> None:
    for proc in _children:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cache_entries(rehearse: bool) -> int:
    d = REHEARSE_CACHE if rehearse else XLA_CACHE
    if not os.path.isdir(d):
        return 0
    return sum(1 for n in os.listdir(d) if not n.startswith("."))


def child_env(rehearse: bool, devices: int = 1) -> dict:
    env = dict(os.environ)
    env["DYN_LOG"] = "info"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if rehearse:
        # the only way any child of this script runs off the chip
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_COMPILATION_CACHE_DIR"] = REHEARSE_CACHE
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}"
        )
    return env


def spawn(name: str, argv: list, env: dict) -> tuple:
    """Start a long-lived child of the program with its output in a log."""
    os.makedirs(os.path.join(WORK, "logs"), exist_ok=True)
    log = os.path.join(WORK, "logs", f"{name}.log")
    out = open(log, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu", *argv], cwd=ROOT, env=env,
        stdout=out, stderr=subprocess.STDOUT, start_new_session=True,
    )
    out.close()
    _children.append(proc)
    _servers[name] = (proc, log)
    return proc, log


def run_child(name: str, args: list, env: dict, limit_s: float) -> None:
    """Run one ``--child`` of this file to its end; its lines pass through."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", name, *args],
        cwd=ROOT, env=env, start_new_session=True,
    )
    _children.append(proc)
    try:
        rc = proc.wait(timeout=limit_s)
    except subprocess.TimeoutExpired:
        fail(f"child {name} still running after {limit_s:.0f}s")
    if rc != 0:
        fail(f"child {name} exited {rc}")


def http(method: str, url: str, body: dict | None = None,
         timeout: float = 600.0) -> tuple:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def wait_ready(proc, log: str, check, what: str, limit_s: float) -> float:
    """Poll ``check()`` until it holds; the child dying or the limit
    passing fails the run.  Returns the seconds it took."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < limit_s:
        for name, (p, plog) in _servers.items():
            if p.poll() is not None:
                fail(f"waiting for {what}: {name} exited {p.returncode}", plog)
        try:
            if check():
                return time.monotonic() - t0
        except (OSError, urllib.error.URLError):
            pass
        time.sleep(0.5)
    tails = []
    for name, (_, plog) in _servers.items():
        with open(plog, errors="replace") as f:
            tails.append(f"--- {name}\n" + "".join(f.readlines()[-15:]))
    fail(f"{what}: not ready after {limit_s:.0f}s\n" + "".join(tails))


def require_tpu_log(log: str, rehearse: bool, count: int = 1) -> None:
    """The served child logs the devices its engine sees, once; on a chip
    run anything but the TPU (at the expected count) fails here, before a
    request is sent."""
    with open(log, errors="replace") as f:
        lines = [ln for ln in f if "engine devices:" in ln]
    if not lines:
        fail("engine logged no device line", log)
    want = "platform=cpu" if rehearse else "platform=tpu"
    if want not in lines[0] or f"count={count} " not in lines[0]:
        fail(f"engine is not on {want} x{count}: {lines[0].strip()}", log)


def terminate(proc, log: str, what: str, want_drain: bool) -> None:
    for name in [n for n, (p, _) in _servers.items() if p is proc]:
        del _servers[name]
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=90)
    except subprocess.TimeoutExpired:
        fail(f"{what}: still running 90s after SIGTERM", log)
    if rc != 0:
        fail(f"{what}: exited {rc} after SIGTERM", log)
    if want_drain:
        with open(log, errors="replace") as f:
            if "drain complete" not in f.read():
                fail(f"{what}: no 'drain complete' in its log", log)


# ---------------------------------------------------------------------------
# set-up (parent, no JAX)


def cgroup_cpu_max() -> str | None:
    """The cgroup's CPU quota, if any: a machine that shows more cores than
    its quota allows throttles every process when one of them spins up a
    thread per core."""
    try:
        with open("/sys/fs/cgroup/cpu.max") as f:
            return f.read().strip()
    except OSError:
        return None


def build_native() -> None:
    """Rebuild native/build/libdynnative.so from native/*.cpp, always: the
    directory is git-ignored, and ``ensure_native_built`` is satisfied by
    any library that exists, stale or not."""
    t0 = time.monotonic()
    proc = subprocess.run(
        ["make", "-B", "-C", os.path.join(ROOT, "native")],
        capture_output=True, text=True,
    )
    if proc.returncode != 0:
        fail(f"native build failed:\n{proc.stdout[-2000:]}{proc.stderr[-2000:]}")
    from dynamo_tpu.tokens import hashing

    if hashing.NATIVE is None:
        fail("native library was built but does not load")
    emit(phase="setup", native_build_s=round(time.monotonic() - t0, 2),
         hashing_backend="native", cpu_count=os.cpu_count(),
         cpus_allowed=len(os.sched_getaffinity(0)), cpu_max=cgroup_cpu_max())


def build_model_dir(seed: int, rehearse: bool, kv_heads: int = 0) -> str:
    """config.json + tokenizer + seeded random bf16 weights, at full size
    (``kv_heads`` widens the tiny model so that a rehearsed tp divides it)."""
    import torch
    from safetensors.torch import save_file
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    t0 = time.monotonic()
    shape = TINY if rehearse else TINYLLAMA
    if rehearse and kv_heads:
        shape = dict(TINY, num_key_value_heads=kv_heads)
    d = os.path.join(WORK, "model-tiny" if rehearse else "model")
    os.makedirs(d, exist_ok=True)
    cfg = dict(shape, model_type="llama", architectures=["LlamaForCausalLM"],
               rope_theta=10000.0, rms_norm_eps=1e-5,
               tie_word_embeddings=False, torch_dtype="bfloat16")
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(cfg, f)
    # the tokenizer bench.py builds for its served leg
    tok = Tokenizer(models.BPE(unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.train_from_iterator(
        ["the quick brown fox jumps over the lazy dog " * 8],
        trainers.BpeTrainer(vocab_size=128, special_tokens=["<unk>"],
                            show_progress=False),
    )
    tok.decoder = decoders.BPEDecoder()
    tok.save(os.path.join(d, "tokenizer.json"))
    with open(os.path.join(d, "tokenizer_config.json"), "w") as f:
        json.dump({}, f)

    gen = torch.Generator().manual_seed(seed)
    H, I, V = shape["hidden_size"], shape["intermediate_size"], shape["vocab_size"]
    q_rows = shape["num_attention_heads"] * shape["head_dim"]
    kv_rows = shape["num_key_value_heads"] * shape["head_dim"]

    def w(rows, cols):
        return (torch.randn(rows, cols, generator=gen) * 0.02).to(torch.bfloat16)

    ones = torch.ones(H, dtype=torch.bfloat16)
    tensors = {"model.embed_tokens.weight": w(V, H), "lm_head.weight": w(V, H),
               "model.norm.weight": ones.clone()}
    for i in range(shape["num_hidden_layers"]):
        p = f"model.layers.{i}."
        tensors[p + "self_attn.q_proj.weight"] = w(q_rows, H)
        tensors[p + "self_attn.k_proj.weight"] = w(kv_rows, H)
        tensors[p + "self_attn.v_proj.weight"] = w(kv_rows, H)
        tensors[p + "self_attn.o_proj.weight"] = w(H, q_rows)
        tensors[p + "mlp.gate_proj.weight"] = w(I, H)
        tensors[p + "mlp.up_proj.weight"] = w(I, H)
        tensors[p + "mlp.down_proj.weight"] = w(H, I)
        tensors[p + "input_layernorm.weight"] = ones.clone()
        tensors[p + "post_attention_layernorm.weight"] = ones.clone()
    path = os.path.join(d, "model.safetensors")
    save_file(tensors, path)
    emit(phase="setup", model_dir=os.path.relpath(d, ROOT), seed=seed,
         params=sum(t.numel() for t in tensors.values()),
         weights_mib=round(os.path.getsize(path) / 2**20, 1),
         seconds=round(time.monotonic() - t0, 1))
    return d


def sizes(args) -> tuple:
    """(prompt lengths, vocabulary) of this run's model."""
    if args.rehearse:
        return REHEARSE_PROMPTS, TINY["vocab_size"]
    return PROMPTS, TINYLLAMA["vocab_size"]


def prompt_ids(n: int, seed: int, vocab: int) -> list:
    """``n`` seeded token ids over the model's whole vocabulary."""
    import random

    rng = random.Random(seed * 1000003 + n)
    return [rng.randrange(1, vocab) for _ in range(n)]


# ---------------------------------------------------------------------------
# requests and what they must answer


def check_completion(status: int, raw: bytes, what: str, log: str,
                     want_logprobs: bool = False) -> dict:
    if status != 200:
        fail(f"{what}: HTTP {status}: {raw[:400]!r}", log)
    body = json.loads(raw)
    got = body["usage"]["completion_tokens"]
    if got != MAX_TOKENS:
        fail(f"{what}: completion_tokens {got}, asked {MAX_TOKENS}", log)
    if want_logprobs:
        lps = body["choices"][0]["logprobs"]["token_logprobs"]
        bad = [x for x in lps if x is None or x != x or abs(x) == float("inf")]
        if len(lps) != MAX_TOKENS or bad:
            fail(f"{what}: {len(lps)} logprobs, {len(bad)} not finite", log)
    return body


def completion_body(model: str, ids: list, **extra) -> dict:
    return dict(model=model, prompt=ids, max_tokens=MAX_TOKENS,
                ignore_eos=True, temperature=0.0, **extra)


def stream_chat(base: str, model: str, log: str) -> float:
    """One streaming chat: well-formed SSE to ``[DONE]``.  Returns seconds."""
    t0 = time.monotonic()
    body = dict(model=model, stream=True, max_tokens=16, ignore_eos=True,
                messages=[{"role": "user", "content": "the quick brown fox"}])
    req = urllib.request.Request(
        base + "/v1/chat/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    chunks, done = 0, False
    with urllib.request.urlopen(req, timeout=600) as resp:
        if resp.status != 200:
            fail(f"chat stream: HTTP {resp.status}", log)
        for line in resp:
            line = line.decode().strip()
            if not line:
                continue
            if not line.startswith("data:"):
                fail(f"chat stream: not an SSE data line: {line[:200]!r}", log)
            payload = line[5:].strip()
            if payload == "[DONE]":
                done = True
                break
            obj = json.loads(payload)
            if "error" in obj or "choices" not in obj:
                fail(f"chat stream: bad chunk {payload[:300]!r}", log)
            chunks += 1
    if not done or chunks == 0:
        fail(f"chat stream: {chunks} chunks, [DONE] seen: {done}", log)
    return time.monotonic() - t0


def metric_values(text: str, name: str) -> dict:
    """``{label-string: value}`` of one family of Prometheus text."""
    out = {}
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            head, _, val = line.rpartition(" ")
            if head == name or head.startswith(name + "{"):
                out[head[len(name):]] = float(val)
    return out


# ---------------------------------------------------------------------------
# phases (parent)


def phase_kernels(args) -> None:
    t0 = time.monotonic()
    extra = ["--seed", str(args.seed)] + (["--rehearse"] if args.rehearse else [])
    run_child("kernels", extra, child_env(args.rehearse), limit_s=700)
    emit(phase="kernels", seconds=round(time.monotonic() - t0, 1))


def phase_serve(args, model_dir: str) -> float:
    """Returns the served child's seconds to ready (cold)."""
    t0 = time.monotonic()
    port = args.port or free_port()
    base = f"http://127.0.0.1:{port}"
    model = os.path.basename(model_dir)
    lens, vocab = sizes(args)
    before = cache_entries(args.rehearse)
    argv = ["run", "in=http", "out=jax", "--model-path", model_dir,
            "--port", str(port)] + (REHEARSE_FLAGS if args.rehearse else [])
    proc, log = spawn("serve", argv, child_env(args.rehearse))
    ready_s = wait_ready(
        proc, log, lambda: http("GET", base + "/health", timeout=5)[0] == 200,
        "serve", limit_s=420,
    )
    require_tpu_log(log, args.rehearse)
    status, raw = http("GET", base + "/v1/models")
    if status != 200 or model not in raw.decode():
        fail(f"/v1/models: HTTP {status} {raw[:300]!r}", log)
    first_s = stream_chat(base, model, log)

    # concurrent completions: the default mixed path, chunks beside decodes
    results: dict = {}

    def post(key: str, body: dict) -> None:
        t = time.monotonic()
        try:
            results[key] = (*http("POST", base + "/v1/completions", body),
                            time.monotonic() - t)
        except Exception as e:  # reported by the check below
            results[key] = (0, repr(e).encode(), time.monotonic() - t)

    bodies = {
        k: completion_body(model, prompt_ids(lens[k], args.seed, vocab),
                           **({"logprobs": 1} if k == "mid" else {}))
        for k in ("short", "mid", "long", "longest")
    }
    threads = [threading.Thread(target=post, args=kv) for kv in bodies.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    answered = {}
    for k in bodies:
        status, raw, secs = results[k]
        answered[k] = check_completion(
            status, raw, f"completion {k} ({lens[k]} tokens)", log,
            want_logprobs=(k == "mid"),
        )
        emit(phase="serve", request=k, prompt_tokens=lens[k],
             smoke_seconds=round(secs, 2))
    # the mid prompt again: its prefix is cached now (suffix path)
    post("mid-warm", bodies["mid"])
    status, raw, warm_s = results["mid-warm"]
    warm = check_completion(status, raw, "completion mid, repeated", log, True)
    cold_lp = answered["mid"]["choices"][0]["logprobs"]["token_logprobs"]
    warm_lp = warm["choices"][0]["logprobs"]["token_logprobs"]
    agree = sum(abs(a - b) < 0.05 for a, b in zip(cold_lp, warm_lp))
    emit(phase="serve", request="mid-warm", smoke_seconds=round(warm_s, 2),
         cold_warm_logprob_agreement=f"{agree}/{MAX_TOKENS}",
         note="printed, not gated: random weights, thin bf16 argmax margins")
    # a penalised request: _mixed_tick_ok sends it down the classic path
    # (flash prefill + paged decode kernels)
    post("penalised", completion_body(
        model, prompt_ids(lens["penalised"], args.seed, vocab),
        frequency_penalty=0.5,
    ))
    status, raw, pen_s = results["penalised"]
    check_completion(status, raw, "completion with frequency_penalty", log)
    emit(phase="serve", request="penalised", prompt_tokens=lens["penalised"],
         smoke_seconds=round(pen_s, 2))

    status, raw = http("GET", base + "/metrics")
    if status != 200:
        fail(f"/metrics: HTTP {status}", log)
    text = raw.decode()
    dispatches = metric_values(text, "dynamo_engine_dispatches_total")
    if dispatches.get('{kind="unified"}', 0) <= 0:
        fail(f"no unified dispatch was counted: {dispatches}", log)
    if not any(k != '{kind="unified"}' and v > 0 for k, v in dispatches.items()):
        fail(f"the classic path never dispatched: {dispatches}", log)
    http_reqs = {}
    for line in text.splitlines():
        if "_http_service_requests_total{" in line:
            head, _, val = line.rpartition(" ")
            http_reqs[head] = float(val)
    if not http_reqs:
        fail("/metrics carries no http_service_requests_total series", log)
    errors = {k: v for k, v in http_reqs.items()
              if 'status="success"' not in k and v > 0}
    if errors:
        fail(f"error counters on /metrics: {errors}", log)
    compiles = metric_values(text, "dynamo_compile_events_total")
    terminate(proc, log, "serve", want_drain=False)
    emit(phase="serve", seconds=round(time.monotonic() - t0, 1),
         seconds_to_ready_cold=round(ready_s, 1),
         first_request_smoke_seconds=round(first_s, 2),
         xla_compiles=int(sum(compiles.values())),
         xla_compiles_by_entry={k: int(v) for k, v in compiles.items()},
         dispatches={k: int(v) for k, v in dispatches.items()},
         multistep_k=metric_values(text, "dynamo_engine_multistep_k"),
         cache_dir=os.path.relpath(
             REHEARSE_CACHE if args.rehearse else XLA_CACHE, ROOT),
         cache_entries_before=before,
         cache_entries_after=cache_entries(args.rehearse))
    return round(ready_s, 1)


def phase_distributed(args, model_dir: str, cold_ready_s) -> None:
    """hub + one out=jax worker + a kv-routing frontend: the system's own
    shape.  No platform variable is set for any of them on a chip run, so
    the phase passes only if hub and frontend leave the chip alone."""
    t0 = time.monotonic()
    env = child_env(args.rehearse)
    hub_port, port = free_port(), free_port()
    hub_addr = f"127.0.0.1:{hub_port}"
    base = f"http://127.0.0.1:{port}"
    model = os.path.basename(model_dir)
    lens, vocab = sizes(args)
    before = cache_entries(args.rehearse)

    hub, hub_log = spawn(
        "hub", ["hub", "--host", "127.0.0.1", "--port", str(hub_port)], env)

    def hub_up() -> bool:
        with socket.create_connection(("127.0.0.1", hub_port), timeout=2):
            return True

    wait_ready(hub, hub_log, hub_up, "hub", limit_s=60)
    # the frontend takes its hub lease first and holds it while the worker
    # loads beside it: a normal deployment.  On the chip's machine a TPU
    # runtime starting in the worker stops EVERY process for seconds (6.8 s
    # measured); the hub used to expire the frontend's lease the moment both
    # thawed.  The phase passes only if the lease outlives that.
    front, front_log = spawn(
        "frontend", ["run", "in=http", "out=dyn", "--hub", hub_addr,
                     "--router-mode", "kv", "--port", str(port)], env)
    wait_ready(
        front, front_log,
        lambda: http("GET", base + "/health", timeout=5)[0] == 200,
        "frontend", limit_s=120,
    )
    worker, worker_log = spawn(
        "worker", ["run", "in=dyn", "out=jax", "--model-path", model_dir,
                   "--hub", hub_addr]
        + (REHEARSE_FLAGS if args.rehearse else []), env)

    def worker_serving() -> bool:
        with open(worker_log, errors="replace") as f:
            return "worker serving model" in f.read()

    ready_s = wait_ready(worker, worker_log, worker_serving, "worker",
                         limit_s=240)
    require_tpu_log(worker_log, args.rehearse)
    at_ready = cache_entries(args.rehearse)
    wait_ready(
        front, front_log,
        lambda: model in http("GET", base + "/v1/models", timeout=5)[1].decode(),
        "frontend to list the model", limit_s=60,
    )
    # two requests sharing a prefix, through the router
    shared = prompt_ids(lens["mid"], args.seed, vocab)
    for i, tail in enumerate((lens["short"], 2 * lens["short"])):
        ids = shared + prompt_ids(tail, args.seed + 1 + i, vocab)
        t = time.monotonic()
        status, raw = http("POST", base + "/v1/completions",
                           completion_body(model, ids, logprobs=1))
        check_completion(status, raw, f"routed completion {i}", worker_log, True)
        emit(phase="distributed", request=i, prompt_tokens=len(ids),
             smoke_seconds=round(time.monotonic() - t, 2))
    for proc, log, what in ((hub, hub_log, "hub"), (front, front_log, "frontend")):
        if proc.poll() is not None:
            fail(f"{what} died during the phase (exit {proc.returncode})", log)
    terminate(worker, worker_log, "worker", want_drain=True)
    terminate(front, front_log, "frontend", want_drain=False)
    del _servers["hub"]
    hub.send_signal(signal.SIGINT)
    try:
        if hub.wait(timeout=30) != 0:
            fail(f"hub: exited {hub.returncode} after SIGINT", hub_log)
    except subprocess.TimeoutExpired:
        fail("hub: still running 30s after SIGINT", hub_log)
    if not args.rehearse and at_ready != before:
        fail(f"worker changed the compile cache before ready: "
             f"{before} -> {at_ready} entries", worker_log)
    with open(hub_log, errors="replace") as f:
        stalls = re.findall(r"hub did not run for ([0-9.]+)s", f.read())
    emit(phase="distributed", seconds=round(time.monotonic() - t0, 1),
         hub_stalls_s=[float(x) for x in stalls],
         seconds_to_ready_warm=round(ready_s, 1),
         seconds_to_ready_cold=cold_ready_s,
         cache_entries_before=before, cache_entries_at_ready=at_ready,
         cache_entries_after=cache_entries(args.rehearse))


def phase_tp(args, model_dir: str) -> None:
    """--chips 4: serve the same model with --tp 4, then with tp=1, answer
    one prompt set with both, compare.  No other phase runs."""
    lens, vocab = sizes(args)
    model = os.path.basename(model_dir)
    env = child_env(args.rehearse, devices=4)
    keys = ("short", "mid", "long")
    bodies = {k: completion_body(model, prompt_ids(lens[k], args.seed, vocab),
                                 logprobs=1) for k in keys}
    answers: dict = {}
    for tp in (4, 1):
        t0 = time.monotonic()
        port = free_port()
        base = f"http://127.0.0.1:{port}"
        argv = ["run", "in=http", "out=jax", "--model-path", model_dir,
                "--port", str(port), "--tp", str(tp)]
        proc, log = spawn(f"tp{tp}", argv + (REHEARSE_FLAGS if args.rehearse
                                             else []), env)
        ready_s = wait_ready(
            proc, log,
            lambda: http("GET", base + "/health", timeout=5)[0] == 200,
            f"tp={tp} server", limit_s=600,
        )
        require_tpu_log(log, args.rehearse, count=4)
        answers[tp] = {}
        for k in keys:
            status, raw = http("POST", base + "/v1/completions", bodies[k])
            body = check_completion(status, raw, f"tp={tp} {k}", log, True)
            answers[tp][k] = body["choices"][0]["logprobs"]["token_logprobs"]
        terminate(proc, log, f"tp={tp} server", want_drain=False)
        emit(phase=f"tp{tp}", seconds=round(time.monotonic() - t0, 1),
             seconds_to_ready=round(ready_s, 1))
    for k in keys:
        a, b = answers[4][k], answers[1][k]
        first = abs(a[0] - b[0])
        agree = sum(abs(x - y) < 0.05 for x, y in zip(a, b))
        emit(phase="tp", prompt=k, prompt_tokens=lens[k],
             first_token_logprob_tp4=a[0], first_token_logprob_tp1=b[0],
             first_token_logprob_diff=round(first, 5),
             tolerance=TP_LOGPROB_TOLERANCE,
             logprob_agreement=f"{agree}/{MAX_TOKENS}")
        if first > TP_LOGPROB_TOLERANCE:
            fail(f"tp=4 and tp=1 disagree on {k}: first-token logprob "
                 f"{a[0]} vs {b[0]}")
    # positive evidence of sharding, from an engine built in-process
    extra = ["--model-dir", model_dir] + (["--rehearse"] if args.rehearse else [])
    run_child("shard-evidence", extra, env, limit_s=600)


# ---------------------------------------------------------------------------
# children of this file (these import JAX)


def child_devices(rehearse: bool, count: int):
    """Import JAX, check the platform once, report it."""
    import jax
    import jaxlib

    devs = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want or len(devs) != count:
        print(f"need {count} {want} device(s), JAX found {devs}",
              file=sys.stderr)
        sys.exit(3)
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = None
    emit(jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
         platform=devs[0].platform, device_kind=devs[0].device_kind,
         count=len(devs))
    return jax


def mintable_packed_shapes(budget: int, lanes: int, page: int) -> list:
    """Every ``(Np, s_max)`` the default engine's mixed dispatch can mint:
    for each window bucket, from one lane alone up to the lane with the
    widest segment first and the rest of the token budget behind it
    (PackedShapeBudget._np_for is the engine's own packed-axis rule: the
    window rule, which the smoke's narrow heads take)."""
    from dynamo_tpu.engine.bucketing import PackedShapeBudget, pow2_bucket

    np_for = PackedShapeBudget()._np_for
    shapes = []
    s = 1
    while s <= pow2_bucket(max(budget, page)):
        low = np_for(s, 0, s // 2 + 1)
        total = min(budget, lanes * s)
        high = np_for(s, max(total - 1, 0), total)
        n = low
        while n <= high:
            shapes.append((n, s))
            n *= 2
        s *= 2
    return shapes


def packed_layout(Np: int, s_max: int, B: int, wide: int | None = None):
    """(q_lens, seg_off, lane, rel) of one packed dispatch: the lane with
    the widest segment (``wide`` rows, by default just over half the
    window) last, so that every lane's window ends inside the packed axis
    (off + s_max <= Np); the lanes before it share the rows that leaves."""
    import numpy as np

    if wide is None:
        wide = s_max // 2 + 1 if s_max > 1 else 1
    n = min((Np - s_max) // (B - 1), wide)
    lens = np.asarray([n] * (B - 1) + [wide], np.int32)
    off = np.zeros((B,), np.int32)
    lane = np.full((Np,), B, np.int32)
    rel = np.zeros((Np,), np.int32)
    o = 0
    for b in range(B):
        if lens[b] == 0:
            continue
        off[b] = o
        lane[o:o + lens[b]] = b
        rel[o:o + lens[b]] = np.arange(lens[b])
        o += lens[b]
    assert off.max() + s_max <= Np and o <= Np, (Np, s_max, lens)
    return lens, off, lane, rel


def moe_grouped_rows(args, jax) -> None:
    """The expert MLP both ways, by packed rows N: the capacity buffers
    ``[E, C = N, H]`` against the grouped product over the ``N*K`` routed
    rows (``ops.grouped_matmul``; half the rows masked as a packed step's
    padding or a fused step's idle lanes are, too).  Two tables, the
    measurements the two clauses of ``model._moe_takes_grouped`` are set
    from.  At Mixtral widths (8 experts, top-2): the smallest N from which
    the grouped path is the faster by more than 2% is
    ``model._GROUPED_MIN_ROWS``.  At the widths of a router wider than the
    step's assignments (top-4 of 128, 32 of them held here): the grouped
    path reads only the experts a row reaches (``model._moe_reaches_few``;
    N = 32 routes 128 and is the first step the rule leaves to the
    buffers)."""
    import contextlib

    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine import attention as att
    from dynamo_tpu.engine import model as M

    on_tpu, choice = att._on_tpu, M._moe_takes_grouped
    kernel_mode, dtype = contextlib.nullcontext, "bfloat16"
    dense = dict(hidden_size=4096, intermediate_size=14336, num_experts=8,
                 num_experts_per_tok=2, moe_capacity_factor=4.0)
    held = dict(hidden_size=4096, intermediate_size=2048, num_experts=128,
                num_local_experts=32, num_experts_per_tok=4,
                moe_capacity_factor=32.0)
    rows, held_rows = (32, 64, 128, 256, 512, 1024), (8, 16, 32)
    if args.rehearse:
        small = dict(hidden_size=128, intermediate_size=256)
        dense, held = {**dense, **small}, {**held, **small}
        dtype, rows, held_rows = "float32", (16, 128), (8, 32)
        att._on_tpu = lambda: True  # the kernel itself, interpreted
        kernel_mode = pltpu.force_tpu_interpret_mode
    reps = 1 if args.rehearse else 10

    def timed(cfg, lp, grouped, x, valid):
        M._moe_takes_grouped = lambda *a: grouped  # a fresh jit traces anew
        f = jax.jit(lambda l, y, v: M._moe_mlp(l, y, cfg, v))
        with kernel_mode():
            out = jax.block_until_ready(f(lp, x, valid))
            t0 = time.perf_counter()
            for _ in range(reps):
                out = f(lp, x, valid)
            jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps * 1e3, out

    def table(widths, rows):
        cfg = ModelConfig(
            vocab_size=256, num_layers=1, num_heads=32, num_kv_heads=8,
            head_dim=128, dtype=dtype, **widths,
        )
        params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
        lp = jax.tree.map(lambda a: a[0], params.pop("layers"))
        lines = []
        for n in rows:
            x = jax.random.normal(jax.random.PRNGKey(n), (1, n, cfg.hidden_size),
                                  jnp.dtype(dtype))
            half = (jnp.arange(n) < n // 2)[None]
            cap_ms, cap = timed(cfg, lp, False, x, None)
            grp_ms, grp = timed(cfg, lp, True, x, None)
            half_ms, _ = timed(cfg, lp, True, x, half)
            # no row at all: the kernel's visit axis is empty
            _, none = timed(cfg, lp, True, x, jnp.zeros_like(half))
            cap, grp = np.asarray(cap, np.float32), np.asarray(grp, np.float32)
            err = float(np.max(np.abs(cap - grp)) / max(np.max(np.abs(cap)), 1e-9))
            lines.append(dict(N=n, capacity_ms=round(cap_ms, 3), grouped_ms=round(grp_ms, 3),
                              grouped_half_masked_ms=round(half_ms, 3),
                              max_rel_diff=round(err, 5)))
            shared = np.asarray(M._shared_experts(lp, x[0], cfg), np.float32)
            empty = np.max(np.abs(np.asarray(none, np.float32)[0] - shared))
            if not np.isfinite(grp).all() or err > TOLERANCE[dtype] or empty > 0:
                emit(phase="kernels", failed=lines[-1], tolerance=TOLERANCE[dtype],
                     no_row_max_abs=float(empty))
                sys.exit(1)
        return lines

    try:
        by_rows, by_reach = table(dense, rows), table(held, held_rows)
    finally:
        M._moe_takes_grouped, att._on_tpu = choice, on_tpu
    emit(phase="kernels", moe_grouped=by_rows, moe_grouped_held=by_reach,
         grouped_min_rows=M._GROUPED_MIN_ROWS, compiled=not args.rehearse)


def device_peak(args, jax) -> dict:
    """The chip's published peaks (``benchmark/peaks.json``, by device kind;
    a rehearsal reads the v5e's)."""
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    return peaks["TPU v5e" if args.rehearse else jax.devices()[0].device_kind]


def latent_packed_times(args, jax) -> None:
    """``latent_packed_attention`` alone at ``mistral-small-4-119b``'s widths
    (32 heads, C 256, R 64, a pool of 32768 pages, both halves of a slab):
    milliseconds a launch beside the least the chip could take
    (``benchmark/costs_mla.absorbed_launch``), for a 2048-row chunk at three
    depths of a 32k document beside 15 decode rows, a 64-row question at
    16k, and 16 decode rows.  A sample of each launch's rows is compared
    with float32 attention over the same pool."""
    import contextlib

    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    from benchmark import costs, costs_mla
    from dynamo_tpu.engine.kv_cache import LatentKV
    from dynamo_tpu.ops.latent_attention import latent_packed_attention

    B = 16
    if args.rehearse:
        Hq, C, R, pages, P, dt, reps = 4, 128, 64, 160, 40, jnp.float32, 1
        kernel_mode = pltpu.force_tpu_interpret_mode
        decode_ctx = np.linspace(150, 640, B - 1).astype(int)
        cases = [("chunk@0", 64, 32, 32, 0, B - 1),
                 ("chunk@600", 64, 32, 32, 600, B - 1),
                 ("question@300", 16, 8, 5, 300, 0),
                 ("decodes", 16, 1, 1, 639, B - 1)]
    else:
        Hq, C, R, pages, P, dt, reps = 32, 256, 64, 32768, 2064, jnp.bfloat16, 10
        kernel_mode = contextlib.nullcontext
        decode_ctx = np.linspace(8192, 32768, B - 1).astype(int)
        # (name, Np, s_max, the last lane's fresh rows, its base, decode lanes)
        cases = [("chunk@0", 4096, 2048, 2048, 0, B - 1),
                 ("chunk@14336", 4096, 2048, 2048, 14336, B - 1),
                 ("chunk@30720", 4096, 2048, 2048, 30720, B - 1),
                 ("question@16384", 256, 128, 64, 16384, 0),
                 ("question@16384+decodes", 256, 128, 64, 16384, B - 1),
                 ("decodes", 16, 1, 1, 32767, B - 1)]
    page, slabs = 16, 3
    cfg = dict(num_attention_heads=Hq, kv_lora_rank=C, qk_rope_head_dim=R)
    peak = device_peak(args, jax)
    key = jax.random.PRNGKey(args.seed)
    pool = LatentKV(jax.random.normal(
        key, (slabs, 1, pages, page, 1, 2 * (C + R)), dt), C)
    rs = np.random.RandomState(args.seed)
    table = jnp.asarray(rs.randint(1, pages, (B, P)), jnp.int32)
    flat = np.asarray(
        pool.data[1].reshape(pages * page, 2 * (C + R)), np.float32)
    tab = np.asarray(table)
    table_out = []
    for name, Np, s_max, rows, base_last, n_dec in cases:
        lens = np.zeros(B, np.int32)
        base = np.zeros(B, np.int32)
        lens[:n_dec], base[:n_dec] = 1, decode_ctx[:n_dec] - 1
        lens[B - 1], base[B - 1] = rows, base_last
        off = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
        off = np.minimum(off, Np - s_max)
        ctxs = base + lens
        # small queries: a diffuse softmax, as trained weights give
        q = jax.random.normal(jax.random.fold_in(key, Np + base_last),
                              (Np, Hq, C + R), dt) / 16
        ms = {}
        # everything on the device before the clock starts: a launch of a
        # millisecond is otherwise timed by its arguments' transfers
        on_device = jax.block_until_ready(
            [jnp.asarray(a) for a in (base, off, lens)])
        for layer in (2, 3):  # the two halves of slab 1
            at = jax.block_until_ready(jnp.asarray(layer, jnp.int32))
            call = (q, pool, table, *on_device, s_max, at)
            with kernel_mode():
                out = jax.block_until_ready(latent_packed_attention(*call))
                t0 = time.perf_counter()
                for _ in range(reps):
                    out = latent_packed_attention(*call)
                jax.block_until_ready(out)
            ms[layer] = (time.perf_counter() - t0) / reps * 1e3
        # float32 attention for a sample of the rows of the last launch
        err = 0.0
        for b in range(B):
            if not lens[b]:
                continue
            picks = sorted({0, int(lens[b]) // 2, int(lens[b]) - 1})
            n_keys = int(base[b] + lens[b])
            at = (tab[b, np.arange(n_keys) // page] * page
                  + np.arange(n_keys) % page)
            rows_b = flat[at]
            k = np.concatenate(
                [rows_b[:, C:2 * C], rows_b[:, 2 * C + R:]], axis=1)
            for i in picks:
                qi = np.asarray(q[off[b] + i], np.float32)  # [Hq, C + R]
                s = qi @ k[: base[b] + i + 1].T / np.sqrt(C + R)
                p = np.exp(s - s.max(-1, keepdims=True))
                want = (p / p.sum(-1, keepdims=True)) @ k[: base[b] + i + 1, :C]
                got = np.asarray(out[off[b] + i], np.float32)
                err = max(err, float(np.max(np.abs(got - want))))
        flops, nbytes = costs_mla.absorbed_launch(
            [int(n) for n in lens if n], [int(c) for c, n in zip(ctxs, lens) if n],
            cfg)
        least, bound = costs.roofline_seconds(flops, nbytes, peak)
        row = dict(case=name, Np=Np, s_max=s_max,
                   ms_even_half=round(ms[2], 3), ms_odd_half=round(ms[3], 3),
                   least_ms=round(least * 1e3, 3), bound=bound,
                   roofline_pct=round(least * 1e5 / ms[3], 1),
                   max_abs_err=round(err, 5))
        table_out.append(row)
        tol = TOLERANCE["float32" if args.rehearse else "bfloat16"]
        if not np.isfinite(np.asarray(out, np.float32)).all() or err > tol:
            emit(phase="kernels", failed=row, tolerance=tol)
            sys.exit(1)
    emit(phase="kernels", latent_packed=table_out, compiled=not args.rehearse)


def work_list_item_times(args, jax) -> None:
    """The pair pools' work-list kernel alone, by what a launch costs an
    item: the decode launch (one item a lane) at five cells' lanes, contexts
    and heads, then the packed launch with a chunk's wide tiles ahead of the
    decode rows.  A case may name its own query heads and head width (the
    last two cases: Qwen3-Next's 16 heads of 256 over 2, whose one-row tile
    reads the pool a token a row of heads).  Milliseconds a launch on the
    device (``reps`` launches chained in one executable, each reading the one
    before; what does not depend on the one before, as the parent's relayout
    of a layer's pages, XLA hoists out of the chain and is not in the
    reading), microseconds an item, and the least an item's K and V bytes
    could take.  Every launch is compared with the XLA gather over the same
    pool."""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import attention as att
    from dynamo_tpu.engine.kv_cache import index_kv_layer
    from dynamo_tpu.ops.ragged_attention import (
        decode_work_list_attention, packed_ragged_attention,
    )

    *cell_heads, page = (4, 128, 8) if args.rehearse else (32, 128, 16)
    if args.rehearse:
        dt, reps, interp, pages = jnp.float32, 2, True, 256
        # (name, lanes, context, kv heads, window, table width, chunk rows[,
        # query heads, head width])
        cases = [("decode 4x300", 4, 300, 2, 0, 64, 0),
                 ("decode 4x300 window 128", 4, 300, 2, 128, 64, 0),
                 ("packed 28 + 3 rows", 4, 300, 2, 0, 64, 28),
                 ("decode 4x300 wide heads", 4, 300, 2, 0, 64, 0, 8, 256),
                 ("packed 29 + 3 rows wide heads", 4, 300, 2, 0, 64, 29, 8, 256)]
    else:
        dt, reps, interp, pages = jnp.bfloat16, 100, False, 6144
        cases = [
            # batch-closed, docqa-open, mixedlen-open's window layers,
            # sessions-open (two 64-wide heads a pool row)
            ("mixtral-8x7b", 32, 930, 8, 0, 256, 0),
            ("mistral-7b", 16, 3500, 8, 4096, 528, 0),
            ("mellum2 window", 32, 3000, 4, 1024, 2064, 0),
            ("lfm2 narrow", 32, 1500, 4, 0, 448, 0),
            # a chunk's wide tiles with 15 decode rows behind them, as
            # docqa-open's chunk steps launch them
            ("mistral-7b 512 rows", 16, 3500, 8, 4096, 528, 496),
            ("mistral-7b 1024 rows", 16, 3500, 8, 4096, 528, 1008),
            # longsessions-open: a decode step's launch, and a chunk step's
            ("qwen3-next", 16, 20000, 2, 0, 2112, 0, 16, 256),
            ("qwen3-next 2048 rows", 16, 20000, 2, 0, 2112, 2033, 16, 256),
        ]
    peak = device_peak(args, jax)
    tol = TOLERANCE["float32" if args.rehearse else "bfloat16"]
    key = jax.random.PRNGKey(args.seed)
    table_out = []
    for name, B, ctx, Hkv, window, P, chunk, *heads in cases:
        Hq, D = heads or cell_heads
        # every lane owns its pages; lanes' contexts differ by a few tokens
        need = -(-(ctx + 1) // page)
        assert need <= P, name
        pool = jax.random.normal(
            jax.random.fold_in(key, Hkv),
            (2, 2, max(pages, 1 + B * need), page, Hkv, D), dt)
        table = np.zeros((B, P), np.int32)
        table[:, :need] = 1 + np.arange(B * need).reshape(B, need)
        table = jnp.asarray(table)
        lens = np.asarray([ctx - 3 * (b % 5) for b in range(B)], np.int32)
        # queries are drawn small: a diffuse softmax, as trained weights give
        if chunk:
            q_lens = np.ones(B, np.int32)
            q_lens[0] = chunk  # the chunk first, the decode rows behind it
            base = lens - q_lens
            off = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
            # the axis is the power of two that holds the rows: a row of
            # padding, or none (2033 + 15)
            Np = 1 << (chunk + B - 2).bit_length()
            s_max = Np // 2
            lane = np.repeat(np.arange(B + 1), list(q_lens) + [Np - q_lens.sum()])
            lane = lane.astype(np.int32)
            rel = np.arange(Np, dtype=np.int32) - off[np.minimum(lane, B - 1)]
            rel[lane == B] = 0
            q, k, v = (
                jax.random.normal(jax.random.fold_in(key, i), (Np, h, D), dt) / 16
                for i, h in ((1, Hq), (2, Hkv), (3, Hkv)))
            vecs = [jnp.asarray(a) for a in (base, off, q_lens)]
            written = att.write_packed_kv(
                pool, k, v, table, jnp.asarray(lane),
                jnp.asarray(base[np.minimum(lane, B - 1)] + rel),
                jnp.asarray(lane < B), 1)

            def launch(q, pool):
                return packed_ragged_attention(
                    q, k, v, pool, table, *vecs, s_max, 1, window,
                    interpret=interp)

            pool = written

            # a row is a decode row at its own position: the gather over a
            # sample of the chunk's rows and every decode row
            valid = np.asarray(
                [0, chunk // 2] + list(range(chunk - 1, chunk + B - 1)))
            ref = att.paged_decode_attention(
                q[valid], index_kv_layer(pool, 1), table[lane[valid], :need],
                jnp.asarray(base[lane[valid]] + rel[valid] + 1), window)
            items = -(-chunk // min(s_max, 256)) + B - 1
        else:
            q = jax.random.normal(key, (B, Hq, D), dt) / 16
            at = jnp.asarray(lens)

            def launch(q, pool):
                return decode_work_list_attention(
                    q, pool, table, at, 1, window, interpret=interp)

            ref = att.paged_decode_attention(
                q, index_kv_layer(pool, 1), table[:, :need], at, window)
            valid, items = np.ones(B, bool), B

        @jax.jit
        def chained(q, pool):  # the pool an argument, not a constant
            def body(q, _):
                return q + launch(q, pool) * jnp.asarray(1e-6, dt), None

            return jax.lax.scan(body, q, None, length=reps)[0]

        got = np.asarray(jax.block_until_ready(launch(q, pool)), np.float32)
        jax.block_until_ready(chained(q, pool))
        t0 = time.perf_counter()
        jax.block_until_ready(chained(q, pool))
        ms = (time.perf_counter() - t0) / reps * 1e3
        err = float(np.max(np.abs(got[valid] - np.asarray(ref, np.float32))))
        seen = min(ctx, window) if window else ctx
        least_us = seen * Hkv * D * 2 * jnp.dtype(dt).itemsize / peak[
            "hbm_bytes_per_s"] * 1e6
        row = dict(case=name, lanes=B, context=ctx, kv_heads=Hkv,
                   window=window, chunk_rows=chunk, items=items,
                   ms_launch=round(ms, 4), us_item=round(ms * 1e3 / items, 2),
                   least_us_decode_item=round(least_us, 2),
                   max_abs_err=round(err, 5))
        table_out.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)  # as they come
        if not np.isfinite(got).all() or err > tol:
            emit(phase="kernels", failed=row, tolerance=tol)
            sys.exit(1)
        del pool, ref
    emit(phase="kernels", work_list_items=table_out, compiled=not args.rehearse)


def gdn_chunk_times(args, jax) -> None:
    """The gated delta rule's packed layer alone (``attention.
    packed_delta_mix``) at the shapes of the cell that serves it: 2048 packed
    rows at Qwen3-Next's widths (32 value heads, state 128 x 128, convolution
    over 8192 channels), as one segment, as a chunk beside 15 decode rows
    with a snapshot taken mid-segment, and as 16 decode rows; and the fused
    steps' one-token update (``decode_delta_mix``).  Each packed case twice:
    the chunks through the launch ``gated_delta_chunks`` (what the chip
    serves; rehearsed through the interpreter) and through the XLA
    composition (what a CPU serves, and the launch's reference).
    Milliseconds a layer on the device (``reps`` layers chained in one
    executable, each reading the state the one before left) and the largest
    difference from the token-by-token recurrence over the same rows."""
    import functools
    import time as _time

    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import attention as att
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.kv_cache import DeltaKV

    if args.rehearse:
        Hk, Hv, dk, dv, Np, B, reps = 2, 4, 8, 8, 160, 4, 2
    else:
        Hk, Hv, dk, dv, Np, B, reps = 16, 32, 128, 128, 2048, 16, 9
    cfg = ModelConfig(
        linear_num_key_heads=Hk, linear_num_value_heads=Hv,
        linear_key_head_dim=dk, linear_value_head_dim=dv,
        layer_pattern=("linear", "full"), num_layers=2)
    C, S = cfg.linear_conv_width, 4
    rng = np.random.RandomState(args.seed)
    dt = jnp.float32 if args.rehearse else jnp.bfloat16
    # rows of their own for each chained layer: the bulk of the convolution
    # reads nothing of the state, and one executable would compute it once
    # for all of them
    us = jnp.asarray(rng.standard_normal((reps, Np, C)), dt)
    u = us[0]
    taps = jnp.asarray(rng.standard_normal((4, C)) / 2, dt)
    g = -jnp.asarray(rng.uniform(1e-3, 0.1, (Np, Hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.9, (Np, Hv)), jnp.float32)

    def state(plan):
        return DeltaKV(
            jnp.zeros((1,), dt),
            jnp.asarray(rng.standard_normal((1, B, Hv, dk, dv)) * 0.1, jnp.float32),
            jnp.asarray(rng.standard_normal((1, 3 * B, C)), dt),
            jnp.zeros((1, S, Hv, dk, dv), jnp.float32),
            jnp.zeros((1, 3 * S, C), dt),
            jnp.asarray(plan, jnp.int32),
        )

    def recurrence(q_lens, seg_off, base, st):
        """Token by token over every live lane's rows, from the lane's
        state and history (zeros at position 0), in float64 on the host:
        what both forms of the chunks are rounded against."""
        f64 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)  # noqa: E731
        out = np.zeros((Np, Hv, dv), np.float64)
        hist = f64(st.conv[0]).reshape(B, 3, C)
        w, uu, gg, bb = f64(taps), f64(u), f64(g), f64(beta)
        for b in range(B):
            n, o = int(q_lens[b]), int(seg_off[b])
            if not n:
                continue
            first = int(base[b]) == 0
            rows = np.concatenate(
                [np.zeros((3, C)) if first else hist[b], uu[o:o + n]])
            x = sum(w[i] * rows[i:i + n] for i in range(4))
            x = x / (1 + np.exp(-x))
            q, k, v = np.split(x, [Hk * dk, 2 * Hk * dk], axis=-1)

            def unit(a):
                a = a.reshape(n, Hk, dk)
                a = a / np.sqrt(np.sum(a * a, axis=-1, keepdims=True) + 1e-6)
                return np.repeat(a, Hv // Hk, axis=1)

            q, k, v = unit(q) * dk ** -0.5, unit(k), v.reshape(n, Hv, dv)
            S = np.zeros((Hv, dk, dv)) if first else f64(st.lanes[0, b])
            for t in range(n):
                S *= np.exp(gg[o + t])[:, None, None]
                kS = np.matmul(k[t][:, None, :], S)[:, 0]
                S += k[t][:, :, None] * (bb[o + t][:, None] * (v[t] - kS))[:, None, :]
                out[o + t] = np.matmul(q[t][:, None, :], S)[:, 0]
        return out

    none = np.full((3, B), -1, np.int32)
    chunk = Np - (B - 1)
    mid = none.copy()
    mid[1, 0], mid[2, 0] = 1, 1000 + chunk // 2 // 16 * 16
    cases = [
        ("one segment of %d" % Np, [Np] + [0] * (B - 1), [0] * B, none),
        ("chunk of %d + %d decode rows, snapshot mid-chunk" % (chunk, B - 1),
         [chunk] + [1] * (B - 1), [1000] + [500] * (B - 1), mid),
        ("%d decode rows" % B, [1] * B, [700] * B, none),
    ]
    for name, q_lens, base, plan in cases:
        q_lens = np.asarray(q_lens, np.int32)
        seg_off = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
        lane = np.full((Np,), B, np.int32)
        for b in range(B):
            lane[seg_off[b]:seg_off[b] + q_lens[b]] = b
        ops = tuple(jnp.asarray(a, jnp.int32) for a in (base, seg_off, q_lens))

        st = state(plan)
        want = recurrence(q_lens, seg_off, base, st)
        live = lane < B
        for backend in ("kernel", "xla"):
            # the backend is packed_delta_mix's own choice at trace time (a
            # child process: nothing else of it reads the choice)
            att.delta_backend = lambda backend=backend: backend
            mix = functools.partial(
                att.packed_delta_mix,
                interpret=args.rehearse and backend == "kernel")

            @jax.jit
            def layers(st):
                o = None
                for r in range(reps):
                    o, st = mix(cfg, us[r], taps, g, beta, st, jnp.int32(0), *ops)
                return o, st

            @jax.jit
            def once(st):
                return mix(cfg, u, taps, g, beta, st, jnp.int32(0), *ops)

            got = np.asarray(jax.block_until_ready(once(st))[0])
            gap = float(np.abs(got - want)[live].max())
            jax.block_until_ready(layers(st))
            t0 = _time.perf_counter()
            jax.block_until_ready(layers(st))
            ms = (_time.perf_counter() - t0) * 1e3 / reps
            emit(gdn_chunk=name, chunks=backend, ms_a_layer=round(ms, 3),
                 max_gap_vs_recurrence=gap,
                 scale=float(np.abs(want[live]).max()))
            if not gap < (1e-4 if args.rehearse else 2e-2) * max(
                    1.0, float(np.abs(want).max())):
                fail(f"gdn_chunk {name} ({backend}): differs from the "
                     f"recurrence by {gap}")

    ub = u[:B]

    @jax.jit
    def decode(st):
        o = None
        for _ in range(reps):
            o, st = att.decode_delta_mix(
                cfg, ub, taps, g[:B], beta[:B], st, jnp.int32(0),
                jnp.ones((B,), bool))
        return o, st

    st = state(none)
    jax.block_until_ready(decode(st))
    t0 = _time.perf_counter()
    jax.block_until_ready(decode(st))
    emit(gdn_decode="%d lanes" % B,
         ms_a_layer=round((_time.perf_counter() - t0) * 1e3 / reps, 3))


def child_kernels(args) -> None:
    jax = child_devices(args.rehearse, 1)
    import math

    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
    from dynamo_tpu.engine import attention as att
    from dynamo_tpu.engine.kv_cache import (
        QuantKV, gather_layer_kv, index_kv_layer, quantize_kv_rows,
    )
    from dynamo_tpu.ops.flash_prefill import (
        flash_prefill_attention, flash_prefix_prefill_attention,
    )
    from dynamo_tpu.ops.paged_attention import paged_decode_attention_v2
    from dynamo_tpu.ops.ragged_attention import (
        decode_work_list_attention, packed_ragged_attention,
        packed_ragged_attention_xla,
    )

    interp = args.rehearse
    ecfg = EngineConfig()
    if args.rehearse:
        cfg = ModelConfig.tiny(dtype="float32")
        L, pages, budget = cfg.num_layers, 96, 16
        flash_T = (64,)
    else:
        cfg = ModelConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_layers=22, num_heads=32, num_kv_heads=4, head_dim=64,
            max_position=2048, dtype="bfloat16",
        )
        L, pages, budget = cfg.num_layers, 768, ecfg.mixed_token_budget
        flash_T = (1024, 2048)
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    page, B = ecfg.page_size, ecfg.max_batch_size
    dt = jnp.dtype(cfg.dtype)
    tol = TOLERANCE[cfg.dtype]
    P = 32 if not args.rehearse else 8  # table width: 512 resident tokens
    key = jax.random.PRNGKey(args.seed)

    def rnd(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape, dt)

    pool = rnd(0, (L, 2, pages, page, Hkv, D))
    q8, s8 = jax.jit(quantize_kv_rows)(pool)
    qpool = QuantKV(q=q8, s=s8)
    rs = np.random.RandomState(args.seed)
    table = jnp.asarray(rs.randint(1, pages, (B, P)), jnp.int32)
    layer = L - 1
    rows = []

    def record(kernel, shape, pool_kind, got, ref, valid=None):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        if valid is not None:
            got, ref = got[valid], ref[valid]
        err = float(np.max(np.abs(got - ref)))
        ok = bool(np.isfinite(got).all()) and err <= tol
        rows.append(dict(kernel=kernel, shape=shape, pool=pool_kind,
                         max_abs_err=round(err, 5), ok=ok))
        if not ok:
            emit(phase="kernels", failed=rows[-1], tolerance=tol)
            sys.exit(1)

    def packed_case(Np, s_max, quant, wide=None):
        lens, off, lane, rel = packed_layout(Np, s_max, B, wide)
        base = rs.randint(0, P * page - 1, (B,)).astype(np.int32)
        qp, kp, vp = rnd(1, (Np, Hq, D)), rnd(2, (Np, Hkv, D)), rnd(3, (Np, Hkv, D))
        t0 = time.monotonic()
        got = packed_ragged_attention(
            qp, kp, vp, qpool.q if quant else pool, table, base, off, lens,
            s_max, layer, interpret=interp,
            kv_scales=qpool.s if quant else None,
        ).block_until_ready()
        secs = time.monotonic() - t0
        ref = packed_ragged_attention_xla(
            qp, kp, vp, qpool if quant else pool, table, base, off, lens,
            jnp.asarray(lane), jnp.asarray(rel), s_max, layer,
        )
        record("packed_ragged_attention",
               f"Np={Np} s_max={s_max} q={lens[-1]}",
               "int8" if quant else cfg.dtype, got, ref, valid=lane < B)
        rows[-1]["compile_and_run_s"] = round(secs, 2)

    shapes = mintable_packed_shapes(budget, B, page)
    for Np, s_max in shapes:
        packed_case(Np, s_max, quant=False)
    for Np, s_max in (shapes[0], shapes[len(shapes) // 2], shapes[-1]):
        packed_case(Np, s_max, quant=True)
    # the widest shape with its window full and one row short of full: the
    # last query block, the whole-window write and the causal pairing of
    # the block that ends the window
    Np, s_max = shapes[-1]
    for quant in (False, True):
        for wide in (s_max, s_max - 1):
            packed_case(Np, s_max, quant, wide=wide)

    # the work-list kernel a dense pool of 128-lane heads takes (Mixtral's
    # and Mistral-7B's widths; TinyLlama's 64-wide heads keep the grid
    # kernel above): lanes own their pages, the dispatch's rows are
    # scattered first and every key is read from the pool
    wHq, wHkv, wD = (4, 2, 128) if args.rehearse else (32, 8, 128)
    wpool = rnd(11, (2, 2, 1 + B * P, page, wHkv, wD))
    wtable = jnp.asarray(1 + np.arange(B * P).reshape(B, P), jnp.int32)
    for Np, s_max in shapes:
        for window in (0, P * page // 2):
            lens, off, lane, rel = packed_layout(Np, s_max, B)
            base = rs.randint(0, P * page - s_max, (B,)).astype(np.int32)
            qp, kp, vp = (rnd(12, (Np, wHq, wD)), rnd(13, (Np, wHkv, wD)),
                          rnd(14, (Np, wHkv, wD)))
            lane_c = np.minimum(lane, B - 1)
            written = att.write_packed_kv(
                wpool, kp, vp, wtable, jnp.asarray(lane),
                jnp.asarray(base[lane_c] + rel), jnp.asarray(lane < B), 1)
            got = packed_ragged_attention(
                qp, kp, vp, written, wtable, base, off, lens, s_max, 1,
                window, interpret=interp)
            ref = packed_ragged_attention_xla(
                qp, kp, vp, wpool, wtable, base, off, lens,
                jnp.asarray(lane), jnp.asarray(rel), s_max, 1, window)
            record("packed_ragged_attention (work list)",
                   f"Np={Np} s_max={s_max} q={lens[-1]} window={window}",
                   cfg.dtype, got, ref, valid=lane < B)
    # the same kernel as the fused steps' decode launch: one item a lane
    wq = rnd(15, (B, wHq, wD))
    wlens = rs.randint(1, P * page, (B,)).astype(np.int32)
    for window in (0, P * page // 2):
        got = decode_work_list_attention(
            wq, wpool, wtable, wlens, 1, window, interpret=interp)
        ref = att.paged_decode_attention(
            wq, index_kv_layer(wpool, 1), wtable, wlens, window)
        record("paged_decode_attention (work list)",
               f"B={B} P={P} window={window}", cfg.dtype, got, ref)

    q = rnd(7, (B, Hq, D))
    Pd = min(16, P)
    lens = rs.randint(1, Pd * page, (B,)).astype(np.int32)
    got = paged_decode_attention_v2(
        q, pool, table[:, :Pd], lens, layer, 0, group=8, interpret=interp)
    ref = att.paged_decode_attention(
        q, index_kv_layer(pool, layer), table[:, :Pd], lens)
    record("paged_decode_attention_v2", f"B={B} P={Pd} group=8", cfg.dtype,
           got, ref)

    for T in flash_T:
        q, k, v = rnd(8, (2, T, Hq, D)), rnd(9, (2, T, Hkv, D)), rnd(10, (2, T, Hkv, D))
        lens = np.asarray([T, T // 2 + 3], np.int32)
        got = flash_prefill_attention(q, k, v, lens, interpret=interp)
        ref = att.prefill_attention(q, k, v, lens)
        valid = np.arange(T)[None, :] < lens[:, None]
        record("flash_prefill_attention", f"T={T}", cfg.dtype, got, ref, valid)
        # the suffix twin: the same T behind a resident prefix of Kp keys
        BK = math.gcd(T, 256)  # the kernel's key tile: Kp must tile by it
        Kp = max(min(T, P * page) // 2 // BK * BK, BK)
        ptab = table[:2, : Kp // page]
        offs = np.asarray([Kp, Kp // 2], np.int32)
        lkv = index_kv_layer(pool, layer)
        kp = gather_layer_kv(lkv, 0, ptab, dt).reshape(2, Kp, Hkv, D)
        vp = gather_layer_kv(lkv, 1, ptab, dt).reshape(2, Kp, Hkv, D)
        got = flash_prefix_prefill_attention(
            q, jnp.concatenate([kp, k], 1), jnp.concatenate([vp, v], 1),
            offs, lens, interpret=interp)
        ref = att.prefill_prefix_attention(
            q, k, v, pool, layer, ptab, offs, lens)
        record("flash_prefix_prefill_attention", f"T={T} Kp={Kp}", cfg.dtype,
               got, ref, valid)

    emit(phase="kernels", tolerance=tol, compiled=not interp,
         max_abs_err_table=rows)
    del pool, qpool
    work_list_item_times(args, jax)
    moe_grouped_rows(args, jax)
    latent_packed_times(args, jax)

    # the engine's own packed steps, lowered as the engine calls them: on
    # the chip the executable must embed the kernel, not the XLA fallback
    # the dispatch gates would otherwise pick in silence
    eng = JaxEngine.random_init(cfg, ecfg, seed=args.seed)
    eng._sync_device_state()
    d = eng._dev
    Np, s_max = shapes[-1]

    def zeros(n, dtype=np.int32):
        return jnp.zeros((n,), dtype)

    def batch(dtype=np.int32):
        return eng._put_batch(np.zeros((B,), dtype))

    operands = (
        eng.params, eng.model_cfg, eng.kv.pages, d["tokens"], d["seq_lens"],
        d["limit_lens"], d["active"], d["stop_ids"], d["page_table"][:, :8],
        zeros(Np), zeros(Np), zeros(Np), zeros(Np, bool),
        batch(), batch(), batch(bool), batch(bool), batch(bool), batch(),
        batch(), eng._rng, d["sampling"],
    )
    for name, extra in (
        ("packed_unified_step", (s_max, 0, 0, False)),
        ("packed_unified_multistep", (s_max, ecfg.multistep_max_k, 0, 0, False)),
    ):
        t0 = time.monotonic()
        text = getattr(eng._fns, name).lower(*operands, *extra).compile().as_text()
        n = text.count("tpu_custom_call")
        emit(phase="kernels", lowered=name, shape=f"Np={Np} s_max={s_max}",
             tpu_custom_calls=n, compile_s=round(time.monotonic() - t0, 1))
        if not args.rehearse and n == 0:
            print(f"{name}: no tpu_custom_call in the compiled step",
                  file=sys.stderr)
            sys.exit(1)
    stats = jax.devices()[0].memory_stats() or {}
    emit(phase="kernels", engine="in-process, full size, random weights",
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_in_use=stats.get("bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"))


def child_shard_evidence(args) -> None:
    """Build the tp=4 engine in-process and print where its state lives."""
    jax = child_devices(args.rehearse, 4)
    from dynamo_tpu.engine import EngineConfig, JaxEngine

    flags = dict(max_seq_len=256, num_pages=128) if args.rehearse else {}
    eng = JaxEngine.from_pretrained(args.model_dir, EngineConfig(tp=4, **flags))
    per_device = []
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        per_device.append(stats.get("bytes_in_use"))
    shard_bytes = sorted({s.data.nbytes for s in eng.kv.pages.addressable_shards})
    emit(phase="tp", mesh=dict(eng.mesh.shape),
         kv_sharding_spec=str(eng.kv.pages.sharding.spec),
         kv_shard_geometry=eng.kv.shard_geometry,
         kv_shard_bytes=shard_bytes, kv_total_bytes=eng.kv.pages.nbytes,
         bytes_in_use_per_device=per_device)
    if dict(eng.mesh.shape).get("tp") != 4 or len(shard_bytes) != 1 or (
        shard_bytes[0] * 4 != eng.kv.pages.nbytes
    ):
        print("the KV pool is not sharded four ways", file=sys.stderr)
        sys.exit(1)
    if not args.rehearse and not all(b and b > 0 for b in per_device):
        print(f"a device holds nothing: {per_device}", file=sys.stderr)
        sys.exit(1)

    # the per-shard kernel against its XLA twin on this mesh: the engine's
    # own dispatch, traced inside its mesh as its dispatch thread does, over
    # a pool sharded as the engine's is; the widest packed shape, window full
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import attention as att
    from dynamo_tpu.engine.bucketing import pow2_bucket
    from dynamo_tpu.ops.ragged_attention import packed_ragged_attention_xla

    m, ecfg = eng.model_cfg, eng.cfg
    B = ecfg.max_batch_size
    s_max = pow2_bucket(max(ecfg.mixed_token_budget, ecfg.page_size))
    Np, P = 2 * s_max, 8
    lens, off, lane, rel = packed_layout(Np, s_max, B, wide=s_max)
    key = jax.random.PRNGKey(args.seed)
    dt = eng.kv.pages.dtype

    def rnd(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape, dt)

    pool = jax.device_put(rnd(0, eng.kv.pages.shape), eng.kv.pages.sharding)
    rs = np.random.RandomState(args.seed)
    table = jnp.asarray(rs.randint(1, pool.shape[2], (B, P)), jnp.int32)
    base = rs.randint(0, P * ecfg.page_size - 1, (B,)).astype(np.int32)
    layer = jnp.int32(m.num_layers - 1)
    ops = (rnd(1, (Np, m.num_heads, m.head_dim)),
           rnd(2, (Np, m.num_kv_heads, m.head_dim)),
           rnd(3, (Np, m.num_kv_heads, m.head_dim)),
           pool, layer, table, base, off, lens, jnp.asarray(lane),
           jnp.asarray(rel))
    with eng.mesh_scope():
        call = jax.jit(
            lambda *a: att.packed_ragged_attention_dispatch(*a, s_max)
        ).lower(*ops).compile()
    kernels = call.as_text().count("tpu_custom_call")
    got = np.asarray(call(*ops), np.float32)[lane < B]
    ref = np.asarray(packed_ragged_attention_xla(
        *ops[:4], *ops[5:], s_max, layer), np.float32)[lane < B]
    err = float(np.max(np.abs(got - ref)))
    tol = TOLERANCE[str(jnp.dtype(m.dtype))]
    emit(phase="tp", kernel="packed_ragged_attention per tp shard",
         shape=f"Np={Np} s_max={s_max} q={s_max}", tpu_custom_calls=kernels,
         max_abs_err=round(err, 5), tolerance=tol)
    if not np.isfinite(got).all() or err > tol or (
        not args.rehearse and kernels == 0
    ):
        print("the per-shard kernel disagrees with its XLA twin, or the "
              "sharded call embeds no kernel", file=sys.stderr)
        sys.exit(1)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the --tp 4 path and its tp=1 comparison, "
                         "and no other phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, ModelConfig.tiny, kernels in interpret mode")
    ap.add_argument("--phases", default=None,
                    help="comma-separated subset of kernels,serve,distributed "
                         "(debugging; such a run never prints \"ok\")")
    ap.add_argument("--port", type=int, default=0,
                    help="port of the served child (default: a free one)")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--model-dir", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "dynamo_tpu")):
        print("chip_smoke.py runs from the root of the repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    if args.child == "kernels":
        child_kernels(args)
        return 0
    if args.child == "moe-grouped":  # that line of the kernels child, alone
        moe_grouped_rows(args, child_devices(args.rehearse, 1))
        return 0
    if args.child == "work-list-items":  # and that one
        work_list_item_times(args, child_devices(args.rehearse, 1))
        return 0
    if args.child == "latent-packed":  # and that one
        latent_packed_times(args, child_devices(args.rehearse, 1))
        return 0
    if args.child == "gdn-chunk":  # the delta rule's layer alone
        gdn_chunk_times(args, child_devices(args.rehearse, 1))
        return 0
    if args.child == "shard-evidence":
        child_shard_evidence(args)
        return 0

    t0 = time.monotonic()
    all_phases = ["kernels", "serve", "distributed"]
    phases = args.phases.split(",") if args.phases else all_phases
    device = None
    try:
        import shutil

        shutil.rmtree(os.path.join(WORK, "logs"), ignore_errors=True)
        os.makedirs(WORK, exist_ok=True)
        build_native()
        if args.chips == 4:
            model_dir = build_model_dir(args.seed, args.rehearse, kv_heads=4)
            phase_tp(args, model_dir)
        else:
            if "kernels" in phases:
                phase_kernels(args)
            if "serve" in phases or "distributed" in phases:
                model_dir = build_model_dir(args.seed, args.rehearse)
            cold_ready_s = None
            if "serve" in phases:
                cold_ready_s = phase_serve(args, model_dir)
            if "distributed" in phases:
                phase_distributed(args, model_dir, cold_ready_s)
        if args.chips == 4 or phases == all_phases:
            # the device, as a child of the program logged it
            device = logged_device()
    except Failed as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        stop_all()
        keep_logs()
    emit(total_seconds=round(time.monotonic() - t0, 1))
    if args.chips == 1 and phases != all_phases:
        emit(partial=phases, passed=True)
        return 0
    emit(ok=True, device=device)
    return 0


def keep_logs() -> None:
    """Copy the children's logs where the chip tool brings files back."""
    import shutil

    src = os.path.join(WORK, "logs")
    if os.path.isdir(src):
        dst = os.path.join(ROOT, "chiprun_out", "chip_smoke_logs")
        shutil.rmtree(dst, ignore_errors=True)
        shutil.copytree(src, dst)


def logged_device() -> dict:
    """platform / kind / count as the last served child's engine logged
    them (``jax.devices()[0].platform``, ``.device_kind``,
    ``len(jax.devices())``) -- the parent itself never asks JAX."""
    logs = os.path.join(WORK, "logs")
    for name in ("tp4", "worker", "serve"):
        path = os.path.join(logs, f"{name}.log")
        if not os.path.exists(path):
            continue
        with open(path, errors="replace") as f:
            m = re.search(
                r"engine devices: platform=(\w+) kind='([^']*)' count=(\d+)",
                f.read(),
            )
        if m:
            return {"platform": m.group(1), "kind": m.group(2),
                    "count": int(m.group(3))}
    fail("no served child logged its device")


if __name__ == "__main__":
    sys.exit(main())
