"""dynamo-tpu run: the single launch entrypoint.

Reference parity: launch/dynamo-run (opt.rs:23,83 ``in=http|text|dyn://…``
x ``out=echo|mocker|vllm|dyn``; flags.rs:26-137).  Usage::

    python -m dynamo_tpu run in=http out=jax --model-path /m/tinyllama
    python -m dynamo_tpu run in=http out=mocker --model-path /m/tok-only
    python -m dynamo_tpu run in=dyn  out=jax --model-path … --hub H:P
    python -m dynamo_tpu run in=http out=dyn --hub H:P          # frontend
    python -m dynamo_tpu run in=text out=jax --model-path …     # local REPL

``in=http out=<engine>`` is single-process aggregated serving (static mode,
no hub).  ``in=dyn`` serves the engine as a worker on the hub (registering
the model + KV/metrics publishers); ``in=http out=dyn`` runs the
discovery-driven frontend.  ``--hub auto`` spawns an in-process HubServer
(dev convenience).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import logging
import os
import signal
import sys
from typing import Optional, Tuple

logger = logging.getLogger("dynamo.run")

from .protocols.endpoint import parse_endpoint_id  # noqa: E402 (re-export)


def _add_engine_flags(p) -> None:
    """Engine-construction flags consumed by ``_make_engine`` -- defined
    once, shared by every subcommand that builds a local engine (`run`,
    `profile-sla`), so the flag set and _make_engine's input contract
    cannot drift apart."""
    p.add_argument("--echo-delay-ms", type=float, default=0.0,
                   help="out=echo: per-token delay")
    p.add_argument("--model-path", help="HF model dir (weights + tokenizer)")
    p.add_argument("--model-name", help="served model name (default: dir name)")
    p.add_argument("--max-batch-size", type=int, default=8)
    p.add_argument("--max-seq-len", type=int, default=2048)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--num-pages", type=int, default=512)
    p.add_argument("--num-window-pages", type=int, default=0,
                   help="a trunk of window and full layers (layer_types): "
                        "pages of the window layers' pool, beside "
                        "--num-pages for the full layers'")
    p.add_argument("--block-size", type=int, default=None,
                   help="router-visible KV block size (default: page size)")
    p.add_argument("--decode-block-size", type=int, default=16)
    p.add_argument("--quantize", choices=["int8"], default=None,
                   help="weight-only quantization (int8 + per-channel "
                        "scales; ~half the HBM stream per decode step)")
    p.add_argument("--kv-dtype", default=None, metavar="DTYPE",
                   help="paged KV pool dtype: 'int8' = quantized per-row "
                        "layout (~half the pool's HBM, dequant fused into "
                        "the ragged kernels), default = model dtype (env "
                        "DYN_KV_DTYPE overrides)")
    p.add_argument("--no-async-dispatch", dest="async_dispatch",
                   action="store_false", default=True,
                   help="disable the double-buffered host tick pipeline "
                        "(async commit + off-tick stream fanout); the "
                        "tick loop reverts to the exact serial "
                        "dispatch-then-commit order (env "
                        "DYN_ASYNC_DISPATCH overrides)")
    p.add_argument("--prefill-chunk-tokens", type=int, default=None,
                   help="chunked prefill: split long prompts into chunks "
                        "of this many tokens, interleaved with decode")
    p.add_argument("--no-mixed-batching", dest="mixed_batching",
                   action="store_false", default=True,
                   help="disable unified mixed prefill+decode dispatches "
                        "(ragged paged attention); prefill and decode "
                        "revert to separate launches per tick")
    p.add_argument("--mixed-token-budget", type=int, default=None,
                   help="fresh tokens per unified mixed-batch dispatch "
                        "(decode lanes cost one each, the rest packs "
                        "prefill chunks; env DYN_MIXED_TOKEN_BUDGET "
                        "overrides)")
    p.add_argument("--no-multistep-decode", dest="multistep_decode",
                   action="store_false", default=True,
                   help="disable multi-step device-resident decode (K "
                        "iterations fused into one packed dispatch on "
                        "pure-decode ticks, adaptive K); pure-decode "
                        "ticks revert to the classic fixed-width decode "
                        "block (env DYN_MULTISTEP overrides: 0=off, "
                        "adaptive, or a fixed integer K)")
    p.add_argument("--multistep-max-k", type=int, default=8,
                   metavar="K",
                   help="widest block the adaptive multi-step decode "
                        "controller may fuse (default 8); below it the "
                        "controller stops at as many steps as hide the "
                        "tick loop's own work")
    p.add_argument("--no-fold-spec-verify", dest="fold_spec_verify",
                   action="store_false", default=True,
                   help="disable folded speculative verify (spec columns "
                        "riding the packed unified dispatch); verify "
                        "reverts to the standalone post-commit dispatch "
                        "(env DYN_SPEC_FOLD overrides)")
    p.add_argument("--no-spec-auto-disable", dest="spec_auto_disable",
                   action="store_false", default=True,
                   help="keep low-acceptance lanes drafting instead of "
                        "reverting them to plain decode (env "
                        "DYN_SPEC_AUTO_DISABLE overrides)")
    p.add_argument("--draft-model", default=None, metavar="PATH",
                   help="model-based drafter: checkpoint dir (or "
                        "'random[:seed]' test preset) loaded as a second "
                        "weight set, registered under drafter kind "
                        "'model' (env DYN_DRAFT_MODEL overrides)")
    p.add_argument("--kv-admit-budget", default=None, metavar="SPEC",
                   help="KV-budget admission: 'on' or "
                        "'util=0.9,headroom=256,reserve=16,floor_s=2,"
                        "skips=4' -- admit against predicted KV pages "
                        "with a skip-ahead fairness floor instead of "
                        "slot count (env DYN_KV_ADMIT_BUDGET overrides)")
    p.add_argument("--kv-prefetch-window", type=int, default=None,
                   help="queue-side prefetch window: offloaded prefix "
                        "chains of the first N queued requests stage "
                        "toward host RAM while they wait; 0 disables "
                        "(env DYN_KV_PREFETCH overrides)")
    p.add_argument("--host-offload-blocks", type=int, default=0,
                   help="G2 host-RAM KV offload capacity (blocks); 0 = off "
                        "(env DYN_KV_OFFLOAD arms/overrides the whole plane)")
    p.add_argument("--disk-offload-blocks", type=int, default=0,
                   help="G3 disk KV offload capacity (blocks); 0 = off")
    p.add_argument("--disk-offload-dir",
                   help="directory for G3 disk offload files")
    p.add_argument("--kv-remote", default=None, metavar="SPEC",
                   help="G4 fleet KV store tier: 'on', or "
                        "'mirror=1,fetch=1,prefill_tok_s=4000,gbps=1.0,"
                        "namespace=dynamo' (offload.parse_kv_remote_spec); "
                        "requires the offload plane armed and a hub; env "
                        "DYN_KV_REMOTE wins")
    p.add_argument("--no-swap-preemption", dest="swap_preemption",
                   action="store_false", default=True,
                   help="disable swap-based preemption (offload the "
                        "victim's KV and restore it on resume); preempted "
                        "sequences always recompute instead")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel degree (shards over local devices)")
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel degree (decode batch sharded over dp)")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel degree (ring-attention prefill)")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel degree (microbatched prefill)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel degree (MoE experts sharded)")
    # multi-host engine bootstrap (jax.distributed; env DYN_NUM_NODES /
    # DYN_NODE_RANK / DYN_LEADER_ADDR also work)
    p.add_argument("--num-nodes", type=int, default=None,
                   help="hosts in the engine's multi-host world")
    p.add_argument("--node-rank", type=int, default=None,
                   help="this host's rank (0 = leader)")
    p.add_argument("--leader-addr", default=None,
                   help="leader host:port for the jax.distributed "
                        "coordinator")


def _positive_int(v: str) -> int:
    n = int(v)
    if n < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {n}")
    return n


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dynamo-tpu",
        description="TPU-native distributed LLM serving (dynamo rebuild)",
    )
    sub = p.add_subparsers(dest="cmd", required=True)
    run = sub.add_parser("run", help="launch an engine/frontend/worker")
    run.add_argument("io", nargs=2, metavar=("in=...", "out=..."),
                     help="in=http|text|dyn out=jax|mocker|echo|dyn")
    run.add_argument("--hub", help="hub address host:port, or 'auto'")
    run.add_argument("--endpoint", default="dyn://dynamo.backend.generate",
                     help="worker endpoint id (dyn://ns.comp.ep)")
    run.add_argument("--host", default="127.0.0.1")
    run.add_argument("--port", type=int, default=8080)
    run.add_argument("--router-mode", default="round_robin",
                     choices=["round_robin", "random", "kv"])
    run.add_argument("--router-index-shards", type=_positive_int, default=1,
                     help="KV router index shards (>1 = worker-sharded "
                          "index for large fleets)")
    _add_engine_flags(run)
    run.add_argument("--request-template",
                     help="JSON file with request defaults "
                          "{model, temperature, max_completion_tokens} "
                          "applied when the client omits them")
    run.add_argument("--prompt", help="in=text: run one prompt and exit")
    run.add_argument("--input-file", help="in=batch: JSONL prompts file")
    run.add_argument("--output-file", help="in=batch: JSONL results path "
                                           "(default stdout)")
    run.add_argument("--max-tokens", type=int, default=128)
    # disaggregated prefill/decode (in=dyn workers only)
    run.add_argument("--disagg", choices=["decode", "prefill"],
                     help="serve as a disaggregated decode or prefill worker")
    run.add_argument("--max-local-prefill-length", type=int, default=512)
    run.add_argument("--max-prefill-queue-depth", type=int, default=16)
    run.add_argument(
        "--kv-chunk-layers", type=int, default=None,
        help="layers per chunk for the streamed KV export (prefill "
             "workers; default splits the stack into ~8 groups)",
    )
    run.add_argument(
        "--no-chunked-kv", action="store_true",
        help="legacy monolithic KV export/upload (disables the pipelined "
             "chunked transfer path)",
    )

    # standalone hub (the control plane process; k8s hub Deployment)
    hub = sub.add_parser("hub", help="run a standalone hub server")
    hub.add_argument("--host", default="0.0.0.0")
    hub.add_argument("--port", type=int, default=6650)
    hub.add_argument("--data-dir", default=None,
                     help="persist state (WAL + snapshot) here; a restart "
                          "restores KV/leases/queues/objects")

    # standalone cluster metrics component (reference components/metrics)
    mt = sub.add_parser("metrics",
                        help="cluster Prometheus metrics on :9091")
    mt.add_argument("--hub", required=True, help="hub address host:port")
    mt.add_argument("--namespace", default="dynamo")
    mt.add_argument("--component", default="backend",
                    help="worker component to scrape")
    mt.add_argument("--host", default="0.0.0.0")
    mt.add_argument("--port", type=int, default=9091)

    # fleet: the observatory's read side over the hub -- subscribe to the
    # workers' telemetry topic, render a live cluster table
    fl = sub.add_parser("fleet",
                        help="live fleet table from worker telemetry")
    fl.add_argument("--hub", required=True, help="hub address host:port")
    fl.add_argument("--namespace", default="dynamo")
    fl.add_argument("--interval", type=float, default=2.0,
                    help="seconds between table refreshes")
    fl.add_argument("--once", action="store_true",
                    help="print one table after --interval and exit")
    fl.add_argument("--json", dest="json_out", action="store_true",
                    help="print the raw /fleet summary JSON instead")
    fl.add_argument("--plan", action="store_true",
                    help="show the planner's last adjustment + reason per "
                         "pool (note_adjustment / snapshot plan merge)")

    # trace: assemble one request's cross-component span timeline from the
    # hub (every served component auto-exposes a _trace scrape endpoint)
    tr = sub.add_parser("trace",
                        help="assemble a request's cross-component trace")
    tr.add_argument("--hub", required=True, help="hub address host:port")
    tr.add_argument("--namespace", default="dynamo")
    tr.add_argument("request_id", help="the request id (X-Request-Id header)")
    tr.add_argument("--json", dest="json_out",
                    help="write Chrome-trace JSON here (chrome://tracing / "
                         "ui.perfetto.dev)")
    tr.add_argument("--timeout", type=float, default=2.0,
                    help="per-component scrape timeout seconds")

    # llmctl: cluster model administration (reference llmctl/src/main.rs)
    ctl = sub.add_parser("llmctl", help="list/remove models on a hub")
    ctl.add_argument("--hub", required=True, help="hub address host:port")
    ctlsub = ctl.add_subparsers(dest="llmcmd", required=True)
    ctlsub.add_parser("list", help="list registered models + instances")
    rm = ctlsub.add_parser("remove", help="deregister a model by name")
    rm.add_argument("name")

    # api-store: deployment-artifact registry (reference deploy/cloud/
    # api-store -- FastAPI+Postgres+S3 there, the hub here)
    ap = sub.add_parser("api-store",
                        help="run the deployment-artifact registry")
    ap.add_argument("--hub", required=True, help="hub address host:port")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8282)

    # eval: perplexity of a checkpoint on real text (first-party accuracy
    # flow; the reference reaches this through its engines' lm-eval docs)
    ev = sub.add_parser("eval",
                        help="score a checkpoint's perplexity on a text")
    ev.add_argument("--model-path", required=True)
    ev.add_argument("--text-file", help="UTF-8 text to score")
    ev.add_argument("--text", help="inline text to score")
    ev.add_argument("--window", type=int, default=512,
                    help="independent scoring window (tokens)")
    ev.add_argument("--quantize", choices=["int8"], default=None)

    # operator: the reconcile controller over api-store deployment records
    # (reference deploy/cloud/operator controller loop)
    op = sub.add_parser("operator",
                        help="reconcile deployment records against the "
                             "cluster (controller loop)")
    op.add_argument("--hub", required=True, help="hub address host:port")
    op.add_argument("--kubectl", default="kubectl")
    op.add_argument("--namespace", default="default")
    op.add_argument("--interval", type=float, default=10.0)
    op.add_argument("--image", default="dynamo-tpu:latest")
    op.add_argument("--once", action="store_true",
                    help="run one reconcile round and exit")

    # build/deploy: graph packaging against the api-store (reference
    # `dynamo build` -> api-store upload, `dynamo deploy` -> manifests)
    bd = sub.add_parser("build",
                        help="package a graph dir and push it to api-store")
    bd.add_argument("--store", required=True,
                    help="api-store base url, e.g. http://H:8282")
    bd.add_argument("--name", required=True)
    bd.add_argument("--version", required=True)
    bd.add_argument("--path", required=True, help="graph directory to package")
    dp = sub.add_parser("deploy",
                        help="fetch a built graph and render its k8s manifests")
    dp.add_argument("--store", required=True)
    dp.add_argument("--name", required=True)
    dp.add_argument("--version", required=True)
    dp.add_argument("--out-dir", required=True,
                    help="where manifests + the unpacked artifact land")
    dp.add_argument("--model-path", default="/models/model",
                    help="model path the rendered workers mount")
    dp.add_argument("--image", default="dynamo-tpu:latest")

    # disagg-conf: live-reload the disagg routing policy (reference
    # disagg_router.rs:38-90 etcd watch); decode workers pick it up without
    # restarts
    dc = sub.add_parser("disagg-conf",
                        help="update the live disagg routing policy")
    dc.add_argument("--hub", required=True, help="hub address host:port")
    dc.add_argument("--namespace", default="dynamo")
    dc.add_argument("--max-local-prefill-length", type=int, default=None)
    dc.add_argument("--max-prefill-queue-depth", type=int, default=None)

    # datagen: workload analysis + synthesis (reference benchmarks/
    # data_generator `datagen analyze|synthesize`)
    dg = sub.add_parser("datagen", help="analyze/synthesize prefix workloads")
    dgsub = dg.add_subparsers(dest="dgcmd", required=True)
    an = dgsub.add_parser("analyze", help="prefix-sharing stats for a trace")
    an.add_argument("--input-file", required=True, help="JSONL trace")
    an.add_argument("--block-size", type=int, default=512)
    sy = dgsub.add_parser("synthesize", help="generate a synthetic trace")
    sy.add_argument("--input-file", required=True, help="JSONL seed trace")
    sy.add_argument("--output-file", required=True)
    sy.add_argument("--num-requests", type=int, default=1000)
    sy.add_argument("--block-size", type=int, default=512)
    sy.add_argument("--num-copies", type=int, default=1)
    sy.add_argument("--speedup-ratio", type=float, default=1.0)
    sy.add_argument("--prefix-len-multiplier", type=float, default=1.0,
                    help="scale shared-prefix lengths (any positive float; "
                         "<1 shrinks, like the reference synthesizer)")
    sy.add_argument("--prompt-len-multiplier", type=float, default=1.0)
    sy.add_argument("--seed", type=int, default=0)

    # profile-sla: pre-deployment TTFT/ITL profiling (reference
    # docs/architecture/planner.md profile_sla workflow)
    # profile: the tick-phase profiler's read side against a live frontend
    # (GET /profile/ticks; runtime/profiling.py) -- where does a serving
    # tick's wall time go, and how big is the dispatch gap?
    pf = sub.add_parser("profile",
                        help="tick-phase profile of a live serving frontend")
    pf.add_argument("url", help="frontend base url, e.g. "
                                "http://127.0.0.1:8080")
    pf.add_argument("--enable", action="store_true",
                    help="arm tick profiling on the server first "
                         "(POST /profile/ticks)")
    pf.add_argument("--disable", action="store_true",
                    help="disarm tick profiling on the server and exit")
    pf.add_argument("--watch", type=float, default=None, metavar="SECONDS",
                    help="arm profiling, wait this long under live "
                         "traffic, then report (implies --enable)")
    pf.add_argument("--json", dest="json_out",
                    help="write the merged Chrome-trace JSON (tick phases "
                         "+ request spans) here")
    pf.add_argument("--device", type=float, default=None, metavar="SECONDS",
                    help="also capture a bounded jax.profiler device "
                         "trace (POST /profile/device)")

    ps = sub.add_parser("profile-sla",
                        help="measure TTFT/ITL per config, recommend SLO point")
    ps.add_argument("--out", default="jax", choices=["jax", "mocker", "echo"],
                    help="engine to profile")
    ps.add_argument("--isl", default="128,512",
                    help="comma-separated prefill lengths to probe")
    ps.add_argument("--batch", default="1,4,8",
                    help="comma-separated decode batch sizes to probe")
    ps.add_argument("--osl", type=int, default=64,
                    help="decode tokens per probe stream (span several "
                         "decode blocks or ITL reads near zero)")
    ps.add_argument("--ttft-slo-ms", type=float, default=None)
    ps.add_argument("--itl-slo-ms", type=float, default=None)
    _add_engine_flags(ps)

    # bench: serving benchmark against a running OpenAI frontend (the
    # north-star measurement: output tok/s + TTFT percentiles on a
    # ShareGPT-like workload -- BASELINE.md)
    bn = sub.add_parser("bench",
                        help="drive a frontend with a workload; report "
                             "tok/s + TTFT percentiles")
    bn.add_argument("--host", default="127.0.0.1")
    bn.add_argument("--port", type=int, required=True)
    bn.add_argument("--model", required=True)
    bn.add_argument("--num-requests", type=int, default=None,
                    help="synthetic: workload size (default 64); trace: "
                         "cap on records replayed (default: whole trace)")
    bn.add_argument("--isl", type=int, default=128)
    bn.add_argument("--osl", type=int, default=64)
    bn.add_argument("--request-rate", type=float, default=0.0,
                    help="Poisson arrival rate (req/s); 0 = all at once")
    bn.add_argument("--concurrency", type=int, default=64)
    bn.add_argument("--vocab-size", type=int, default=29000)
    bn.add_argument("--trace", help="datagen JSONL trace to replay instead "
                                    "of the synthetic workload")
    bn.add_argument("--trace-block-size", type=int, default=16,
                    help="tokens per trace hash id (fallback only: the "
                         "trace's input_length fields take precedence)")
    bn.add_argument("--speedup-ratio", type=float, default=1.0,
                    help="trace replay time compression")
    bn.add_argument("--seed", type=int, default=0)
    bn.add_argument("--fleet", action="store_true",
                    help="also fetch GET /fleet from the frontend and "
                         "attach the cluster summary to the report")
    return p


def _load_template(args):
    """--request-template JSON -> RequestTemplate (reference
    request_template.rs:18), or None."""
    if not getattr(args, "request_template", None):
        return None
    from .protocols.openai import RequestTemplate

    return RequestTemplate.load(args.request_template)


def _parse_io(io) -> Tuple[str, str]:
    try:
        kv = dict(part.split("=", 1) for part in io)
    except ValueError:
        kv = {}
    if "in" not in kv or "out" not in kv:
        raise SystemExit("usage: run in=<http|text|dyn> out=<jax|mocker|dyn>")
    return kv["in"], kv["out"]


async def _make_engine(args):
    """Build the local engine for out=jax|mocker|echo."""
    if args.out == "echo":
        from .llm.echo import EchoEngineCore

        return EchoEngineCore(delay_ms=args.echo_delay_ms)
    if args.out == "mocker":
        from .mocker import MockerConfig, MockerEngine

        block = args.block_size or args.page_size
        vocab = 32000
        if args.model_path:
            # emit ids the model's tokenizer can actually detokenize
            vocab = _tokenizer_for(args).vocab_size
        return MockerEngine(MockerConfig(block_size=block, vocab_size=vocab))
    from .engine import EngineConfig, JaxEngine

    if not args.model_path:
        raise SystemExit("out=jax requires --model-path")
    from .llm.local_model import resolve_model_path

    # local dir used as-is; an org/repo id resolves through the HF hub
    # (reference local_model.rs:27 + hub.rs)
    args.model_path = resolve_model_path(args.model_path)
    cfg = EngineConfig(
        max_batch_size=args.max_batch_size,
        max_seq_len=args.max_seq_len,
        page_size=args.page_size,
        num_pages=args.num_pages,
        num_window_pages=args.num_window_pages,
        block_size=args.block_size,
        decode_block_size=args.decode_block_size,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        mixed_batching=args.mixed_batching,
        host_offload_blocks=args.host_offload_blocks,
        disk_offload_blocks=args.disk_offload_blocks,
        disk_offload_dir=args.disk_offload_dir,
        swap_preemption=args.swap_preemption,
        kv_remote=args.kv_remote,
        kv_admit_budget=args.kv_admit_budget,
        quantize=args.quantize,
        kv_dtype=args.kv_dtype,
        async_dispatch=args.async_dispatch,
        fold_spec_verify=args.fold_spec_verify,
        spec_auto_disable=args.spec_auto_disable,
        draft_model=args.draft_model,
        multistep_decode=args.multistep_decode,
        multistep_max_k=args.multistep_max_k,
    )
    if args.mixed_token_budget is not None:
        cfg.mixed_token_budget = args.mixed_token_budget
    if args.kv_prefetch_window is not None:
        cfg.kv_prefetch_window = args.kv_prefetch_window
    logger.info("loading %s ...", args.model_path)
    from .parallel.multihost import MultiNodeConfig, initialize_multihost

    mn = MultiNodeConfig.from_env()
    if args.num_nodes is not None:
        mn.num_nodes = args.num_nodes
    if args.node_rank is not None:
        mn.node_rank = args.node_rank
    if args.leader_addr is not None:
        mn.leader_addr = args.leader_addr
    initialize_multihost(mn)  # must precede the first jax backend touch
    # DYN_TP / DYN_DP env overrides (the engine-startup knob, mirrors
    # DYN_KV_OFFLOAD): a set variable wins over the flag, so a deployment
    # can re-degree a worker without editing its launch line.  sp/pp/ep
    # stay flag-only -- they select step routes, not just shardings.
    from .parallel.mesh import env_parallel_spec

    env = env_parallel_spec()
    if env["tp"] is not None:
        args.tp = env["tp"]
    if env["dp"] is not None:
        args.dp = env["dp"]
    mesh_cfg = None
    if max(args.tp, args.dp, args.sp, args.pp, args.ep) > 1:
        from .parallel.mesh import MeshConfig

        mesh_cfg = MeshConfig(
            dp=args.dp, tp=args.tp, pp=args.pp, sp=args.sp, ep=args.ep
        )
    if mesh_cfg is not None:
        import jax

        from .engine.config import ModelConfig
        from .parallel.mesh import build_mesh

        devices = jax.devices()
        if len(devices) < mesh_cfg.num_devices:
            raise SystemExit(
                f"mesh dp={args.dp} tp={args.tp} pp={args.pp} sp={args.sp} "
                f"ep={args.ep} needs {mesh_cfg.num_devices} devices, have "
                f"{len(devices)}"
            )
        if args.dp > 1 and args.max_batch_size % args.dp:
            raise SystemExit(
                f"--max-batch-size {args.max_batch_size} must be divisible "
                f"by --dp {args.dp} (batch lanes shard over dp)"
            )
        model_cfg = None
        if args.tp > 1:
            # fail before any weight loads: a tp that cannot shard the kv
            # heads would silently replicate the KV pool and pay a
            # cross-chip gather per decode step
            model_cfg = ModelConfig.from_pretrained(args.model_path)
            try:
                model_cfg.validate_tp(args.tp)
            except ValueError as e:
                raise SystemExit(str(e))
        mesh = build_mesh(mesh_cfg, devices[: mesh_cfg.num_devices])
        return JaxEngine.from_pretrained(
            args.model_path, cfg, mesh=mesh, model_cfg=model_cfg
        )
    return JaxEngine.from_pretrained(args.model_path, cfg)


def _tokenizer_for(args):
    from .llm.tokenizer import Tokenizer

    if not args.model_path:
        raise SystemExit("this mode needs --model-path for the tokenizer")
    from .llm.local_model import resolve_model_path

    args.model_path = resolve_model_path(args.model_path)
    return Tokenizer.from_model_dir(args.model_path)


def _model_name(args) -> str:
    import os

    if args.model_name:
        return args.model_name
    if args.model_path:
        return os.path.basename(os.path.normpath(args.model_path))
    return "mocker"


async def _resolve_hub(args):
    """Returns (hub_address, owned_hub_server|None); spawns one for 'auto'."""
    if args.hub == "auto":
        from .runtime.transports.hub import HubServer

        server = HubServer()
        host, port = await server.start()
        logger.info("spawned in-process hub at %s:%d", host, port)
        return f"{host}:{port}", server
    return args.hub, None


async def run_http_local(args) -> None:
    """in=http out=jax|mocker: single-process aggregated serving."""
    from .http.service import HttpService, ModelManager
    from .llm.backend import Backend
    from .llm.preprocessor import OpenAIPreprocessor
    from .runtime.pipeline import link

    engine = await _make_engine(args)
    tokenizer = _tokenizer_for(args)
    name = _model_name(args)
    pipeline = link(OpenAIPreprocessor(name, tokenizer), Backend(tokenizer), engine)
    manager = ModelManager()
    manager.add_chat_model(name, pipeline)
    manager.add_completion_model(name, pipeline)
    from .llm.embedding import EmbeddingEngine, fake_embedder

    # /v1/embeddings: the JAX trunk embeds for real; echo/mocker get the
    # deterministic fake so the route works in every out= mode
    embed_fn = engine.embed if hasattr(engine, "embed") else fake_embedder()
    max_in = getattr(getattr(engine, "cfg", None), "max_seq_len", None)
    manager.add_embedding_model(
        name,
        EmbeddingEngine(embed_fn, tokenizer=tokenizer, max_input_tokens=max_in),
    )
    service = HttpService(
        manager, host=args.host, port=args.port,
        template=_load_template(args),
    )
    await service.start()
    print(f"serving {name} at {service.url}  (POST /v1/chat/completions)")
    try:
        await _wait_forever()
    finally:
        await service.stop()
        await engine.stop()


async def run_http_frontend(args) -> None:
    """in=http out=dyn: discovery-driven frontend over the hub."""
    if not args.hub:
        raise SystemExit("in=http out=dyn requires --hub")
    from .http.service import HttpService, ModelManager
    from .llm.discovery import ModelWatcher
    from .runtime.component import DistributedRuntime, RouterMode

    addr, owned_hub = await _resolve_hub(args)
    runtime = await DistributedRuntime.detached(addr)
    manager = ModelManager()
    # fleet observatory: ingest every worker's telemetry snapshots off the
    # hub and surface them at GET /fleet (+ the dynamo_fleet_* families).
    # Built before the router factory: the KV router's quarantine filter
    # and fetch-vs-recompute gate read its live link/straggler state.
    from .fleet import FleetObservatory

    observatory = FleetObservatory()
    if args.router_mode == "kv":
        from .llm.backend import Backend
        from .llm.kv_router.router import KvPushRouter, KvRouter
        from .llm.preprocessor import OpenAIPreprocessor
        from .offload import env_remote_spec
        from .runtime.pipeline import link

        try:
            remote_spec = env_remote_spec()
        except ValueError:
            logger.warning("ignoring malformed DYN_KV_REMOTE")
            remote_spec = None

        async def kv_factory(entry, card, client, router):
            ns = runtime.namespace(entry.namespace)
            comp = ns.component(entry.component)
            chooser = KvRouter(
                ns, comp, block_size=card.kv_block_size,
                index_shards=args.router_index_shards,
                quarantine=observatory.quarantine_source(),
            )
            await chooser.start()
            tokenizer = card.tokenizer()
            engine = link(
                OpenAIPreprocessor(entry.name, tokenizer),
                Backend(tokenizer),
                KvPushRouter(
                    router, chooser,
                    transfer_ms=observatory.predict_transfer_ms,
                    remote_spec=remote_spec,
                ),
            )
            return engine, chooser.stop  # watcher stops the chooser w/ model

        watcher = ModelWatcher(runtime, manager, engine_factory=kv_factory)
    else:
        watcher = ModelWatcher(
            runtime, manager, router_mode=RouterMode(args.router_mode)
        )
    await watcher.start()
    await observatory.start(runtime.namespace("dynamo"))
    service = HttpService(
        manager, host=args.host, port=args.port,
        template=_load_template(args),
        observatory=observatory,
    )
    await service.start()
    print(f"frontend at {service.url} (hub {addr}); models appear on discovery")
    stop = asyncio.Event()
    # hub loss must terminate the frontend (fail loud), not freeze its view
    lost = _stop_on_hub_loss(runtime.hub, stop)
    try:
        await _wait_forever(stop)
    finally:
        await service.stop()
        await observatory.stop()
        await watcher.stop()
        await runtime.shutdown()
        if owned_hub:
            await owned_hub.stop()
    _exit_if_lost(lost)


async def run_worker(args) -> None:
    """in=dyn out=jax|mocker: engine worker on the hub."""
    if not args.hub:
        raise SystemExit("in=dyn requires --hub")
    from .llm.kv_router.publisher import KvEventPublisher, WorkerMetricsPublisher
    from .llm.model_card import register_llm
    from .runtime.component import DistributedRuntime

    ns_name, comp_name, ep_name = parse_endpoint_id(args.endpoint)
    # build the engine BEFORE connecting: weight loading blocks the event
    # loop long enough to starve lease keepalives and get this worker evicted
    engine = await _make_engine(args)
    addr, owned_hub = await _resolve_hub(args)
    runtime = await DistributedRuntime.detached(addr)
    ns = runtime.namespace(ns_name)
    comp = ns.component(comp_name)
    ep = comp.endpoint(ep_name)
    prefill_worker = None
    if args.disagg == "prefill":
        # queue consumer only: no generate endpoint, no model registration
        from .llm.disagg import PrefillWorker

        prefill_worker = PrefillWorker(
            engine, ns,
            chunked=not args.no_chunked_kv,
            layers_per_chunk=args.kv_chunk_layers,
        )
        await prefill_worker.start()
        print(f"prefill worker consuming {ns_name}_prefill_queue (hub {addr})")
    elif args.disagg == "decode":
        from .llm.disagg import (
            KV_DELIVER_ENDPOINT,
            DisaggConfig,
            DisaggDecodeEngine,
        )

        disagg = DisaggDecodeEngine(
            engine,
            ns,
            comp_name,
            # serve() registers under the primary lease; fixing the id now
            # avoids a window where a shipped job carries a placeholder
            instance_id=runtime.primary_lease,
            cfg=DisaggConfig(
                max_local_prefill_length=args.max_local_prefill_length,
                max_prefill_queue_depth=args.max_prefill_queue_depth,
            ),
            block_size=args.block_size or args.page_size,
        )
        # kv_deliver must exist before any request can be shipped remote, or
        # the prefill worker's write-back races a missing endpoint
        await comp.endpoint(KV_DELIVER_ENDPOINT).serve_raw(
            disagg.kv_deliver_handler()
        )
        await disagg.start_config_watch()  # live policy reload from the hub
        served = await _wire_prefix_onboard(disagg, engine, ns, comp, comp_name)
        await ep.serve(served)
    else:
        served = await _wire_prefix_onboard(engine, engine, ns, comp, comp_name)
        await ep.serve(served)
    embed_ep_name = ""
    if hasattr(engine, "embed") and args.disagg != "prefill":
        # pooled-embedding leg: a sibling endpoint the frontend watcher
        # discovers through the model entry's embed_endpoint field
        from .llm.embedding import EmbeddingEngine

        embed_ep_name = f"{ep_name}_embed"
        await comp.endpoint(embed_ep_name).serve(
            EmbeddingEngine(
                engine.embed,
                max_input_tokens=getattr(
                    getattr(engine, "cfg", None), "max_seq_len", None
                ),
            )
        )
    pub = KvEventPublisher(ns, worker_id=runtime.primary_lease)
    pub.hook(engine)
    # fleet KV economy: arm the G4 tier over the hub blob verbs when the
    # engine parsed a kv_remote spec, and publish tier-residency deltas
    # whenever the offload plane exists at all (peer host/disk holdings
    # feed the cluster-global prefix index even without G4)
    holdings_pub = None
    if getattr(engine, "offload_engine", None) is not None:
        from .llm.kv_router.publisher import KvHoldingsPublisher

        if getattr(engine, "kv_remote_spec", None) is not None:
            from .runtime.transports.client import HubBlobClient

            engine.attach_remote_kv(
                HubBlobClient(runtime.hub, asyncio.get_running_loop()),
                worker_id=runtime.primary_lease,
            )
        holdings_pub = KvHoldingsPublisher(ns, worker_id=runtime.primary_lease)
        holdings_pub.hook(engine)
    metrics_pub = WorkerMetricsPublisher(engine.metrics)
    await metrics_pub.attach(comp)
    # fleet plane: identity-label this worker's exposition and publish
    # periodic telemetry snapshots to the hub for the observatory
    from .runtime import metrics as rtm
    from .runtime.telemetry import TelemetryPublisher

    role = args.disagg or "worker"
    rtm.set_worker_identity(worker_id=runtime.primary_lease, role=role)
    telemetry_pub = TelemetryPublisher(
        ns,
        worker_id=runtime.primary_lease,
        role=role,
        # mocker engines route their synthetic link observations through a
        # per-engine log; everything else uses the process-wide one the
        # disagg delivery path feeds
        transfer_log=getattr(engine, "transfer_log", None),
    )
    telemetry_pub.start()
    stop = asyncio.Event()
    # hub loss orphans this worker's registrations: exit so a supervisor
    # restarts it into a live cluster (fail loud)
    lost = _stop_on_hub_loss(runtime.hub, stop)
    if args.model_path and args.disagg != "prefill":
        card = await register_llm(
            runtime, ep, args.model_path,
            model_name=args.model_name,
            kv_block_size=args.block_size or args.page_size,
            embed_endpoint=embed_ep_name,
        )
        print(f"worker serving model {card.name} on {args.endpoint} (hub {addr})")
    elif args.disagg != "prefill":
        print(f"worker serving on {args.endpoint} (hub {addr}; no model card)")
    try:
        await _wait_forever(stop, drain_runtime=runtime)
    finally:
        if prefill_worker is not None:
            await prefill_worker.stop()
        await telemetry_pub.stop(final=False)
        if holdings_pub is not None:
            await holdings_pub.close()
        await pub.close()
        await engine.stop()
        await runtime.shutdown()
        if owned_hub:
            await owned_hub.stop()
    _exit_if_lost(lost)


async def run_text(args) -> None:
    """in=text out=jax|mocker: REPL / one-shot prompt through the full
    preprocessor->engine->detokenizer pipeline."""
    from .llm.backend import Backend
    from .llm.preprocessor import OpenAIPreprocessor
    from .protocols.openai import ChatCompletionRequest
    from .runtime.engine import Annotated, Context, as_response_stream
    from .runtime.pipeline import link

    engine = await _make_engine(args)
    tokenizer = _tokenizer_for(args)
    name = _model_name(args)
    pipeline = link(OpenAIPreprocessor(name, tokenizer), Backend(tokenizer), engine)

    async def ask(text: str) -> None:
        req = ChatCompletionRequest.from_dict(
            {
                "model": name,
                "messages": [{"role": "user", "content": text}],
                "stream": True,
                "max_tokens": args.max_tokens,
            }
        )
        stream = await as_response_stream(pipeline, Context.new(req))
        async for item in stream:
            if not isinstance(item, Annotated):
                item = Annotated.from_data(item)
            if item.is_error():
                print(f"\n[error] {item.error_message()}", flush=True)
                return
            data = item.data or {}
            for choice in data.get("choices", []):
                delta = (choice.get("delta") or {}).get("content")
                if delta:
                    print(delta, end="", flush=True)
        print()

    try:
        if args.prompt is not None:
            await ask(args.prompt)
            return
        loop = asyncio.get_running_loop()
        while True:
            line = await loop.run_in_executor(None, sys.stdin.readline)
            if not line:
                break
            line = line.strip()
            if line in ("exit", "quit", ""):
                if line:
                    break
                continue
            await ask(line)
    finally:
        await engine.stop()


async def run_batch(args) -> None:
    """in=batch out=jax|mocker|echo: run a JSONL file of prompts through the
    full pipeline concurrently; one JSON result line per prompt, in input
    order (reference dynamo-run ``in=batch:file``).

    Input lines: ``{"text": "..."}`` (or ``{"prompt": ...}``), optional
    ``max_tokens``.  Output lines: ``{"index", "text", "response"}``.
    """
    import json as _json

    from .llm.backend import Backend
    from .llm.preprocessor import OpenAIPreprocessor
    from .protocols.openai import ChatCompletionRequest
    from .runtime.engine import Annotated, Context, as_response_stream
    from .runtime.pipeline import link

    if not args.input_file:
        raise SystemExit("in=batch requires --input-file prompts.jsonl")
    engine = await _make_engine(args)
    tokenizer = _tokenizer_for(args)
    name = _model_name(args)
    pipeline = link(OpenAIPreprocessor(name, tokenizer), Backend(tokenizer), engine)

    def _read_prompts() -> list:
        out = []
        with open(args.input_file, encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if line:
                    out.append(_json.loads(line))
        return out

    # file I/O off the loop: the engine may already be serving its tick
    # loop on this thread
    prompts = await asyncio.to_thread(_read_prompts)

    async def one(i, entry):
        text = entry.get("text") or entry.get("prompt") or ""
        req = ChatCompletionRequest.from_dict(
            {
                "model": name,
                "messages": [{"role": "user", "content": text}],
                "stream": True,
                "max_tokens": int(entry.get("max_tokens", args.max_tokens)),
            }
        )
        parts: list = []
        error = None
        stream = await as_response_stream(pipeline, Context.new(req))
        async for item in stream:
            if not isinstance(item, Annotated):
                item = Annotated.from_data(item)
            if item.is_error():
                error = item.error_message()
                break
            for choice in (item.data or {}).get("choices", []):
                delta = (choice.get("delta") or {}).get("content")
                if delta:
                    parts.append(delta)
        out = {"index": i, "text": text, "response": "".join(parts)}
        if error:
            out["error"] = error
        return out

    def _write_results(results: list) -> None:
        sink = (
            open(args.output_file, "w", encoding="utf-8")
            if args.output_file else sys.stdout
        )
        try:
            for r in results:
                sink.write(_json.dumps(r) + "\n")
        finally:
            if args.output_file:
                sink.close()

    try:
        results = await asyncio.gather(
            *(one(i, e) for i, e in enumerate(prompts))
        )
        await asyncio.to_thread(_write_results, results)
    finally:
        await engine.stop()


def _stop_on_hub_loss(hub, stop: asyncio.Event) -> list:
    """Losing the hub (its connection, or a lease it expired) ends this
    process: it must not serve on from a frozen view of the cluster.  The
    returned list is non-empty once that happened, and :func:`_exit_if_lost`
    then turns the clean-up's end into a failing exit code -- a supervisor
    must see a failure, not a clean stop.  A loss seen after ``stop`` was
    already set is this process's own shutdown closing the connection."""
    lost: list = []

    def on_lost() -> None:
        if not stop.is_set():
            lost.append(True)
        stop.set()

    if hasattr(hub, "on_connection_lost"):
        hub.on_connection_lost = on_lost
    return lost


def _exit_if_lost(lost: list) -> None:
    if lost:
        raise SystemExit("hub connection or lease lost; exiting")


async def _wait_forever(
    stop: Optional[asyncio.Event] = None, drain_runtime=None
) -> None:
    """Park until a signal (or ``stop``).  With ``drain_runtime`` set,
    SIGTERM triggers a graceful drain first -- deregister from discovery,
    finish in-flight requests (``DYN_DRAIN_TIMEOUT_S``, default 30) --
    before stopping, so supervisor scale-down / k8s rollout never drops
    requests a drain could have finished.  SIGINT stays immediate."""
    stop = stop or asyncio.Event()
    loop = asyncio.get_running_loop()
    drain_tasks: set = set()

    async def _drain_then_stop() -> None:
        try:
            await drain_runtime.drain(
                float(os.environ.get("DYN_DRAIN_TIMEOUT_S", "30"))
            )
        finally:
            stop.set()

    def _on_term() -> None:
        if drain_runtime is None or drain_runtime.draining:
            stop.set()
            return
        task = asyncio.ensure_future(_drain_then_stop())
        drain_tasks.add(task)
        task.add_done_callback(drain_tasks.discard)

    with contextlib.suppress(NotImplementedError):
        loop.add_signal_handler(signal.SIGINT, stop.set)
    with contextlib.suppress(NotImplementedError):
        loop.add_signal_handler(signal.SIGTERM, _on_term)
    await stop.wait()


async def run_llmctl(args) -> int:
    """Model administration against a live hub (reference llmctl: list /
    remove chat-models)."""
    from .llm.model_card import MDC_OBJ_PREFIX, MODEL_ROOT, ModelEntry, slugify
    from .runtime.transports.client import HubClient

    host, _, port = args.hub.rpartition(":")
    try:
        hub = await HubClient(host or "127.0.0.1", int(port)).connect()
    except OSError as e:
        raise SystemExit(f"cannot reach hub at {args.hub}: {e}")
    try:
        entries = await hub.kv_get_prefix(f"{MODEL_ROOT}/")
        if args.llmcmd == "list":
            by_slug = {}
            for key, blob in entries:
                slug = key.split("/")[1]
                by_slug.setdefault(slug, []).append(ModelEntry.from_json(blob))
            if not by_slug:
                print("no models registered")
                return 0
            for slug, insts in sorted(by_slug.items()):
                e = insts[0]
                print(
                    f"{e.name}  instances={len(insts)}  "
                    f"endpoint=dyn://{e.namespace}.{e.component}.{e.endpoint}  "
                    f"type={e.model_type}"
                )
            return 0
        # remove
        slug = slugify(args.name)
        n = await hub.kv_delete_prefix(f"{MODEL_ROOT}/{slug}/")
        # the MDC object is keyed by slug as well; best-effort cleanup
        with contextlib.suppress(Exception):
            await hub.obj_del(f"{MDC_OBJ_PREFIX}/{slug}")
        print(f"removed {n} instance entr{'y' if n == 1 else 'ies'} for "
              f"{args.name!r}")
        return 0 if n else 1
    finally:
        await hub.close()


async def run_profile(args) -> int:
    """profile: read a live frontend's tick-phase profile
    (``GET /profile/ticks``) and print where tick wall time goes -- the
    host phases, occupancy, and the dispatch gap ROADMAP item 2 attacks.
    ``--watch S`` arms profiling, samples S seconds of live traffic, then
    reports; ``--device S`` additionally triggers a bounded
    ``jax.profiler`` capture on the server."""
    import json as _json
    import urllib.request

    base = args.url.rstrip("/")
    if "://" not in base:
        base = "http://" + base

    def _call(path: str, payload=None):
        import urllib.error

        data = None
        if payload is not None:
            data = _json.dumps(payload).encode()
        req = urllib.request.Request(
            base + path,
            data=data,
            headers={"Content-Type": "application/json"} if data else {},
        )
        try:
            with urllib.request.urlopen(req, timeout=35.0) as resp:
                return _json.loads(resp.read().decode())
        except urllib.error.HTTPError as e:
            # structured non-2xx bodies (e.g. /profile/device's graceful
            # 503 {ok:false,error}) are answers, not connectivity failures
            body = e.read().decode(errors="replace")
            try:
                return _json.loads(body)
            except ValueError:
                raise OSError(f"HTTP {e.code}: {body[:200]}") from e

    async def call(path: str, payload=None):
        return await asyncio.to_thread(_call, path, payload)

    try:
        if args.disable:
            out = await call("/profile/ticks", {"enabled": False})
            print(f"tick profiling disabled (server enabled={out['enabled']})")
            return 0
        if args.enable or args.watch is not None:
            await call("/profile/ticks", {"enabled": True, "clear": True})
        if args.watch is not None:
            print(f"profiling armed; sampling {args.watch:g}s of traffic...")
            await asyncio.sleep(max(args.watch, 0.0))
        if args.device is not None:
            dev = await call(
                "/profile/device", {"duration_s": args.device}
            )
            if dev.get("ok"):
                print(f"device trace captured to {dev['log_dir']}")
            else:
                print(f"device trace unavailable: {dev.get('error')}")
        data = await call("/profile/ticks")
    except OSError as e:
        print(f"cannot reach {base}: {e}")
        return 1
    summ = data.get("summary") or {}
    if not summ.get("ticks"):
        print(
            "no tick records yet (is the profiler enabled -- "
            "DYN_TICK_PROFILE=1, --enable, or --watch -- and is the "
            "engine serving traffic?)"
        )
        return 1
    wall = summ.get("wall_s") or 0.0
    print(
        f"{summ['ticks']} ticks, {summ['dispatches']} dispatches, "
        f"wall {wall:.3f}s, host occupancy "
        f"{summ.get('host_occupancy')}"
    )
    print(f"{'phase':<12} {'total_s':>10} {'% wall':>8}")
    totals = summ.get("phase_totals_s") or {}
    for name, tot in sorted(totals.items(), key=lambda kv: -kv[1]):
        frac = 100.0 * tot / wall if wall else 0.0
        print(f"{name:<12} {tot:>10.4f} {frac:>7.1f}%")
    print(
        f"dispatch gap p50={summ.get('gap_p50_ms')}ms "
        f"p95={summ.get('gap_p95_ms')}ms"
    )
    if args.json_out:
        payload = _json.dumps(data.get("chrome_trace") or {}, indent=2)
        await asyncio.to_thread(_write_text, args.json_out, payload)
        print(f"chrome trace written to {args.json_out}")
    return 0


async def run_profile_sla(args) -> int:
    """profile-sla: drive the engine, print the TTFT/ITL table + the SLO
    recommendation as one JSON object."""
    import json

    from .planner.profile_sla import SlaProfiler

    isls = [int(x) for x in args.isl.split(",") if x]
    batches = [int(x) for x in args.batch.split(",") if x]
    engine = await _make_engine(args)  # same builder as `run` (shared flags)
    vocab = _tokenizer_for(args).vocab_size if args.model_path else 30000
    try:
        prof = await SlaProfiler(engine, vocab_size=vocab).profile(
            isls=isls, batches=batches, osl=args.osl
        )
        print(
            json.dumps(
                {
                    "profile": prof.to_dict(),
                    "recommendation": prof.recommend(
                        args.ttft_slo_ms, args.itl_slo_ms
                    ),
                },
                indent=2,
            )
        )
    finally:
        await engine.stop()
    return 0


async def run_bench(args) -> int:
    """bench: fire the workload at a running frontend, print one JSON
    summary (output tok/s, TTFT percentiles, error counts)."""
    import json

    from .bench_serving import run_bench as drive, synth_workload, trace_workload

    if args.trace:
        workload = trace_workload(
            args.trace,
            block_size=args.trace_block_size,
            vocab=args.vocab_size,
            speedup=args.speedup_ratio,
            limit=args.num_requests,  # None = replay the whole trace
        )
    else:
        workload = synth_workload(
            args.num_requests if args.num_requests is not None else 64,
            args.isl, args.osl, args.request_rate,
            vocab=args.vocab_size, seed=args.seed,
        )
    report = await drive(
        args.host, args.port, args.model, workload,
        concurrency=args.concurrency,
    )
    summary = report.summary()
    if args.fleet:
        from .bench_serving import fetch_fleet

        try:
            summary["fleet"] = await fetch_fleet(args.host, args.port)
        except Exception as e:
            summary["fleet"] = {"error": repr(e)}
    print(json.dumps(summary, indent=2))
    return 0 if summary["num_errors"] == 0 else 1


async def run_metrics(args) -> int:
    """metrics: the standalone cluster Prometheus component (reference
    components/metrics :9091) -- scrapes worker load_metrics through the
    hub, subscribes to kv-hit-rate events, serves GET /metrics."""
    from .llm.components import MetricsService
    from .runtime.component import DistributedRuntime

    runtime = await DistributedRuntime.detached(args.hub)
    svc = MetricsService(runtime, args.namespace, args.component)
    await svc.start()
    host, port = await svc.serve_http(args.host, args.port)
    print(f"cluster metrics at http://{host}:{port}/metrics (hub {args.hub})")
    stop = asyncio.Event()
    lost = _stop_on_hub_loss(runtime.hub, stop)
    try:
        await _wait_forever(stop)
    finally:
        await svc.stop()
        await runtime.shutdown()
    _exit_if_lost(lost)
    return 0


def format_fleet_table(summary, show_plan: bool = False) -> str:
    """Render one /fleet summary as the `dynamo-tpu fleet` table."""
    lines = []
    totals = summary.get("totals", {})
    roles = totals.get("workers_by_role", {})
    head = ", ".join(
        f"{n} {role}" for role, n in sorted(roles.items())
    ) or "no workers"
    lines.append(
        f"fleet: {head} | kv pressure "
        f"{totals.get('kv_pressure', 0.0):.2f} | queue "
        f"{totals.get('queue_depth', 0)}"
    )
    slo = totals.get("slo_attainment") or {}
    if slo:
        lines.append(
            "slo:   "
            + "  ".join(f"{k}={v:.3f}" for k, v in sorted(slo.items()))
        )
    cols = ("id", "role", "tok/s", "step ms", "kv", "queue", "slots", "flag")
    rows = []
    for w in summary.get("workers", []):
        step = w.get("step_ms")
        rows.append(
            (
                str(w["worker_id"]),
                w.get("role", "?"),
                f"{w.get('tokens_per_s', 0.0):.1f}",
                "-" if step is None else f"{step:.2f}",
                f"{w.get('kv_pages_used', 0)}/{w.get('kv_pages_total', 0)}",
                str(w.get("queue_depth", 0)),
                f"{w.get('batch_occupancy', 0)}/{w.get('batch_slots', 0)}",
                "QUARANTINED" if w.get("quarantined")
                else ("STRAGGLER" if w.get("straggler") else ""),
            )
        )
    if rows:
        widths = [
            max(len(cols[i]), max(len(r[i]) for r in rows))
            for i in range(len(cols))
        ]
        fmt = "  ".join(f"{{:<{w}}}" for w in widths)
        lines.append(fmt.format(*cols))
        for r in rows:
            lines.append(fmt.format(*r))
    if show_plan:
        plan = summary.get("plan") or {}
        if not plan:
            lines.append("plan:  (no planner adjustments yet)")
        for kind in sorted(plan):
            rec = plan[kind]
            age = ""
            ts = rec.get("ts")
            if ts:
                import time as _time

                age = f" ({max(_time.time() - ts, 0.0):.0f}s ago)"
            lines.append(
                f"plan:  {kind}: {rec.get('action', '?')} from "
                f"{rec.get('count_before', '?')} -- "
                f"{rec.get('reason', '')}{age}"
            )
    for link in summary.get("links", []):
        bw = link.get("bandwidth_bytes_per_s")
        setup = link.get("setup_ms")
        lines.append(
            f"link {link['src']}->{link['dst']}: "
            + ("fitting..." if bw is None
               else f"{bw / 1e6:.1f} MB/s + {setup or 0.0:.2f} ms setup")
            + f" ({link.get('samples', 0)} samples)"
        )
    return "\n".join(lines)


async def run_fleet(args) -> int:
    """fleet: subscribe to worker telemetry on the hub, print a live
    cluster table (the CLI face of GET /fleet)."""
    import json

    from .fleet import FleetObservatory
    from .runtime.component import DistributedRuntime
    from .runtime.metrics import MetricsRegistry

    runtime = await DistributedRuntime.detached(args.hub)
    # private registry: the CLI process has no scrape surface, and must
    # not pollute a colocated default registry with fleet families
    observatory = FleetObservatory(MetricsRegistry())
    await observatory.start(runtime.namespace(args.namespace))
    stop = asyncio.Event()
    if hasattr(runtime.hub, "on_connection_lost"):
        runtime.hub.on_connection_lost = stop.set
    try:
        while not stop.is_set():
            with contextlib.suppress(asyncio.TimeoutError):
                await asyncio.wait_for(stop.wait(), args.interval)
            if stop.is_set():
                break
            summary = observatory.summary()
            if args.json_out:
                print(json.dumps(summary, indent=2))
            else:
                print(format_fleet_table(
                    summary, show_plan=getattr(args, "plan", False)
                ))
                print()
            if args.once:
                break
    except KeyboardInterrupt:
        pass
    finally:
        await observatory.stop()
        await runtime.shutdown()
    return 0


def run_datagen(args) -> int:
    """datagen analyze|synthesize (reference benchmarks/data_generator/cli.py)."""
    import json

    from .datagen import PrefixAnalyzer, Synthesizer
    from .datagen.analyzer import load_trace

    if args.dgcmd == "analyze":
        stats = PrefixAnalyzer.from_file(
            args.input_file, block_size=args.block_size
        ).analyze()
        print(json.dumps(stats, indent=2))
        return 0
    syn = Synthesizer(
        load_trace(args.input_file),
        block_size=args.block_size,
        num_copies=args.num_copies,
        speedup_ratio=args.speedup_ratio,
        prefix_len_multiplier=args.prefix_len_multiplier,
        prompt_len_multiplier=args.prompt_len_multiplier,
        seed=args.seed,
    )
    records = syn.synthesize(args.num_requests)
    Synthesizer.dump(records, args.output_file)
    print(f"wrote {len(records)} requests to {args.output_file}")
    return 0


async def _wire_prefix_onboard(served, engine, ns, comp, comp_name):
    """Enable cross-worker prefix onboarding (G4) when the engine has a host
    offload tier to stage imports in: serve ``kv_export`` (donor side) and
    wrap the serving engine (importer side)."""
    if getattr(engine, "offload", None) is None:
        return served
    from .llm.prefix_onboard import (
        KV_EXPORT_ENDPOINT,
        PrefixOnboardEngine,
        kv_export_handler,
    )

    await comp.endpoint(KV_EXPORT_ENDPOINT).serve_raw(kv_export_handler(engine))
    return PrefixOnboardEngine(served, ns, comp_name, engine=engine)


def run_build(args) -> int:
    """Package a graph directory (tar.gz) and register it with api-store
    (reference `dynamo build`: containerize + push; here the artifact is the
    graph source + manifest, and the runtime image is container/Dockerfile's
    -- one image serves every graph, args select the role)."""
    import io
    import json as _json
    import tarfile
    import urllib.error
    import urllib.request

    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w:gz") as tar:
        tar.add(args.path, arcname=args.name)
    blob = buf.getvalue()

    base = args.store.rstrip("/")

    def post(path, body):
        req = urllib.request.Request(
            base + path, data=_json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            with urllib.request.urlopen(req) as r:
                return r.status, _json.load(r)
        except urllib.error.HTTPError as e:
            raw = e.read() or b"{}"
            try:
                return e.code, _json.loads(raw)
            except ValueError:  # non-JSON error page (proxy, wrong server)
                return e.code, {"error": raw[:200].decode("latin1")}
        except urllib.error.URLError as e:
            raise SystemExit(f"cannot reach api-store at {base}: {e.reason}")

    status, out = post("/api/v1/components", {"name": args.name})
    if status not in (201, 409):  # existing component is fine
        raise SystemExit(f"component create failed: {status} {out}")
    status, out = post(
        f"/api/v1/components/{args.name}/versions",
        {"version": args.version, "manifest": {"entry": args.name}},
    )
    if status != 201:
        raise SystemExit(f"version create failed: {status} {out}")
    req = urllib.request.Request(
        f"{base}/api/v1/components/{args.name}/versions/{args.version}/artifact",
        data=blob, method="PUT",
        headers={"Content-Type": "application/octet-stream"},
    )
    try:
        with urllib.request.urlopen(req) as r:
            out = _json.load(r)
    except urllib.error.HTTPError as e:
        raise SystemExit(
            f"artifact upload failed: HTTP {e.code} {e.read()[:200]!r}"
        )
    except urllib.error.URLError as e:
        raise SystemExit(f"cannot reach api-store at {base}: {e.reason}")
    print(
        f"built {args.name}:{args.version} "
        f"({out.get('artifact_bytes', len(blob))} bytes) -> {base}"
    )
    return 0


def run_deploy(args) -> int:
    """Fetch a built graph from api-store, unpack it, render its k8s
    manifests, and record the deployment (reference `dynamo deploy`)."""
    import io
    import json as _json
    import os
    import tarfile
    import urllib.error
    import urllib.request

    base = args.store.rstrip("/")
    url = (
        f"{base}/api/v1/components/{args.name}/versions/"
        f"{args.version}/artifact"
    )
    try:
        with urllib.request.urlopen(url) as r:
            blob = r.read()
    except urllib.error.HTTPError as e:
        raise SystemExit(
            f"{args.name}:{args.version} not fetchable from {base}: "
            f"HTTP {e.code} {e.read()[:200]!r}"
        )
    except urllib.error.URLError as e:
        raise SystemExit(f"cannot reach api-store at {base}: {e.reason}")
    os.makedirs(args.out_dir, exist_ok=True)
    with tarfile.open(fileobj=io.BytesIO(blob), mode="r:gz") as tar:
        try:
            tar.extractall(args.out_dir, filter="data")
        except TypeError:  # 3.10 < 3.10.12 lacks the filter kwarg
            tar.extractall(args.out_dir)  # noqa: S202 - own-store artifact

    from .deploy import DeploymentSpec, render_manifests

    spec = DeploymentSpec(
        name=args.name, model_path=args.model_path, image=args.image
    )
    mdir = os.path.join(args.out_dir, "manifests")
    os.makedirs(mdir, exist_ok=True)
    for fname, text in render_manifests(spec).items():
        with open(os.path.join(mdir, fname), "w") as f:
            f.write(text)
    req = urllib.request.Request(
        base + "/api/v1/deployments",
        data=_json.dumps(
            {"name": args.name,
             "spec": {"version": args.version, "image": args.image,
                      "model_path": args.model_path}}
        ).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        with urllib.request.urlopen(req):
            pass
    except urllib.error.HTTPError as e:
        raise SystemExit(
            f"deployment record failed: HTTP {e.code} {e.read()[:200]!r}"
        )
    except urllib.error.URLError as e:
        raise SystemExit(f"cannot reach api-store at {base}: {e.reason}")
    print(
        f"deployed {args.name}:{args.version}: artifact + manifests under "
        f"{args.out_dir} (kubectl apply -f {mdir})"
    )
    return 0


async def run_api_store(args) -> int:
    """Serve the deployment-artifact registry over the hub."""
    from .api_store import ApiStoreService
    from .runtime.component import DistributedRuntime

    rt = await DistributedRuntime.detached(args.hub)
    svc = ApiStoreService(rt.hub, host=args.host, port=args.port)
    await svc.start()
    print(f"api-store at http://{args.host}:{svc.address[1]} (hub {args.hub})")
    stop = asyncio.Event()
    rt.hub.on_connection_lost = stop.set
    try:
        await stop.wait()
        print("hub connection lost; exiting", file=sys.stderr)
        return 1
    finally:
        await svc.stop()
        await rt.shutdown()


def run_eval(args) -> int:
    """Perplexity of a checkpoint on text: load weights exactly as serving
    would (incl. --quantize int8), score with llm/evaluate.py, print one
    JSON line."""
    import json as _json
    import os

    from .engine.config import ModelConfig
    from .engine.weights import load_safetensors_params
    from .llm.evaluate import evaluate_perplexity
    from .llm.tokenizer import Tokenizer

    if not args.text and not args.text_file:
        raise SystemExit("need --text or --text-file")
    text = args.text or open(args.text_file, encoding="utf-8").read()
    model_cfg = ModelConfig.from_pretrained(args.model_path)
    # load weights exactly as JaxEngine.from_pretrained would: safetensors
    # when present, else a GGUF checkpoint (dequantize-on-load)
    has_st = os.path.isdir(args.model_path) and any(
        f.endswith(".safetensors") for f in os.listdir(args.model_path)
    )
    if has_st:
        params = load_safetensors_params(args.model_path, model_cfg)
    else:
        from .llm.gguf import find_gguf_file, load_gguf_params

        gguf = find_gguf_file(args.model_path)
        if gguf is None:
            raise SystemExit(
                f"{args.model_path}: no .safetensors and no .gguf weights"
            )
        params = load_gguf_params(gguf, model_cfg)
    if args.quantize == "int8":
        from .engine.quant import quantize_params

        params = quantize_params(params, model_cfg)
    tok = Tokenizer.from_model_dir(args.model_path)
    ids = tok.encode(text)
    out = evaluate_perplexity(params, model_cfg, ids, window=args.window)
    out["model"] = args.model_path
    out["quantize"] = args.quantize
    print(_json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                       for k, v in out.items()}))
    return 0


async def run_operator(args) -> int:
    """Run the reconcile controller (reference operator equivalent)."""
    from .operator import KubectlBackend, Operator, OperatorConfig
    from .runtime.component import DistributedRuntime

    rt = await DistributedRuntime.detached(args.hub)
    op = Operator(
        rt.hub,
        KubectlBackend(kubectl=args.kubectl, namespace=args.namespace),
        OperatorConfig(
            interval_s=args.interval,
            image=args.image,
            namespace=args.namespace,
        ),
    )
    try:
        if args.once:
            actions = await op.reconcile_once()
            for a in actions:
                if a.action != "ok":
                    print(f"{a.deployment}: {a.action}")
            print(f"reconciled ({len(actions)} deployments checked)")
            return 0
        await op.start()
        print(f"operator reconciling every {args.interval}s (hub {args.hub})")
        stop = asyncio.Event()
        rt.hub.on_connection_lost = stop.set
        await stop.wait()
        print("hub connection lost; exiting", file=sys.stderr)
        return 1
    finally:
        await op.stop()
        await rt.shutdown()


async def run_trace(args) -> int:
    """Assemble one request's span timeline from every component on the hub.

    Discovery comes from the hub's ``instances/`` keyspace; each component's
    auto-served ``_trace`` endpoint returns its process's spans for the
    request id, and the merged set prints as one offset-ordered timeline
    (plus optional Chrome-trace JSON for chrome://tracing / Perfetto)."""
    import json as _json

    from .runtime import tracing
    from .runtime.component import (
        INSTANCE_ROOT_PATH,
        DistributedRuntime,
        Instance,
    )

    rt = await DistributedRuntime.detached(args.hub)
    try:
        prefix = f"{INSTANCE_ROOT_PATH}/{args.namespace}/"
        components = set()
        for _key, value in await rt.hub.kv_get_prefix(prefix):
            try:
                components.add(Instance.from_json(value).component)
            except Exception:
                logger.warning("skipping malformed instance record at %s", _key)
        if not components:
            print(f"no components registered under namespace {args.namespace}")
            return 1
        ns = rt.namespace(args.namespace)
        # scrape components concurrently: one wedged component costs one
        # timeout in total, not one per component
        results = await asyncio.gather(
            *(
                ns.component(comp).scrape_trace(
                    args.request_id, timeout_s=args.timeout
                )
                for comp in sorted(components)
            ),
            return_exceptions=True,
        )
        spans = []
        for comp, res in zip(sorted(components), results):
            if isinstance(res, Exception):
                logger.warning("trace scrape failed for %s: %s", comp, res)
            else:
                spans.extend(res)
        # colocated components share one process collector: the same span
        # comes back from every component scrape in that process
        seen_ids = set()
        deduped = []
        for s in spans:
            key = s.get("span_id")
            if key:
                if key in seen_ids:
                    continue
                seen_ids.add(key)
            deduped.append(s)
        spans = deduped
        if not spans:
            print(
                f"no spans for request {args.request_id} "
                f"(is DYN_TRACE=1 set on the serving processes?)"
            )
            return 1
        spans.sort(key=lambda s: s.get("start_s", 0.0))
        t0 = spans[0].get("start_s", 0.0)
        trace_ids = {s.get("trace_id") for s in spans if s.get("trace_id")}
        print(
            f"request {args.request_id}: {len(spans)} spans across "
            f"{len({s.get('component') or 'process' for s in spans})} "
            f"components (trace {', '.join(sorted(trace_ids)) or 'n/a'})"
        )
        print(f"{'offset_ms':>10}  {'dur_ms':>9}  {'component':<24} name")
        for s in spans:
            off = (s.get("start_s", 0.0) - t0) * 1e3
            print(
                f"{off:10.3f}  {s.get('duration_ms', 0.0):9.3f}  "
                f"{(s.get('component') or '-'):<24} {s.get('name', '')}"
            )
        if args.json_out:
            payload = _json.dumps(tracing.chrome_trace(spans), indent=2)
            await asyncio.to_thread(_write_text, args.json_out, payload)
            print(f"chrome trace written to {args.json_out}")
        return 0
    finally:
        await rt.shutdown()


def _write_text(path: str, payload: str) -> None:
    with open(path, "w", encoding="utf-8") as f:
        f.write(payload)


async def run_disagg_conf(args) -> int:
    """Write the live disagg routing policy to the hub; every decode worker
    watching the key reloads it (llm/disagg.py start_config_watch)."""
    import json as _json

    from .llm.disagg import disagg_conf_key
    from .runtime.component import DistributedRuntime

    conf = {}
    if args.max_local_prefill_length is not None:
        conf["max_local_prefill_length"] = args.max_local_prefill_length
    if args.max_prefill_queue_depth is not None:
        conf["max_prefill_queue_depth"] = args.max_prefill_queue_depth
    if not conf:
        print("nothing to update (pass --max-local-prefill-length and/or "
              "--max-prefill-queue-depth)")
        return 2
    rt = await DistributedRuntime.detached(args.hub)
    try:
        # read-modify-write: a partial update must not drop fields an
        # earlier update set -- workers that join later apply the snapshot
        key = disagg_conf_key(args.namespace)
        merged: dict = {}
        for _k, value in await rt.hub.kv_get_prefix(key):
            try:
                merged.update(_json.loads(value))
            except Exception:
                # malformed old value: overwrite it, but say so
                logger.warning("discarding malformed disagg conf at %s", _k)
        merged.update(conf)
        await rt.hub.kv_put(key, _json.dumps(merged).encode())
        print(f"disagg conf updated for namespace {args.namespace}: {merged}")
    finally:
        await rt.shutdown()
    return 0


def main(argv=None) -> int:
    from .runtime.utils import configure_logging

    configure_logging()  # DYN_LOG filter spec + DYN_LOG_JSONL mode
    args = build_parser().parse_args(argv)
    if args.cmd == "hub":
        from .runtime.transports.hub import HubServer

        try:
            asyncio.run(
                HubServer(
                    host=args.host, port=args.port, data_dir=args.data_dir
                ).serve_forever()
            )
        except KeyboardInterrupt:
            pass
        return 0
    if args.cmd == "llmctl":
        return asyncio.run(run_llmctl(args))
    if args.cmd == "metrics":
        return asyncio.run(run_metrics(args))
    if args.cmd == "fleet":
        return asyncio.run(run_fleet(args))
    if args.cmd == "datagen":
        return run_datagen(args)
    if args.cmd == "profile":
        return asyncio.run(run_profile(args))
    if args.cmd == "profile-sla":
        return asyncio.run(run_profile_sla(args))
    if args.cmd == "bench":
        return asyncio.run(run_bench(args))
    if args.cmd == "disagg-conf":
        return asyncio.run(run_disagg_conf(args))
    if args.cmd == "trace":
        return asyncio.run(run_trace(args))
    if args.cmd == "api-store":
        return asyncio.run(run_api_store(args))
    if args.cmd == "eval":
        return run_eval(args)
    if args.cmd == "operator":
        return asyncio.run(run_operator(args))
    if args.cmd == "build":
        return run_build(args)
    if args.cmd == "deploy":
        return run_deploy(args)
    args.inp, args.out = _parse_io(args.io)
    try:
        if args.inp == "http" and args.out in ("jax", "mocker", "echo"):
            asyncio.run(run_http_local(args))
        elif args.inp == "http" and args.out == "dyn":
            asyncio.run(run_http_frontend(args))
        elif args.inp == "dyn":
            asyncio.run(run_worker(args))
        elif args.inp == "text":
            asyncio.run(run_text(args))
        elif args.inp == "batch":
            asyncio.run(run_batch(args))
        else:
            raise SystemExit(f"unsupported combination in={args.inp} out={args.out}")
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
