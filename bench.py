"""Benchmark: serving throughput of the first-party JAX engine on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus a
decode batch sweep, a served-path measurement (HTTP frontend: output tok/s
AND TTFT p50, the north-star pair -- BASELINE.md), and the disaggregated
leg.  The model is a TinyLlama-1.1B-shaped random-init in bfloat16 (no
checkpoint ships with this environment -- zero egress; shapes, dtypes and
kernels are identical to real weights, logit VALUES are not, so this is a
throughput tracker, not a quality benchmark).  ``vs_baseline`` is the ratio
against the reference's published per-device decode number (51.22 tok/s/GPU,
H100 TP4, Llama-70B -- docs/architecture/planner.md:86); the models differ
in size, so the ratio is a tracking index, not a same-model claim.
"""

from __future__ import annotations

import asyncio
import json
import time

# Published peaks per chip, keyed by ``jax.devices()[0].device_kind`` (Google
# Cloud documentation, "TPU v5e": 819 GB/s of HBM bandwidth).  A device that
# is not in the table is an error, not a default.
DEVICE_PEAKS = {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}


def hbm_bytes_per_s() -> float:
    """HBM bandwidth of the attached device, for the shape-arithmetic
    utilization estimates below; raises for a device the table lacks."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise RuntimeError(
            f"no peaks recorded for device kind {kind!r}: an estimate "
            "against another chip's bandwidth would be wrong"
        )
    return DEVICE_PEAKS[kind]["hbm_bytes_per_s"]


def build_engine(
    max_batch_size: int = 8,
    num_pages: int = 768,
    decode_block: int = 64,
    quantize=None,
    max_seq_len: int = 1024,
    grow_chunk_pages: int = 4,
    # offload armed by default since ISSUE 10: BENCH_r01-r05 predate the
    # offload engine (PR 5) and ROADMAP explicitly asks the next round to
    # re-establish the curve with the plane on.  Eviction snapshots ride
    # the dedicated offload thread, so the bs8/bs64 decode lines stay
    # methodology-comparable -- the armed plane only changes behavior
    # when evictions/preemptions actually occur.
    host_offload_blocks: int = 256,
    swap_preemption: bool = True,
    mixed_batching: bool = True,
    mixed_token_budget: int = 512,
    kv_dtype=None,
    async_dispatch: bool = True,
    **extra_cfg,
):
    """decode_block is the throughput/latency dial: more steps per host
    round trip amortize the sync, but the first block must finish before
    any token streams, so the latency-sensitive legs (prefill TTFT, served
    SSE) run K=16 -- production picks K by its ITL granularity budget.
    Which K wins on a directly attached chip is not measured yet."""
    import jax

    from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig

    model_cfg = ModelConfig(
        vocab_size=32000,
        hidden_size=2048,
        intermediate_size=5632,
        num_layers=22,
        num_heads=32,
        num_kv_heads=4,
        head_dim=64,
        rope_theta=10000.0,
        max_position=2048,
        dtype="bfloat16",
    )
    cfg = EngineConfig(
        max_batch_size=max_batch_size,
        max_seq_len=max_seq_len,
        page_size=16,
        num_pages=num_pages,
        decode_block_size=decode_block,
        quantize=quantize,
        grow_chunk_pages=grow_chunk_pages,
        host_offload_blocks=host_offload_blocks,
        swap_preemption=swap_preemption,
        mixed_batching=mixed_batching,
        mixed_token_budget=mixed_token_budget,
        kv_dtype=kv_dtype,
        async_dispatch=async_dispatch,
        seed=0,
        **extra_cfg,
    )
    return JaxEngine.random_init(model_cfg, cfg)


async def run_batch(engine, prompts, max_tokens):
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    async def one(prompt):
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=max_tokens),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        stream = await engine.generate(Context.new(req))
        n = 0
        async for item in stream:
            data = item.data or {}
            n += len(data.get("token_ids") or [])
        return n

    results = await asyncio.gather(*[one(p) for p in prompts])
    return sum(results)


async def run_disagg(rs, allow_local: bool = True):
    """Disaggregated serving mode: decode engine + prefill engine over the
    hub (both on the one chip -- they contend, so this tracks the disagg
    PATH's overhead vs aggregated, not a two-chip speedup).  Every prompt
    ships remote: hub queue -> prefill engine -> KV blockset delivery ->
    decode resumes.

    ``allow_local`` selects the delivery leg: True takes the same-process
    device-resident handoff (NIXL-DMA analog), False forces the chunked
    wire upload -- layer-group chunks stream onto the wire as they
    materialize (engine.prefill_export_batch_stream), so ``export_ms`` is
    export-BEFORE-FIRST-BYTE, ``export_total_ms`` the full materialize,
    and ``overlap_ratio`` the fraction of export that overlapped transfer.
    Returns (decode tok/s, transfer stats)."""
    from dynamo_tpu.llm.disagg import (
        KV_DELIVER_ENDPOINT,
        DisaggConfig,
        DisaggDecodeEngine,
        PrefillWorker,
    )
    from dynamo_tpu.runtime.component import DistributedRuntime
    from dynamo_tpu.runtime.transports.hub import HubServer

    cleanups = []
    try:
        decode_engine = build_engine()
        cleanups.append(decode_engine.stop)
        prefill_engine = build_engine()
        cleanups.append(prefill_engine.stop)
        hub = HubServer()
        host, port = await hub.start()
        cleanups.append(hub.stop)
        addr = f"{host}:{port}"
        drt = await DistributedRuntime.detached(addr)
        cleanups.append(drt.shutdown)
        dns = drt.namespace("bench")
        decode = DisaggDecodeEngine(
            decode_engine, dns, "backend", drt.primary_lease,
            DisaggConfig(max_local_prefill_length=0),  # everything ships remote
            block_size=16,
        )
        await dns.component("backend").endpoint(KV_DELIVER_ENDPOINT).serve_raw(
            decode.kv_deliver_handler()
        )
        prt = await DistributedRuntime.detached(addr)
        cleanups.append(prt.shutdown)
        pw = PrefillWorker(
            prefill_engine, prt.namespace("bench"), allow_local=allow_local
        )
        await pw.start()
        cleanups.append(pw.stop)
        prompts = [rs.randint(1, 30000, (128,)).tolist() for _ in range(8)]
        await run_batch(decode, prompts, max_tokens=8)  # warm both engines
        # fresh prompts for the measured pass: reusing the warmup's would
        # let any prefix reuse shortcut the remote prefill being measured
        prompts = [rs.randint(1, 30000, (128,)).tolist() for _ in range(8)]
        before = decode.remote_prefills
        t0 = time.monotonic()
        total = await run_batch(decode, prompts, max_tokens=64)
        elapsed = time.monotonic() - t0
        assert decode.remote_prefills - before >= 8, "disagg path not exercised"
        stats = pw.transfer_stats()
        expect = "device" if allow_local else "wire"
        assert expect in stats, f"{expect} leg not exercised: {stats}"
        return total / elapsed, stats.get(expect) or {}
    finally:
        for stop in reversed(cleanups):
            try:
                await stop()
            except Exception:
                pass


def _build_tokenizer(tmpdir: str):
    """Minimal BPE tokenizer dir for the serving leg's detok path."""
    import json as _json
    import os

    from tokenizers import Tokenizer as _Tok
    from tokenizers import decoders, models, pre_tokenizers, trainers

    tok = _Tok(models.BPE(unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.train_from_iterator(
        ["the quick brown fox jumps over the lazy dog " * 8],
        trainers.BpeTrainer(vocab_size=128, special_tokens=["<unk>"]),
    )
    tok.decoder = decoders.BPEDecoder()
    os.makedirs(tmpdir, exist_ok=True)
    tok.save(os.path.join(tmpdir, "tokenizer.json"))
    with open(os.path.join(tmpdir, "tokenizer_config.json"), "w") as f:
        _json.dump({}, f)
    from dynamo_tpu.llm.tokenizer import Tokenizer

    return Tokenizer.from_model_dir(tmpdir)


async def run_serving(engine) -> dict:
    """Served-path measurement: HTTP frontend + SSE streaming over the live
    engine; reports output tok/s and TTFT percentiles together (the
    north-star pair, BASELINE.md row 1).

    Two legs: a *throughput* leg (concurrency 16 over a bs-8 engine --
    requests queue, so its TTFT is saturation-shaped) and a *latency* leg
    (concurrency 4 <= bs, no self-inflicted queueing) whose TTFT is what an
    SLO-governed deployment would observe.  Reference comparison point:
    ~48 ms prefill TTFT on H100 (BASELINE.md row 4)."""
    import tempfile

    from dynamo_tpu.bench_serving import run_bench, synth_workload
    from dynamo_tpu.http import HttpService
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.runtime import profiling
    from dynamo_tpu.runtime.pipeline import link

    with tempfile.TemporaryDirectory() as td:
        tok = _build_tokenizer(td)
        name = "bench-model"
        pipeline = link(OpenAIPreprocessor(name, tok), Backend(tok), engine)
        svc = HttpService()
        svc.manager.add_chat_model(name, pipeline)
        svc.manager.add_completion_model(name, pipeline)
        await svc.start()
        prof = profiling.profiler
        prof_was_enabled = prof.enabled
        try:
            host, port = svc.address
            vocab = max(3, tok.vocab_size - 1)
            # the serving line runs SPECULATION ON by default (ISSUE 15 /
            # RTP-LLM posture): every request arms the n-gram drafter and
            # the engine's acceptance-aware auto-disable reverts
            # low-acceptance lanes to plain decode -- spec_accept_rate +
            # spec_enabled_frac land next to the throughput pair so the
            # trajectory shows what default-on speculation actually does
            # under random (low-repetition) serving traffic
            spec_knobs = {"num_draft_tokens": 4, "drafter": "ngram"}
            warm = synth_workload(8, isl=128, osl=8, request_rate=0.0,
                                  vocab=vocab, seed=7,
                                  speculation=spec_knobs)
            await run_bench(host, port, name, warm, concurrency=8)
            # tick-phase profiling covers only the measured window (the
            # warmup's compile storms would drown the steady-state split);
            # the serving line reports where host tick time actually goes
            # and the dispatch gap -- the ROADMAP item 2 localizers
            prof.clear()
            prof.enable()
            d0, a0 = engine.spec_drafted, engine.spec_accepted
            work = synth_workload(48, isl=128, osl=64, request_rate=0.0,
                                  vocab=vocab, seed=8,
                                  speculation=spec_knobs)
            report = await run_bench(host, port, name, work, concurrency=16)
            s = report.summary()
            assert s["num_errors"] == 0, f"serving bench errors: {s}"
            lat = synth_workload(16, isl=128, osl=64, request_rate=0.0,
                                 vocab=vocab, seed=9,
                                 speculation=spec_knobs)
            lat_report = await run_bench(host, port, name, lat, concurrency=4)
            ls = lat_report.summary()
            assert ls["num_errors"] == 0, f"latency bench errors: {ls}"
            psum = prof.summary()
            drafted = engine.spec_drafted - d0
            accepted = engine.spec_accepted - a0
            return {
                "serving_tok_s": s["output_tok_s"],
                "ttft_p50_ms": s["ttft_ms"]["p50"],
                "ttft_p99_ms": s["ttft_ms"]["p99"],
                "ttft_lat_p50_ms": ls["ttft_ms"]["p50"],
                "ttft_lat_p99_ms": ls["ttft_ms"]["p99"],
                # top host phases of the serving window (name, seconds):
                # which host-side leg to attack before the next TPU round
                "host_phase_top3": psum["top_phases"][:3],
                "host_occupancy": psum["host_occupancy"],
                "dispatch_gap_p50_ms": psum["gap_p50_ms"],
                # KV pool footprint next to the serving line (ISSUE 13):
                # the quantization win must be visible in the trajectory
                "kv_dtype": str(engine.kv.dtype),
                "kv_pool_gb": round(engine.kv.pool_bytes / 1e9, 4),
                "async_dispatch": bool(engine._async_dispatch),
                # default-on speculation health (acceptance-aware disable):
                # accept rate over the measured window and the fraction of
                # spec-armed requests that kept drafting
                "serving_spec_accept_rate": (
                    round(accepted / drafted, 4) if drafted else None
                ),
                "serving_spec_enabled_frac": round(
                    engine.spec_enabled_frac, 4
                ),
            }
        finally:
            if not prof_was_enabled:
                prof.disable()
            await svc.stop()


async def run_host_pipeline(rs) -> dict:
    """Host tick-pipeline A/B (ISSUE 13): the identical workload on the
    mocker with the double-buffered dispatch lanes on vs off.

    The mocker simulates device time (``decode_s_per_step``), so this is
    the chip-free measurement of exactly what the async pipeline buys:
    with lanes on, tick N+1's dispatch is enqueued before tick N's host
    commit/fanout runs and the host-observed dispatch gap collapses to
    ~zero; with ``async_dispatch=False`` (the ``--no-async-dispatch``
    fallback) every tick's host work sits in the gap.  The acceptance
    line is ``pipe_gap_p50_ms_async <= pipe_gap_p50_ms_serial / 2``.

    The multi-step K sweep (ISSUE 16) rides the same workload: K in
    {1, 4, 8} plus the adaptive controller, each leg reporting host
    occupancy, dispatch-gap p50, and tok/s -- a K-step fused dispatch
    amortizes the per-tick host work over K tokens, so occupancy and gap
    must fall monotonically toward K=8 (``pipe_host_occ_k8 <
    pipe_host_occ_k1`` is the acceptance line).

    Each leg also reports ``pipe_compiles_<name>``: the compile-sentry
    events the leg's engine minted (one per distinct fused-K executable),
    so the silicon round can price what a K sweep costs in recompiles --
    a controller that buys occupancy by melting the compile cache shows
    up here, not just in tok/s."""
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.runtime import compile_sentry, profiling

    prof = profiling.profiler
    was_enabled = prof.enabled
    out = {}
    legs = (
        ("serial", False, 1),
        ("async", True, 1),
        # multi-step sweep: fixed K, then the adaptive controller (0)
        ("k1", True, 1),
        ("k4", True, 4),
        ("k8", True, 8),
        ("kadapt", True, 0),
    )
    try:
        for name, async_on, ms_k in legs:
            compiles_before = compile_sentry.total()
            eng = MockerEngine(
                MockerConfig(
                    max_batch_size=16,
                    decode_s_per_step=2e-5,
                    async_dispatch=async_on,
                    multistep_k=ms_k,
                )
            )
            prompts = [
                rs.randint(1, 30000, (64,)).tolist() for _ in range(16)
            ]
            await run_batch(eng, prompts, max_tokens=8)  # warm
            prof.clear()
            prof.enable()
            t0 = time.monotonic()
            total = await run_batch(eng, prompts, max_tokens=64)
            elapsed = time.monotonic() - t0
            psum = prof.summary()
            prof.disable()
            await eng.stop()
            out[f"pipe_gap_p50_ms_{name}"] = psum["gap_p50_ms"]
            out[f"pipe_tok_s_{name}"] = round(total / elapsed, 2)
            out[f"pipe_compiles_{name}"] = (
                compile_sentry.total() - compiles_before
            )
            if name.startswith("k"):
                out[f"pipe_host_occ_{name}"] = psum["host_occupancy"]
        gs, ga = out.get("pipe_gap_p50_ms_serial"), out.get(
            "pipe_gap_p50_ms_async"
        )
        if gs is not None and ga is not None and gs > 0:
            out["pipe_gap_reduction"] = round(gs / max(ga, 1e-6), 2)
    finally:
        if was_enabled:
            prof.enable()
        else:
            prof.disable()
    return out


async def run_slo_rig(scale: str = "smoke") -> dict:
    """Self-healing fleet control proof rig (ISSUE 19): a mocker fleet at
    production shape under bursty Poisson + diurnal arrivals and mixed
    prompt lengths, with ``DYN_FAULTS`` armed to kill workers mid-run.

    Three legs, identical workload seed:

      * ``noloss``   -- planner ON, no chaos (the baseline the SLOs were
        sized against);
      * ``loss_on``  -- planner ON, >=2 ``worker.kill`` fires mid-run:
        the control loop must detect the attainment breach, scale the
        pool back out (drain-safe actuation, standby promotion), and
        recover;
      * ``loss_off`` -- same kills, planner absent: what worker loss
        costs with the loop open.

    The acceptance lines ride the report: ``slo_rig_attainment_gain``
    (planner ON minus OFF, must be > 0), ``slo_rig_recovery_s``
    (per-kill time from first post-kill breach back to min(floor,
    pre-kill attainment), must be finite), ``slo_rig_planner_forced_kills``
    and
    ``slo_rig_dropped`` (must be 0: planner scale-downs drain, never
    drop), and ``slo_rig_identity_failures`` (greedy token identity is
    unaffected by quarantine/scale events).  ``scale="smoke"`` is the
    CPU-sized tier-1 shape; ``scale="full"`` is the slow-lane production
    shape (thousands of streams)."""
    import itertools
    import random as _random

    from dynamo_tpu.fleet.observatory import FleetObservatory
    from dynamo_tpu.llm.kv_router.indexer import OverlapScores
    from dynamo_tpu.llm.kv_router.scheduler import (
        DefaultWorkerSelector,
        NoEndpointsError,
        ProcessedEndpoints,
    )
    from dynamo_tpu.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.planner.connector import LocalConnector
    from dynamo_tpu.planner.planner import Planner, PlannerConfig
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime import faults, slo
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    shapes = {
        # CPU-sized smoke: ~hundreds of streams, seconds per leg
        "smoke": dict(
            base_workers=3, min_workers=2, max_workers=6,
            duration_s=3.0, base_rate=80.0, burst_p=0.06, burst_n=4,
            max_batch=6, kv_blocks=96, decode_s_per_step=7e-4,
            prompt_lens=(16, 48, 96), prompt_weights=(0.5, 0.3, 0.2),
            max_tokens=12, ttft_ms=200.0, itl_ms=10.0,
            kill_fracs=(0.30, 0.55), interval_s=0.12, window_s=1.0,
        ),
        # slow-lane production shape: thousands of concurrent streams
        "full": dict(
            base_workers=6, min_workers=3, max_workers=12,
            duration_s=20.0, base_rate=160.0, burst_p=0.08, burst_n=8,
            max_batch=16, kv_blocks=512, decode_s_per_step=1.5e-4,
            prompt_lens=(32, 128, 512), prompt_weights=(0.5, 0.35, 0.15),
            max_tokens=24, ttft_ms=300.0, itl_ms=12.0,
            kill_fracs=(0.30, 0.50, 0.70), interval_s=0.25, window_s=2.0,
        ),
    }
    shp = shapes[scale]
    # diurnal phases: arrival-rate multipliers over equal slices of the run
    phases = (1.0, 1.8, 0.7, 1.5)
    floor = 0.9
    vocab = 32000
    block_size = 16

    class _RigWorker:
        """One fleet member: engine + its telemetry publisher, exposing
        the drain/stop/crash surface the connector and chaos use."""

        def __init__(self, engine, publisher):
            self.engine = engine
            self.publisher = publisher
            self.worker_id = engine.cfg.worker_id

        async def drain(self, timeout_s: float = 2.0) -> bool:
            return await self.engine.drain(timeout_s)

        async def stop(self) -> None:
            await self.publisher.stop(final=False)
            await self.engine.stop()

        async def crash(self) -> None:
            await self.publisher.stop(final=False)
            await self.engine.crash()

    wid_counter = itertools.count(0)

    async def run_leg(leg: str, *, planner_on: bool, chaos_on: bool) -> dict:
        rng = _random.Random(1234)  # identical workload schedule per leg
        slo.tracker.configure(
            f"ttft={shp['ttft_ms']}ms,itl={shp['itl_ms']}ms,"
            f"window={shp['window_s']}s"
        )
        if chaos_on:
            faults.injector.configure("seed=42;worker.kill=1")
        else:
            faults.injector.disable()
        obs = FleetObservatory(registry=MetricsRegistry())
        selector = DefaultWorkerSelector(quarantine=obs.quarantine_source())

        async def make_worker():
            wid = next(wid_counter)
            eng = MockerEngine(
                MockerConfig(
                    block_size=block_size,
                    kv_capacity_blocks=shp["kv_blocks"],
                    max_batch_size=shp["max_batch"],
                    decode_s_per_step=shp["decode_s_per_step"],
                    worker_id=wid,
                ),
                registry=MetricsRegistry(),
            )
            await eng.start()
            pub = eng.telemetry_publisher(
                None, interval_s=0.05, sink=obs.ingest
            )
            pub.start()
            return _RigWorker(eng, pub)

        connector = LocalConnector(
            {"decode": make_worker},
            drain_timeout_s=2.0,
            victim_source=obs.victim_source(),
            standby_spares=1 if planner_on else 0,
        )
        for _ in range(shp["base_workers"]):
            await connector.add_worker("decode")
        if planner_on:
            await connector.prewarm("decode")

        def metrics_source():
            att = {
                k: slo.tracker.attainment(k) for k in ("ttft", "itl")
            }
            out = {}
            for h in list(connector.workers["decode"]):
                m = h.engine.metrics()
                m.slo_ttft_attainment = (
                    1.0 if att["ttft"] is None else att["ttft"]
                )
                m.slo_itl_attainment = (
                    1.0 if att["itl"] is None else att["itl"]
                )
                m.slo_ttft_queue_violations = float(
                    slo.tracker.violation_count("ttft", "queue")
                )
                m.slo_ttft_service_violations = float(
                    slo.tracker.violation_count("ttft", "service")
                )
                out[h.worker_id] = m
            return out

        planner = None
        if planner_on:
            planner = Planner(
                connector,
                metrics_source,
                cfg=PlannerConfig(
                    adjustment_interval_s=shp["interval_s"],
                    kv_load_scale_up=0.85,
                    kv_load_scale_down=0.05,
                    min_decode_workers=shp["min_workers"],
                    max_decode_workers=shp["max_workers"],
                    decode_grace_periods=2,
                    slo_attainment_floor=floor,
                    slo_breach_rounds=2,
                    slo_cooldown_rounds=2,
                ),
                quarantine_source=obs.quarantine_source(),
                on_adjustment=lambda adj: obs.note_adjustment(
                    adj.kind, adj.action, adj.reason, adj.count_before
                ),
            )
            await planner.start()

        ttft_samples: list = []  # (t_monotonic, seconds)
        itl_samples: list = []
        kills: list = []  # (t_monotonic, worker_id)
        stats = {
            "completed": 0, "dropped": 0, "identity_failures": 0,
            "retries": 0,
        }
        rid_counter = itertools.count(0)
        t0 = time.monotonic()
        t_end = t0 + shp["duration_s"]

        def pick_worker(isl: int):
            pool = list(connector.workers["decode"])
            if not pool:
                return None
            eps = ProcessedEndpoints(
                endpoints={h.worker_id: h.engine.metrics() for h in pool}
            )
            try:
                wid, _ = selector.select_worker(
                    eps, OverlapScores(scores={}), isl, block_size
                )
            except NoEndpointsError:
                return None
            return next((h for h in pool if h.worker_id == wid), pool[0])

        async def one_stream(prompt):
            rid = f"rig-{next(rid_counter)}"
            t_arr = time.monotonic()
            got_first = False
            last_t = None
            for _ in range(4):  # original attempt + failover retries
                h = pick_worker(len(prompt))
                if h is None:
                    stats["dropped"] += 1
                    return
                req = PreprocessedRequest(
                    token_ids=list(prompt),
                    stop_conditions=StopConditions(
                        max_tokens=shp["max_tokens"]
                    ),
                    sampling_options=SamplingOptions(temperature=0.0),
                )
                stream = await h.engine.generate(Context.new(req))
                tokens: list = []
                errored = False
                async for item in stream:
                    if item.event == "error":
                        errored = True
                        break
                    data = item.data or {}
                    got = data.get("token_ids") or []
                    if got:
                        now = time.monotonic()
                        tokens.extend(got)
                        if not got_first:
                            got_first = True
                            ttft = now - t_arr
                            slo.tracker.record_ttft(rid, ttft)
                            ttft_samples.append((now, ttft))
                        elif last_t is not None:
                            itl = now - last_t
                            slo.tracker.record_itl(itl)
                            itl_samples.append((now, itl))
                        last_t = now
                if errored:
                    # the worker died under us: client-side failover --
                    # re-dispatch from scratch on a live worker (partial
                    # tokens discarded; TTFT stays anchored to arrival)
                    stats["retries"] += 1
                    continue
                stats["completed"] += 1
                # greedy token identity: the mocker's token function is
                # pure (prompt, index), so quarantine/scale/failover
                # events must never change what a request decodes
                base = (
                    sum(prompt) * 1000003 + len(prompt) * 8191
                )
                expect = [
                    (base + i * 7919) % vocab for i in range(len(tokens))
                ]
                if tokens != expect:
                    stats["identity_failures"] += 1
                return
            stats["dropped"] += 1

        async def chaos():
            for frac in shp["kill_fracs"]:
                delay = t0 + frac * shp["duration_s"] - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                pool = connector.workers["decode"]
                if len(pool) <= 1:
                    continue
                victim = pool[0]  # oldest = carrying the most streams
                if faults.injector.should_fire(
                    "worker.kill", f"worker-{victim.worker_id}"
                ):
                    pool.remove(victim)
                    kills.append((time.monotonic(), victim.worker_id))
                    await victim.crash()

        chaos_task = (
            asyncio.create_task(chaos()) if chaos_on else None
        )
        stream_tasks: list = []
        now = time.monotonic()
        while now < t_end:
            frac = (now - t0) / shp["duration_s"]
            rate = shp["base_rate"] * phases[
                min(int(frac * len(phases)), len(phases) - 1)
            ]
            await asyncio.sleep(rng.expovariate(rate))
            n = 1 + (shp["burst_n"] if rng.random() < shp["burst_p"] else 0)
            for _ in range(n):
                L = rng.choices(
                    shp["prompt_lens"], weights=shp["prompt_weights"]
                )[0]
                prompt = [rng.randrange(1, vocab) for _ in range(L)]
                stream_tasks.append(
                    asyncio.create_task(one_stream(prompt))
                )
            now = time.monotonic()
        if chaos_task is not None:
            await chaos_task
        await asyncio.wait_for(
            asyncio.gather(*stream_tasks, return_exceptions=True),
            timeout=30.0,
        )
        adjustments = 0
        if planner is not None:
            await planner.stop()
            adjustments = sum(
                1 for a in planner.adjustments if a.action != "hold"
            )
        quarantined_peak = len(obs.quarantined)
        for h in list(connector.workers["decode"]) + list(
            connector.spares.get("decode") or []
        ):
            await h.stop()

        def windowed_attainment(samples, target_s, t, width=0.5):
            recent = [v for ts, v in samples if t - width <= ts <= t]
            from dynamo_tpu.runtime.slo import attainment_of

            return attainment_of(recent, target_s)

        # recovery per kill: first post-kill breach -> first return to the
        # pre-kill service level (0.0 when the kill never dented
        # attainment).  The recovery bar is min(floor, pre-kill worst
        # attainment): on a contended host the whole run may sit under
        # the absolute floor, and "recovered" then means "back to the
        # service level the fleet was actually delivering before the
        # loss", not an unreachable absolute
        def worst_at(t):
            atts = [
                windowed_attainment(ttft_samples, shp["ttft_ms"] / 1e3, t),
                windowed_attainment(itl_samples, shp["itl_ms"] / 1e3, t),
            ]
            real = [a for a in atts if a is not None]
            return min(real) if real else None

        recoveries = []
        for t_kill, _wid in kills:
            baseline = worst_at(t_kill)  # window ends at the kill instant
            bar = floor if baseline is None else min(floor, baseline)
            breach_t = None
            recover_t = None
            t = t_kill
            while t <= t_end + 1.0:
                worst = worst_at(t)
                if worst is not None:
                    if breach_t is None and worst < bar:
                        breach_t = t
                    elif breach_t is not None and worst >= bar:
                        recover_t = t
                        break
                t += 0.05
            if breach_t is None:
                recoveries.append(0.0)
            elif recover_t is not None:
                recoveries.append(round(recover_t - t_kill, 3))
            else:
                recoveries.append(None)  # never recovered (open loop)

        from dynamo_tpu.runtime.slo import attainment_of

        att_ttft = attainment_of(
            [v for _, v in ttft_samples], shp["ttft_ms"] / 1e3
        )
        att_itl = attainment_of(
            [v for _, v in itl_samples], shp["itl_ms"] / 1e3
        )
        slo.tracker.disable()
        faults.injector.disable()
        return {
            "attainment_ttft": round(att_ttft, 4) if att_ttft else 0.0,
            "attainment_itl": round(att_itl, 4) if att_itl else 0.0,
            "kills": len(kills),
            "recoveries_s": recoveries,
            "adjustments": adjustments,
            "forced_kills": connector.forced_kills,
            "final_workers": connector.worker_count("decode"),
            "quarantined": quarantined_peak,
            **stats,
        }

    legs = {}
    legs["noloss"] = await run_leg("noloss", planner_on=True, chaos_on=False)
    legs["loss_on"] = await run_leg("loss_on", planner_on=True, chaos_on=True)
    legs["loss_off"] = await run_leg(
        "loss_off", planner_on=False, chaos_on=True
    )

    def score(leg):
        return min(leg["attainment_ttft"], leg["attainment_itl"])

    out = {"slo_rig_scale": scale}
    for name, leg in legs.items():
        out[f"slo_rig_attainment_ttft_{name}"] = leg["attainment_ttft"]
        out[f"slo_rig_attainment_itl_{name}"] = leg["attainment_itl"]
        out[f"slo_rig_streams_{name}"] = leg["completed"]
    out["slo_rig_kills"] = legs["loss_on"]["kills"]
    out["slo_rig_recovery_s"] = legs["loss_on"]["recoveries_s"]
    finite = [r for r in legs["loss_on"]["recoveries_s"] if r is not None]
    out["slo_rig_recovery_max_s"] = max(finite) if finite else None
    out["slo_rig_adjustments_on"] = legs["loss_on"]["adjustments"]
    out["slo_rig_planner_forced_kills"] = (
        legs["noloss"]["forced_kills"]
        + legs["loss_on"]["forced_kills"]
    )
    out["slo_rig_dropped"] = sum(leg["dropped"] for leg in legs.values())
    out["slo_rig_retries"] = sum(leg["retries"] for leg in legs.values())
    out["slo_rig_identity_failures"] = sum(
        leg["identity_failures"] for leg in legs.values()
    )
    out["slo_rig_quarantined_peak"] = max(
        leg["quarantined"] for leg in legs.values()
    )
    out["slo_rig_final_workers_on"] = legs["loss_on"]["final_workers"]
    out["slo_rig_final_workers_off"] = legs["loss_off"]["final_workers"]
    out["slo_rig_attainment_gain"] = round(
        score(legs["loss_on"]) - score(legs["loss_off"]), 4
    )
    return out


async def run_prefix_economy(scale: str = "smoke") -> dict:
    """Fleet KV economy proof rig (ISSUE 20): cold-worker TTFT on a long
    shared prefix, three ways.

    A warm worker W serves the prefix, mirrors its host-tier evictions
    into a fleet G4 blob store, then churns until the prefix is fully
    off-device.  Two cold workers answer the same prompt: R recomputes
    the whole prefill; C fetches the prefix frames from the G4 store
    through the offload onboarding plane and prefills only the suffix.
    All three engines share one weight seed, so token identity across
    warm-local / recompute / G4-fetch is asserted outright -- greedy AND
    per-request-seeded sampling.

    The acceptance lines: ``prefix_econ_ttft_g4_fetch_ms`` strictly below
    ``prefix_econ_ttft_recompute_ms`` (the economy's premise), the fleet
    prefix hit rate, ``kv_g4_gbps`` from the transfer telemetry, and the
    router gate's decision evidence (both cost estimates, the JSONL row
    bench consumers scrape)."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
    from dynamo_tpu.llm.kv_router.indexer import REMOTE_SOURCE_ID
    from dynamo_tpu.llm.kv_router.router import KvPushRouter
    from dynamo_tpu.llm.prefix_onboard import PrefixOnboardEngine
    from dynamo_tpu.offload import InMemoryBlobStore
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.tokens.sequence import TokenBlockSequence

    shapes = {
        # CPU-sized smoke: a 64-block (256-token) shared prefix on a
        # 4-layer/128-hidden tiny variant -- deep enough that recomputing
        # the prefix prefill measurably loses to fetching its KV frames
        "smoke": dict(page=4, prefix_blocks=64, sfx=4, pages=160,
                      max_seq=320, max_tokens=6),
        # slow-lane shape: the bench model, 32-block (512-token) prefix
        "full": dict(page=16, prefix_blocks=32, sfx=16, pages=640,
                     max_seq=1024, max_tokens=16),
    }
    shp = shapes[scale]
    page, n_prefix, sfx = shp["page"], shp["prefix_blocks"], shp["sfx"]
    plen = n_prefix * page

    def mk_engine(host_blocks: int):
        if scale == "smoke":
            cfg = EngineConfig(
                max_batch_size=2,
                max_seq_len=shp["max_seq"],
                page_size=page,
                num_pages=shp["pages"],
                host_offload_blocks=host_blocks,
                seed=0,
            )
            model = ModelConfig.tiny(
                hidden_size=128,
                intermediate_size=256,
                num_layers=4,
                num_heads=8,
                num_kv_heads=4,
                max_position=1024,
            )
            return JaxEngine.random_init(model, cfg)
        return build_engine(
            max_batch_size=2,
            num_pages=shp["pages"],
            max_seq_len=shp["max_seq"],
            host_offload_blocks=host_blocks,
        )

    # deterministic token streams; co-prime strides keep block hashes
    # distinct across the prefixes, suffixes, warmups and churn prompts
    pfx = [(7 * i) % 197 + 1 for i in range(plen)]
    pfx2 = [(11 * i) % 193 + 1 for i in range(plen)]
    sfx_t = [(3 * i) % 50 + 20 for i in range(sfx)]
    sfx_b = [(5 * i) % 50 + 90 for i in range(sfx)]
    sfx_c = [(7 * i) % 50 + 150 for i in range(sfx)]
    warm0 = [(13 * i) % 191 + 1 for i in range(plen + sfx)]
    pstar = pfx + sfx_t

    async def run_one(engine, tokens, *, temperature=0.0, seed=None):
        """Returns (ttft_seconds, output_tokens) for one request."""
        r = PreprocessedRequest(
            token_ids=list(tokens),
            stop_conditions=StopConditions(max_tokens=shp["max_tokens"]),
            sampling_options=SamplingOptions(
                temperature=temperature, seed=seed
            ),
        )
        t0 = time.perf_counter()
        stream = await engine.generate(Context.new(r))
        ttft, out = None, []
        async for item in stream:
            data = item.data or {}
            toks = data.get("token_ids") or []
            if toks and ttft is None:
                ttft = time.perf_counter() - t0
            out.extend(toks)
        return ttft, out

    store = InMemoryBlobStore()

    # ---- W: the warm worker -- serves, measures warm-local, publishes ----
    w = mk_engine(host_blocks=4 * n_prefix)
    try:
        w.offload_engine.attach_remote(
            store, worker_id=1, namespace="bench", mirror=True
        )
        bs = w.sched.block_size
        pfx_hashes = TokenBlockSequence(pfx, block_size=bs).sequence_hashes()
        pfx2_hashes = TokenBlockSequence(pfx2, block_size=bs).sequence_hashes()
        await run_one(w, warm0)  # compile the prefill bucket + decode
        _, tok_warm = await run_one(w, pstar)
        # compile the cached-prefix suffix-prefill bucket off the clock
        await run_one(w, pfx + sfx_c)
        # warm-local TTFT: same prefix, different suffix, all blocks G1
        ttft_warm, _ = await run_one(w, pfx + sfx_b)
        _, stok_warm = await run_one(w, pstar, temperature=0.8, seed=7)
        await run_one(w, pfx2 + sfx_t)  # the fetch leg's warmup prefix
        pool = w.sched.pool
        remote = w.offload_engine.remote
        all_hashes = [*pfx_hashes, *pfx2_hashes]
        for i in range(32):
            w.offload_engine.drain()
            resident = sum(1 for h in all_hashes if pool.is_registered(h))
            if resident == 0 and all(remote.contains(h) for h in all_hashes):
                break
            churn = [
                (29 * j + 37 * i) % 180 + 1 for j in range(plen + sfx)
            ]
            await run_one(w, churn)
        w.offload_engine.drain()
        published = sum(1 for h in pfx_hashes if remote.contains(h))
        g4_bytes = sum(
            len(store.get(f"kv/bench/{h & (2**64 - 1):016x}") or b"")
            for h in pfx_hashes
        )
    finally:
        await w.stop()

    # ---- R: cold recompute -- no shared blocks, full prefill ----
    r_eng = mk_engine(host_blocks=0)
    try:
        await run_one(r_eng, warm0)  # compile: same bucket, no shared prefix
        ttft_rec, tok_rec = await run_one(r_eng, pstar)
        _, stok_rec = await run_one(r_eng, pstar, temperature=0.8, seed=7)
    finally:
        await r_eng.stop()

    # ---- C: cold fetch -- G4 frames through the onboarding plane ----
    c = mk_engine(host_blocks=4 * n_prefix)
    try:
        c_remote = c.offload_engine.attach_remote(
            store, worker_id=2, namespace="bench", mirror=False
        )
        onboarder = PrefixOnboardEngine.__new__(PrefixOnboardEngine)
        onboarder.inner = c
        onboarder.engine = c
        onboarder.onboarded_blocks = 0
        onboarder.failed_fetches = 0
        await run_one(c, warm0)  # compile the prefill bucket + decode
        # warm the fetch+scatter+suffix-prefill paths on the OTHER prefix
        await onboarder._onboard_remote([int(h) for h in pfx2_hashes])
        await run_one(c, pfx2 + sfx_t)
        # the gate's verdict for this donor, priced with the real bytes
        gate = KvPushRouter(
            None,
            c.sched,  # duck-typed: the gate only reads .block_size
            remote_spec={"prefill_tok_s": 2000.0, "gbps": 1.0},
        )
        gate_row = gate._gate_donor(
            "bench-prefix-economy",
            2,
            0,
            {
                "instance": REMOTE_SOURCE_ID,
                "blocks": n_prefix,
                "source": "remote",
                "nbytes": g4_bytes,
            },
        )
        # measured leg: TTFT includes the G4 fetch + host put + the
        # suffix-only prefill -- exactly what a routed request pays
        t0 = time.perf_counter()
        await onboarder._onboard_remote([int(h) for h in pfx_hashes])
        onboard_s = time.perf_counter() - t0
        gen_ttft, tok_fetch = await run_one(c, pstar)
        ttft_fetch = onboard_s + (gen_ttft or 0.0)
        _, stok_fetch = await run_one(c, pstar, temperature=0.8, seed=7)
        fetch_stats = dict(c_remote.stats())
    finally:
        await c.stop()

    fetched = int(onboarder.onboarded_blocks)
    return {
        "prefix_econ_scale": scale,
        "prefix_econ_prefix_tokens": plen,
        "prefix_econ_ttft_warm_local_ms": round(ttft_warm * 1e3, 2),
        "prefix_econ_ttft_recompute_ms": round(ttft_rec * 1e3, 2),
        "prefix_econ_ttft_g4_fetch_ms": round(ttft_fetch * 1e3, 2),
        "prefix_econ_g4_onboard_ms": round(onboard_s * 1e3, 2),
        "prefix_econ_published_blocks": published,
        "prefix_econ_fetched_blocks": fetched,
        # both onboard passes (warmup prefix + measured prefix) count:
        # every block the fleet needed that G4 actually delivered
        "prefix_econ_fleet_prefix_hit_rate": round(
            fetched / (2 * n_prefix), 3
        ),
        "prefix_econ_failed_fetches": int(onboarder.failed_fetches),
        "prefix_econ_g4_bytes": g4_bytes,
        "prefix_econ_kv_g4_gbps": fetch_stats.get("kv_g4_gbps"),
        "prefix_econ_token_identity_greedy": (
            tok_fetch == tok_rec == tok_warm
        ),
        "prefix_econ_token_identity_seeded": (
            stok_fetch == stok_rec == stok_warm
        ),
        "prefix_econ_gate_decision": gate_row["decision"],
        "prefix_econ_gate_source": gate_row["source"],
        "prefix_econ_gate_pred_fetch_ms": gate_row["pred_fetch_ms"],
        "prefix_econ_gate_pred_prefill_ms": gate_row["pred_prefill_ms"],
        "prefix_econ_gate_ship_bytes": gate_row["ship_bytes"],
    }


async def run_decode_sweep(rs) -> dict:
    """Decode throughput at larger batches on a 64-lane engine (the bs=8
    headline engine stays separate for round-over-round comparability).

    ``decode_tok_s_bsN`` keeps the historical whole-request methodology
    (cold prefill + decode in one window).  ``decode_marginal_tok_s_bs64``
    isolates the pure decode rate by differencing two output lengths on
    identical admission patterns -- prefill, admission, and stream-plumbing
    costs cancel, leaving tokens/second of steady-state decode (the number
    the north-star output-throughput target actually depends on)."""
    from dynamo_tpu.engine.weights import param_bytes

    # grow_chunk_pages=16: one growth event covers a whole request's decode
    # instead of re-putting the page table every block (the pool has slack
    # for it: 64 lanes x 20 pages + chunk < 1536)
    engine = build_engine(max_batch_size=64, num_pages=1536, grow_chunk_pages=16)
    out = {}
    try:
        for bs in (32, 64):
            prompts = [rs.randint(1, 30000, (128,)).tolist() for _ in range(bs)]
            await run_batch(engine, prompts, max_tokens=8)  # compile/warm
            prompts = [rs.randint(1, 30000, (128,)).tolist() for _ in range(bs)]
            t0 = time.monotonic()
            total = await run_batch(engine, prompts, max_tokens=128)
            elapsed = time.monotonic() - t0
            tok_s = total / elapsed
            pbytes = param_bytes(engine.params)
            steps_s = (total / bs) / elapsed
            kv_per_step = (
                bs * 320 * engine.kv.bytes_per_page // engine.kv.page_size
            )
            out[f"decode_tok_s_bs{bs}"] = round(tok_s, 2)
            out[f"est_hbm_util_bs{bs}"] = round(
                (pbytes + kv_per_step) * steps_s / hbm_bytes_per_s(), 4
            )
        # marginal decode at bs64: diff mt=192 vs mt=64 runs (fresh prompts
        # each pass so every pass pays the same cold prefill, which the
        # difference cancels).  Drift-robust measurement (VERDICT r5 #2):
        # the compared legs interleave A/B/A/B inside ONE window -- each
        # pair's legs see the same ambient host load, so the pairwise
        # difference cancels drift that best-of-2-per-leg accumulates.
        # The best pairwise marginal is the recorded value.
        bs = 64
        mk = lambda: [rs.randint(1, 30000, (128,)).tolist() for _ in range(bs)]
        await run_batch(engine, mk(), max_tokens=192)  # compile long shapes
        pairs = []
        for _ in range(2):
            pair = []
            for mt in (64, 192):
                t0 = time.monotonic()
                await run_batch(engine, mk(), max_tokens=mt)
                pair.append(time.monotonic() - t0)
            pairs.append(tuple(pair))
        d_tok = bs * (192 - 64)
        deltas = [b - a for a, b in pairs if b - a > 0]
        if deltas:
            d_el = min(deltas)  # the quietest interleaved pair
            marginal = d_tok / d_el
            pbytes = param_bytes(engine.params)
            steps_s = (192 - 64) / d_el
            kv_per_step = (
                bs * 320 * engine.kv.bytes_per_page // engine.kv.page_size
            )
            out["decode_marginal_tok_s_bs64"] = round(marginal, 2)
            out["est_hbm_util_marginal_bs64"] = round(
                (pbytes + kv_per_step) * steps_s / hbm_bytes_per_s(), 4
            )
        else:
            # host-load drift inverted every pair: a difference metric from
            # them would be garbage; record the invalidity explicitly
            out["decode_marginal_tok_s_bs64"] = None
    finally:
        await engine.stop()
    return out


async def run_mem_pressure(rs) -> dict:
    """Memory-pressure scenario: an undersized page pool forces constant
    capacity preemption, measured twice -- once with swap-based preemption
    (KV offloaded and restored through the chunked scatter path) and once
    with classic recompute (full re-prefill of the folded prompt).

    The headline pair is the *resume rate*: KV tokens recovered per second
    the preempted lane spent not-runnable.  Swap pays a D2H+H2D move
    (``kv_onboard_gbps``); recompute pays a full prefill of the same
    tokens -- the gap is the scenario's whole point.  ``*_run_tok_s`` are
    the end-to-end throughputs of the identical workload under each mode,
    and a final warm re-run reports the tiered prefix-hit counters (the
    churn's evictions land in G2 and serve the repeat prompts)."""
    out = {}
    bs, isl, osl = 8, 128, 256
    run_tok_s = {}
    for mode in ("swap", "recompute"):
        # each lane wants (128+256)/16 = 24 pages; 8 lanes want 192 against
        # 144 usable -> every request gets preempted at least once
        engine = build_engine(
            max_batch_size=bs,
            num_pages=145,
            decode_block=16,
            max_seq_len=512,
            host_offload_blocks=(256 if mode == "swap" else 0),
            swap_preemption=(mode == "swap"),
        )
        try:
            mk = lambda: [
                rs.randint(1, 30000, (isl,)).tolist() for _ in range(bs)
            ]
            # warm pass at full osl so the preemption/resume paths compile
            # outside the measured window
            await run_batch(engine, mk(), max_tokens=osl)
            measured = mk()
            t0 = time.monotonic()
            total = await run_batch(engine, measured, max_tokens=osl)
            elapsed = time.monotonic() - t0
            run_tok_s[mode] = total / elapsed
            sched = engine.sched
            tok_bytes = engine.kv.bytes_per_page / engine.kv.page_size
            if mode == "swap":
                assert sched.preempt_swap > 0, "swap preemption not exercised"
                stats = engine.offload_engine.stats()
                swap_det = stats["onboard_detail"].get("swap") or {}
                sec = swap_det.get("seconds") or 0.0
                toks = (swap_det.get("bytes") or 0) / tok_bytes
                out["preempt_resume_tok_s"] = (
                    round(toks / sec, 1) if sec > 0 else None
                )
                out["kv_onboard_gbps"] = stats.get("onboard_gbps")
                out["preempt_swap_count"] = sched.preempt_swap
                # warm re-run: the churn's evictions are parked in G2, so
                # the measured prompts' prefixes now onboard from the host
                # tier instead of re-prefilling
                engine.offload_engine.drain()
                await run_batch(engine, measured[:2], max_tokens=8)
                out["kv_tier_prefix_hits"] = sum(
                    engine.offload_engine.tier_hits.values()
                )
            else:
                assert sched.preempt_recompute > 0, (
                    "recompute preemption not exercised"
                )
                sec = engine.resume_prefill_seconds
                out["preempt_resume_tok_s_recompute"] = (
                    round(engine.resume_prefill_tokens / sec, 1)
                    if sec > 0
                    else None
                )
        finally:
            await engine.stop()
    out["preempt_run_tok_s_swap"] = round(run_tok_s["swap"], 2)
    out["preempt_run_tok_s_recompute"] = round(run_tok_s["recompute"], 2)
    a, b = out.get("preempt_resume_tok_s"), out.get(
        "preempt_resume_tok_s_recompute"
    )
    out["preempt_swap_speedup"] = round(a / b, 2) if a and b else None
    return out


async def run_spec(rs, build=build_engine, bs: int = 8, osl: int = 64) -> dict:
    """Speculative-decoding scenario: the same workload measured with
    per-request n-gram/prompt-lookup drafting on and off.

    Prompts are repetitive (a tiled token pattern) so prompt-lookup has
    continuations to propose; greedy decode from random weights also
    settles into token cycles the drafter picks up.  Reported numbers:
    ``spec_accept_rate`` (accepted/drafted over the measured pass),
    ``spec_tok_s`` vs ``spec_base_tok_s`` (effective output tok/s with
    speculation on vs off -- the ISSUE's headline pair), drafted tokens
    per request, and the verify-dispatch count.  Acceptance is
    workload-dependent: the scenario tracks the machinery's throughput
    conversion, not a quality claim."""
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        SpeculationOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    def mk_prompts():
        # per-lane tiled pattern: repetition inside one prompt (lookup
        # fodder), distinct across lanes and passes (no prefix-cache help)
        out = []
        for _ in range(bs):
            pat = rs.randint(1, 30000, (16,)).tolist()
            out.append((pat * 8)[:128])
        return out

    async def run_mode(engine, prompts, spec_on):
        async def one(p):
            req = PreprocessedRequest(
                token_ids=p,
                stop_conditions=StopConditions(max_tokens=osl, ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0),
                speculation=(
                    SpeculationOptions(enabled=True, num_draft_tokens=4)
                    if spec_on
                    else None
                ),
            )
            stream = await engine.generate(Context.new(req))
            n = 0
            async for item in stream:
                data = item.data or {}
                n += len(data.get("token_ids") or [])
            return n

        results = await asyncio.gather(*[one(p) for p in prompts])
        return sum(results)

    out = {}
    tok_s = {}
    disp_s = {}
    # folded-vs-post-commit A/B (ISSUE 15): the same spec workload on the
    # default engine (verify columns folded into the packed unified
    # dispatch) and on the two-dispatch fallback.  ``*_dispatches_s`` is
    # the per-leg device-launch rate -- the folded leg's headline is
    # fewer dispatches for the same committed tokens.
    legs = (
        ("base", dict(), False),
        ("spec", dict(), True),  # folded (the default)
        ("spec_postcommit", dict(fold_spec_verify=False), True),
    )
    for name, cfg_extra, spec_on in legs:
        engine = build(decode_block=16, **cfg_extra)
        try:
            await run_mode(engine, mk_prompts(), spec_on)  # warm/compile
            measured = mk_prompts()
            d0, a0 = engine.spec_drafted, engine.spec_accepted
            v0 = engine.spec_verify_steps
            s0 = engine._steps
            t0 = time.monotonic()
            total = await run_mode(engine, measured, spec_on)
            elapsed = time.monotonic() - t0
            tok_s[name] = total / elapsed
            disp_s[name] = (engine._steps - s0) / elapsed
            if spec_on:
                drafted = engine.spec_drafted - d0
                accepted = engine.spec_accepted - a0
                assert drafted > 0, "speculation not exercised"
                if name == "spec":
                    assert engine._fold_spec, "fold must be the default"
                    out["spec_accept_rate"] = round(accepted / drafted, 4)
                    out["spec_drafted_per_req"] = round(drafted / bs, 1)
                    out["spec_verify_steps"] = engine.spec_verify_steps - v0
                    out["spec_enabled_frac"] = round(
                        engine.spec_enabled_frac, 4
                    )
        finally:
            await engine.stop()
            del engine
    out["spec_tok_s"] = round(tok_s["spec"], 2)
    out["spec_base_tok_s"] = round(tok_s["base"], 2)
    out["spec_speedup"] = round(tok_s["spec"] / tok_s["base"], 3)
    out["spec_postcommit_tok_s"] = round(tok_s["spec_postcommit"], 2)
    out["spec_fold_speedup"] = round(
        tok_s["spec"] / tok_s["spec_postcommit"], 3
    )
    out["spec_dispatches_s"] = round(disp_s["spec"], 2)
    out["spec_postcommit_dispatches_s"] = round(disp_s["spec_postcommit"], 2)
    return out


async def run_prefill_under_decode_load(rs, build=build_engine) -> dict:
    """Mixed-batching scenario (ISSUE 7): a steady bs8 decode batch with a
    prefill arrival stream riding on top.

    Three measured passes: (a) pure decode, no arrivals -- the ITL floor;
    (b) decode + arrivals with mixed batching ON (arrivals pack into the
    decode tick as ragged chunks of the unified dispatch); (c) the same
    with mixed batching OFF (arrivals run as dedicated prefill dispatches
    that stall the decode batch).  A fourth leg measures the dedicated
    prefill path alone so prefill throughput under decode load has its
    denominator.  Reported: ``pfload_itl_p99_ms_*`` (per-token arrival-gap
    p99 over the decode lanes, per mode), ``pfload_prefill_tok_s`` vs
    ``pfload_prefill_dedicated_tok_s``, and ``mixed_dispatch_ratio`` =
    dispatches_s / decode_steps_s in the mixed window (~1 when every tick
    is one unified dispatch; BENCH_r05's separate-dispatch engine sat at
    ~1/32)."""
    import numpy as np

    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    bs, osl = 8, 48
    pf_len, n_pf = 256, 6  # n_pf: dedicated-leg request count

    def _req(tokens, max_tokens, ignore_eos=True):
        return PreprocessedRequest(
            token_ids=tokens,
            stop_conditions=StopConditions(
                max_tokens=max_tokens, ignore_eos=ignore_eos
            ),
            sampling_options=SamplingOptions(temperature=0.0),
        )

    async def decode_lane(engine, prompt):
        # (arrival time, tokens in the commit event): the legs deliver
        # tokens in different event sizes (decode_block=4 commits 4 at a
        # time, the unified dispatch 1), so per-token ITL must amortize
        # each event gap over its tokens -- duplicating one stamp per
        # token would dilute the blocked legs' p99 with zero gaps
        stream = await engine.generate(Context.new(_req(prompt, osl)))
        events = []
        async for item in stream:
            data = item.data or {}
            n = len(data.get("token_ids") or [])
            if n:
                events.append((time.monotonic(), n))
        return events

    async def prefill_one(engine, prompt):
        stream = await engine.generate(Context.new(_req(prompt, 1)))
        async for _item in stream:
            pass

    async def run_mode(mixed, arrivals):
        # slots beyond the decode batch so arrivals admit immediately
        engine = build(
            max_batch_size=16, num_pages=1024, decode_block=4,
            mixed_batching=mixed,
        )
        try:
            # warm/compile the decode path and the arrival shapes at load
            # concurrency (4-wide bursts group-batch into a different
            # executable than a lone prefill)
            await asyncio.gather(
                *[
                    decode_lane(engine, rs.randint(1, 30000, (48,)).tolist())
                    for _ in range(bs)
                ],
                *[
                    prefill_one(
                        engine, rs.randint(1, 30000, (pf_len,)).tolist()
                    )
                    for _ in range(4)
                ],
            )
            d_prompts = [
                rs.randint(1, 30000, (48,)).tolist() for _ in range(bs)
            ]
            steps0 = engine._steps
            t0 = time.monotonic()
            lanes = [
                asyncio.ensure_future(decode_lane(engine, p))
                for p in d_prompts
            ]
            # dispatch count at decode-window close: the post-window drain
            # of in-flight arrivals must not pollute the ratio's numerator
            steps_at_close = None

            async def arrival_stream():
                # saturating prefill pressure for the whole decode window
                # (four in flight), so the mixed engine packs chunks into
                # every tick and the ratio measures the steady state
                nonlocal steps_at_close
                done_tokens = 0
                pt0 = time.monotonic()

                async def one():
                    nonlocal done_tokens
                    await prefill_one(
                        engine, rs.randint(1, 30000, (pf_len,)).tolist()
                    )
                    done_tokens += pf_len

                inflight = {asyncio.ensure_future(one()) for _ in range(4)}
                while not all(l.done() for l in lanes):
                    fin, inflight = await asyncio.wait(
                        inflight, return_when=asyncio.FIRST_COMPLETED
                    )
                    for f in fin:
                        f.result()
                    while len(inflight) < 4:
                        inflight.add(asyncio.ensure_future(one()))
                window = time.monotonic() - pt0
                steps_at_close = engine._steps
                tokens_at_close = done_tokens
                if inflight:
                    await asyncio.gather(*inflight)
                return tokens_at_close / window

            pf_tok_s = await arrival_stream() if arrivals else None
            lane_events = await asyncio.gather(*lanes)
            elapsed = time.monotonic() - t0
            dispatches = (
                steps_at_close if steps_at_close is not None
                else engine._steps
            ) - steps0
            gaps = [
                (tb - ta) * 1000.0 / nb
                for ev in lane_events
                for (ta, _na), (tb, nb) in zip(ev, ev[1:])
                for _ in range(nb)
            ]
            itl_p99 = float(np.percentile(gaps, 99)) if gaps else 0.0
            n_tokens = sum(n for ev in lane_events for _t, n in ev)
            decode_steps_s = n_tokens / bs / elapsed
            return itl_p99, pf_tok_s, dispatches / elapsed / decode_steps_s
        finally:
            await engine.stop()

    itl_idle, _, _ = await run_mode(mixed=True, arrivals=False)
    itl_on, pf_on_tok_s, ratio = await run_mode(mixed=True, arrivals=True)
    itl_off, pf_off_tok_s, _ = await run_mode(mixed=False, arrivals=True)

    # dedicated-prefill denominator: the arrival stream alone, no decode,
    # at the SAME concurrency (4 in flight) as the load legs -- the classic
    # engine batches concurrent same-shape prefills into group dispatches,
    # so a sequential leg would understate the path and mask regressions
    engine = build(max_batch_size=16, num_pages=1024, decode_block=4,
                   mixed_batching=False)
    try:
        # warm the burst shape AND the lone shape: a 4-wide burst
        # compiles the grouped prefill executable, a straggler admitted
        # on its own tick the single-prompt one
        await asyncio.gather(
            *[
                prefill_one(engine, rs.randint(1, 30000, (pf_len,)).tolist())
                for _ in range(4)
            ]
        )
        await prefill_one(engine, rs.randint(1, 30000, (pf_len,)).tolist())
        t0 = time.monotonic()
        done = 0
        while done < n_pf:
            burst = min(4, n_pf - done)
            await asyncio.gather(
                *[
                    prefill_one(
                        engine, rs.randint(1, 30000, (pf_len,)).tolist()
                    )
                    for _ in range(burst)
                ]
            )
            done += burst
        pf_dedicated_tok_s = done * pf_len / (time.monotonic() - t0)
    finally:
        await engine.stop()

    return {
        "pfload_itl_p99_ms_idle": round(itl_idle, 2),
        "pfload_itl_p99_ms_mixed_on": round(itl_on, 2),
        "pfload_itl_p99_ms_mixed_off": round(itl_off, 2),
        "pfload_prefill_tok_s": round(pf_on_tok_s, 1),
        "pfload_prefill_off_tok_s": round(pf_off_tok_s, 1),
        "pfload_prefill_dedicated_tok_s": round(pf_dedicated_tok_s, 1),
        "mixed_dispatch_ratio": round(ratio, 3),
    }


def _tp_scaling_model():
    """Small llama-shaped config whose 8 kv heads shard at every
    measured tp degree (ROADMAP S1 replaces it with a benchmark cell)."""
    from dynamo_tpu.engine import ModelConfig

    return ModelConfig(
        vocab_size=2048,
        hidden_size=256,
        intermediate_size=512,
        num_layers=4,
        num_heads=8,
        num_kv_heads=8,
        head_dim=32,
        rope_theta=10000.0,
        max_position=256,
        dtype="float32",
    )


async def _tp_scaling_impl(degrees=(1, 2, 4, 8)) -> dict:
    """tok/s/chip of the SERVED engine path at each tensor-parallel
    degree: one engine per tp, same workload, same seed, on the devices
    this process sees."""
    import os

    import numpy as np

    from dynamo_tpu.engine import EngineConfig, JaxEngine

    # ambient DYN_TP/DYN_DP would win over every leg's EngineConfig.tp
    # (env-over-config is the serving contract) and silently re-degree
    # the whole sweep -- the measurement owns its parallelism.  Saved and
    # restored: this runs inside the main bench process, and scenarios
    # after the sweep must see the operator's environment unchanged.
    saved = {k: os.environ.pop(k, None) for k in ("DYN_TP", "DYN_DP")}
    model = _tp_scaling_model()
    rs = np.random.RandomState(0)
    bs, isl, osl = 8, 32, 32
    out = {}
    try:
        for tp in degrees:
            engine = JaxEngine.random_init(
                model,
                EngineConfig(
                    max_batch_size=bs, max_seq_len=128, page_size=16,
                    num_pages=64, decode_block_size=16, tp=tp, seed=0,
                ),
            )
            try:
                mk = lambda: [
                    rs.randint(1, 2000, (isl,)).tolist() for _ in range(bs)
                ]
                await run_batch(engine, mk(), max_tokens=osl)  # compile/warm
                t0 = time.monotonic()
                total = await run_batch(engine, mk(), max_tokens=osl)
                elapsed = time.monotonic() - t0
                out[f"tp{tp}_tok_s_per_chip"] = round(
                    total / elapsed / tp, 2
                )
                if tp > 1:
                    spec = engine.kv.pages.sharding.spec
                    assert "tp" in [ax for ax in spec if ax], (
                        f"tp={tp} KV pool not sharded: {spec}"
                    )
            finally:
                await engine.stop()
    finally:
        for k, v in saved.items():
            if v is not None:
                os.environ[k] = v
    return out


async def run_tp_scaling() -> dict:
    """Tensor-parallel scaling scenario (ROADMAP item 1): tok/s/chip of
    the served engine at every tp in {1, 2, 4, 8} the attached chips
    allow, in this process, on the chips.  It measures a device, so it
    runs only where JAX sees TPUs: off the chip it raises (the CPU twin
    of this path is tier-1's tests/test_tp_serving.py, which reports no
    speed), and a failing leg raises too -- neither may leave a number or
    a string under ``tp*_tok_s_per_chip``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            "run_tp_scaling measures tok/s per chip and found no TPU "
            f"(devices: {devices[0].platform} x{len(devices)})"
        )
    out = await _tp_scaling_impl(
        tuple(tp for tp in (1, 2, 4, 8) if tp <= len(devices))
    )
    out["tp_scaling_devices"] = f"{devices[0].device_kind} x{len(devices)}"
    return out


def _long_context_model(max_len: int):
    """Small llama-shaped config for the long-context scenario: the
    numbers this scenario tracks are SCHEDULING numbers (TTFT under
    admission pressure, padded-token fractions, prefetch overlap), so
    the trunk stays small enough that a 128k-token prefill is dominated
    by the machinery being measured, not by model width."""
    from dynamo_tpu.engine import ModelConfig

    return ModelConfig(
        vocab_size=2048,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=32,
        rope_theta=1e6,
        max_position=max_len,
        dtype="float32",
    )


async def run_long_context(
    rs,
    lengths=(1024, 32768, 131072),
    counts=(8, 4, 2),
    osl: int = 8,
) -> dict:
    """Long-context scenario (ISSUE 10 / ROADMAP item 5): a mixed
    1k/32k/128k prompt workload through the long-context fast path --
    KV-budget admission, fully-packed ragged prefill, and
    prefetch-overlapped onboarding -- reporting the numbers that path
    exists to move.

    Legs:

    * **cold mix** -- all classes submitted together against a pool that
      holds ~1.5 long requests, budget admission on: TTFT p50 per length
      class, preemption counts by kind, admission skip/block counters,
      and the packed step's padded-token fraction (from the run's
      per-dispatch accounting).
    * **warm prefix, prefetch off vs on** -- the long prompts re-run
      after pool churn demoted their prefix chains to the host/disk
      tiers.  With prefetch off, the admission-time tier lookup misses
      disk-resident blocks and the prefix recomputes; with the
      queue-position prefetch on, the disk->host walk overlaps queue
      wait and admission onboards from RAM.  The TTFT gap is the
      tentpole's headline; ``lctx_prefetch_overlap_ratio`` reports how
      much of the walk actually hid behind queue wait.

    ``lengths`` scales the scenario: the CPU smoke (tests) runs a
    shortened ladder through the identical machinery; the TPU bench
    runs the full 1k/32k/128k.
    """
    import os
    import tempfile

    import numpy as np

    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    page = 16
    block = 64  # router-style coarse blocks: 4 pages per offload blob
    max_len = lengths[-1] + 4 * osl + page
    long_pages = -(-(lengths[-1] + osl) // page)
    long_blocks = -(-long_pages * page // block)
    num_pages = int(1.5 * long_pages) + 16 * len(lengths) + 64
    chunk = min(512, max(64, lengths[0] // 2))
    vocab = 2048

    def mk_prompt(L):
        return rs.randint(1, vocab - 1, (int(L),)).tolist()

    def req(tokens, max_tokens=osl):
        return PreprocessedRequest(
            token_ids=tokens,
            stop_conditions=StopConditions(
                max_tokens=max_tokens, ignore_eos=True
            ),
            sampling_options=SamplingOptions(temperature=0.0),
        )

    async def one_ttft(engine, tokens, max_tokens=osl):
        """(ttft_seconds, total_tokens) for one request."""
        t0 = time.monotonic()
        stream = await engine.generate(Context.new(req(tokens, max_tokens)))
        ttft = None
        n = 0
        async for item in stream:
            data = item.data or {}
            got = len(data.get("token_ids") or [])
            if got and ttft is None:
                ttft = time.monotonic() - t0
            n += got
        return (ttft if ttft is not None else time.monotonic() - t0), n

    out = {"lctx_lengths": list(lengths)}
    with tempfile.TemporaryDirectory() as td:
        engine = JaxEngine.random_init(
            _long_context_model(max_len + page),
            EngineConfig(
                max_batch_size=8,
                max_seq_len=max_len,
                page_size=page,
                block_size=block,
                num_pages=num_pages,
                decode_block_size=8,
                prefill_chunk_tokens=chunk,
                mixed_token_budget=chunk,
                # the fast path under measurement
                kv_admit_budget="on",
                # the ring holds ONE long chain with slack; churn volume
                # (> ring) pushes resident chains to the disk tier, which
                # is exactly the state the prefetch legs contrast: off =
                # disk miss at admission -> recompute, on = chain
                # promoted to RAM during queue wait -> onboard scatter
                host_offload_blocks=long_blocks + 32,
                disk_offload_blocks=8 * long_blocks + 256,
                disk_offload_dir=os.path.join(td, "g3"),
                seed=0,
            ),
        )
        try:
            sched = engine.sched
            # warm/compile the chunk shapes AND the mixed compositions
            # outside the measured windows (two concurrent requests per
            # class so multi-lane packed shapes compile too; fresh token
            # ids: the measured pass must not prefix-hit the warmup's
            # registrations)
            await asyncio.gather(
                *[
                    one_ttft(engine, mk_prompt(L), 2)
                    for L in lengths
                    for _ in range(2)
                ]
            )
            # -- cold mix ------------------------------------------------
            used0 = engine.mixed_used_tokens
            disp0 = engine.mixed_dispatched_tokens
            classes = []  # (class_idx, prompt)
            for i, (L, n) in enumerate(zip(lengths, counts)):
                classes += [(i, mk_prompt(L)) for _ in range(n)]
            # round-robin interleave so long prompts contend with short
            # traffic from the first tick (the starvation shape the
            # budget admission exists for)
            classes.sort(key=lambda t: t[0])
            interleaved = []
            by_cls = [
                [p for c, p in classes if c == i] for i in range(len(lengths))
            ]
            while any(by_cls):
                for lane in by_cls:
                    if lane:
                        interleaved.append(lane.pop(0))
            results = await asyncio.gather(
                *[one_ttft(engine, p) for p in interleaved]
            )
            # results align with interleaved order; re-derive the class
            # of each from its prompt length
            per_class = {i: [] for i in range(len(lengths))}
            for (ttft, _n), p in zip(results, interleaved):
                per_class[lengths.index(len(p))].append(ttft * 1000.0)
            # per-bucket SLO attainment (runtime/slo.py): the DYN_SLO ttft
            # target if armed, else a ladder default -- the number the
            # SLO-loop planner work (ROADMAP item 1) scales against
            from dynamo_tpu.runtime import slo as _slo

            slo_spec = os.environ.get("DYN_SLO", "")
            try:
                ttft_target = _slo.parse_slo_spec(slo_spec)[0].get("ttft")
            except _slo.SloSpecError:
                ttft_target = None
            if ttft_target is None:
                ttft_target = 2.0  # seconds; CPU-smoke-realistic default
            out["lctx_slo_ttft_target_ms"] = round(ttft_target * 1e3, 1)
            names = ["short", "mid", "long"][: len(lengths)]
            for i, name in enumerate(names):
                vals = per_class[i]
                out[f"lctx_ttft_p50_ms_{name}"] = round(
                    float(np.percentile(vals, 50)), 1
                )
                out[f"lctx_ttft_p95_ms_{name}"] = round(
                    float(np.percentile(vals, 95)), 1
                )
                att = _slo.attainment_of(
                    [v / 1e3 for v in vals], ttft_target
                )
                out[f"lctx_slo_ttft_attainment_{name}"] = (
                    round(att, 4) if att is not None else None
                )
            used = engine.mixed_used_tokens - used0
            disp = engine.mixed_dispatched_tokens - disp0
            out["lctx_padded_frac_packed"] = (
                round(1.0 - used / disp, 4) if disp else None
            )
            out["lctx_preempt_swap"] = sched.preempt_swap
            out["lctx_preempt_recompute"] = sched.preempt_recompute
            out["lctx_admit_skips"] = sched.admit_skips
            out["lctx_admit_blocked"] = sched.admit_blocked

            # -- warm prefix: prefetch off vs on -------------------------
            long_prompts = [p for p in interleaved if len(p) == lengths[-1]]

            async def churn():
                # cycle the pool so the long chains' G1 blocks evict
                # through the offload cascade (host ring overflows to
                # disk); fresh token ids so churn itself never hits
                need = num_pages * page
                fill = min(max_len - 2 * page, 4096)
                reqs = [
                    one_ttft(engine, mk_prompt(fill), 1)
                    for _ in range(-(-need // fill))
                ]
                await asyncio.gather(*reqs)
                engine.offload_engine.drain()

            warm = {}
            for mode, window in (("off", 0), ("on", 32)):
                await churn()
                # the prefetch window is an engine-construction knob;
                # the scenario flips the resolved value between legs so
                # both run against the SAME tier state
                engine._prefetch_window = window
                ttfts = await asyncio.gather(
                    *[one_ttft(engine, p) for p in long_prompts]
                )
                warm[mode] = float(
                    np.percentile([t * 1000.0 for t, _n in ttfts], 50)
                )
                out[f"lctx_warm_long_ttft_ms_prefetch_{mode}"] = round(
                    warm[mode], 1
                )
            stats = engine.offload_engine.stats()
            out["lctx_prefetch_hits"] = stats.get("prefetch_hits", 0)
            out["lctx_prefetch_overlap_ratio"] = stats.get(
                "prefetch_overlap_ratio"
            )
            out["lctx_prefetch_wasted_bytes"] = stats.get(
                "prefetch_wasted_bytes", 0
            )
        finally:
            await engine.stop()
    return out


async def best_of(n: int, run):
    """Best of ``n`` timed passes of ``run()`` (fresh-args coroutine
    factory): host-clock timings drift with ambient load on a machine
    that shares its CPU cores, and ROADMAP S1 replaces this with medians.
    Returns ``(result_of_best_pass, best_elapsed_s)``."""
    best = None
    for _ in range(n):
        t0 = time.monotonic()
        result = await run()
        elapsed = time.monotonic() - t0
        if best is None or elapsed < best[1]:
            best = (result, elapsed)
    return best


async def main():
    import numpy as np

    from dynamo_tpu.engine.weights import param_bytes

    engine = build_engine()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, 30000, (128,)).tolist() for _ in range(8)]

    # warmup: compiles prefill bucket + decode + sampler.  Two passes: the
    # first runs cache-cold (full-prefill path), the second hits the prefix
    # cache the first pass registered and compiles the suffix-prefill path.
    # Both passes land in the 16-page decode bucket (prompt 128 + budget 128
    # = 256 tokens exactly; page growth is capped at the useful total), the
    # same bucket the measured run lives in -- the measured window contains
    # zero XLA compiles.
    await run_batch(engine, prompts, max_tokens=8)
    await run_batch(engine, prompts, max_tokens=8)

    async def _headline_pass():
        steps0 = engine._steps
        total = await run_batch(engine, prompts, max_tokens=128)
        return total, engine._steps - steps0

    (total, steps), elapsed = await best_of(2, _headline_pass)

    tok_s = total / elapsed
    steps_s = steps / elapsed
    # each decode step streams ~all weights once (batch small) plus the
    # batch's KV reads; utilization vs the attached device's HBM peak
    pbytes = param_bytes(engine.params)
    kv_bytes_per_step = 8 * 320 * engine.kv.bytes_per_page // engine.kv.page_size
    decode_steps_s = (total / 8) / elapsed  # token rows per lane per second
    hbm_bw = (pbytes + kv_bytes_per_step) * decode_steps_s
    util = hbm_bw / hbm_bytes_per_s()
    kv_pool_gb = round(engine.kv.pool_bytes / 1e9, 4)
    kv_dtype = str(engine.kv.dtype)
    await engine.stop()
    del engine

    # weight-only int8: the HBM-stream lever (engine/quant.py; interleaved
    # A/B measured +26-57% decode over bf16 on this chip).  Methodology
    # mirrors the bf16 headline exactly -- same prompts re-measured (warm
    # prefix cache, decode-dominated window), best of two passes -- so the
    # two numbers are directly comparable.
    q_engine = build_engine(quantize="int8")
    q_prompts = [rs.randint(1, 30000, (128,)).tolist() for _ in range(8)]
    await run_batch(q_engine, q_prompts, max_tokens=8)
    await run_batch(q_engine, q_prompts, max_tokens=8)
    q_total, q_elapsed = await best_of(
        2, lambda: run_batch(q_engine, q_prompts, max_tokens=128)
    )
    int8_tok_s = q_total / q_elapsed
    await q_engine.stop()
    del q_engine

    # int8-quantized paged KV pool (ISSUE 13): identical A/B methodology.
    # The pool is the HBM ceiling at large batch (bs64 est_hbm_util 0.28
    # in r05), so the headline here is the FOOTPRINT pair (kv_pool_gb at
    # each dtype -- freed bytes = resident batch/context headroom) next
    # to a decode line proving the fused-dequant path costs ~nothing.
    kq_engine = build_engine(kv_dtype="int8")
    kv_pool_gb_int8 = round(kq_engine.kv.pool_bytes / 1e9, 4)
    kq_prompts = [rs.randint(1, 30000, (128,)).tolist() for _ in range(8)]
    await run_batch(kq_engine, kq_prompts, max_tokens=8)
    await run_batch(kq_engine, kq_prompts, max_tokens=8)
    kq_total, kq_elapsed = await best_of(
        2, lambda: run_batch(kq_engine, kq_prompts, max_tokens=128)
    )
    kv_int8_tok_s = kq_total / kq_elapsed
    await kq_engine.stop()
    del kq_engine

    # latency-sensitive legs on the K=16 serving config: prefill TTFT and
    # the served SSE path must not wait out a 64-step decode block for
    # their first token
    engine = build_engine(decode_block=16)
    # prefill throughput: 8 cold 512-token prompts (prefix caching off via
    # fresh token ids), one token each -- measures prompt ingestion
    pf_prompts = [rs.randint(1, 30000, (512,)).tolist() for _ in range(8)]
    await run_batch(engine, pf_prompts, max_tokens=1)  # compile the bucket

    def _cold_prefill(T: int, eng):
        # fresh token ids per pass: repeats would hit the prefix cache and
        # measure the suffix path instead of cold prompt ingestion
        async def run():
            ps = [rs.randint(1, 30000, (T,)).tolist() for _ in range(8)]
            await run_batch(eng, ps, max_tokens=1)
        return run

    _, best_pf = await best_of(2, _cold_prefill(512, engine))
    prefill_tok_s = 8 * 512 / best_pf

    # served path: HTTP + SSE over the live engine (tok/s + TTFT together)
    serving = await run_serving(engine)

    # release the aggregated engine BEFORE the other legs spin up their
    # engines -- multiple resident models would waste HBM and cap model size
    await engine.stop()
    del engine

    # long-prompt prefill: 8 cold 2048-token prompts, the regime where the
    # Pallas flash kernel carries the score tensor (attention.py auto
    # threshold T >= 1024; the T=512 leg above stays XLA-composed)
    engine = build_engine(decode_block=16, max_seq_len=2048, num_pages=1160)
    long_prompts = [rs.randint(1, 30000, (2048,)).tolist() for _ in range(8)]
    await run_batch(engine, long_prompts, max_tokens=1)  # compile the bucket
    _, best_long = await best_of(2, _cold_prefill(2048, engine))
    prefill_tok_s_t2048 = 8 * 2048 / best_long
    await engine.stop()
    del engine

    sweep = await run_decode_sweep(rs)
    tp_scaling = await run_tp_scaling()
    mem_pressure = await run_mem_pressure(rs)
    spec = await run_spec(rs)
    pf_load = await run_prefill_under_decode_load(rs)
    long_ctx = await run_long_context(rs)
    host_pipe = await run_host_pipeline(rs)
    slo_rig = await run_slo_rig(scale="full")
    prefix_econ = await run_prefix_economy(scale="full")
    disagg_tok_s, _dev_stats = await run_disagg(rs, allow_local=True)
    disagg_wire_tok_s, wire_stats = await run_disagg(rs, allow_local=False)

    baseline = 51.22  # H100 TP4 per-GPU decode tok/s (reference planner.md:86)
    print(
        json.dumps(
            {
                "metric": "engine_decode_tok_s_per_chip_tinyllama1b_bs8",
                "value": round(tok_s, 2),
                "unit": "tok/s",
                "vs_baseline": round(tok_s / baseline, 3),
                "decode_steps_s": round(decode_steps_s, 2),
                "dispatches_s": round(steps_s, 2),
                "prefill_tok_s": round(prefill_tok_s, 1),
                "prefill_tok_s_t2048": round(prefill_tok_s_t2048, 1),
                "disagg_tok_s": round(disagg_tok_s, 2),
                "disagg_wire_tok_s": round(disagg_wire_tok_s, 2),
                "disagg_transfer_ms_p50": wire_stats.get("deliver_ms_p50"),
                "disagg_transfer_bytes_p50": wire_stats.get("bytes_p50"),
                # export-before-first-byte of the chunked pipeline (the
                # legacy monolithic path reported whole-blob materialize
                # here -- 431 ms p50 in BENCH_r05)
                "disagg_export_ms_p50": wire_stats.get("export_ms_p50"),
                "disagg_export_total_ms_p50": wire_stats.get(
                    "export_total_ms_p50"
                ),
                "disagg_chunk_overlap_ratio": wire_stats.get(
                    "overlap_ratio_p50"
                ),
                "decode_tok_s_int8": round(int8_tok_s, 2),
                # ISSUE 13: the --kv-dtype int8 pool line (bf16 = the
                # exact default); the pool-footprint pair is the win
                "decode_tok_s_kv_int8": round(kv_int8_tok_s, 2),
                "kv_dtype_default": kv_dtype,
                "kv_pool_gb_default": kv_pool_gb,
                "kv_pool_gb_int8": kv_pool_gb_int8,
                "est_hbm_util_v5e": round(util, 4),
                "param_bytes": pbytes,
                **sweep,
                **tp_scaling,
                **mem_pressure,
                **spec,
                **pf_load,
                **long_ctx,
                **host_pipe,
                **slo_rig,
                **prefix_econ,
                **serving,
            }
        )
    )


if __name__ == "__main__":
    import sys

    asyncio.run(main())
