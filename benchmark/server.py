"""The child that holds the chip: weights from the seed, the engine behind
the program's own HTTP service, and a few ``/bench/*`` routes through which
the parent reads counters, takes a device trace and asks the plain
reference for numbers.  It serves until the parent says ``/bench/exit``.

Run by ``benchmark/run.py``; never by hand in a measurement.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import importlib
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, Optional

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

CONFIGS = os.path.join(HERE, "configs")
# what a configuration's file names, beside its sizes: the modules that hold
# its plain reference, its weights and its counts, each found with importlib
# (README, "A configuration")
NAMED = ("reference", "weights", "costs")


def load_config(name: str, rehearse: bool) -> Dict[str, Any]:
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        cfg = json.load(f)
    for key in NAMED:
        if not cfg.get(key):
            raise SystemExit(f"configuration {name!r} names no {key!r} module")
    tiny = cfg.pop("rehearse", None)
    if rehearse:
        if not tiny:
            raise SystemExit(
                f"configuration {name!r} has no 'rehearse' block: it cannot be rehearsed")
        cfg.update(tiny)  # its tiny sizes, and under "engine" the tiny engine's
    return cfg


def build_tokenizer(vocab: int, workdir: str):
    """A word-level tokenizer that renders token id ``i`` as the word
    ``w<i>``: the detokeniser does a real model's work per token, and the
    client can count the tokens of a chunk."""
    from tokenizers import Tokenizer as HFTokenizer
    from tokenizers import models, pre_tokenizers

    from dynamo_tpu.llm.tokenizer import Tokenizer

    tok = HFTokenizer(
        models.WordLevel({f"w{i}": i for i in range(vocab)}, unk_token="w0")
    )
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    os.makedirs(workdir, exist_ok=True)
    tok.save(os.path.join(workdir, "tokenizer.json"))
    with open(os.path.join(workdir, "tokenizer_config.json"), "w") as f:
        json.dump({}, f)
    return Tokenizer.from_model_dir(workdir)


def model_config(cfg: Dict[str, Any]):
    from dynamo_tpu.engine.config import ModelConfig

    mc = ModelConfig.from_hf_config(
        {k: v for k, v in cfg.items() if k != "engine"}
    )
    extra: Dict[str, Any] = {"dtype": cfg.get("torch_dtype", "bfloat16")}
    if mc.is_moe:
        # no dropped assignment: every expert's buffer holds every token
        extra["moe_capacity_factor"] = mc.num_experts / mc.num_experts_per_tok
    return dataclasses.replace(mc, **extra)


def device_info() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def memory_peak() -> Optional[int]:
    import jax

    peaks = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class Bench:
    def __init__(self, args) -> None:
        self.args = args
        self.cfg = load_config(args.config, args.rehearse)
        self.parts: Dict[str, float] = {}
        self.engine = None
        self.reference = None
        self.trace_dir = os.path.join(args.workdir, "trace")
        self.trace_t0: Optional[float] = None
        self.done = asyncio.Event()

    def _mark(self, name: str, since: float) -> float:
        now = time.monotonic()
        self.parts[name] = now - since
        return now

    def build_params(self, seed: int):
        import jax

        each = None
        if self.args.control == "int8_weights":
            # the control: the program's own int8 form of every matrix it
            # quantizes, made as each is drawn (quantize="int8" converts a
            # finished tree a layer at a time, and a layer of eight experts
            # in float32 does not fit beside the model)
            from dynamo_tpu.engine.quant import QUANT_KEYS, _quantize_slice

            dtype = model_config(self.cfg).dtype

            def each(name, w):
                if name in QUANT_KEYS or name == "lm_head":
                    return _quantize_slice(w, dtype)
                return w

        params = importlib.import_module(self.cfg["weights"]).build_params(self.cfg, seed, each)
        jax.block_until_ready(params)
        return params

    def build_engine(self):
        from dynamo_tpu.engine.engine import EngineConfig, JaxEngine

        t = self._mark("runtime_s", T_START)
        params = self.build_params(self.args.seed)
        t = self._mark("weights_s", t)
        settings = dict(self.cfg["engine"])
        shapes = settings.pop("packed_shapes", None)
        settings.pop("warm_anchor_tokens", None)
        if self.args.control == "int8_kv":
            settings["kv_dtype"] = "int8"
        elif self.args.control not in ("", "int8_weights"):
            raise SystemExit(f"unknown --control {self.args.control!r}")
        if shapes:
            os.environ["DYN_PACKED_SHAPE_BUDGET"] = str(len(shapes))
        engine = JaxEngine(model_config(self.cfg), params, EngineConfig(**settings))
        del params
        if shapes:
            # the configuration fixes the set of packed executables: mint
            # them before any traffic, so that every dispatch merges into
            # one of them and the set does not depend on arrival order.
            # The program has no setting for this yet (PERF.md, Open
            # questions): without the attribute this fails, and loudly
            for np_, s_max in shapes:
                engine._packed_shapes.fit(s_max, np_ - s_max, np_ - s_max + 1)
        self._mark("engine_s", t)
        self.engine = engine
        return engine

    # -- routes --------------------------------------------------------------

    async def state(self, req):
        from dynamo_tpu.runtime import compile_sentry, profiling

        import jax

        prof = profiling.profiler
        leaves = {str(x.dtype) for x in jax.tree_util.tree_leaves(self.engine.params)}
        return self.reply(
            {
                "device": device_info(),
                "memory_peak_bytes": memory_peak(),
                "compiles": compile_sentry.counts(),
                "setup_parts": self.parts,
                "profiler": prof.summary() if prof.enabled else None,
                "packed_shapes": self.engine._packed_shapes.pairs,
                # the types the engine serves in, as it states them
                "dtypes": {
                    "weights_dtype": leaves.pop() if len(leaves) == 1 else sorted(leaves),
                    "kv_cache_dtype": str(self.engine.kv.dtype),
                },
            }
        )

    async def trace(self, req):
        import jax

        body = req.json() or {}
        if body.get("action") == "start":
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            # device operations only: tracing Python slows the host it shares
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.trace_t0 = time.monotonic()
            return self.reply({"ok": True})
        window = time.monotonic() - (self.trace_t0 or time.monotonic())
        await asyncio.to_thread(jax.profiler.stop_trace)
        return self.reply({"ok": True, "window_s": window})

    async def trace_reduce(self, req):
        from . import trace_reduce

        body = req.json() or {}
        out = await asyncio.to_thread(
            trace_reduce.reduce_dir, self.trace_dir, body.get("dump")
        )
        return self.reply(out)

    async def reference_route(self, req):
        body = req.json()
        if self.reference is None:
            self.reference = importlib.import_module(self.cfg["reference"]).Reference(self.cfg)
        lp = await asyncio.to_thread(
            self.reference.logprobs, body["seed"], body["tokens"],
            body["rows"], body["ids"],
        )
        return self.reply({"logprobs": lp.tolist()})

    async def reseed(self, req):
        """Calibration only: new weights from another seed in the live
        engine (same shapes, so nothing compiles again)."""
        seed = int((req.json() or {})["seed"])
        self.engine.params = None
        self.engine.params = await asyncio.to_thread(self.build_params, seed)
        return self.reply({"ok": True, "seed": seed})

    async def log_compiles(self, req):
        """Name every program XLA compiles from now on (on stderr)."""
        import jax

        jax.config.update("jax_log_compiles", bool((req.json() or {}).get("on")))
        return self.reply({"ok": True})

    async def exit_route(self, req):
        self.done.set()
        return self.reply({"ok": True})

    # -- main ----------------------------------------------------------------

    async def serve(self) -> None:
        from dynamo_tpu.http import HttpService
        from dynamo_tpu.llm.backend import Backend
        from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
        from dynamo_tpu.runtime.pipeline import link

        from dynamo_tpu.http.server import Response

        from .client import MODEL

        self.reply = Response.json
        engine = self.build_engine()
        tok = build_tokenizer(
            self.cfg["vocab_size"], os.path.join(self.args.workdir, "tokenizer")
        )
        pipeline = link(OpenAIPreprocessor(MODEL, tok), Backend(tok), engine)
        svc = HttpService()
        svc.manager.add_completion_model(MODEL, pipeline)
        route = svc.server.route
        route("GET", "/bench/state", self.state)
        route("POST", "/bench/trace", self.trace)
        route("POST", "/bench/trace_reduce", self.trace_reduce)
        route("POST", "/bench/reference", self.reference_route)
        route("POST", "/bench/reseed", self.reseed)
        route("POST", "/bench/log_compiles", self.log_compiles)
        route("POST", "/bench/exit", self.exit_route)
        await svc.start()
        await engine.start()
        host, port = svc.address
        print(
            "BENCH_READY "
            + json.dumps(
                {"host": host, "port": port, "device": device_info(),
                 "setup_parts": self.parts}
            ),
            flush=True,
        )
        try:
            await self.done.wait()
        finally:
            await svc.stop()
            await engine.stop()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--workdir", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default="")
    args = ap.parse_args()
    # the tick profiler keeps every tick of the window
    os.environ.setdefault("DYN_TICK_RING", "262144")
    import jax

    info = device_info()
    if not args.rehearse and (info["platform"] != "tpu" or info["count"] < args.chips):
        print(
            f"benchmark: needs {args.chips} TPU chip(s), JAX found {info}",
            file=sys.stderr,
        )
        return 3
    del jax
    asyncio.run(Bench(args).serve())
    return 0


if __name__ == "__main__":
    sys.exit(main())
