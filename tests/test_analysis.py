"""dynalint (dynamo_tpu.analysis): per-rule fixtures, suppressions,
baseline round-trip, CLI contract, and the tier-1 zero-violation gate over
the real package."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from dynamo_tpu.analysis import ALL_RULES, Analyzer, Baseline, get_rules
from dynamo_tpu.analysis.cli import run as cli_run

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE_DIR = os.path.join(REPO_ROOT, "dynamo_tpu")
BASELINE_PATH = os.path.join(REPO_ROOT, ".dynalint-baseline.json")


def lint_source(tmp_path, source, rules=None, name="mod.py"):
    """Write a fixture module and lint it; returns findings."""
    path = tmp_path / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    analyzer = Analyzer(get_rules(rules), root=str(tmp_path))
    return analyzer.analyze_paths([str(path)])


def rule_ids(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# DT001: blocking calls in async def
# ---------------------------------------------------------------------------


def test_dt001_direct_blocking_calls(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import time, subprocess

        async def bad():
            time.sleep(1)
            subprocess.run(["ls"])
            with open("/tmp/x") as f:
                data = f.read()
            return data
        """,
        rules=["DT001"],
    )
    # time.sleep, subprocess.run, open, f.read
    assert len(findings) == 4
    assert all(f.rule == "DT001" for f in findings)
    assert all(f.qualname == "bad" for f in findings)


def test_dt001_clean_twin(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import asyncio

        async def good():
            await asyncio.sleep(1)
            data = await asyncio.to_thread(_read)
            return data

        def _read():
            with open("/tmp/x") as f:
                return f.read()
        """,
        rules=["DT001"],
    )
    # the blocking I/O lives in a sync helper passed BY REFERENCE to
    # to_thread -- never called from async code
    assert findings == []


def test_dt001_future_result(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        async def bad(fut):
            return fut.result()
        """,
        rules=["DT001"],
    )
    assert rule_ids(findings) == ["DT001"]


def test_dt001_transitive_sync_helper(tmp_path):
    """The planner/hub bug shape: async code calling a same-module sync
    helper that does file I/O."""
    findings = lint_source(
        tmp_path,
        """
        class Worker:
            async def loop(self):
                self._record("x")

            def _record(self, item):
                with open("/tmp/log", "a") as f:
                    f.write(item)
        """,
        rules=["DT001"],
    )
    assert rule_ids(findings) == ["DT001"]
    assert "_record" in findings[0].message
    assert findings[0].qualname == "Worker.loop"


def test_dt001_transitive_does_not_cross_classes(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        class A:
            async def loop(self):
                self.save()

            def save(self):
                pass  # A.save is clean

        class B:
            def save(self):
                open("/tmp/x", "w").write("y")
        """,
        rules=["DT001"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# DT002: threading lock across await
# ---------------------------------------------------------------------------


def test_dt002_lock_across_await(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import asyncio, threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            async def bad(self):
                with self._lock:
                    await asyncio.sleep(0.1)
        """,
        rules=["DT002"],
    )
    assert rule_ids(findings) == ["DT002"]


def test_dt002_clean_twins(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import asyncio, threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._alock = asyncio.Lock()

            async def ok_no_await_inside(self):
                with self._lock:
                    x = 1
                await asyncio.sleep(x)

            async def ok_asyncio_lock(self):
                async with self._alock:
                    await asyncio.sleep(0.1)

            def ok_sync(self):
                with self._lock:
                    return 2
        """,
        rules=["DT002"],
    )
    assert findings == []


def test_dt002_blocking_acquire(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import threading

        lock = threading.RLock()

        async def bad():
            lock.acquire()
        """,
        rules=["DT002"],
    )
    assert rule_ids(findings) == ["DT002"]


# ---------------------------------------------------------------------------
# DT003: silent except swallow
# ---------------------------------------------------------------------------


def test_dt003_silent_swallows(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        def bad_pass():
            try:
                risky()
            except Exception:
                pass

        def bad_bare():
            try:
                risky()
            except:
                return None
        """,
        rules=["DT003"],
    )
    assert rule_ids(findings) == ["DT003", "DT003"]


def test_dt003_clean_twins(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import logging

        logger = logging.getLogger(__name__)

        def ok_logs():
            try:
                risky()
            except Exception:
                logger.warning("risky failed", exc_info=True)

        def ok_reraises():
            try:
                risky()
            except Exception:
                cleanup()
                raise

        def ok_uses_exception(results):
            try:
                risky()
            except Exception as e:
                results.append(e)

        def ok_narrow():
            try:
                risky()
            except ValueError:
                pass
        """,
        rules=["DT003"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# DT004/DT005: hot-path rules (decorator + manifest)
# ---------------------------------------------------------------------------

HOT_PREAMBLE = """
    import jax
    import numpy as np
    import jax.numpy as jnp

    def hot_path(fn):
        return fn
"""


def test_dt004_sync_in_hot_path(tmp_path):
    findings = lint_source(
        tmp_path,
        HOT_PREAMBLE + """
        @hot_path
        def step(handles, arr):
            out = jax.device_get(handles)
            arr.block_until_ready()
            host = np.asarray(arr)
            return out, host
        """,
        rules=["DT004"],
    )
    assert rule_ids(findings) == ["DT004", "DT004", "DT004"]


def test_dt004_clean_twin(tmp_path):
    findings = lint_source(
        tmp_path,
        HOT_PREAMBLE + """
        @hot_path
        def step(items):
            # literal/list-comp construction is host-side work, not a sync
            ids = np.asarray([i for i in items], np.int32)
            pad = np.asarray([0, 0], np.int32)
            return ids, pad

        def cold(arr):
            return np.asarray(arr)  # not marked hot: fine
        """,
        rules=["DT004"],
    )
    assert findings == []


def test_dt005_recompile_hazard(tmp_path):
    findings = lint_source(
        tmp_path,
        HOT_PREAMBLE + """
        @hot_path
        def step(reqs):
            toks = [r.tok for r in reqs]
            a = jnp.asarray(toks)               # name -> list comp
            b = jnp.asarray([r.t for r in reqs])  # direct list comp
            c = jnp.asarray(list(reqs))         # list() call
            return a, b, c
        """,
        rules=["DT005"],
    )
    assert rule_ids(findings) == ["DT005", "DT005", "DT005"]


def test_dt005_clean_twin(tmp_path):
    findings = lint_source(
        tmp_path,
        HOT_PREAMBLE + """
        @hot_path
        def step(slot, arr):
            fixed = jnp.asarray([slot], jnp.int32)  # static length: fine
            padded = jnp.asarray(arr)               # ndarray: fine
            return fixed, padded
        """,
        rules=["DT005"],
    )
    assert findings == []


def test_hot_path_manifest_applies(tmp_path):
    """A function listed in HOT_PATH_MANIFEST is hot without a decorator."""
    from dynamo_tpu.analysis import hotpath

    src = """
    import jax

    def decode_block(handles):
        return jax.device_get(handles)
    """
    key = "fixture_pkg/step.py"
    old = hotpath.HOT_PATH_MANIFEST.get(key)
    hotpath.HOT_PATH_MANIFEST[key] = ["decode_block"]
    try:
        findings = lint_source(
            tmp_path, src, rules=["DT004"], name="fixture_pkg/step.py"
        )
    finally:
        if old is None:
            del hotpath.HOT_PATH_MANIFEST[key]
        else:
            hotpath.HOT_PATH_MANIFEST[key] = old
    assert rule_ids(findings) == ["DT004"]


# ---------------------------------------------------------------------------
# DT006: codec frame-kind exhaustiveness
# ---------------------------------------------------------------------------


def test_dt006_missing_decoder(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        FRAME_KINDS = ("frame", "chunk")

        def encode_frame(h):
            return h

        def read_frame(r):
            return r

        def encode_chunk_frame(i):
            return i
        """,
        rules=["DT006"],
        name="runtime/transports/codec.py",
    )
    assert rule_ids(findings) == ["DT006"]
    assert "chunk" in findings[0].message and "decoder" in findings[0].message


def test_dt006_complete_registry_clean(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        FRAME_KINDS = ("frame",)

        def encode_frame(h):
            return h

        def read_frame(r):
            return r
        """,
        rules=["DT006"],
        name="runtime/transports/codec.py",
    )
    assert findings == []


def test_dt006_missing_registry(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        def encode_frame(h):
            return h
        """,
        rules=["DT006"],
        name="runtime/transports/codec.py",
    )
    assert rule_ids(findings) == ["DT006"]
    assert "FRAME_KINDS" in findings[0].message


def test_dt006_kind_match_is_exact(tmp_path):
    """encode_chunk_frame implements 'chunk', NOT 'frame': one kind's
    codec must never satisfy another kind whose name it contains."""
    findings = lint_source(
        tmp_path,
        """
        FRAME_KINDS = ("frame", "chunk")

        def encode_chunk_frame(i):
            return i

        def decode_chunk_frame(i):
            return i
        """,
        rules=["DT006"],
        name="runtime/transports/codec.py",
    )
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 2
    assert "'frame' has no encoder" in msgs
    assert "'frame' has no decoder" in msgs


def test_dt006_ignores_other_modules(tmp_path):
    findings = lint_source(
        tmp_path, "x = 1\n", rules=["DT006"], name="other.py"
    )
    assert findings == []


# ---------------------------------------------------------------------------
# DT007: metrics-registry hygiene
# ---------------------------------------------------------------------------


def test_dt007_inline_prometheus_construction(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        from prometheus_client import Counter, Gauge

        reqs = Counter("reqs_total", "requests", ["route"])

        def make():
            return Gauge("depth", "queue depth")
        """,
        rules=["DT007"],
    )
    assert rule_ids(findings) == ["DT007", "DT007"]
    assert "runtime/metrics.py" in findings[0].message
    assert findings[1].qualname == "make"


def test_dt007_module_attribute_call(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import prometheus_client as pc

        h = pc.Histogram("lat_seconds", "latency")
        """,
        rules=["DT007"],
    )
    assert rule_ids(findings) == ["DT007"]
    assert "Histogram" in findings[0].message


def test_dt007_collections_counter_is_clean(tmp_path):
    """A Counter that is not prometheus_client's must never trip the rule."""
    findings = lint_source(
        tmp_path,
        """
        from collections import Counter

        def tally(xs):
            return Counter(xs)
        """,
        rules=["DT007"],
    )
    assert findings == []


def test_dt007_registry_module_is_exempt(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        from prometheus_client import Counter

        def counter(name, doc):
            return Counter(name, doc)
        """,
        rules=["DT007"],
        name="runtime/metrics.py",
    )
    assert findings == []


def test_dt007_registry_facade_usage_is_clean(tmp_path):
    """Minting through the MetricsRegistry facade is the sanctioned path."""
    findings = lint_source(
        tmp_path,
        """
        from dynamo_tpu.runtime.metrics import MetricsRegistry

        reg = MetricsRegistry()
        hits = reg.counter("hits", "cache hits")
        """,
        rules=["DT007"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# DT008: fire-and-forget tasks
# ---------------------------------------------------------------------------


def test_dt008_discarded_task_handle(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import asyncio

        async def bad(coro, other):
            asyncio.create_task(coro)
            asyncio.ensure_future(other)
        """,
        rules=["DT008"],
    )
    assert rule_ids(findings) == ["DT008", "DT008"]
    assert all(f.qualname == "bad" for f in findings)


def test_dt008_clean_twins(tmp_path):
    """Stored handles, done-callback chains, container registration, and
    inline awaits all keep (or surface) the task -- no findings."""
    findings = lint_source(
        tmp_path,
        """
        import asyncio

        tasks = set()

        async def good(coro, a, b, c):
            t = asyncio.create_task(coro)
            tasks.add(asyncio.create_task(a))
            asyncio.create_task(b).add_done_callback(tasks.discard)
            await asyncio.ensure_future(c)
            return t
        """,
        rules=["DT008"],
    )
    assert findings == []


def test_dt008_taskgroup_is_clean(tmp_path):
    """TaskGroup.create_task holds the reference and surfaces crashes at
    __aexit__ -- discarding its result is the canonical pattern."""
    findings = lint_source(
        tmp_path,
        """
        import asyncio

        async def good(coro, other):
            async with asyncio.TaskGroup() as tg:
                tg.create_task(coro)
            loop = asyncio.get_running_loop()
            loop.create_task(other)  # this one IS the hazard
        """,
        rules=["DT008"],
    )
    assert rule_ids(findings) == ["DT008"]
    assert "loop.create_task" in findings[0].message


def test_dt008_suppression(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import asyncio

        async def main(coro):
            # short-lived helper; crash surfaced by the join below
            asyncio.create_task(coro)  # dynalint: disable=DT008
        """,
        rules=["DT008"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# DT009: sync device<->host transfers in offload-engine modules
# ---------------------------------------------------------------------------


def test_dt009_sync_transfers_outside_helpers(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import jax
        import numpy as np

        COPY_HELPERS = ("to_host",)

        def to_host(arr):
            return np.asarray(arr)

        def lookup(store, snap, dev):
            a = jax.device_get(snap)
            b = np.asarray(snap)
            jax.device_put(b)
            dev.block_until_ready()
            return a
        """,
        rules=["DT009"],
        name="fixture_pkg/offload.py",
    )
    assert rule_ids(findings) == ["DT009"] * 4


def test_dt009_copy_helper_is_exempt(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import numpy as np

        COPY_HELPERS = ("to_host",)

        def to_host(arr):
            return np.asarray(arr)

        def store(tier, h, snap):
            tier.put(h, to_host(snap))

        def probe(shape):
            return np.asarray([1, 2, 3])  # literal: host-side construction
        """,
        rules=["DT009"],
        name="fixture_pkg/offload.py",
    )
    assert findings == []


def test_dt009_ignores_other_modules(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import jax

        def anywhere(handles):
            return jax.device_get(handles)
        """,
        rules=["DT009"],
        name="fixture_pkg/engine.py",
    )
    assert findings == []


# ---------------------------------------------------------------------------
# DT010: jitted step entry points missing from the hot-path manifest
# ---------------------------------------------------------------------------

DT010_FIXTURE = """
    import functools
    import jax
    from functools import partial

    @jax.jit
    def bare_jit_step(x):
        return x

    @partial(jax.jit, static_argnames=("n",))
    def partial_jit_step(x, n):
        return x

    @functools.partial(jax.jit, donate_argnames=("kv",))
    def functools_jit_step(kv):
        return kv

    def plain_helper(x):  # not jitted: never flagged
        return x
    """


def test_dt010_unlisted_jitted_entry_points(tmp_path):
    findings = lint_source(
        tmp_path, DT010_FIXTURE, rules=["DT010"],
        name="fixture_pkg/engine/step.py",
    )
    assert rule_ids(findings) == ["DT010"] * 3
    assert {f.qualname for f in findings} == {
        "bare_jit_step", "partial_jit_step", "functools_jit_step"
    }


def test_dt010_ops_modules_covered(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("interpret",))
        def my_kernel_entry(q, interpret=False):
            return q
        """,
        rules=["DT010"],
        name="fixture_pkg/ops/new_kernel.py",
    )
    assert rule_ids(findings) == ["DT010"]


def test_dt010_manifest_or_decorator_covers(tmp_path):
    """A manifest pattern or an @hot_path decorator both count as
    coverage; only the unmarked entry point is drift."""
    from dynamo_tpu.analysis import hotpath

    src = """
    import jax
    from dynamo_tpu.analysis.hotpath import hot_path

    @jax.jit
    def listed_step(x):
        return x

    @hot_path
    @jax.jit
    def decorated_step(x):
        return x

    @jax.jit
    def drifted_step(x):
        return x
    """
    key = "fixture_pkg/engine/step.py"
    old = hotpath.HOT_PATH_MANIFEST.get(key)
    hotpath.HOT_PATH_MANIFEST[key] = ["listed_step"]
    try:
        findings = lint_source(
            tmp_path, src, rules=["DT010"], name=key
        )
    finally:
        if old is None:
            del hotpath.HOT_PATH_MANIFEST[key]
        else:
            hotpath.HOT_PATH_MANIFEST[key] = old
    assert rule_ids(findings) == ["DT010"]
    assert findings[0].qualname == "drifted_step"


def test_dt010_ignores_other_modules(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import jax

        @jax.jit
        def helper(x):
            return x
        """,
        rules=["DT010"],
        name="fixture_pkg/runtime/helpers.py",
    )
    assert findings == []


def test_dt010_manifest_covers_current_step_surface():
    """The real manifest covers every jitted entry point shipping today in
    step.py and ops/ -- including the packed unified mixed-batch step and
    the packed ragged-attention kernels with their XLA reference."""
    from dynamo_tpu.analysis.hotpath import HOT_PATH_MANIFEST

    step = HOT_PATH_MANIFEST["dynamo_tpu/engine/step.py"]
    assert "packed_unified_step" in step and "prefill_step" in step
    # the raw implementations behind the assignment-form jit wrappers (the
    # bodies the sharded serving path re-jits) are the scanned surface
    assert "_decode_block" in step and "_packed_unified_step" in step
    ragged = HOT_PATH_MANIFEST["dynamo_tpu/ops/ragged_attention.py"]
    assert "packed_ragged_attention*" in ragged
    assert "ragged_paged_attention_xla" in ragged
    assert "flash_prefill_attention" in HOT_PATH_MANIFEST[
        "dynamo_tpu/ops/flash_prefill.py"
    ]
    # multichip serving entry points (sharded re-jit factory + sp/pp
    # prefill routes) are manifest-covered
    assert "make_sharded_steps" in HOT_PATH_MANIFEST[
        "dynamo_tpu/parallel/sharding.py"
    ]
    assert "pp_prefill_step" in HOT_PATH_MANIFEST[
        "dynamo_tpu/parallel/pipeline_parallel.py"
    ]


def test_dt010_assignment_form_wrappers(tmp_path):
    """``step = partial(jax.jit, ...)(_impl)`` and ``step = jax.jit(_impl)``
    are entry points too: unlisted ones are drift (this is exactly how the
    sharded-serving refactor would have silently dropped DT004/DT005
    coverage of every step body)."""
    src = """
    import jax
    from functools import partial

    def _impl_a(x):
        return x

    def _impl_b(x):
        return x

    wrapped_a = partial(jax.jit, donate_argnames=("x",))(_impl_a)
    wrapped_b = jax.jit(_impl_b)
    not_a_jit = partial(print, "x")
    """
    findings = lint_source(
        tmp_path, src, rules=["DT010"], name="fixture_pkg/engine/step.py"
    )
    assert rule_ids(findings) == ["DT010"] * 2
    assert {f.qualname for f in findings} == {"wrapped_a", "wrapped_b"}


def test_dt010_assignment_form_covered_by_manifest(tmp_path):
    """Coverage via EITHER the assigned (public) name or the raw impl
    satisfies the assignment-form check."""
    from dynamo_tpu.analysis import hotpath

    src = """
    import jax
    from functools import partial

    def _by_public(x):
        return x

    def _by_raw(x):
        return x

    public_step = partial(jax.jit, static_argnames=("n",))(_by_public)
    raw_step = jax.jit(_by_raw)
    """
    key = "fixture_pkg/engine/step.py"
    old = hotpath.HOT_PATH_MANIFEST.get(key)
    hotpath.HOT_PATH_MANIFEST[key] = ["public_step", "_by_raw"]
    try:
        findings = lint_source(tmp_path, src, rules=["DT010"], name=key)
    finally:
        if old is None:
            del hotpath.HOT_PATH_MANIFEST[key]
        else:
            hotpath.HOT_PATH_MANIFEST[key] = old
    assert findings == []


def test_dt010_parallel_modules_covered(tmp_path):
    """parallel/ is DT010 scope: a new sharded entry point there must be
    manifest-listed like any step/kernel."""
    findings = lint_source(
        tmp_path,
        """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("mesh",))
        def new_parallel_step(x, mesh):
            return x
        """,
        rules=["DT010"],
        name="fixture_pkg/parallel/new_parallel.py",
    )
    assert rule_ids(findings) == ["DT010"]


# ---------------------------------------------------------------------------
# DT011: multichip jit entry points must declare in/out shardings
# ---------------------------------------------------------------------------


def test_dt011_missing_shardings(tmp_path):
    """Call-form jax.jit in parallel/ without in_shardings/out_shardings
    is flagged -- placement would fall back to operand propagation and
    the KV pool could be silently replicated."""
    src = """
    import jax

    def _impl(params, kv):
        return kv

    def make_steps(param_sh, kv_sh):
        no_shardings = jax.jit(_impl)
        only_in = jax.jit(_impl, in_shardings=(param_sh, kv_sh))
        only_out = jax.jit(_impl, out_shardings=kv_sh)
        return no_shardings, only_in, only_out
    """
    findings = lint_source(
        tmp_path, src, rules=["DT011"], name="fixture_pkg/parallel/sharding.py"
    )
    assert rule_ids(findings) == ["DT011"] * 3
    msgs = " ".join(f.message for f in findings)
    assert "in_shardings" in msgs and "out_shardings" in msgs


def test_dt011_declared_shardings_clean(tmp_path):
    """Both kwargs declared (None = deliberately unconstrained counts) and
    decorator-form jits (shard_map-internal modules) are clean."""
    src = """
    import jax
    from functools import partial

    def _impl(params, kv):
        return kv

    @partial(jax.jit, static_argnames=("mesh",))
    def decorator_form(x, mesh):  # shards internally via shard_map
        return x

    def make_steps(param_sh, kv_sh):
        return jax.jit(
            _impl,
            in_shardings=(param_sh, kv_sh),
            out_shardings=None,
        )
    """
    findings = lint_source(
        tmp_path, src, rules=["DT011"], name="fixture_pkg/parallel/sharding.py"
    )
    assert findings == []


def test_dt011_ignores_other_modules(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import jax

        def _impl(x):
            return x

        bare = jax.jit(_impl)
        """,
        rules=["DT011"],
        name="fixture_pkg/engine/helpers.py",
    )
    assert findings == []


# ---------------------------------------------------------------------------
# DT012: ad-hoc perf_counter timing in engine/ hot paths
# ---------------------------------------------------------------------------


def test_dt012_stopwatch_pair_in_engine(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import time

        def _commit(self, entries):
            t0 = time.perf_counter()
            do_work(entries)
            elapsed = time.perf_counter() - t0
            print(elapsed)

        def _dispatch(self):
            t0 = time.perf_counter_ns()
            return t0
        """,
        rules=["DT012"],
        name="fixture_pkg/engine/engine.py",
    )
    assert rule_ids(findings) == ["DT012"] * 3


def test_dt012_clean_twin_routes_through_profiler(tmp_path):
    """Timing through the TickProfiler (marks) or a registry family is
    the sanctioned shape; stamp *references* (default_factory) are out of
    scope -- they are consumed by metrics code, not stopwatch pairs."""
    findings = lint_source(
        tmp_path,
        """
        import time
        from dataclasses import dataclass, field

        @dataclass
        class Inflight:
            dispatched_at: float = field(default_factory=time.perf_counter)

        def _commit(self, entries):
            tick = self._tick
            if tick is not None:
                tick.mark("dispatch")
            do_work(entries)
            if tick is not None:
                tick.mark("commit")
        """,
        rules=["DT012"],
        name="fixture_pkg/engine/engine.py",
    )
    assert findings == []


def test_dt012_suppression(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import time

        def _commit(self, entries):
            # dynalint: disable=DT012 -- routes into a registry family
            now = time.perf_counter()
            self.obs.observe_step("decode", now - entries[0].dispatched_at)
        """,
        rules=["DT012"],
        name="fixture_pkg/engine/engine.py",
    )
    assert findings == []


def test_dt012_scoped_to_engine_modules(tmp_path):
    """perf_counter elsewhere (the profiler itself, the mocker, bench
    harnesses) is not DT012's business."""
    findings = lint_source(
        tmp_path,
        """
        import time

        def measure():
            t0 = time.perf_counter()
            return time.perf_counter() - t0
        """,
        rules=["DT012"],
        name="fixture_pkg/runtime/profiling.py",
    )
    assert findings == []


# ---------------------------------------------------------------------------
# Suppressions
# ---------------------------------------------------------------------------


def test_trailing_suppression(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import time

        async def f():
            time.sleep(1)  # dynalint: disable=DT001 -- fixture
        """,
        rules=["DT001"],
    )
    assert findings == []


def test_standalone_suppression_skips_comments_and_blanks(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import time

        async def f():
            # dynalint: disable=DT001 -- justified here,
            # with a second explanatory line

            time.sleep(1)
        """,
        rules=["DT001"],
    )
    assert findings == []


def test_suppression_is_rule_specific(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import time

        async def f():
            time.sleep(1)  # dynalint: disable=DT003 -- wrong rule id
        """,
        rules=["DT001"],
    )
    assert rule_ids(findings) == ["DT001"]


def test_star_suppression(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import time

        async def f():
            time.sleep(1)  # dynalint: disable=*
        """,
    )
    assert findings == []


# ---------------------------------------------------------------------------
# Baseline round-trip
# ---------------------------------------------------------------------------

# pre-dedented: concatenated with other snippets below, where mixed
# indentation would defeat textwrap.dedent
BASELINE_FIXTURE = textwrap.dedent(
    """
    import time

    async def old_offender():
        time.sleep(1)
    """
)


def test_baseline_round_trip(tmp_path):
    findings = lint_source(tmp_path, BASELINE_FIXTURE, rules=["DT001"])
    assert len(findings) == 1

    bl_path = tmp_path / "baseline.json"
    Baseline.from_findings(findings).save(str(bl_path))
    loaded = Baseline.load(str(bl_path))
    assert loaded.filter(findings) == []

    # a NEW violation in the SAME module is not covered by the old baseline
    new = lint_source(
        tmp_path,
        BASELINE_FIXTURE + textwrap.dedent(
            """
            async def fresh_offender():
                time.sleep(2)
            """
        ),
        rules=["DT001"],
    )
    fresh = loaded.filter(new)
    assert [f.qualname for f in fresh] == ["fresh_offender"]


def test_baseline_counts_duplicates(tmp_path):
    src = textwrap.dedent(
        """
        import time

        async def f():
            time.sleep(1)
            time.sleep(1)
        """
    )
    findings = lint_source(tmp_path, src, rules=["DT001"])
    assert len(findings) == 2
    # identical lines in one function share a fingerprint; the baseline
    # stores count=2 and covers both -- but not a third in the same module
    bl = Baseline.from_findings(findings)
    assert list(bl.counts.values()) == [2]
    assert bl.filter(findings) == []
    three = lint_source(
        tmp_path, src + "    time.sleep(1)\n", rules=["DT001"]
    )
    assert len(three) == 3
    assert len(bl.filter(three)) == 1


def test_fingerprint_survives_line_drift(tmp_path):
    """An unrelated edit that shifts line numbers does not invalidate the
    baseline (re-linting the SAME file after inserting lines above)."""
    before = lint_source(tmp_path, BASELINE_FIXTURE, rules=["DT001"])
    after = lint_source(
        tmp_path, "\nX = 1\nY = 2\n" + BASELINE_FIXTURE, rules=["DT001"]
    )
    assert before[0].line != after[0].line
    assert before[0].fingerprint == after[0].fingerprint


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_json_and_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\nasync def f():\n    time.sleep(1)\n")

    rc = cli_run([str(bad), "--root", str(tmp_path), "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["schema_version"] == 2
    assert doc["summary"]["total"] == 1
    assert doc["summary"]["by_rule"] == {"DT001": 1}
    f = doc["findings"][0]
    assert f["rule"] == "DT001" and f["path"] == "bad.py"

    # write a baseline, then the same run gates clean
    bl = tmp_path / "bl.json"
    rc = cli_run(
        [str(bad), "--root", str(tmp_path), "--baseline", str(bl),
         "--write-baseline"]
    )
    assert rc == 0
    capsys.readouterr()
    rc = cli_run([str(bad), "--root", str(tmp_path), "--baseline", str(bl)])
    assert rc == 0

    clean = tmp_path / "clean.py"
    clean.write_text("X = 1\n")
    rc = cli_run([str(clean), "--root", str(tmp_path)])
    assert rc == 0


def test_cli_select_and_unknown_rule(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import time\n\nasync def f():\n"
        "    try:\n        time.sleep(1)\n    except Exception:\n"
        "        pass\n"
    )
    rc = cli_run([str(bad), "--root", str(tmp_path), "--select", "DT003"])
    out = capsys.readouterr().out
    assert rc == 1 and "DT003" in out and "DT001" not in out
    assert cli_run([str(bad), "--select", "DT999"]) == 2


def test_cli_module_entrypoint():
    proc = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.analysis", "--list-rules"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0
    for rule in ALL_RULES:
        assert rule.id in proc.stdout


# ---------------------------------------------------------------------------
# The tier-1 gate: the real package must be violation-free
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# DT013: blocking work on the tick thread outside the async-commit helpers
# ---------------------------------------------------------------------------


def test_dt013_blocking_calls_in_tick_module(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import jax

        TICK_COMMIT_HELPERS = ("_commit_all",)

        def _dispatch_block(self):
            mats = jax.device_get(self.handles)
            self.kv.pages.block_until_ready()
            return mats

        def _run(self):
            self.queue.put_nowait(42)
            text = self.decoder.decode_stream()
        """,
        rules=["DT013"],
        name="fixture_pkg/engine/engine.py",
    )
    assert rule_ids(findings) == ["DT013"] * 4


def test_dt013_clean_twin_designated_helpers(tmp_path):
    """The same calls inside TICK_COMMIT_HELPERS-listed functions are the
    sanctioned shape (the designed sync/fanout points)."""
    findings = lint_source(
        tmp_path,
        """
        import jax

        TICK_COMMIT_HELPERS = ("_commit_all", "_dispatch")

        def _commit_all(self, entries):
            mats = jax.device_get([e.sampled for e in entries])
            return mats

        def _dispatch(self, events):
            for ev in events:
                self.queue.put_nowait(ev)
        """,
        rules=["DT013"],
        name="fixture_pkg/engine/engine.py",
    )
    assert findings == []


def test_dt013_scope_is_tick_modules_only(tmp_path):
    """Other modules (export workers, offload, tests) are out of scope --
    the rule guards the tick thread, not every device_get in the repo."""
    findings = lint_source(
        tmp_path,
        """
        import jax

        def helper(x):
            return jax.device_get(x)
        """,
        rules=["DT013"],
        name="fixture_pkg/engine/step.py",
    )
    assert findings == []


def test_dt013_mocker_module_covered(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        TICK_COMMIT_HELPERS = ("_finish",)

        def _simulate_tick(self):
            self.queue.put_nowait(1)

        def _finish(self, seq):
            self.queue.put_nowait(None)
        """,
        rules=["DT013"],
        name="fixture_pkg/mocker/engine.py",
    )
    assert rule_ids(findings) == ["DT013"]


# ---------------------------------------------------------------------------
# DT014: shared-mutable-attribute race (interprocedural thread roles)
# ---------------------------------------------------------------------------

RACY_COUNTER = """
    import asyncio
    import threading
    from concurrent.futures import ThreadPoolExecutor

    class Plane:
        def __init__(self):
            self._ex = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="kv-offload"
            )
            self.copied = 0

        def submit(self, snap):
            self._ex.submit(self._store, snap)

        def _store(self, snap):
            self.copied += 1

        async def stats(self):
            return self.copied
    """


def test_dt014_unlocked_cross_role_counter(tmp_path):
    findings = lint_source(tmp_path, RACY_COUNTER, rules=["DT014"])
    assert rule_ids(findings) == ["DT014"]
    f = findings[0]
    assert "copied" in f.message
    assert "kv-offload" in f.message and "event-loop" in f.message


def test_dt014_lock_protected_twin(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import asyncio
        import threading
        from concurrent.futures import ThreadPoolExecutor

        class Plane:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="kv-offload"
                )
                self._lock = threading.Lock()
                self.copied = 0

            def submit(self, snap):
                self._ex.submit(self._store, snap)

            def _store(self, snap):
                with self._lock:
                    self.copied += 1

            async def stats(self):
                with self._lock:
                    return self.copied
        """,
        rules=["DT014"],
    )
    assert findings == []


def test_dt014_queue_handoff_twin(tmp_path):
    """State crossing domains through a queue.Queue attribute is the
    sanctioned handoff -- no shared plain attribute, no finding."""
    findings = lint_source(
        tmp_path,
        """
        import queue
        from concurrent.futures import ThreadPoolExecutor

        class Plane:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="kv-offload"
                )
                self._q = queue.Queue()

            def submit(self, snap):
                self._ex.submit(self._store, snap)

            def _store(self, snap):
                self._q.put(("stored", snap))

            async def drain(self):
                return self._q.get_nowait()
        """,
        rules=["DT014"],
    )
    assert findings == []


def test_dt014_thread_confined_justification(tmp_path):
    """@thread_confined('kv-offload') pins the reader into the writer's
    role: the reviewed justification silences the race."""
    findings = lint_source(
        tmp_path,
        """
        from concurrent.futures import ThreadPoolExecutor

        def thread_confined(role):
            def deco(fn):
                return fn
            return deco

        class Plane:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="kv-offload"
                )
                self.copied = 0

            def submit(self, snap):
                self._ex.submit(self._store, snap)

            def _store(self, snap):
                self.copied += 1

            @thread_confined("kv-offload")
            def stats_probe(self):
                return self.copied
        """,
        rules=["DT014"],
    )
    assert findings == []


def test_dt014_locked_suffix_convention(tmp_path):
    """*_locked helpers are called with the class lock held (the HostTier
    convention): their accesses carry the lockset."""
    findings = lint_source(
        tmp_path,
        """
        import threading
        from concurrent.futures import ThreadPoolExecutor

        class Ring:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="kv-offload"
                )
                self._lock = threading.Lock()
                self.slots = {}

            def submit(self, h, blob):
                self._ex.submit(self._store, h, blob)

            def _store(self, h, blob):
                with self._lock:
                    self._insert_locked(h, blob)

            def _insert_locked(self, h, blob):
                self.slots[h] = blob

            async def lookup(self, h):
                with self._lock:
                    return self.slots.get(h)
        """,
        rules=["DT014"],
    )
    assert findings == []


def test_dt014_inline_suppression(tmp_path):
    src = RACY_COUNTER.replace(
        "self.copied += 1",
        "self.copied += 1  # dynalint: disable=DT014 -- test-only counter",
    )
    assert lint_source(tmp_path, src, rules=["DT014"]) == []


def test_dt014_serialized_tick_roles_do_not_conflict():
    """The engine contract: 'tick' (executor) and 'tick-coro' (the awaiting
    coroutine) are mutually serialized; loop-resident roles co-schedule."""
    from dynamo_tpu.analysis.threads import roles_conflict

    assert not roles_conflict("tick", "tick-coro")
    assert not roles_conflict("event-loop", "fanout-worker")
    assert not roles_conflict("event-loop", "tick-coro")
    assert roles_conflict("tick", "event-loop")
    assert roles_conflict("kv-offload", "tick")
    assert roles_conflict("kv-offload", "event-loop")
    # the anonymous pool races even itself; handoff conflicts with nothing
    assert roles_conflict("worker", "worker")
    assert not roles_conflict("handoff", "kv-offload")


# ---------------------------------------------------------------------------
# DT014 role-inference edge cases: lambda, partial, method handles
# ---------------------------------------------------------------------------


def test_dt014_lambda_target_inference(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        from concurrent.futures import ThreadPoolExecutor

        class Plane:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="kv-offload"
                )
                self.n = 0

            def submit(self, snap):
                self._ex.submit(lambda: self._store(snap))

            def _store(self, snap):
                self.n += 1

            async def stats(self):
                return self.n
        """,
        rules=["DT014"],
    )
    assert rule_ids(findings) == ["DT014"]


def test_dt014_partial_target_inference(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        from concurrent.futures import ThreadPoolExecutor
        from functools import partial

        class Plane:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="kv-offload"
                )
                self.n = 0

            def submit(self, snap):
                self._ex.submit(partial(self._store, snap))

            def _store(self, snap):
                self.n += 1

            async def stats(self):
                return self.n
        """,
        rules=["DT014"],
    )
    assert rule_ids(findings) == ["DT014"]


def test_dt014_method_handle_inference(tmp_path):
    """self.tier.put as a submit target resolves through the attribute's
    constructor type: Tier.put runs under kv-offload, and its unlocked
    write races Tier's async reader."""
    findings = lint_source(
        tmp_path,
        """
        from concurrent.futures import ThreadPoolExecutor

        class Tier:
            def __init__(self):
                self.stored = 0

            def put(self, blob):
                self.stored += 1

            async def occupancy(self):
                return self.stored

        class Plane:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="kv-offload"
                )
                self.tier = Tier()

            def submit(self, blob):
                self._ex.submit(self.tier.put, blob)
        """,
        rules=["DT014"],
    )
    assert rule_ids(findings) == ["DT014"]
    assert "stored" in findings[0].message


# ---------------------------------------------------------------------------
# DT015: cross-thread publication hazard
# ---------------------------------------------------------------------------


def test_dt015_live_container_published(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        from concurrent.futures import ThreadPoolExecutor

        class Plane:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="kv-offload"
                )
                self.pending = []

            def flush(self):
                self._ex.submit(self._store, self.pending)

            def _store(self, items):
                for item in items:
                    pass
        """,
        rules=["DT015"],
    )
    assert rule_ids(findings) == ["DT015"]
    assert "pending" in findings[0].message


def test_dt015_snapshot_twin_is_clean(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        from concurrent.futures import ThreadPoolExecutor

        class Plane:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="kv-offload"
                )
                self.pending = []
                self.index = {}

            def flush(self):
                self._ex.submit(self._store, list(self.pending))
                self._ex.submit(self._store, self.index.copy())

            def _store(self, items):
                for item in items:
                    pass
        """,
        rules=["DT015"],
    )
    assert findings == []


def test_dt015_queue_put_of_live_container(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import queue

        class Plane:
            def __init__(self):
                self._q = queue.Queue()
                self.batch = {}

            def publish(self):
                self._q.put_nowait(self.batch)

            def publish_safely(self):
                self._q.put_nowait(dict(self.batch))
        """,
        rules=["DT015"],
    )
    assert rule_ids(findings) == ["DT015"]
    assert findings[0].qualname == "Plane.publish"


# ---------------------------------------------------------------------------
# DT016: thread-role manifest drift
# ---------------------------------------------------------------------------


def test_dt016_raw_thread_without_role(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import threading

        class Loop:
            def start(self):
                t = threading.Thread(target=self._run, daemon=True)
                t.start()

            def _run(self):
                pass
        """,
        rules=["DT016"],
    )
    assert rule_ids(findings) == ["DT016"]
    assert "_run" in findings[0].message


def test_dt016_prefixless_executor_is_drift(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        from concurrent.futures import ThreadPoolExecutor

        class Plane:
            def __init__(self):
                self._ex = ThreadPoolExecutor(max_workers=1)

            def go(self):
                self._ex.submit(self._work)

            def _work(self):
                pass
        """,
        rules=["DT016"],
    )
    assert rule_ids(findings) == ["DT016"]
    assert "thread_name_prefix" in findings[0].message


def test_dt016_named_executor_auto_minted_role(tmp_path):
    """A thread_name_prefix IS the role declaration: no drift, and the
    prefix-minted role feeds DT014."""
    findings = lint_source(
        tmp_path,
        """
        from concurrent.futures import ThreadPoolExecutor

        class Plane:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="my-new-plane"
                )

            def go(self):
                self._ex.submit(self._work)

            def _work(self):
                pass
        """,
        rules=["DT016"],
    )
    assert findings == []


def test_dt016_manifest_covers_entry(tmp_path):
    """The THREAD_ROLE_MANIFEST pins what inference cannot -- adding the
    entry turns the drift failure green (and removing it turns it red:
    the drift gate)."""
    from dynamo_tpu.analysis import threads

    src = """
    import threading

    class Loop:
        def start(self):
            t = threading.Thread(target=self._run, daemon=True)
            t.start()

        def _run(self):
            pass
    """
    key = "fixture_pkg/threaded.py"
    old = threads.THREAD_ROLE_MANIFEST.get(key)
    threads.THREAD_ROLE_MANIFEST[key] = {"Loop._run": "worker"}
    try:
        covered = lint_source(
            tmp_path, src, rules=["DT016"], name="fixture_pkg/threaded.py"
        )
    finally:
        if old is None:
            del threads.THREAD_ROLE_MANIFEST[key]
        else:
            threads.THREAD_ROLE_MANIFEST[key] = old
    assert covered == []
    # without the manifest entry the same module fails: drift is a gate
    drifted = lint_source(
        tmp_path, src, rules=["DT016"], name="fixture_pkg/threaded2.py"
    )
    assert rule_ids(drifted) == ["DT016"]


def test_dt016_to_thread_of_project_function_is_covered(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import asyncio

        class Export:
            async def run(self):
                return await asyncio.to_thread(self._materialize)

            def _materialize(self):
                return 1
        """,
        rules=["DT016"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# DT017/DT018: recompile hazards (unbucketed shapes, unbounded statics)
# ---------------------------------------------------------------------------

JITTED_SINK = """
    from functools import partial

    import jax
    import jax.numpy as jnp


    @partial(jax.jit, static_argnames=("width",))
    def decode_step(tokens, width):
        return tokens * 2
"""


def test_dt017_unbucketed_traced_shape(tmp_path):
    findings = lint_source(
        tmp_path,
        JITTED_SINK + """

    def dispatch(reqs):
        n = len(reqs)
        buf = jnp.zeros((n, 4))
        pad = [0] * n
        return decode_step(buf, width=4), decode_step(jnp.array(pad), width=4)
        """,
        rules=["DT017"],
    )
    assert rule_ids(findings) == ["DT017", "DT017"]
    assert "decode_step" in findings[0].message
    assert "bucketing helper" in findings[0].message


def test_dt017_bucketed_twin_is_clean(tmp_path):
    """The same flow routed through a blessed bucketing helper (free
    function or .fit method) launders the count: bounded shape set."""
    findings = lint_source(
        tmp_path,
        JITTED_SINK + """

    from dynamo_tpu.engine.bucketing import pow2_bucket


    def dispatch(self, reqs):
        m = pow2_bucket(len(reqs))
        buf = jnp.zeros((m, 4))
        np_rows = self.budget.fit(len(reqs))
        packed = jnp.zeros((np_rows, 4))
        return decode_step(buf, width=4), decode_step(packed, width=4)
        """,
        rules=["DT017"],
    )
    assert findings == []


def test_dt017_constant_shapes_are_clean(tmp_path):
    findings = lint_source(
        tmp_path,
        JITTED_SINK + """

    def dispatch(reqs):
        buf = jnp.zeros((8, 4))
        return decode_step(buf, width=4)
        """,
        rules=["DT017"],
    )
    assert findings == []


def test_dt018_unbounded_static_argument(tmp_path):
    findings = lint_source(
        tmp_path,
        JITTED_SINK + """

    def dispatch(reqs, buf):
        n = len(reqs)
        return decode_step(buf, width=n)
        """,
        rules=["DT018"],
    )
    assert rule_ids(findings) == ["DT018"]
    assert "'width'" in findings[0].message


def test_dt018_static_argnums_positional(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp


        @jax.jit
        def _impl(tokens, k):
            return tokens


        fused_step = jax.jit(_impl, static_argnums=(1,))


        def dispatch(reqs, buf):
            total = sum(len(reqs), 1)
            return fused_step(buf, total)
        """,
        rules=["DT018"],
    )
    # the assignment-form wrapper's static_argnums position is honored
    assert "DT018" in rule_ids(findings)


def test_dt018_bucketed_static_is_clean(tmp_path):
    findings = lint_source(
        tmp_path,
        JITTED_SINK + """

    from dynamo_tpu.engine.bucketing import pow2_bucket


    def dispatch(reqs, buf):
        w = pow2_bucket(len(reqs))
        return decode_step(buf, width=w)
        """,
        rules=["DT018"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# DT019: one dispatch per tick (PACKED_DISPATCH_SITES manifest)
# ---------------------------------------------------------------------------


def test_dt019_undeclared_device_touch_on_tick(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import jax.numpy as jnp
        from concurrent.futures import ThreadPoolExecutor

        class Engine:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="jax-engine"
                )

            def submit(self, x):
                self._ex.submit(self._touch, x)

            def _touch(self, x):
                return jnp.asarray(x)
        """,
        rules=["DT019"],
    )
    assert rule_ids(findings) == ["DT019"]
    assert "jnp.asarray" in findings[0].message
    assert "PACKED_DISPATCH_SITES" in findings[0].message


def test_dt019_declared_site_is_clean(tmp_path):
    """The same touch inside a declared packed-dispatch site is the
    sanctioned shape, and jnp.* inside the jitted trace (the entry impl
    and its transitive callees) never counts as a tick-thread launch."""
    findings = lint_source(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp
        from concurrent.futures import ThreadPoolExecutor

        PACKED_DISPATCH_SITES = ("_dispatch",)

        @jax.jit
        def step(x):
            return _inner(x)

        def _inner(x):
            return jnp.add(x, 1)

        class Engine:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="jax-engine"
                )

            def tick(self, x):
                self._ex.submit(self._dispatch, x)

            def _dispatch(self, x):
                return step(x)
        """,
        rules=["DT019"],
    )
    assert findings == []


def test_dt019_jitted_entry_call_is_a_dispatch(tmp_path):
    """Calling a jitted entry point IS a device launch, even with no
    jnp.* in sight -- an undeclared one on the tick role is a second
    dispatch."""
    findings = lint_source(
        tmp_path,
        """
        import jax
        from concurrent.futures import ThreadPoolExecutor

        @jax.jit
        def step(x):
            return x

        class Engine:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="jax-engine"
                )

            def tick(self, x):
                self._ex.submit(self._sneak, x)

            def _sneak(self, x):
                return step(x)
        """,
        rules=["DT019"],
    )
    assert rule_ids(findings) == ["DT019"]
    assert "step" in findings[0].message


def test_dt019_off_tick_roles_out_of_scope(tmp_path):
    """Device touches on non-tick roles (offload workers) are DT009/DT013
    territory, not dispatch discipline."""
    findings = lint_source(
        tmp_path,
        """
        import jax.numpy as jnp
        from concurrent.futures import ThreadPoolExecutor

        class Offload:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="kv-offload"
                )

            def submit(self, x):
                self._ex.submit(self._store, x)

            def _store(self, x):
                return jnp.asarray(x)
        """,
        rules=["DT019"],
    )
    assert findings == []


def test_dt019_engine_manifest_matches_repo():
    """The real engine module's PACKED_DISPATCH_SITES entries exist: a
    dispatch-method rename must fail here, not silently undeclare the
    site and re-trip DT019 on the next run."""
    import dynamo_tpu.engine.engine as engine_mod

    sites = engine_mod.PACKED_DISPATCH_SITES
    assert "_dispatch_unified" in sites and "_commit_all" in sites
    for name in sites:
        assert hasattr(engine_mod.JaxEngine, name), name


# ---------------------------------------------------------------------------
# DT020: jit construction on a per-tick/hot path
# ---------------------------------------------------------------------------


def test_dt020_jit_construction_on_tick_role(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import jax
        from functools import partial
        from concurrent.futures import ThreadPoolExecutor

        class Engine:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="jax-engine"
                )

            def go(self, fn, x):
                self._ex.submit(self._hot, fn, x)

            def _hot(self, fn, x):
                stepper = jax.jit(fn)
                wrapped = partial(jax.jit, donate_argnums=(0,))(fn)
                return stepper(x), wrapped(x)
        """,
        rules=["DT020"],
    )
    assert rule_ids(findings) == ["DT020", "DT020"]
    assert "fresh wrapper" in findings[0].message


def test_dt020_hot_path_marker(tmp_path):
    findings = lint_source(
        tmp_path,
        """
        import jax

        def hot_path(fn):
            return fn

        @hot_path
        def per_request(fn, x):
            return jax.jit(fn)(x)
        """,
        rules=["DT020"],
    )
    assert rule_ids(findings) == ["DT020"]


def test_dt020_factory_and_decorator_are_clean(tmp_path):
    """make_*/build_* construction-time factories are the sanctioned
    place for jit(); a @partial(jax.jit) DECORATOR on a tick-roled
    function is a declaration, not a per-call construction."""
    findings = lint_source(
        tmp_path,
        """
        import jax
        from functools import partial
        from concurrent.futures import ThreadPoolExecutor

        class Engine:
            def __init__(self):
                self._ex = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="jax-engine"
                )

            def boot(self, fn):
                self._ex.submit(self.make_table, fn)
                self._ex.submit(self._step, 1)

            def make_table(self, fn):
                return {"step": jax.jit(fn)}

            @partial(jax.jit, static_argnames=("k",))
            def _step(self, k):
                return k
        """,
        rules=["DT020"],
    )
    assert findings == []


def test_thread_role_manifest_matches_repo():
    """The checked-in manifest's engine pins exist: a rename must fail
    here, not silently unpin the tick coroutine from the race scan."""
    from dynamo_tpu.analysis.threads import THREAD_ROLE_MANIFEST

    eng = THREAD_ROLE_MANIFEST["dynamo_tpu/engine/engine.py"]
    assert eng["JaxEngine._run"] == "tick-coro"
    assert eng["JaxEngine._fanout_worker"] == "fanout-worker"
    import dynamo_tpu.engine.engine as engine_mod

    assert hasattr(engine_mod.JaxEngine, "_run")
    assert hasattr(engine_mod.JaxEngine, "_fanout_worker")
    assert hasattr(engine_mod.JaxEngine, "_offload_lookup")


# ---------------------------------------------------------------------------
# CLI satellites: --only/--changed, JSON baseline audit
# ---------------------------------------------------------------------------


def test_cli_only_alias(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\nasync def f():\n    time.sleep(1)\n")
    rc = cli_run([str(bad), "--root", str(tmp_path), "--only", "DT001"])
    out = capsys.readouterr().out
    assert rc == 1 and "DT001" in out
    rc = cli_run([str(bad), "--root", str(tmp_path), "--only", "DT003"])
    assert rc == 0  # filtered to a rule the file does not trip


def test_cli_sarif_output(tmp_path, capsys):
    """--format sarif emits a valid SARIF 2.1.0 log: rules catalog,
    results wired by ruleIndex, repo-relative artifact URIs, dynalint
    fingerprints -- and keeps the exit-code contract."""
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\nasync def f():\n    time.sleep(1)\n")
    rc = cli_run([str(bad), "--root", str(tmp_path), "--format", "sarif"])
    out = capsys.readouterr().out
    assert rc == 1
    doc = json.loads(out)
    assert doc["version"] == "2.1.0"
    sarif_run = doc["runs"][0]
    assert sarif_run["tool"]["driver"]["name"] == "dynalint"
    results = sarif_run["results"]
    assert [r["ruleId"] for r in results] == ["DT001"]
    rules = sarif_run["tool"]["driver"]["rules"]
    assert rules[results[0]["ruleIndex"]]["id"] == "DT001"
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "bad.py"
    assert loc["region"]["startLine"] == 4
    assert results[0]["partialFingerprints"]["dynalint/v1"]

    ok = tmp_path / "ok.py"
    ok.write_text("X = 1\n")
    rc = cli_run([str(ok), "--root", str(tmp_path), "--format", "sarif"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["runs"][0]["results"] == []


def test_cli_changed_mode(tmp_path, capsys):
    """--changed lints exactly the files changed vs merge-base HEAD main
    (committed and working-tree), and exits 0 with nothing changed."""
    repo = tmp_path / "repo"
    repo.mkdir()
    git = ["git", "-C", str(repo)]
    subprocess.run(git + ["init", "-q", "-b", "main"], check=True)
    subprocess.run(git + ["config", "user.email", "t@t"], check=True)
    subprocess.run(git + ["config", "user.name", "t"], check=True)
    (repo / "clean.py").write_text("X = 1\n")
    (repo / "old_bad.py").write_text(
        "import time\n\nasync def f():\n    time.sleep(1)\n"
    )
    subprocess.run(git + ["add", "."], check=True)
    subprocess.run(git + ["commit", "-qm", "base"], check=True)

    # nothing changed: exit 0 without linting the pre-existing offender
    rc = cli_run([str(repo), "--root", str(repo), "--changed"])
    assert rc == 0
    assert "no changed python files" in capsys.readouterr().out

    # a fresh working-tree offender IS linted; old_bad.py stays invisible
    (repo / "new_bad.py").write_text(
        "import time\n\nasync def g():\n    time.sleep(2)\n"
    )
    rc = cli_run([str(repo), "--root", str(repo), "--changed"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "new_bad.py" in out and "old_bad.py" not in out

    # linting a SUBDIRECTORY still sees its changes (git paths are
    # toplevel-relative; they must not be joined onto the sub-root)
    sub = repo / "pkg"
    sub.mkdir()
    (sub / "sub_bad.py").write_text(
        "import time\n\nasync def h():\n    time.sleep(3)\n"
    )
    rc = cli_run([str(sub), "--root", str(sub), "--changed"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "sub_bad.py" in out and "new_bad.py" not in out


def test_cli_changed_without_git_is_exit_2(tmp_path, capsys):
    lone = tmp_path / "lone"
    lone.mkdir()
    (lone / "x.py").write_text("X = 1\n")
    env_home = os.environ.get("GIT_CEILING_DIRECTORIES")
    os.environ["GIT_CEILING_DIRECTORIES"] = str(tmp_path)
    try:
        rc = cli_run([str(lone), "--root", str(lone), "--changed"])
    finally:
        if env_home is None:
            os.environ.pop("GIT_CEILING_DIRECTORIES", None)
        else:
            os.environ["GIT_CEILING_DIRECTORIES"] = env_home
    assert rc == 2
    assert "--changed needs git" in capsys.readouterr().err


def test_cli_help_documents_exit_codes(capsys):
    with pytest.raises(SystemExit) as exc:
        cli_run(["--help"])
    assert exc.value.code == 0
    out = capsys.readouterr().out
    assert "exit codes" in out
    for code in ("0 ", "1 ", "2 "):
        assert code in out


def test_cli_json_baseline_audit(tmp_path, capsys):
    """--format json + --baseline reports used and stale fingerprints, so
    a checked-in baseline can be pruned without re-deriving hashes."""
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\nasync def f():\n    time.sleep(1)\n")
    bl = tmp_path / "bl.json"
    rc = cli_run(
        [str(bad), "--root", str(tmp_path), "--baseline", str(bl),
         "--write-baseline"]
    )
    assert rc == 0
    capsys.readouterr()

    # same file: the one baseline entry is "used", nothing stale
    rc = cli_run(
        [str(bad), "--root", str(tmp_path), "--baseline", str(bl),
         "--format", "json"]
    )
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["summary"]["baselined"] == 1
    assert len(doc["baseline"]["used"]) == 1
    assert doc["baseline"]["stale"] == {}

    # offender fixed: the entry flips to stale (prunable)
    bad.write_text("import asyncio\n\nasync def f():\n    await asyncio.sleep(1)\n")
    rc = cli_run(
        [str(bad), "--root", str(tmp_path), "--baseline", str(bl),
         "--format", "json"]
    )
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["baseline"]["used"] == {}
    assert len(doc["baseline"]["stale"]) == 1


def test_repo_is_dynalint_clean():
    """Zero non-baselined DT001-DT012 violations across dynamo_tpu/.

    This is the gate the whole subsystem exists for: introducing a
    blocking call on an event loop, a silent except, a host sync in a
    marked hot path, or an unpaired codec frame kind anywhere in the
    package fails tier-1.  Fix the hazard, or -- for a justified
    exception -- add an inline ``# dynalint: disable=RULE -- why`` or
    regenerate the baseline (see README "Static analysis (dynalint)").
    """
    analyzer = Analyzer(get_rules(), root=REPO_ROOT)
    findings = analyzer.analyze_paths([PACKAGE_DIR])
    assert analyzer.errors == [], f"unparseable sources: {analyzer.errors}"
    if os.path.exists(BASELINE_PATH):
        findings = Baseline.load(BASELINE_PATH).filter(findings)
    rendered = "\n".join(f.render() for f in findings)
    assert findings == [], f"new dynalint violations:\n{rendered}"


def test_spec_package_is_dynalint_clean():
    """The speculative-decoding subsystem (dynamo_tpu/spec) must stay
    zero-finding under every rule DT001-DT012 with NO baseline and NO
    suppressions: drafting runs on the engine executor inside the verify
    cadence, so a blocking call, silent except, host sync, or recompile
    hazard there stalls every speculating lane's token stream.  Scoped
    separately from the whole-repo gate so a future grandfathered baseline
    entry elsewhere can never quietly cover this package."""
    spec_dir = os.path.join(PACKAGE_DIR, "spec")
    analyzer = Analyzer(get_rules(), root=REPO_ROOT)
    findings = analyzer.analyze_paths([spec_dir])
    assert analyzer.errors == [], f"unparseable sources: {analyzer.errors}"
    rendered = "\n".join(f.render() for f in findings)
    assert findings == [], f"spec/ dynalint violations:\n{rendered}"
    # the hot-path manifest actually covers the drafting surface (a rename
    # must not silently drop DT004/DT005 coverage)
    from dynamo_tpu.analysis.hotpath import HOT_PATH_MANIFEST

    assert "NGramDrafter.propose" in HOT_PATH_MANIFEST[
        "dynamo_tpu/spec/drafter.py"
    ]


def test_repo_baseline_is_empty():
    """The checked-in baseline must stay empty: every known hazard in the
    package is either fixed or carries an inline justified suppression.
    If a future PR must grandfather a finding, it should shrink this
    expectation consciously, not silently."""
    with open(BASELINE_PATH) as f:
        data = json.load(f)
    assert data["findings"] == {}


def test_codec_frame_kinds_registry_present():
    """DT006's anchor: the registry exists and covers the wire formats the
    transfer plane speaks today (frames, KV chunks, trace contexts,
    deadline budgets)."""
    from dynamo_tpu.runtime.transports import codec

    assert set(codec.FRAME_KINDS) == {"frame", "chunk", "trace", "deadline"}
