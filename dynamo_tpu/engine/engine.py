"""JaxEngine: the first-party TPU engine behind the AsyncEngine interface.

This is the component the reference delegates to vLLM/SGLang/TRT-LLM
subprocesses (launch/dynamo-run/src/subprocess/vllm_inc.py:53-120); here it
is first-party: ``generate(Context[PreprocessedRequest]) ->
AsyncIterator[Annotated[LLMEngineOutput-dict]]`` -- the token-level
``ExecutionContext`` shape of the reference (lib/llm/src/backend.rs:60).

Threading model: one asyncio task drives ticks; device dispatches run in a
single-worker executor thread so the event loop keeps serving I/O while XLA
executes.  All scheduler state is touched either inside an executor call or
between them (the tick awaits each call), so no locks are needed.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import contextlib
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import (
    Any, AsyncIterator, Callable, Dict, Iterable, List, NamedTuple, Optional,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.hotpath import hot_path
from ..runtime import compile_sentry, profiling, slo, thread_sentry, tracing
from ..runtime.engine import Annotated, Context, ResponseStream
from ..runtime.utils import log_throttled
from ..protocols.common import (
    FinishReason,
    ForwardPassMetrics,
    LLMEngineOutput,
    PreprocessedRequest,
)
from ..block_manager import PagePool
from ..spec.drafter import spec_live as _spec_state_live
from ..tokens.sequence import TokenBlock
from .config import ModelConfig
from .kv_cache import (
    PagedKVCache,
    as_device_blob,
    assemble_blob,
    blob_layers,
    blob_nbytes,
    blob_num_layers,
    blob_num_pages,
    blob_pages,
    blob_shape,
    blob_to_host,
    coerce_kv_blob,
    concat_blob_layers,
    concat_blob_pages,
    gather_layer_pages,
    layer_chunk_spans,
    pad_page_axis,
    refuse,
    scatter_block_pages,
    scatter_layer_pages,
    slice_block_pages,
)
from .metrics import EngineMetrics
from .model import Params, init_params
from .multistep import FusedStepCeiling
from .sampling import SamplingParams
from .scheduler import (
    Scheduler,
    SchedulerConfig,
    SeqState,
    StepEvent,
    parse_kv_admit_spec,
)
from .step import (
    bump_counts,
    decode_block,
    inject_token,
    inject_tokens,
    seed_count_rows,
    update_lanes,
    zero_count_rows,
    pick_bucket,
    pick_page_bucket,
    pow2_bucket,
    prefill_and_sample,
    prefill_buckets,
    prefill_suffix_and_sample,
    packed_unified_multistep,
    packed_unified_step,
    verify_and_sample,
)

logger = logging.getLogger("dynamo.engine")

# a watched loop's parked wait is annotated in slices of this length
# (JaxEngine._park): what a device trace loses at its edges
PARKED_SLICE_S = 0.05

# The designated blocking/fanout sites of the tick-loop module (dynalint
# DT013): blocking device fetches, detok, and stream-fanout queue puts may
# appear ONLY inside these functions.  _commit_all is the pipeline's one
# designed sync point (readiness probed or depth-forced); _apply_swap_in's
# barrier is a deliberate executor-thread wait; the export helpers run in
# the prefill-worker role on the engine executor, never inside a serving
# tick; _dispatch/_fail_seq are the designated fanout emitters (invoked
# from the off-tick worker in async mode, inline in the serial fallback).
TICK_COMMIT_HELPERS = (
    "_commit_all",
    "_apply_swap_in",
    "_dispatch",
    "_fail_seq",
    "_put_error",
    "_prefill_export",
    "_export_group",
    "_export_group_stream",
    "materialize",
)

# The declared device-touch inventory of the tick role (dynalint DT019):
# every function here may issue device work (a jitted dispatch, a
# device_put/get, jnp staging) while running under the tick/tick-coro
# role; anything else that touches the device on the tick thread is an
# undeclared launch and fails the lint.  Grouping:
# - the dispatch plane proper (one packed launch per tick, plus the
#   prefill/verify/score columns it absorbs or falls back to),
# - _commit_all, the pipeline's single designed sync point,
# - KV page maintenance (swap/onboard/evict/external delivery), which
#   batches scatter/slice launches between dispatches by design,
# - the export plane (prefill-worker role on the engine executor), and
# - _push_device_state/_put_batch, the host->device staging helpers
#   every dispatch assembly shares.
PACKED_DISPATCH_SITES = (
    "_dispatch_block",
    "_dispatch_unified",
    "_dispatch_verify",
    "_dispatch_chunk",
    "_dispatch_prompt_score",
    "_dispatch_full_prefill",
    "_dispatch_full_prefill_batch",
    "_dispatch_mm_prefill_batch",
    "_dispatch_suffix_prefill_batch",
    "_dispatch_parallel_prefill",
    "_do_prefill_group",
    "_finish_prefill",
    "_commit_all",
    "_embed_sync",
    "_apply_swap_in",
    "_apply_onboards",
    "_apply_dirty_rows",
    "_apply_external_chunks",
    "_apply_external_kv",
    "_on_pool_evict",
    "_swap_out",
    "_push_device_state",
    "_put_batch",
    "_prefill_export",
    "_export_group",
    "_export_group_stream",
)


def _start_host_copy(arr) -> None:
    """Kick off the async device->host DMA for ``arr`` so the later
    device_get is a wait, not a transfer.  Values without
    ``copy_to_host_async`` (mocks, host arrays) skip it and the commit's
    fetch blocks as it always did; on a jax array the call exists on
    every backend, so a failure there is a bug and raises.  A blob of
    several leaves answers for them all (kv_cache._PoolTree)."""
    start = getattr(arr, "copy_to_host_async", None)
    if start is not None:
        start()


def _handles_ready(arr) -> bool:
    """Non-blocking readiness probe for a dispatched handle: True when the
    device result (and its async host copy) has landed, so the commit's
    device_get is a copy, not a wait.  Values without ``is_ready``
    (mocks) report ready -- the commit then simply blocks as it always
    did; a probe that exists and fails raises.  THE readiness primitive
    of the async-commit pipeline."""
    probe = getattr(arr, "is_ready", None)
    return True if probe is None else bool(probe())


# where the persistent XLA cache lives when JAX_COMPILATION_CACHE_DIR does
# not say: one fixed, git-ignored directory at the root of the checkout
# (the path is part of the cache key, so it must not move between runs)
XLA_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".xla_cache",
)


def _enable_compilation_cache() -> None:
    """Persistent XLA compilation cache: restarts reuse compiled
    executables instead of re-paying seconds to a minute per shape.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already keeps its cache
    there and this sets no other directory; otherwise the cache goes to
    :data:`XLA_CACHE_DIR`.  ``DYN_XLA_CACHE_DIR=off`` disables it (the CPU
    test suite and the virtual-device children run without one)."""
    if os.environ.get("DYN_XLA_CACHE_DIR", "").lower() in ("off", "0"):
        return
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    os.makedirs(XLA_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", XLA_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


@dataclass
class EngineConfig:
    max_batch_size: int = 8
    max_seq_len: int = 2048
    page_size: int = 16
    num_pages: int = 512
    # a trunk of window and full layers (ModelConfig.layer_pattern) keeps
    # two pools: ``num_pages`` the full layers', this many the window
    # layers' (pages let go behind the window: a lane never holds more than
    # its window, the chunk in flight and its decode growth)
    num_window_pages: int = 0
    # a trunk with gated delta-rule layers (ModelConfig.has_linear) keeps a
    # pool of this many snapshots of the lanes' state, which a prefix hit
    # resumes from (kv_cache.DeltaKV): one is as large as a lane's state
    state_snapshot_slots: int = 0
    block_size: Optional[int] = None  # router-visible KV block size
    # decode steps per device dispatch: decode state stays on device for this
    # many tokens, so host round trips amortize K-fold (ITL burstiness trade)
    decode_block_size: int = 16
    # chunked prefill: prompts longer than this prefill in page-aligned
    # chunks of this many tokens, one chunk per tick, so decode blocks for
    # running requests interleave instead of stalling behind one long
    # prompt (the reference gets this from vLLM's chunked prefill; here
    # the suffix-prefill machinery restarts at any page-aligned offset).
    # None = whole prompt in one dispatch.  Under mixed batching this also
    # caps one lane's chunk inside a unified dispatch.
    prefill_chunk_tokens: Optional[int] = None
    # mixed prefill+decode batching (Ragged Paged Attention, ROADMAP item
    # 2): admitted prompts pack into the decode tick as ragged chunks
    # served by ONE unified dispatch (step.packed_unified_step), so prefill
    # never stalls the decode batch behind a separate launch and TTFT/ITL
    # stop trading off.  Output is bit-identical to the separate paths for
    # greedy/seeded lanes.  ``--no-mixed-batching`` restores the classic
    # separate-dispatch behavior exactly; penalized requests always take
    # the classic paths (the unified step carries no penalty histograms).
    # Multimodal prompts PREFILL classically (soft-prompt injection), but
    # once prefilled their decode lanes ride the unified/packed (and
    # multi-step) dispatches like any text lane -- decode state carries
    # no modality (ISSUE 16 satellite; identity-asserted in tier-1).
    mixed_batching: bool = True
    # total fresh tokens per unified dispatch (decode lanes cost one each,
    # the remainder packs prefill chunks); DYN_MIXED_TOKEN_BUDGET
    # overrides at engine construction
    mixed_token_budget: int = 512
    # KV-budget admission (ROADMAP item 5 / scheduler.KVAdmitConfig):
    # admit against predicted KV pages -- prompt + max_tokens headroom --
    # with a skip-ahead + aging fairness floor, instead of slot count.
    # Spec string per scheduler.parse_kv_admit_spec ("on" or
    # "util=0.9,headroom=256,reserve=16,floor_s=2,skips=4"); None = the
    # legacy slot-count admission.  DYN_KV_ADMIT_BUDGET env wins.
    kv_admit_budget: Optional[str] = None
    # queue-side prefetch window: the offloaded prefix chains of the
    # first N queued requests promote toward host RAM (with completion
    # tracking + ring pins) while they wait, so onboarding overlaps
    # queue wait instead of TTFT.  0 disables prefetch entirely;
    # DYN_KV_PREFETCH overrides at engine construction.
    kv_prefetch_window: int = 32
    # sequence-hash prefix-cache reuse (block_manager.PagePool); requires
    # block_size to divide evenly into pages
    enable_prefix_caching: bool = True
    # KV offload tiers (SURVEY.md 5.4 / reference offload.rs): evicted G1
    # blocks demote to host RAM (G2, this many blocks) and overflow to disk
    # (G3); admission onboards offloaded prefixes back into fresh pages.
    # 0 disables.  The DYN_KV_OFFLOAD env knob (offload.env_offload_spec
    # grammar) arms/overrides these at engine construction, so a deployment
    # can turn the whole plane on without touching config; with both unset
    # the plane is a no-op and no offload thread is ever started.
    host_offload_blocks: int = 0
    disk_offload_blocks: int = 0
    disk_offload_dir: Optional[str] = None
    # G4 remote tier (fleet KV economy): spec per
    # offload.parse_kv_remote_spec -- "on", or
    # "mirror=1,fetch=1,prefill_tok_s=4000,gbps=1.0,namespace=prod".
    # The parsed spec is held on the engine (``kv_remote_spec``); the
    # actual store attaches at serve wiring via ``attach_remote_kv``
    # (config alone cannot name a live hub connection).  Requires the
    # offload plane armed -- G4 hangs off its eviction/onboard flow.
    # DYN_KV_REMOTE env wins; malformed env warns and keeps config.
    kv_remote: Optional[str] = None
    # swap-based preemption (FlowKV, arXiv:2504.03775): a capacity-preempted
    # lane's KV is offloaded and restored through the chunked scatter path
    # instead of re-prefilled.  Effective only when the offload plane is
    # armed; recompute remains the fallback when swap budget runs out.
    swap_preemption: bool = True
    # extra pages allocated per growth event so the page table (and its
    # device copy) changes every few blocks instead of every block
    grow_chunk_pages: int = 4
    # width of the device-checked stop-token set per lane
    device_stop_width: int = 8
    # disaggregation: a lane parked for a remote prefill's KV fails after
    # this long (lost queue item / crashed prefill worker backstop)
    external_kv_timeout_s: float = 60.0
    # engine-startup parallelism (ROADMAP item 1): tp shards attention
    # heads / MLP hidden and the paged KV pool (kv heads over tp -- zero
    # cross-chip traffic on the decode hot path), dp shards the batch
    # lanes.  The engine builds the dp x tp mesh itself at construction
    # (parallel/mesh.serving_mesh) and re-jits the serving steps with
    # explicit in/out shardings; DYN_TP / DYN_DP override at startup so a
    # deployment can turn TP on without touching config.  An explicit
    # ``mesh=`` argument (cli multinode path) wins over both.
    tp: int = 1
    dp: int = 1
    seed: int = 0
    dtype: Optional[str] = None
    # weight-only quantization: "int8" stores matmul weights as int8 with
    # per-output-channel scales, dequantized at the point of use (XLA fuses
    # the convert into the matmul read) -- ~half the HBM stream per decode
    # step (engine/quant.py).  None = bf16/f32 as loaded.
    quantize: Optional[str] = None
    # paged KV pool dtype (ISSUE 13): "int8" switches the pool to the
    # quantized per-row layout (int8 and one scale a row -- ~half the pool's
    # HBM, so the freed bytes become resident batch/context), dequant fused
    # into the ragged kernels and quantize applied on every write.  bf16
    # (the model dtype) stays the exact default; DYN_KV_DTYPE env wins at
    # engine construction (the serving-env-knob contract).  None = model
    # dtype.
    kv_dtype: Optional[str] = None
    # host tick pipelining (ISSUE 13): the tick loop runs double-buffered
    # -- tick N+1 plans, assembles, and enqueues while tick N's dispatch
    # executes on device, and commits consume results only when their
    # async host copies have landed (or the pipeline is full).  Token
    # streams are identical to the serial loop; ``--no-async-dispatch``
    # (DYN_ASYNC_DISPATCH=0) is the exact serial fallback.
    async_dispatch: bool = True
    # folded speculative verify (ISSUE 15): speculating lanes' verify
    # columns ride the packed unified dispatch as additional flat-axis
    # segments -- a speculating mixed tick is ONE device dispatch instead
    # of decode + verify.  Token-identical (greedy and seeded) to the
    # post-commit ``verify_and_sample`` path, which remains the fallback
    # for classic ticks (penalized lanes) and ``fold_spec_verify=False``.
    # DYN_SPEC_FOLD=0/1 overrides at engine construction (the
    # serving-env-knob contract).  Only consulted when mixed batching is on.
    fold_spec_verify: bool = True
    # acceptance-aware per-request auto-disable: a speculating lane whose
    # acceptance rate sits below ``spec_min_accept`` after
    # ``spec_disable_after`` drafted tokens stops drafting and reverts to
    # the plain decode scan -- low-acceptance traffic degrades to exactly
    # plain decode (no output change; the SpecState stays attached for
    # stats) instead of paying draft + rejected-column cost forever.
    # This is what makes speculation safe to run default-on in the
    # serving line.  DYN_SPEC_AUTO_DISABLE=0 turns the auto-off off.
    spec_auto_disable: bool = True
    spec_min_accept: float = 0.35
    spec_disable_after: int = 64
    # multi-step device-resident packed decode (ISSUE 16, ROADMAP item
    # 2): chunk-free packed dispatches fuse K decode iterations into ONE
    # device launch (step.packed_unified_multistep -- the decode_block
    # treatment for the default packed path), so the host plans,
    # assembles, and commits once per K tokens instead of per token.  K
    # adapts per tick (engine._multistep_plan_k): prefill/mixed queue
    # pressure, speculating lanes, or pending admissions collapse it to 1
    # (admission/preemption granularity never hurts TTFT); an idle queue
    # ramps it 1, 2, 4, ... toward a ceiling the loop works out from its
    # own record (ISSUE 42, engine/multistep.py): the smallest such K
    # whose dispatch outlasts the loop's own work per tick by a fixed
    # margin, so a 2-ms step still fuses 8 and a 12-18-ms step stops at 2
    # and an arrival waits behind that much less.  ``multistep_max_k`` is
    # the widest block an executable exists for (what a warm-up mints and
    # the page look-ahead covers), not a tuning value.  Token-identical
    # (greedy, seeded, and unseeded-temperature) to K=1 -- the commit
    # replays stop rules over the [B, K] block exactly like decode_block.
    # ``--no-multistep-decode`` / DYN_MULTISTEP=0 pin the exact previous
    # behavior; DYN_MULTISTEP=N forces fixed K=N; "adaptive"/1 arm the
    # controller.  Only consulted when mixed batching + packed are on.
    multistep_decode: bool = True
    multistep_max_k: int = 8
    # model-based drafter (second weight load): a checkpoint path or
    # ``random[:seed]`` (spec/model_drafter.load_draft_model grammar).
    # When set, the engine loads the draft model at startup -- TP-sharded
    # onto the serving mesh with explicit shardings when one exists --
    # and registers it under drafter kind "model", so requests select it
    # with ``speculation: {"drafter": "model"}``.  None = host-side
    # drafters only.  DYN_DRAFT_MODEL wins over config.
    draft_model: Optional[str] = None


@dataclass
class InflightBlock:
    """A dispatched-but-uncommitted decode block (device handle + the slot
    mapping captured at dispatch time)."""

    # packed [B, K, 2 + 2N] int32: token | logprob bits | top ids | top lps
    # (sampling.pack_sampled_logprobs layout; N inferred from the width)
    sampled: Any
    slots: List[Optional[SeqState]]
    # the dispatch record's counts: decode-runnable lanes at dispatch, and
    # the block's steps
    n_decode: int = 0
    n_steps: int = 1
    # dispatch timestamp: commit observes dispatch->materialize latency
    dispatched_at: float = field(default_factory=time.perf_counter)


@dataclass
class InflightPrefill:
    """A dispatched-but-uncommitted prefill: the sampled first token lives on
    device (already injected into the decode state); the host commits it when
    the handle is materialized alongside the next block."""

    sampled: Any  # packed row, jax.Array [1, 2 + 2N]
    tok: Any  # jax.Array [1] token slice (inject re-apply path, device-only)
    seq: SeqState
    slot: int
    # echo+logprobs: packed [1, T, 2 + 2N] prompt-scoring handle (step.
    # score_prompt_step), materialized alongside the sampled row at commit
    prompt_lp: Any = None
    # prompt rows this dispatch computed (a classic chunked prefill's final
    # dispatch also carries the rows of the chunks before it, which have
    # no record of their own)
    rows: int = 0
    dispatched_at: float = field(default_factory=time.perf_counter)


@dataclass
class InflightUnified:
    """A dispatched-but-uncommitted unified mixed-batch step: one ragged
    dispatch served every decode lane (one row each, device-resident
    state) plus the tick's packed prefill chunks.  ``finals`` carries an
    :class:`InflightPrefill` record per lane whose prompt completed this
    dispatch (their sampled first token is already folded into the device
    decode state by the step itself; the records back the pending-inject
    re-apply path and the echo+logprobs ride-along).  Decode columns
    commit through the block replay (K=1), final prefill columns through
    the same path -- the raw matrix is the single source for both."""

    sampled: Any  # packed [B, 2 + 2N]
    slots: List[Optional[SeqState]]
    finals: List[InflightPrefill]
    n_decode: int = 0
    n_prefill_tokens: int = 0
    # folded speculative verify (ISSUE 15): the per-column target samples
    # of the dispatch's verify segments (packed [B, s_spec, 2 + 2N]) and
    # the (seq, slot, draft) snapshots the host accept walk commits them
    # against -- the InflightVerify discipline riding the unified record,
    # so preempt/cancel between dispatch and commit discards a lane's
    # whole column exactly like the standalone path.
    spec_sampled: Any = None
    spec_lanes: List[Tuple[SeqState, int, List[int]]] = field(
        default_factory=list
    )
    # multi-step decode (ISSUE 16): decode iterations fused into this
    # dispatch.  1 = the classic single-step record (``sampled`` is
    # [B, 2 + 2N]); > 1 widens ``sampled`` to [B, K, 2 + 2N] and the
    # commit replays the whole block (Scheduler.commit_block), exactly
    # like an InflightBlock.
    n_steps: int = 1
    # the dispatch record: the executable's packed rows, the real rows of
    # them (over all fused steps), and the serial the ``dispatch`` and
    # ``device_wait`` annotations of a profiler trace share
    np_rows: int = 0
    used_rows: int = 0
    serial: int = 0
    # [2] int32 on the device, of a decode-only dispatch whose expert MLPs
    # read only the experts a row reaches (step._packed_unified_step): the
    # experts read and the experts held, over its layers and steps
    moe_reach: Any = None
    dispatched_at: float = field(default_factory=time.perf_counter)


@dataclass
class InflightVerify:
    """A dispatched-but-uncommitted speculative verify: one forward pass
    scored every speculating lane's draft columns; the host accept walk
    runs at commit.  ``lanes`` snapshots (seq, slot, draft) at dispatch --
    a lane preempted/cancelled since discards its whole column, exactly
    like a stale decode block."""

    sampled: Any  # packed [B, S, 2 + 2N]
    lanes: List[Tuple[SeqState, int, List[int]]]
    dispatched_at: float = field(default_factory=time.perf_counter)


class _DispatchAccount(NamedTuple):
    """One committed entry as the dispatch record counts it."""

    step: str  # chunk | decode | prefill | decode_block | verify
    np_rows: int  # the executable's packed rows, 0 where it has none
    steps: int  # forward passes it ran
    lane_steps: int  # decoding lanes x forward passes
    prefill_rows: int
    used_rows: int  # real rows, over all its steps
    weight: int  # rows x steps dispatched: its share of a bundled commit

    @property
    def carried_prefill(self) -> bool:
        """Whether its service is a first token's ``chunk_steps`` time."""
        return self.step in ("chunk", "prefill")


def _spec_live(seq: SeqState) -> bool:
    """Whether a lane is actively speculating: armed AND not auto-disabled
    (``spec.drafter.spec_live`` -- shared with the scheduler's
    decode-runnable count so the two sides cannot drift)."""
    return _spec_state_live(seq.spec)


# layer-group count the chunked KV export aims for when the caller doesn't
# pin a granularity: enough chunks that the first hits the wire after ~1/8 of
# the device->host transfer, few enough that framing stays negligible
DEFAULT_EXPORT_CHUNKS = 8


class _GroupSpanExport:
    """Shared device->host materializer for one export group's layer-group
    slices: every request in the group views the same span arrays, so each
    span pays ONE transfer no matter how many uploads consume it.  The
    device copies were dispatched (and ``copy_to_host_async`` started) on
    the engine executor; ``host_span`` completes them lazily off-thread, so
    span i+1 transfers while span i is already on the wire."""

    def __init__(self, span_devs: List[Any]) -> None:
        self._devs: List[Any] = span_devs
        self._host: List[Optional[np.ndarray]] = [None] * len(span_devs)
        self._tasks: List[Optional[asyncio.Task]] = [None] * len(span_devs)

    def _materialize(self, idx: int) -> np.ndarray:
        # per-shard assembly: a tp-sharded pool's span comes to host one
        # kv-head slice per chip and reassembles here (the wire format is
        # always full-width); unsharded spans take the plain device_get
        arr = assemble_blob(self._devs[idx])
        # dynalint: disable=DT014 -- per-span slots are disjoint: host_span
        # dedupes to ONE to_thread task per idx on the loop, so concurrent
        # workers never touch the same index
        self._host[idx] = arr
        # dynalint: disable=DT014 -- same disjoint-slot discipline
        self._devs[idx] = None  # release the device copy
        return arr

    async def host_span(self, idx: int) -> np.ndarray:
        got = self._host[idx]
        if got is not None:
            return got
        task = self._tasks[idx]
        if task is None:
            task = self._tasks[idx] = asyncio.ensure_future(
                asyncio.to_thread(self._materialize, idx)
            )
        return await task


@dataclass
class KVExportStream:
    """One remote prefill's KV export as a stream of layer-group chunks.

    The prefill dispatch and the per-span device gathers are already in
    flight when this is handed out; :meth:`chunks` yields each group as it
    lands on host, so the consumer (PrefillWorker) puts the first bytes on
    the wire after one span's transfer instead of the whole blob's.
    ``first_ready_at``/``last_ready_at`` record the pipeline's
    export-before-first-byte and total-materialize times."""

    shape: Tuple[int, ...]  # of the request's blob (kv_cache.blob_shape)
    dtype: str
    row: np.ndarray  # packed [2 + 2N] (token | logprob | tops)
    spans: List[Tuple[int, int]]  # per-chunk [layer_lo, layer_hi)
    # source-pool shard geometry (kv_shard_geometry); chunks are always
    # full-width -- per-shard head slices reassemble at materialize
    shards: Optional[Dict[str, int]] = None
    started_at: float = 0.0
    first_ready_at: Optional[float] = None
    last_ready_at: Optional[float] = None
    _group: Optional[_GroupSpanExport] = None
    _page_off: int = 0
    _blob: Optional[np.ndarray] = None  # pre-materialized fallback path

    @classmethod
    def from_blob(cls, blob: np.ndarray, row: np.ndarray) -> "KVExportStream":
        """Wrap an already-materialized export (single-request fallback)."""
        return cls(
            shape=tuple(blob.shape),
            dtype=str(blob.dtype),
            row=np.asarray(row),
            spans=[(0, blob_num_layers(blob.shape))],
            _blob=blob_to_host(blob),
        )

    @property
    def quantized(self) -> bool:
        return jnp.dtype(self.dtype) == jnp.int8

    @property
    def nbytes(self) -> int:
        """Wire bytes of the full blob (kv_cache.blob_nbytes: byte framing
        on both ends derives identical extents from (shape, dtype))."""
        return blob_nbytes(self.shape, self.dtype)

    @property
    def chunk_bounds(self) -> List[Tuple[int, int]]:
        """Byte range of each chunk in the C-order blob (layer slabs are
        contiguous, so chunk i covers its layers' bytes exactly)."""
        bpl = self.nbytes // blob_num_layers(self.shape)
        return [(lo * bpl, hi * bpl) for lo, hi in self.spans]

    async def chunks(self):
        """Yield ``(idx, layer_lo, layer_hi, array)`` in span order as each
        group materializes; the array is a view, C-contiguity not
        guaranteed."""
        k = blob_num_pages(self.shape)
        for idx, (lo, hi) in enumerate(self.spans):
            if self._blob is not None:
                part = blob_layers(self._blob, lo, hi)
            else:
                assert self._group is not None
                span = await self._group.host_span(idx)
                part = blob_pages(span, self._page_off, self._page_off + k)
            # dynalint: disable=DT012 -- export-stream readiness stamps feed
            # the bench's export-before-first-byte stats, not ad-hoc timing
            now = time.perf_counter()
            if self.first_ready_at is None:
                self.first_ready_at = now
            self.last_ready_at = now
            yield idx, lo, hi, part

    async def assemble(self) -> np.ndarray:
        """Materialize the full blob (same-process handoff / tests)."""
        parts = [part async for _, _, _, part in self.chunks()]
        return concat_blob_layers(parts)


@dataclass
class _ChunkedDelivery:
    """Decode-side staging record for an in-flight chunked KV delivery:
    layer-group parts queue here until the tick loop scatters them (the
    lane may not even hold a slot yet); ``done`` + all layers applied is
    the completion barrier before the first decode step."""

    shape: Tuple[int, ...]
    dtype: str
    parts: List[Tuple[int, int, np.ndarray]] = field(default_factory=list)
    applied_layers: int = 0
    validated: bool = False
    done: bool = False
    first: int = 0
    lp_row: Optional[np.ndarray] = None


@dataclass
class InflightPrefillGroup:
    """A batched prefill dispatch awaiting commit: ``sampled`` is the whole
    group's first tokens as ONE device array, fetched with ONE transfer at
    commit (per-lane [1] handles each cost a device->host round trip on a
    high-RTT link).  ``entries`` keep the per-lane [1] slices for the
    pending-inject re-apply path, which never leaves the device."""

    sampled: Any  # jax.Array [Bp]
    entries: List[InflightPrefill]
    dispatched_at: float = field(default_factory=time.perf_counter)


from types import SimpleNamespace

# one-chip dispatch table: the module-level jitted steps as-is.  The mesh
# path swaps in parallel.sharding.make_sharded_steps, which re-jits the
# same raw implementations with explicit in/out shardings.
_MODULE_STEPS = SimpleNamespace(
    decode_block=decode_block,
    packed_unified_step=packed_unified_step,
    packed_unified_multistep=packed_unified_multistep,
    verify_and_sample=verify_and_sample,
    update_lanes=update_lanes,
    inject_token=inject_token,
    inject_tokens=inject_tokens,
    zero_count_rows=zero_count_rows,
    bump_counts=bump_counts,
    seed_count_rows=seed_count_rows,
    scatter_block_pages=scatter_block_pages,
    slice_block_pages=slice_block_pages,
    gather_layer_pages=gather_layer_pages,
    scatter_layer_pages=scatter_layer_pages,
)


class JaxEngine:
    """Continuous-batching JAX engine over a paged KV cache."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        params: Params,
        cfg: Optional[EngineConfig] = None,
        kv_sharding: Optional[jax.sharding.Sharding] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        metrics_registry=None,  # runtime.metrics.MetricsRegistry | None
    ) -> None:
        _enable_compilation_cache()
        # compile-cache sentry: attribute every XLA compile to its entry
        # label and (armed) enforce step.COMPILE_BUDGET
        compile_sentry.install()
        self.model_cfg = model_cfg
        self.cfg = cfg or EngineConfig()
        self.params = params
        # Serving-integrated parallelism (VERDICT r3 #2): a dp/tp/pp/sp/ep
        # mesh makes every dispatch GSPMD-sharded -- batch arrays placed
        # over ``dp``, params/KV over ``tp``/``ep`` (the caller shards them
        # at load), and long full prefills route through ring (sp) or
        # pipeline (pp) step functions.  Reference capability: engines.rs:43
        # MultiNodeConfig + dynamo-run flags.rs:82-100.
        #
        # With no explicit mesh, the engine builds its own dp x tp serving
        # mesh from EngineConfig.tp/dp (DYN_TP / DYN_DP env overrides) and
        # shards the params it was handed -- TP is an engine-startup knob,
        # not a caller obligation (ROADMAP item 1).
        if mesh is None:
            mesh = self.resolve_mesh(self.cfg, model_cfg)
            if mesh is not None:
                self._refuse("mesh")
                from ..parallel.sharding import shard_params

                params = shard_params(params, model_cfg, mesh)
                self.params = params
        self.mesh = mesh
        dev0 = jax.devices()[0]
        logger.info(
            "engine devices: platform=%s kind=%r count=%d mesh=%s",
            dev0.platform, dev0.device_kind, len(jax.devices()),
            dict(mesh.shape) if mesh is not None else None,
        )
        self._dp = int(mesh.shape.get("dp", 1)) if mesh is not None else 1
        self._sp = int(mesh.shape.get("sp", 1)) if mesh is not None else 1
        self._pp = int(mesh.shape.get("pp", 1)) if mesh is not None else 1
        if self._sp > 1 or self._pp > 1:
            self._refuse("sp_pp")
        if mesh is not None:
            self._refuse("mesh")
        if mesh is not None and kv_sharding is None:
            from ..parallel.sharding import kv_pspec

            kv_sharding = jax.sharding.NamedSharding(mesh, kv_pspec(model_cfg))
        # counters: how many prefill dispatches took the sp/pp route
        self.sp_prefills = 0
        self.pp_prefills = 0
        if self.cfg.quantize:
            if self.cfg.quantize != "int8":
                raise ValueError(
                    f"unsupported quantize={self.cfg.quantize!r} (int8 only)"
                )
            # with a mesh, params arrive already sharded (random_init /
            # from_pretrained shard first) and the quantization ops
            # propagate those shardings onto q and s
            from .quant import quantize_params

            self.params = quantize_params(self.params, model_cfg)
        # KV event sink: fn(event_dict) -- wired to the router event publisher
        self.kv_event_sink: Optional[Callable[[Dict[str, Any]], None]] = None
        # holdings sink: fn(event_dict) -- wired to KvHoldingsPublisher;
        # fed tier-residency deltas from the offload plane (fleet KV economy)
        self.kv_holdings_sink: Optional[Callable[[Dict[str, Any]], None]] = None
        block_size = self.cfg.block_size or self.cfg.page_size
        pool: Optional[PagePool] = None
        wpool: Optional[PagePool] = None
        if self.cfg.enable_prefix_caching:
            if block_size % self.cfg.page_size == 0:
                pool = PagePool(
                    self.cfg.num_pages,
                    pages_per_block=block_size // self.cfg.page_size,
                    event_sink=self._emit_kv_event,
                )
                if model_cfg.two_kind and self.cfg.num_window_pages >= 2:
                    # the window layers' pool.  KV events to the router
                    # follow the full pool alone: a block is routable while
                    # the full layers hold it (one engine here; a router
                    # over two-kind engines would want the tail's state too)
                    wpool = PagePool(
                        self.cfg.num_window_pages,
                        pages_per_block=block_size // self.cfg.page_size,
                    )
            else:
                logger.warning(
                    "prefix caching disabled: block_size %d is not a "
                    "multiple of page_size %d",
                    block_size, self.cfg.page_size,
                )
        # KV pool dtype: config arms it, DYN_KV_DTYPE wins outright (the
        # serving-env-knob contract: malformed env warns and keeps config,
        # a malformed EXPLICIT config fails engine construction loudly)
        import os as _os0

        from .kv_cache import parse_kv_dtype

        kv_dtype = parse_kv_dtype(self.cfg.kv_dtype)
        env_kvd = _os0.environ.get("DYN_KV_DTYPE")
        if env_kvd is not None and env_kvd.strip():
            try:
                kv_dtype = parse_kv_dtype(env_kvd)
            except ValueError:
                logger.warning("ignoring malformed DYN_KV_DTYPE=%r", env_kvd)
        self.kv = PagedKVCache(
            model_cfg,
            num_pages=self.cfg.num_pages,
            page_size=self.cfg.page_size,
            dtype=kv_dtype if kv_dtype is not None else self.cfg.dtype,
            sharding=kv_sharding,
            allocator=pool,
            num_window_pages=self.cfg.num_window_pages,
            window_allocator=wpool,
            max_lanes=self.cfg.max_batch_size,
            state_slots=self.cfg.state_snapshot_slots,
        )
        # serving-step dispatch table: module-level jits on one chip; on a
        # dp/tp (/ep) mesh, re-jitted with explicit in/out shardings
        # (params/KV over tp, decode state over dp) so GSPMD inserts the
        # collectives and the KV pool can never be silently replicated.
        # sp/pp meshes keep the propagation-based module jits: their
        # shard_map prefill routes hand back arrays laid out over sp/pp
        # (e.g. KV over the pp layer groups), which pinned decode
        # shardings would reject at the very next dispatch.
        if mesh is not None and self._sp <= 1 and self._pp <= 1:
            from ..parallel.sharding import make_sharded_steps

            self._fns = make_sharded_steps(
                mesh, model_cfg, self.params, self.kv.pages,
                self.cfg.max_batch_size,
            )
        else:
            self._fns = _MODULE_STEPS
        # KV-budget admission (scheduler.KVAdmitConfig): config arms it,
        # DYN_KV_ADMIT_BUDGET wins outright (an explicit "off" disarms a
        # config-armed budget -- the DYN_KV_OFFLOAD contract)
        import os as _os

        admit_spec: Any = self.cfg.kv_admit_budget
        env_admit = _os.environ.get("DYN_KV_ADMIT_BUDGET")
        if env_admit is not None and env_admit.strip():
            try:
                admit_spec = parse_kv_admit_spec(env_admit)
            except ValueError:
                # malformed env must not kill the server (the contract
                # every sibling serving env knob follows): warn, keep
                # the config-armed spec
                logger.warning(
                    "ignoring malformed DYN_KV_ADMIT_BUDGET=%r", env_admit
                )
        self.sched = Scheduler(
            SchedulerConfig(
                max_batch_size=self.cfg.max_batch_size,
                max_seq_len=self.cfg.max_seq_len,
                page_size=self.cfg.page_size,
                block_size=self.cfg.block_size,
                dp_groups=self._dp,
                kv_admit=parse_kv_admit_spec(admit_spec),
            ),
            self.kv.allocator,
            self.kv.window_allocator,
            model_cfg.sliding_window or 0,
        )
        # registry-backed observability (runtime/metrics.py): the scheduler
        # refreshes queue/occupancy gauges at admission, the engine observes
        # step latency + KV residency at commit
        self.obs = EngineMetrics(
            metrics_registry, max_slots=self.cfg.max_batch_size
        )
        self.sched.metrics = self.obs
        if model_cfg.has_conv:
            self.sched.conv_state = True
            self.obs.mint_conv_state(self.kv.state_bytes)
        if model_cfg.has_linear:
            from .kv_cache import StateSlots

            self.sched.state_slots = StateSlots(self.cfg.state_snapshot_slots)
            self.obs.mint_delta_state(self.kv.state_bytes)
            # what runs a packed step's chunks of the delta rule (read once)
            from . import attention as att

            self._delta_backend = att.delta_backend()
            if pool is not None:  # a slot dies with its block
                pool.on_evict = lambda blk: self.sched.state_slots.drop(
                    blk.sequence_hash)
        # the dispatch record (ISSUE 41), on time.perf_counter(), the clock
        # of every Inflight* record's ``dispatched_at`` and of the commit's
        # one read: service seconds of the committed dispatches that
        # carried prefill rows and of those that carried none, prefill rows
        # committed, and the last commit's clock.  One tuple, swapped whole
        # at each commit (the fanout worker reads it beside the executor
        # thread).  ``_inflight`` is the tick loop's queue of uncommitted
        # generations, for the one reading that clips a dispatch in flight
        # (``_service_mark``); the scheduler copies that reading onto a
        # request at its first admission and reads nothing of it.
        self._served: Tuple[float, float, int, float] = (0.0, 0.0, 0, 0.0)
        self._inflight: Optional[Any] = None
        self._dispatch_serial = 0
        self._loose_prefill_rows = 0
        self.sched.service_mark = self._service_mark
        # the start of the part of the current parked wait that is not yet
        # in dynamo_engine_parked_seconds_total (time.monotonic(); None
        # while the loop is awake): counted when the wait ends, and up to
        # the moment of a scrape that comes in the middle of one
        self._parked_since: Optional[float] = None
        self._parked_lock = threading.Lock()
        self.obs.registry.before_render(self._count_parked)
        # G2/G3 offload plane (offload.KVOffloadEngine): evictions snapshot
        # (async) onto the dedicated offload thread with disk overflow;
        # admission onboards offloaded prefixes through the chunked scatter
        # path; preemption swaps instead of recomputing.  Armed by config
        # or by DYN_KV_OFFLOAD (env wins); a no-op -- no thread -- otherwise.
        self.offload: Optional[Any] = None
        self.offload_engine: Optional[Any] = None
        self._swapped: Dict[str, SeqState] = {}
        from ..offload import env_offload_spec

        host_blocks = self.cfg.host_offload_blocks
        disk_blocks = self.cfg.disk_offload_blocks
        disk_dir = self.cfg.disk_offload_dir
        swap_on = self.cfg.swap_preemption
        env_spec = env_offload_spec()
        if env_spec is not None:
            # env wins outright: the spec defines the whole plane, so an
            # explicit host=0 / disk=0 disarms a config-armed tier (only
            # the disk dir falls back to config -- it is a path, not a
            # capacity)
            host_blocks = env_spec["host"]
            disk_blocks = env_spec["disk"]
            disk_dir = env_spec["dir"] or disk_dir
            swap_on = env_spec["swap"] and self.cfg.swap_preemption
        if host_blocks > 0 or disk_blocks > 0:
            self._refuse("offload")
        if pool is not None and (host_blocks > 0 or disk_blocks > 0):
            from ..offload import KVOffloadEngine

            if disk_blocks > 0 and not disk_dir:
                raise ValueError(
                    "disk_offload_blocks > 0 requires disk_offload_dir"
                )
            self.offload_engine = KVOffloadEngine(
                host_blocks,
                disk_blocks,
                disk_dir,
                swap_enabled=swap_on,
                registry=metrics_registry,
            )
            self.offload = self.offload_engine.host
            self.offload_engine.holdings_cb = self._emit_kv_holdings
            pool.on_evict = self._on_pool_evict
            self.sched.offload_lookup = self._offload_lookup
            if swap_on:
                self.sched.swap_out = self._swap_out
        # G4 remote tier spec (fleet KV economy): parsed now, attached at
        # serve wiring (attach_remote_kv) once a hub blob client exists.
        # Same env-knob contract as the rest of the plane: DYN_KV_REMOTE
        # wins over config; a malformed env value warns and keeps config.
        from ..offload import env_remote_spec, parse_kv_remote_spec

        self.kv_remote_spec: Optional[Dict[str, Any]] = None
        try:
            self.kv_remote_spec = parse_kv_remote_spec(self.cfg.kv_remote or "")
        except ValueError:
            logger.warning(
                "ignoring malformed kv_remote config %r", self.cfg.kv_remote
            )
        if self.kv_remote_spec:
            self._refuse("remote_tier")
        if "DYN_KV_REMOTE" in _os.environ:
            # env wins outright, including an explicit "off" disarming a
            # config-armed tier
            try:
                self.kv_remote_spec = env_remote_spec()
            except ValueError:
                logger.warning(
                    "ignoring malformed DYN_KV_REMOTE=%r",
                    _os.environ.get("DYN_KV_REMOTE"),
                )
        # chunked prefill restarts at page-aligned offsets: normalize the
        # configured chunk up to a whole page so an intermediate chunk can
        # never overrun the remaining prompt (trigger and dispatch both use
        # the normalized value)
        self._chunk_tokens: Optional[int] = None
        if self.cfg.prefill_chunk_tokens is not None:
            ps_ = self.cfg.page_size
            self._chunk_tokens = max(
                ps_, -(-self.cfg.prefill_chunk_tokens // ps_) * ps_
            )
        # mixed prefill+decode batching (unified ragged dispatch): the
        # token budget caps one dispatch's fresh rows; DYN_MIXED_TOKEN_BUDGET
        # overrides config so a deployment can retune without a restart flag
        # sp/pp meshes pin mixed batching OFF: those axes exist to
        # accelerate FULL prefills (ring attention / microbatched
        # pipeline), and the unified mixed dispatch would swallow every
        # prefill into a path that uses neither -- classic dispatch is
        # what routes long prompts through _dispatch_parallel_prefill
        self._mixed = bool(self.cfg.mixed_batching) and (
            self._sp <= 1 and self._pp <= 1
        )
        budget = self.cfg.mixed_token_budget
        env_budget = _os.environ.get("DYN_MIXED_TOKEN_BUDGET")
        if env_budget:
            try:
                budget = int(env_budget)
            except ValueError:
                logger.warning(
                    "ignoring malformed DYN_MIXED_TOKEN_BUDGET=%r", env_budget
                )
        self._mixed_budget = max(int(budget), 1)
        if not self._mixed:
            self._refuse("unmixed")
        if model_cfg.two_kind:
            # every lane's most at once, so that a chunk or a decode page
            # can always be had by taking back reusable blocks: the window
            # behind each lane's next row and its decode growth, and the
            # one budget of chunk rows the lanes share
            ps_ = self.cfg.page_size
            grow = 2 + self.cfg.grow_chunk_pages + -(
                -3 * max(self.cfg.decode_block_size, self.cfg.multistep_max_k)
                // ps_
            )
            floor = (
                self.cfg.max_batch_size
                * (self.sched.window_lane_pages(0) + grow)
                + -(-self._mixed_budget // ps_)
            )
            if self.cfg.num_window_pages - 1 < floor:
                raise ValueError(
                    f"num_window_pages {self.cfg.num_window_pages} is under "
                    f"the {floor + 1} that {self.cfg.max_batch_size} lanes "
                    f"of window {model_cfg.sliding_window} and a mixed token "
                    f"budget of {self._mixed_budget} can hold at once"
                )
        # per-dispatch fresh-token accounting (the padded-token fraction
        # the long-context bench reports): real rows vs rows dispatched
        self.mixed_used_tokens = 0
        self.mixed_dispatched_tokens = 0
        # packed-shape compaction (ISSUE 13 satellite): LRU/merge budget
        # over the packed step's (Np, s_max) executable pairs;
        # DYN_PACKED_SHAPE_BUDGET retunes without a restart flag
        from .bucketing import PackedShapeBudget

        shape_budget = 16
        env_shapes = _os.environ.get("DYN_PACKED_SHAPE_BUDGET")
        if env_shapes:
            try:
                shape_budget = int(env_shapes)
            except ValueError:
                logger.warning(
                    "ignoring malformed DYN_PACKED_SHAPE_BUDGET=%r",
                    env_shapes,
                )
        # what the packed launch's kernel can hold at this model's widths
        # (checked again on every triple the budget resolves), whether it
        # walks a work list: every unified dispatch then takes the page
        # table at its full width (_dispatch_unified), and which rows it
        # may touch: the shape budget's rule (window or window-free)
        launch = self._packed_launch()
        self._packed_full_table = launch.walks_work_list
        self._packed_fits = launch.fits
        self._packed_shapes = PackedShapeBudget(shape_budget, launch.item_rows)
        # which kernels a packed dispatch takes, for its annotation in a
        # profiler trace: the latent path of the packed launch, and what
        # attends the fused steps after the first.  Both depend on the pool
        # and the head shape alone, so they are read once, here
        from . import attention as att

        with self.mesh_scope():
            self._latent_path = (
                att.latent_packed_path(self.kv.pages)
                if model_cfg.is_mla
                else None
            )
            # (the pool's heads: a trunk that packs KV heads into a row asks
            # as the step's trace will, config.kv_head_pack)
            self._decode_backend = att.decode_backend(
                self.kv.pages, model_cfg.num_heads, model_cfg.pool_head_dim,
                model_cfg.dtype,
            )
            self._packed_attn = (
                att._packed_backend(
                    self.kv.pages, model_cfg.num_heads,
                    model_cfg.pool_kv_heads, model_cfg.pool_head_dim,
                )
                if model_cfg.state_kind
                else None
            )
        # queue-side prefetch: window resolved here, walks issued by the
        # tick loop from queue position (_drive_prefetch), finished or
        # cancelled per request
        self._prefetch_window = max(int(self.cfg.kv_prefetch_window), 0)
        env_pf = _os.environ.get("DYN_KV_PREFETCH")
        if env_pf is not None and env_pf.strip():
            v = env_pf.strip().lower()
            if v in ("off", "false", "no"):
                self._prefetch_window = 0
            else:
                try:
                    self._prefetch_window = max(int(v), 0)
                except ValueError:
                    logger.warning("ignoring malformed DYN_KV_PREFETCH=%r", v)
        self._prefetch_issued: set = set()
        # guards _prefetch_issued: the tick coroutine adds (prefetch
        # drive), executor-side admission settles, and event-loop cancel
        # paths clear -- the check-then-act pairs in
        # _note_prefetch_admission/_cancel_prefetch race without it
        # (dynalint DT014) and could double-settle one request's pins
        self._prefetch_lock = threading.Lock()
        # async dispatch pipelining (ISSUE 13): the tick loop carries up
        # to ``_pipe_depth`` uncommitted dispatch generations -- tick N+1
        # plans/assembles/enqueues while tick N executes on device, and
        # commits consume results only when their async host copies have
        # landed (or the pipeline hits its depth: the one blocking
        # backpressure point).  DYN_ASYNC_DISPATCH=0 / --no-async-dispatch
        # pins the exact serial loop.
        self._async_dispatch = bool(self.cfg.async_dispatch)
        env_async = _os.environ.get("DYN_ASYNC_DISPATCH")
        if env_async is not None and env_async.strip():
            self._async_dispatch = env_async.strip().lower() not in (
                "0", "off", "false", "no"
            )
        self._pipe_depth = 2 if self._async_dispatch else 1
        # detok/stream fanout worker (async mode): commits hand their
        # events to a bounded queue consumed off the tick coroutine --
        # a slow SSE consumer backpressures the tick at the queue bound
        # instead of stretching every tick's fanout phase
        self._fanout_q: Optional[asyncio.Queue] = None
        self._fanout_task: Optional[asyncio.Task] = None
        self.buckets = prefill_buckets(self.cfg.page_size, self.cfg.max_seq_len)
        self._rng = jax.random.PRNGKey(self.cfg.seed)
        self._queues: Dict[str, asyncio.Queue] = {}
        self._cancelled: set = set()
        # disaggregation: request_id -> seq awaiting remote KV; deliveries
        # are applied by the tick loop at a controlled point
        self._external: Dict[str, SeqState] = {}
        self._deliveries: Dict[str, Tuple[np.ndarray, int]] = {}
        # chunked deliveries stage layer-group parts here until the tick
        # loop scatters them (incremental onboard with a completion barrier)
        self._chunked: Dict[str, _ChunkedDelivery] = {}
        self._external_deadline: Dict[str, float] = {}
        # chunked prefill: slotted seqs with prompt KV still being written,
        # one chunk dispatched per tick (interleaves with decode blocks)
        self._chunking: List[SeqState] = []
        self._external_errors: Dict[str, str] = {}
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._wake: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None
        # every device dispatch of this engine runs on this one thread.  On
        # a dp/tp mesh the thread carries it as JAX's context mesh for its
        # whole life: each trace made there runs the Pallas kernels through
        # shard_map (attention._per_shard), and jit keys its cache on it
        self._ex = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="jax-engine",
            initializer=self.mesh_scope,
        )
        self._running = False
        # device-resident decode state (tokens/seq_lens/active/...); rebuilt
        # from the scheduler mirrors whenever the slot layout changes; page
        # growth only swaps the device page table + limits (no drain)
        self._dev: Optional[Dict[str, Any]] = None
        self._dev_version = -1
        self._dev_growth = -1
        # host copy of the pushed limit_lens: detects capacity-paused lanes
        self._limit_host = np.zeros((self.cfg.max_batch_size,), np.int32)
        # first tokens injected on device but not yet host-committed; a state
        # re-push must re-apply them (mirrors still hold the placeholder)
        self._pending_injects: Dict[int, InflightPrefill] = {}
        self._prefix_hits = 0
        self._prefix_lookups = 0
        self._steps = 0
        self._tokens_generated = 0
        # recompute-resume accounting (bench preempt_resume_tok_s): KV
        # tokens re-prefilled after a recompute preemption and the
        # dispatch->commit seconds the lane spent not runnable for them
        self.resume_prefill_tokens = 0
        self.resume_prefill_seconds = 0.0
        # speculative decoding (spec/): per-request drafters propose draft
        # tokens from host token history; the batched verify step scores
        # them in one forward pass.  Engine-lifetime counters back the
        # bench acceptance numbers; the registry family is dynamo_spec_*.
        from ..runtime.metrics import SpecMetrics

        self.spec_metrics = SpecMetrics(metrics_registry)
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_verify_steps = 0
        # folded verify (ISSUE 15): speculating lanes' verify columns ride
        # the packed unified dispatch.  Requires the mixed plane;
        # DYN_SPEC_FOLD overrides config (serving-env-knob contract).
        self._fold_spec = bool(self.cfg.fold_spec_verify) and self._mixed
        env_fold = _os.environ.get("DYN_SPEC_FOLD")
        if env_fold is not None and env_fold.strip():
            self._fold_spec = (
                env_fold.strip().lower() not in ("0", "off", "false", "no")
                and self._mixed
            )
        # multi-step packed decode (ISSUE 16): requires the mixed plane
        # like folded verify.  DYN_MULTISTEP grammar: 0/off =
        # disabled (pins the exact single-step behavior), 1/on/adaptive =
        # the adaptive-K controller, an integer N > 1 = fixed K=N (test /
        # bench pinning).  Malformed values warn and keep config.
        self._multistep = bool(self.cfg.multistep_decode) and self._mixed
        self._multistep_fixed: Optional[int] = None  # None = adaptive
        self._multistep_max = max(int(self.cfg.multistep_max_k), 1)
        env_ms = _os.environ.get("DYN_MULTISTEP")
        if env_ms is not None and env_ms.strip():
            v = env_ms.strip().lower()
            if v in ("0", "off", "false", "no"):
                self._multistep = False
            elif v in ("1", "on", "true", "adaptive"):
                self._multistep = self._mixed
                self._multistep_fixed = None
            else:
                try:
                    k = int(v)
                    self._multistep = k > 1 and self._mixed
                    self._multistep_fixed = max(k, 1)
                    self._multistep_max = max(self._multistep_max, k)
                except ValueError:
                    logger.warning("ignoring malformed DYN_MULTISTEP=%r", v)
        # adaptive-K ramp state: consecutive pressure-free ticks double
        # the next block's K toward the ceiling; any pressure resets to 1
        self._ms_ramp = 1
        # the ramp's ceiling (ISSUE 42, engine/multistep.py): the smallest
        # block that outlasts the loop's own work per tick, from two means
        # the loop keeps whether it is watched or not.  Readings count
        # (``_ms_reads``, fixed at a tick's start) once the ramp has run
        # the widest block (``_ms_topped``): by then every fused
        # executable is built, so a warm-up still compiles every minted K
        # and no compile is read as work.  A tick's reading is its wall
        # time (``_tick_began`` to the next tick's start) less the time it
        # sat blocked in commits' fetches (``_fetch_wait``), taken of ticks
        # that enqueued a decode-only dispatch (``_tick_fused``).  Across
        # processes commits are lockstep and K has to be the same number
        # everywhere, which means of local clocks are not: there the
        # ceiling stays ``multistep_max_k``.
        self._ms_ceiling = FusedStepCeiling(self._multistep_max)
        self._ms_local = jax.process_count() == 1
        self._ms_topped = False
        self._ms_reads = False
        self._tick_began = 0.0
        self._fetch_wait = 0.0
        self._tick_fused = False
        # acceptance-aware auto-disable knobs (+ request-lifetime counters
        # backing the bench's spec_enabled_frac line)
        self._spec_auto_disable = bool(self.cfg.spec_auto_disable)
        env_auto = _os.environ.get("DYN_SPEC_AUTO_DISABLE")
        if env_auto is not None and env_auto.strip():
            self._spec_auto_disable = env_auto.strip().lower() not in (
                "0", "off", "false", "no"
            )
        self._spec_min_accept = float(self.cfg.spec_min_accept)
        self._spec_disable_after = max(int(self.cfg.spec_disable_after), 1)
        self.spec_armed_requests = 0
        self.spec_auto_disabled = 0
        # model-based drafter: load the second weight set and bind it to
        # this engine under kind "model" (requests opt in per-request);
        # env wins
        self.model_drafter: Optional[Any] = None
        draft_spec = self.cfg.draft_model
        env_draft = _os.environ.get("DYN_DRAFT_MODEL")
        if env_draft is not None and env_draft.strip():
            draft_spec = env_draft.strip()
            if draft_spec.lower() in ("0", "off", "none"):
                draft_spec = None
        if draft_spec:
            self._init_model_drafter(draft_spec)
        # tick-phase profiler (runtime/profiling.py): the process-wide
        # instance, armed by DYN_TICK_PROFILE / profiler.enable().  The
        # loop opens one tick record per iteration when enabled;
        # ``self._tick`` is the in-progress record every instrumented
        # site consults -- None (one attribute check) when disabled.
        self.profiler = profiling.profiler
        self._tick: Optional[Any] = None

    # -- lifecycle ----------------------------------------------------------

    @staticmethod
    def resolve_mesh(
        cfg: Optional["EngineConfig"], model_cfg: ModelConfig
    ) -> Optional[jax.sharding.Mesh]:
        """The engine-startup dp x tp mesh from config + env, or None for
        single-chip serving.  ``DYN_TP`` / ``DYN_DP`` win outright over
        EngineConfig.tp/dp (a set ``DYN_TP=1`` disarms a config-armed tp);
        the tp degree is validated against the model's head geometry
        before any device is touched."""
        from ..parallel.mesh import env_parallel_spec, serving_mesh

        cfg = cfg or EngineConfig()
        env = env_parallel_spec()
        tp = env["tp"] if env["tp"] is not None else cfg.tp
        dp = env["dp"] if env["dp"] is not None else cfg.dp
        if max(tp, dp) <= 1:
            return None
        model_cfg.validate_tp(tp)
        if dp > 1 and cfg.max_batch_size % dp:
            # same fail-fast contract as validate_tp: an indivisible dp
            # would drop the 'dp' axis from every decode-state spec
            # (_compatible_spec) and disable balanced admission -- all dp
            # chips then compute the full replicated batch while the
            # operator believes the deployment is data-parallel
            raise ValueError(
                f"dp={dp} does not divide max_batch_size="
                f"{cfg.max_batch_size}: batch lanes shard over dp"
            )
        return serving_mesh(tp=tp, dp=dp)

    def mesh_scope(self):
        """Make this engine's dp/tp mesh JAX's context mesh (``jax.set_mesh``:
        at once for the calling thread, restored on leaving a ``with``).
        The dispatch thread keeps it; a trace made on any other thread
        (construction, a test, chip_smoke's lowering) enters it here.  No
        mesh and sp/pp meshes, whose prefill routes bring their own
        shard_map, set none."""
        if self.mesh is None or self._sp > 1 or self._pp > 1:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def _packed_launch(self):
        """Which kernel serves this engine's packed dispatches
        (``attention.packed_launch``: whether it walks a work list, and
        ``fits(Np, s_max)``), once the widest shape a mixed engine's budget
        can mint (one lane's chunk of the whole budget beside other lanes'
        rows) has passed ``fits``: a budget the kernel cannot serve fails
        engine construction, not a user's first long prompt."""
        from . import attention as att

        m = self.model_cfg
        with self.mesh_scope():  # the gates read tp from the context mesh
            launch = att.packed_launch(
                self.kv.pages, m.num_heads, m.pool_kv_heads, m.pool_head_dim,
                m.dtype,
            )
        page = self.cfg.page_size
        s_top = pow2_bucket(max(self._mixed_budget, page))
        if self._mixed and not launch.fits(2 * s_top, s_top):
            ok = s_top
            while ok > page and not launch.fits(2 * ok, ok):
                ok //= 2
            raise ValueError(
                f"mixed_token_budget {self._mixed_budget} needs a packed "
                f"attention shape (Np={2 * s_top}, s_max={s_top}) that "
                f"does not fit the kernel's VMEM at {m.num_heads} heads x "
                f"{m.head_dim}; the largest budget that fits is {ok}"
            )
        return launch

    @classmethod
    def random_init(
        cls,
        model_cfg: ModelConfig,
        cfg: Optional[EngineConfig] = None,
        seed: int = 0,
        mesh: Optional[jax.sharding.Mesh] = None,
    ) -> "JaxEngine":
        if mesh is None:
            mesh = cls.resolve_mesh(cfg, model_cfg)
        params = init_params(model_cfg, jax.random.PRNGKey(seed))
        if mesh is not None:
            from ..parallel.sharding import shard_params

            params = shard_params(params, model_cfg, mesh)
        return cls(model_cfg, params, cfg, mesh=mesh)

    @classmethod
    def from_pretrained(
        cls,
        model_path: str,
        cfg: Optional[EngineConfig] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        model_cfg: Optional[ModelConfig] = None,
    ) -> "JaxEngine":
        import os

        from .weights import load_safetensors_params

        # callers that already parsed the config (cli validate_tp) pass it
        # through instead of paying a second disk read+parse
        if model_cfg is None:
            model_cfg = ModelConfig.from_pretrained(model_path)
        if mesh is None:
            # engine-startup TP: shardings reach the streaming weight
            # loader, so a 70B-class checkpoint loads straight into its
            # per-chip slices instead of materializing whole tensors
            mesh = cls.resolve_mesh(cfg, model_cfg)
        shardings = None
        if mesh is not None:
            from ..parallel.sharding import param_shardings

            shardings = param_shardings(model_cfg, mesh)
        has_st = os.path.isdir(model_path) and any(
            f.endswith(".safetensors") for f in os.listdir(model_path)
        )
        if has_st:
            params = load_safetensors_params(
                model_path, model_cfg, shardings=shardings
            )
        else:
            # GGUF checkpoint: dequantize-on-load (llm/gguf.py)
            from ..llm.gguf import find_gguf_file, load_gguf_params

            gguf = find_gguf_file(model_path)
            if gguf is None:
                raise FileNotFoundError(
                    f"{model_path}: no .safetensors and no .gguf weights"
                )
            params = load_gguf_params(
                gguf, model_cfg, shardings=shardings
            )
        return cls(model_cfg, params, cfg, mesh=mesh)

    async def start(self) -> None:
        if self._running:
            return
        self._running = True
        self._loop = asyncio.get_running_loop()
        self._wake = asyncio.Event()
        if self.offload_engine is not None:
            # a ready swap blob must wake a sleeping tick loop (all lanes
            # parked = nothing runnable = the loop is waiting on _wake)
            # dynalint: disable=DT014 -- installed in start() before the
            # tick task (and any executor dispatch) exists
            self.offload_engine.wake_cb = self._wake_from_thread
        self._flightrec_key = profiling.flight_recorder.add_provider(
            "engine", self._flightrec_state
        )
        if self._async_dispatch:
            # bounded fanout lane: tick commits enqueue event batches,
            # the worker does the per-request queue puts off the tick
            # coroutine.  The bound is the tick's backpressure point.
            import os as _os

            try:
                depth = int(_os.environ.get("DYN_FANOUT_QUEUE", "64"))
            except ValueError:
                depth = 64
            self._fanout_q = asyncio.Queue(maxsize=max(depth, 1))
            self._fanout_task = asyncio.create_task(
                self._fanout_worker(), name="jax-engine-fanout"
            )
        self._task = asyncio.create_task(self._run(), name="jax-engine-loop")

    def _flightrec_state(self) -> Dict[str, Any]:
        """Queue/batch/KV occupancy for flight-recorder snapshots (called
        from failure edges on arbitrary threads: reads only)."""
        alloc = self.kv.allocator
        return {
            "waiting": len(self.sched.waiting),
            "active": self.sched.num_active,
            "slots": self.cfg.max_batch_size,
            "kv_pages_used": alloc.used_pages,
            "kv_pages_total": alloc.num_pages - 1,
            "chunking": len(self._chunking),
            "external_parked": len(self._external),
            "swapped": len(self._swapped),
            "tokens_generated": self._tokens_generated,
        }

    def _wake_from_thread(self) -> None:
        loop, wake = self._loop, self._wake
        if loop is None or wake is None:
            return
        try:
            loop.call_soon_threadsafe(wake.set)
        except RuntimeError:
            pass  # loop already closed during shutdown

    async def stop(self) -> None:
        self._running = False
        if self._wake is not None:
            self._wake.set()
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            except Exception:
                logger.debug("engine loop raised during stop", exc_info=True)
            self._task = None
        # drain the fanout lane AFTER the tick loop stops producing:
        # every committed event batch reaches its stream before teardown
        # (ordering per request is the queue's FIFO), then the worker
        # exits on the sentinel
        if self._fanout_task is not None:
            assert self._fanout_q is not None
            await self._fanout_q.put(None)
            try:
                await asyncio.wait_for(self._fanout_task, timeout=5.0)
            except (asyncio.TimeoutError, asyncio.CancelledError):
                self._fanout_task.cancel()
            except Exception:
                logger.debug("fanout worker raised during stop", exc_info=True)
            # anything a concurrent coroutine enqueued BEHIND the sentinel
            # (a fail_external racing shutdown) still delivers: a stream
            # that never sees its error/terminator hangs its consumer
            while not self._fanout_q.empty():
                item = self._fanout_q.get_nowait()
                if item is None:
                    continue
                try:
                    if isinstance(item, tuple) and item[0] == "error":
                        self._put_error(item[1], item[2])
                    else:
                        self._dispatch(item)
                except Exception:
                    logger.debug("late fanout drain failed", exc_info=True)
            self._fanout_task = None
            self._fanout_q = None
        self._ex.shutdown(wait=False)
        profiling.flight_recorder.remove_provider(
            getattr(self, "_flightrec_key", "engine"), self._flightrec_state
        )
        if self.offload_engine is not None:
            self.offload_engine.close()

    # -- AsyncEngine --------------------------------------------------------

    async def generate(
        self, request: Context[Any], _external: bool = False
    ) -> AsyncIterator[Annotated]:
        """Token-level generate; yields Annotated[LLMEngineOutput-dict]."""
        if not self._running:
            await self.start()
        data = request.data
        if isinstance(data, dict):
            req = PreprocessedRequest.from_dict(data)
        else:
            req = data
        seq = SeqState.from_request(request.id, req, self.sched.block_size)
        # ingress leg: the process received the request (the context's
        # stamp) -> it stands in this engine's queue (arrival_s, just taken)
        seq.created_s = request.created_s
        self.obs.ingress.observe(max(seq.arrival_s - seq.created_s, 0.0))
        if _external:
            # disaggregated: the prompt KV arrives via deliver_external
            seq.awaiting_kv = True
            self._external[request.id] = seq
            self._external_deadline[request.id] = (
                time.monotonic() + self.cfg.external_kv_timeout_s
            )
        ctx = request.ctx
        try:
            if self._seq_penalized(seq) and self.cfg.max_seq_len >= (
                1 << 15
            ):
                # packed-histogram bound (sampling.PROMPT_FLAG): prompt
                # occurrences accumulate FLAG each, so max_seq_len must
                # stay below 2^15 or the int32 packing can overflow --
                # fail the request loudly instead of sampling from a
                # silently corrupted penalty state
                raise ValueError(
                    "sampling penalties are unavailable at max_seq_len "
                    f">= 32768 (engine max_seq_len {self.cfg.max_seq_len})"
                )
            if (
                self._seq_penalized(seq)
                or seq.mm_embeds is not None
                or seq.speculation is not None
            ):
                # each leaves the packed step for a classic prefill or
                # verify dispatch, which takes a prompt's pages at once
                self._refuse("classic_dispatch")
            if seq.prompt_logprobs is not None:
                self._refuse("scoring")
            self._arm_speculation(seq)  # unknown drafter -> error stream
            self.sched.enqueue(seq)
        except ValueError as e:
            # surface as an error item, matching the remote prologue-error path
            self._external.pop(request.id, None)
            self._external_deadline.pop(request.id, None)
            message = str(e)

            async def err_stream() -> AsyncIterator[Annotated]:
                yield Annotated.from_error(message)

            return ResponseStream(ctx, err_stream())
        # queue-side prefetch is driven by the tick loop from queue
        # position (_drive_prefetch): the first _prefetch_window waiting
        # requests get tracked walks, so a deep queue cannot thrash the
        # host ring staging chains hours from admission
        queue: asyncio.Queue = asyncio.Queue()
        self._queues[request.id] = queue
        assert self._wake is not None
        self._wake.set()

        async def stream() -> AsyncIterator[Annotated]:
            try:
                while True:
                    get = asyncio.ensure_future(queue.get())
                    stop_waiter = asyncio.ensure_future(ctx.stopped())
                    done, _ = await asyncio.wait(
                        {get, stop_waiter}, return_when=asyncio.FIRST_COMPLETED
                    )
                    if get not in done:
                        get.cancel()
                        stop_waiter.cancel()
                        self._cancelled.add(request.id)
                        self._wake.set()
                        yield Annotated.from_data(
                            LLMEngineOutput.finished(FinishReason.CANCELLED).to_dict()
                        )
                        return
                    stop_waiter.cancel()
                    # dynalint: disable=DT001 -- 'get' is in 'done': result() is non-blocking
                    item = get.result()
                    if item is None:
                        return
                    yield item
            finally:
                self._queues.pop(request.id, None)
                if ctx.is_killed():
                    # kill() races the consumer's teardown against our
                    # stop_waiter branch above and usually wins (the
                    # ResponseStream cancels the producer first), so the
                    # cancellation must also be recorded here or the lane
                    # keeps decoding into a dropped queue, holding its
                    # KV pages until max_tokens
                    self._cancelled.add(request.id)
                    if self._wake is not None:
                        self._wake.set()

        return ResponseStream(ctx, stream())

    def _arm_speculation(self, seq: SeqState) -> None:
        """Attach a live SpecState to a request that asked for speculation.

        Eligibility: the lane needs a host-visible token history
        (``seq.blocks``; multimodal lanes opt out of block tracking) and no
        sampling penalties -- penalty histograms evolve token-by-token, so
        a multi-token verify cannot reproduce the sequential distribution;
        those requests silently keep the plain decode path (output is the
        contract, speculation is an optimization).  Unknown drafter kinds
        raise ValueError, surfacing as a request error like any other
        invalid option."""
        opts = seq.speculation
        if opts is None or not opts.enabled or opts.num_draft_tokens < 1:
            return
        if seq.blocks is None:
            return  # no token history to draft from (multimodal lane)
        if self._seq_penalized(seq):
            log_throttled(
                logger, "spec-penalized",
                "speculation disabled for a request with sampling "
                "penalties (multi-token verify cannot replay sequential "
                "penalty histograms)", level=logging.DEBUG,
            )
            return
        from ..spec import MAX_DRAFT_TOKENS, SpecState, make_drafter

        # the model drafter binds ENGINE-scoped, not through the
        # process-global registry: a stopped engine's draft weights must
        # not leak into (or silently serve) later engines in the process,
        # and the vocab check ran against THIS engine's target.  A "model"
        # request on an unarmed engine falls through to make_drafter,
        # which raises unless a test/extension registered its own.
        if opts.drafter == "model" and self.model_drafter is not None:
            drafter = self.model_drafter
        else:
            drafter = make_drafter(opts.drafter)  # raises on unknown kind
        seq.spec = SpecState(
            drafter=drafter,
            num_draft_tokens=min(int(opts.num_draft_tokens), MAX_DRAFT_TOKENS),
            kind=opts.drafter,
        )
        self.spec_metrics.requests.inc()
        self.spec_armed_requests += 1
        self.spec_metrics.enabled_frac.set(self.spec_enabled_frac)

    def _init_model_drafter(self, spec: str) -> None:
        """Load the draft model (second weight load) and bind it to THIS
        engine under drafter kind ``"model"`` (``_arm_speculation``
        resolves the kind engine-locally, so stopping the engine releases
        the draft weights with it -- the process-global registry stays
        for host-side/custom drafters).

        Runs once at engine construction on the caller thread -- no
        thread is spawned (the load is synchronous, like the target's).
        On a serving mesh the draft params shard over ``tp`` with the
        same explicit-shardings contract as the target's steps
        (parallel.sharding.make_sharded_drafter), so TP deployments get a
        TP drafter for free.  One shared ModelDrafter instance serves
        every request (``propose`` is stateless), keeping a single
        compile cache for the draft forward."""
        from ..spec.model_drafter import ModelDrafter, load_draft_model

        dcfg, dparams = load_draft_model(spec, mesh=self.mesh)
        if dcfg.vocab_size != self.model_cfg.vocab_size:
            raise ValueError(
                f"draft_model {spec!r} vocab {dcfg.vocab_size} != target "
                f"vocab {self.model_cfg.vocab_size}: drafts and targets "
                "must share one token space"
            )
        self.model_drafter = ModelDrafter(dparams, dcfg, mesh=self.mesh)
        logger.info(
            "model drafter armed: %s (%d layers, hidden %d%s)",
            spec, dcfg.num_layers, dcfg.hidden_size,
            ", tp-sharded" if self.mesh is not None else "",
        )

    @property
    def spec_enabled_frac(self) -> float:
        """Fraction of spec-armed requests still drafting (1 -
        auto-disabled / armed) -- the bench's acceptance-aware health
        number next to spec_accept_rate."""
        if not self.spec_armed_requests:
            return 1.0
        return 1.0 - self.spec_auto_disabled / self.spec_armed_requests

    async def embed(self, token_batches: List[List[int]]) -> List[List[float]]:
        """Pooled embeddings for pre-tokenized inputs (/v1/embeddings).

        Batches inputs into one bucket-padded forward per call (grouped so
        one oversized outlier doesn't balloon every lane's pad), mean-pools
        valid positions, L2-normalizes.  Runs on the engine executor thread,
        serialized with the tick loop -- the trunk forward reads the KV
        buffer but never writes it, so in-flight decode state is untouched.

        Latency note: that serialization means a large embedding call
        head-of-line-blocks every in-flight token stream for its full
        forward, inflating ITL by roughly the embed duration.  For
        latency-sensitive graphs, run embeddings on a dedicated worker
        (``run in=dyn out=jax`` serving only the embed endpoint) rather
        than colocating them with decode.
        """
        if not token_batches:
            return []
        self._refuse("embedding")
        for t in token_batches:
            if not t:
                raise ValueError("embedding input must be non-empty")
            if len(t) > self.cfg.max_seq_len:
                raise ValueError(
                    f"embedding input of {len(t)} tokens exceeds max_seq_len"
                    f" {self.cfg.max_seq_len}"
                )
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(self._ex, self._embed_sync, token_batches)

    def _embed_sync(self, token_batches: List[List[int]]) -> List[List[float]]:
        compile_sentry.set_entry("embed_step")
        from .step import embed_step

        out: List[Optional[List[float]]] = [None] * len(token_batches)
        order = sorted(range(len(token_batches)), key=lambda i: len(token_batches[i]))
        B = self.cfg.max_batch_size
        for start in range(0, len(order), B):
            group = order[start : start + B]
            bucket = pick_bucket(
                self.buckets, max(len(token_batches[i]) for i in group)
            )
            # pad to a power-of-two batch (the _pad_batch convention) so
            # group size doesn't multiply compile-cache entries; pad lanes
            # have length 0 and come out as zero rows
            Bp = min(self._pad_batch(len(group)), B)
            toks = np.zeros((Bp, bucket), np.int32)
            lens = np.zeros((Bp,), np.int32)
            for row, i in enumerate(group):
                t = token_batches[i]
                toks[row, : len(t)] = t
                lens[row] = len(t)
            vecs = np.asarray(
                embed_step(
                    self.params,
                    self.model_cfg,
                    self.kv.pages,
                    jnp.asarray(toks),
                    jnp.asarray(lens),
                )
            )
            for row, i in enumerate(group):
                out[i] = vecs[row].tolist()
        return out  # type: ignore[return-value]

    # -- disaggregation (SURVEY.md 5.8: blockset export/import over the data
    # plane replaces NIXL one-sided writes) --------------------------------

    async def generate_external(
        self, request: Context[Any]
    ) -> AsyncIterator[Annotated]:
        """Admit a request whose prompt KV a remote prefill worker delivers;
        the lane holds pages but decodes only after deliver_external."""
        self._refuse("disagg_serving")
        return await self.generate(request, _external=True)

    def _refuse(self, capability: str) -> None:
        """Raise the sentence with which this model's kind of cache refuses
        ``capability``, if it does (the table is kv_cache.KV_REFUSALS)."""
        refuse(self.model_cfg, capability)

    def awaiting_external(self, request_id: str) -> bool:
        """True while the request is admitted (or queued) and still expects a
        remote prefill delivery."""
        return request_id in self._external

    def deliver_external(
        self,
        request_id: str,
        kv_blob: np.ndarray,
        first_token: int,
        lp_row: Optional[np.ndarray] = None,
    ) -> bool:
        """Hand over a remote prefill's KV (the blob of the prompt's pages)
        plus its sampled first token (and, optionally, the packed logprob
        row the prefill worker sampled it from -- without it a logprobs
        request's first token would ship without its logprob, leaving the
        OpenAI arrays one short).  Returns False when the request is no
        longer waiting (cancelled/failed).  Applied by the tick loop at its
        next iteration -- scheduler state is never touched from here."""
        self._refuse("kv_delivery")
        if request_id not in self._external:
            return False
        arr = np.asarray(first_token).reshape(-1)
        if arr.size > 1 and lp_row is None:
            # caller handed the packed row itself as first_token (the
            # prefill_export return): use it for the logprob too
            lp_row = arr.astype(np.int32)
        self._deliveries[request_id] = (kv_blob, int(arr[0]), lp_row)
        # the KV is in hand: the remote-prefill deadline's job is done.  A
        # delivery that arrives while the request still waits for a slot
        # must not be discarded by the timeout scan (the remaining wait is
        # for decode capacity, not for the prefill worker).
        self._external_deadline.pop(request_id, None)
        if self._wake is not None:
            self._wake.set()
        return True

    def begin_external_chunked(
        self,
        request_id: str,
        shape: Tuple[int, ...],
        dtype: str,
    ) -> bool:
        """Open a chunked KV delivery for a parked external request: the
        sender streams layer-group chunks via :meth:`deliver_external_chunk`
        and closes with :meth:`commit_external_chunked`.  The pipelined
        counterpart of :meth:`deliver_external` -- pages scatter as chunks
        arrive instead of after the whole blob lands.  The completion
        barrier is layer coverage against ``shape[0]``, so chunk
        granularity is entirely the sender's choice."""
        if request_id not in self._external:
            return False
        self._chunked[request_id] = _ChunkedDelivery(
            shape=tuple(int(s) for s in shape),
            dtype=str(dtype),
        )
        return True

    def deliver_external_chunk(
        self,
        request_id: str,
        layer_lo: int,
        layer_hi: int,
        arr: np.ndarray,
    ) -> bool:
        """Stage one layer-group chunk (layers ``[layer_lo, layer_hi)`` of the
        prompt's blob); the tick loop scatters it into the lane's pages at
        its next iteration (or as soon as the lane gets a slot)."""
        self._refuse("kv_delivery")
        rec = self._chunked.get(request_id)
        if rec is None or request_id not in self._external:
            return False
        rec.parts.append((int(layer_lo), int(layer_hi), arr))
        if self._wake is not None:
            self._wake.set()
        return True

    def commit_external_chunked(
        self,
        request_id: str,
        first_token: int,
        lp_row: Optional[np.ndarray] = None,
    ) -> bool:
        """Close a chunked delivery: all chunks are in (or staged); commit
        the remotely-sampled first token once every layer has scattered --
        the completion barrier before the lane's first decode step."""
        rec = self._chunked.get(request_id)
        if rec is None or request_id not in self._external:
            return False
        arr = np.asarray(first_token).reshape(-1)
        if arr.size > 1 and lp_row is None:
            lp_row = arr.astype(np.int32)
        rec.first = int(arr[0])
        rec.lp_row = lp_row
        rec.done = True
        # the KV is in hand; any remaining wait is for decode capacity, not
        # the prefill worker (mirrors deliver_external)
        self._external_deadline.pop(request_id, None)
        if self._wake is not None:
            self._wake.set()
        return True

    def fail_external(self, request_id: str, message: str) -> bool:
        """Remote prefill reported failure: fail the parked request instead of
        letting it ride out the delivery timeout."""
        if request_id not in self._external:
            return False
        self._external_errors[request_id] = message
        if self._wake is not None:
            self._wake.set()
        return True

    def _coerce_blob(self, blob):
        """Bring a delivered/onboarded blob into this pool's dtype domain
        (kv_cache.coerce_kv_blob): same-domain blobs pass through
        untouched -- the byte-exact round trip -- while cross-geometry
        deliveries (a bf16 prefiller feeding an int8 decode pool, or an
        old full-width tier blob restoring into a quantized pool) convert
        through the shared quantization rule."""
        return coerce_kv_blob(blob, self.kv.quantized, self.kv.dtype)

    def _expected_blob_shape(self, seq: SeqState) -> Tuple[int, ...]:
        n_pages = -(-len(seq.prompt) // self.cfg.page_size)
        return blob_shape(self.kv.pages.shape, n_pages)

    def _drop_external(self, rid: str, message: str) -> None:
        """Fail one parked external request without touching the rest of the
        batch (the _fail_all hammer is for engine-wide faults only)."""
        seq = self._external.pop(rid, None)
        self._deliveries.pop(rid, None)
        self._chunked.pop(rid, None)
        self._external_deadline.pop(rid, None)
        if seq is None or seq.finish is not None:
            return
        self._fail_seq(seq, message)
        self.sched.cancel(seq)

    def _process_deliveries(self) -> List[Tuple[Any, ...]]:
        """Tick-loop side: returns work items whose device dispatch is due --
        ``("blob", seq, first, lp_row)`` for a monolithic delivery,
        ``("chunks", seq, parts)`` for staged layer-group scatters, and
        ``("commit", seq, first, lp_row)`` once a chunked delivery's barrier
        clears.  Drops deliveries for dead requests; fails parked lanes
        whose prefill errored, mis-shaped, or timed out."""
        for rid, msg in list(self._external_errors.items()):
            self._external_errors.pop(rid)
            self._drop_external(rid, f"remote prefill failed: {msg}")
        out: List[Tuple[Any, ...]] = []
        for rid in list(self._deliveries):
            blob, first, lp_row = self._deliveries.pop(rid)
            seq = self._external.pop(rid, None)
            if seq is None or seq.finish is not None:
                continue
            if seq.slot < 0:
                # not yet admitted: re-queue the delivery until plan() gives
                # the seq a slot and pages (or it dies)
                self._external[rid] = seq
                self._deliveries[rid] = (blob, first, lp_row)
                continue
            expect = self._expected_blob_shape(seq)
            if (
                tuple(blob.shape) != expect
                or blob_num_pages(expect) > len(seq.pages)
            ):
                # a mis-configured prefill worker (page_size/model mismatch)
                # must not take down the whole decode batch
                self._external_deadline.pop(rid, None)
                self._fail_seq(
                    seq,
                    f"remote prefill KV shape {tuple(blob.shape)} does not "
                    f"match decode geometry {expect}",
                )
                self.sched.cancel(seq)
                continue
            self._external_deadline.pop(rid, None)
            seq._kv_blob = blob  # type: ignore[attr-defined]
            out.append(("blob", seq, first, lp_row))
        out.extend(self._process_chunked_deliveries())
        if self._external_deadline:
            now = time.monotonic()
            for rid, deadline in list(self._external_deadline.items()):
                if now >= deadline:
                    self._drop_external(
                        rid,
                        "timed out waiting for remote prefill KV "
                        f"({self.cfg.external_kv_timeout_s:.0f}s)",
                    )
        return out

    def _process_chunked_deliveries(self) -> List[Tuple[Any, ...]]:
        """Chunked-delivery bookkeeping for :meth:`_process_deliveries`:
        release staged layer-group parts of admitted lanes for scatter, and
        emit the first-token commit once a delivery's barrier (``done`` +
        every layer applied or in this tick's scatter list) clears."""
        out: List[Tuple[Any, ...]] = []
        for rid in list(self._chunked):
            rec = self._chunked[rid]
            seq = self._external.get(rid)
            if seq is None or seq.finish is not None:
                del self._chunked[rid]
                continue
            if seq.slot < 0:
                continue  # not admitted yet: parts stay staged
            if not rec.validated:
                expect = self._expected_blob_shape(seq)
                if (
                    rec.shape != expect
                    or blob_num_pages(expect) > len(seq.pages)
                ):
                    del self._chunked[rid]
                    self._external.pop(rid, None)
                    self._external_deadline.pop(rid, None)
                    self._fail_seq(
                        seq,
                        f"remote prefill KV shape {rec.shape} does not "
                        f"match decode geometry {expect}",
                    )
                    self.sched.cancel(seq)
                    continue
                rec.validated = True
            L = blob_num_layers(rec.shape)
            bad = next(
                (
                    (lo, hi, arr)
                    for lo, hi, arr in rec.parts
                    if not (0 <= lo < hi <= L)
                    or tuple(arr.shape) != blob_shape(
                        rec.shape, num_layers=hi - lo
                    )
                ),
                None,
            )
            if bad is not None:
                lo, hi, arr = bad
                del self._chunked[rid]
                self._external.pop(rid, None)
                self._external_deadline.pop(rid, None)
                self._fail_seq(
                    seq,
                    f"remote prefill KV chunk layers [{lo},{hi}) shape "
                    f"{tuple(arr.shape)} does not match decode geometry "
                    f"{rec.shape}",
                )
                self.sched.cancel(seq)
                continue
            if rec.parts:
                parts, rec.parts = rec.parts, []
                rec.applied_layers += sum(hi - lo for lo, hi, _ in parts)
                out.append(("chunks", seq, parts))
            if rec.done and not rec.parts:
                del self._chunked[rid]
                self._external.pop(rid, None)
                self._external_deadline.pop(rid, None)
                if rec.applied_layers != L:
                    self._fail_seq(
                        seq,
                        f"incomplete chunked KV delivery: "
                        f"{rec.applied_layers} of {L} layers",
                    )
                    self.sched.cancel(seq)
                    continue
                out.append(("commit", seq, rec.first, rec.lp_row))
        return out

    def _lane_scatter_ids(self, seq: SeqState) -> Tuple[int, int, np.ndarray]:
        """Page-bucketed destination ids for scattering a delivered blob
        into ``seq``'s pages: pad slots target trash page 0 with zero
        content, so compile-cache entries stay few across prompt sizes.
        The single source of the bucket/trash-page convention for both the
        monolithic and the chunked delivery scatters."""
        n_pages = -(-len(seq.prompt) // self.cfg.page_size)
        bucket = pick_page_bucket(n_pages, self.sched.max_pages)
        ids = np.zeros((bucket,), np.int32)
        ids[:n_pages] = seq.pages[:n_pages]
        return n_pages, bucket, ids

    def _apply_external_chunks(
        self, seq: SeqState, parts: List[Tuple[int, int, np.ndarray]]
    ) -> None:
        """Executor thread: scatter staged layer-group chunks into the
        lane's pages (the incremental half of a chunked delivery; the
        first-token commit waits for the barrier)."""
        compile_sentry.set_entry("kv_pages")
        _n_pages, bucket, ids = self._lane_scatter_ids(seq)
        ids_dev = jnp.asarray(ids)
        for lo, hi, arr in parts:
            padded = pad_page_axis(
                self._coerce_blob(blob_to_host(arr)), bucket
            )
            # dynalint: disable=DT014 -- the worker-side reader
            # (prefill_export_batch.materialize) touches only immutable kv
            # geometry (shard_geometry); pages rebinds stay tick-domain
            self.kv.pages = self._fns.scatter_layer_pages(
                self.kv.pages,
                jnp.asarray(np.arange(lo, hi, dtype=np.int32)),
                ids_dev,
                as_device_blob(padded),
            )

    def _apply_external_kv(
        self,
        seq: SeqState,
        first_token: int,
        lp_row: Optional[np.ndarray] = None,
    ) -> StepEvent:
        """Executor thread: scatter the delivered KV into the lane's pages,
        then commit the remotely-sampled first token."""
        compile_sentry.set_entry("kv_pages")
        blob = seq._kv_blob  # type: ignore[attr-defined]
        del seq._kv_blob  # type: ignore[attr-defined]
        # donated, jitted scatter (scatter_block_pages): an out-of-jit
        # .at[].set would materialize a full copy of the KV pool per
        # delivery.  Destination ids are page-bucketed by the shared
        # helper (blob shape was validated against the prompt's page count
        # in _process_deliveries).
        _n_pages, bucket, ids = self._lane_scatter_ids(seq)
        padded = pad_page_axis(self._coerce_blob(blob), bucket)
        self.kv.pages = self._fns.scatter_block_pages(
            self.kv.pages, jnp.asarray(ids), as_device_blob(padded)
        )
        return self._apply_external_commit(seq, first_token, lp_row)

    def _apply_external_commit(
        self,
        seq: SeqState,
        first_token: int,
        lp_row: Optional[np.ndarray] = None,
    ) -> StepEvent:
        """Executor thread: the KV is fully in the lane's pages (monolithic
        scatter or chunked barrier cleared); commit the remotely-sampled
        first token and wake the lane."""
        seq.awaiting_kv = False
        lp, top = None, None
        if lp_row is not None and len(lp_row) >= 2:
            from .sampling import unpack_sampled_logprobs

            N = (len(lp_row) - 2) // 2
            _tok, lp_v, tids, tlps = unpack_sampled_logprobs(
                np.asarray(lp_row, np.int32), N
            )
            lp = float(lp_v)
            if N:
                top = [[int(i), float(l)] for i, l in zip(tids, tlps)]
        ev = self.sched.commit_prefill_token(seq, first_token, lp, top)
        # membership semantics changed (parked -> live): fold the lane into
        # the device state at the next dispatch
        if seq.slot >= 0:
            self.sched.dirty_slots.add(seq.slot)
        return ev

    async def prefill_export(
        self, req: PreprocessedRequest
    ) -> Tuple[np.ndarray, int]:
        """Prefill-worker side: run a standalone prefill into scratch pages,
        return (the blob of the prompt's pages, first_token) and free
        the scratch.  Serialized with the tick loop via the engine executor."""
        self._refuse("prefill_export")
        if not self._running:
            await self.start()
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(self._ex, self._prefill_export, req)

    def _prefill_export(self, req: PreprocessedRequest) -> Tuple[np.ndarray, int]:
        compile_sentry.set_entry("kv_export")
        prompt = list(req.token_ids)
        if not prompt:
            raise ValueError("empty prompt")
        n_pages = -(-len(prompt) // self.cfg.page_size)
        pages = self.kv.allocator.alloc(n_pages)
        try:
            seq = SeqState.from_request("export", req, self.sched.block_size)
            sampled = self._dispatch_full_prefill(seq, prompt, pages)
            ids = np.asarray(pages, np.int32)
            blob = assemble_blob(self.kv.read_pages(ids))
            # the full packed row (token | logprob | tops): delivery carries
            # it so a logprobs request's first token keeps its logprob
            row = np.asarray(jax.device_get(sampled))[0]
            return blob, row
        finally:
            self.kv.allocator.free(pages)

    async def prefill_export_batch(
        self, reqs: List[PreprocessedRequest], device: bool = False
    ) -> List[Any]:
        """Batched :meth:`prefill_export`: one padded dispatch + one device
        transfer for a burst of remote-prefill jobs (the prefill worker
        drains its queue into this).  Returns one entry per request, either
        ``(kv_blob, first_token)`` or the per-request ``Exception`` -- one
        bad prompt must not fail its batch-mates.  Shares the dispatch site
        with the aggregated path, preserving disagg == aggregated output.

        ``device=True`` keeps the KV blobs device-resident (jax arrays) for
        same-process delivery into a colocated decode engine -- the TPU
        equivalent of the reference's NIXL device-to-device DMA
        (block_manager/storage/nixl.rs:173): the blob never transits the
        host.  Only the sampled first tokens come back (one tiny
        transfer).

        The wire path (``device=False``) dispatches device-resident slices
        on the engine executor but materializes them in a SEPARATE thread:
        the device->host transfer of the blobs no longer occupies the
        executor, so decode/prefill ticks overlap the transfer instead of
        serializing behind it (round-4 verdict #8)."""
        self._refuse("prefill_export")
        if not self._running:
            await self.start()
        loop = asyncio.get_running_loop()
        results = await loop.run_in_executor(
            self._ex, self._prefill_export_batch, reqs, True
        )
        if device:
            return results

        def materialize() -> List[Any]:
            idx = [i for i, r in enumerate(results) if isinstance(r, tuple)]
            if self.kv.shard_geometry is not None:
                # sharded pool: each blob assembles from its per-shard
                # head slices (one D2H per shard, no device all-gather)
                blobs = [assemble_blob(results[i][0]) for i in idx]
            else:
                # ONE bundled device_get for every blob (a per-item get
                # would pay one device round trip each on a high-RTT link)
                blobs = jax.device_get([results[i][0] for i in idx])
            out: List[Any] = list(results)
            for i, blob in zip(idx, blobs):
                out[i] = (blob_to_host(blob), results[i][1])
            return out

        return await asyncio.to_thread(materialize)

    def _prefill_export_batch(
        self, reqs: List[PreprocessedRequest], device: bool = False
    ) -> List[Any]:
        results: List[Any] = [None] * len(reqs)
        valid: List[int] = []
        for i, req in enumerate(reqs):
            if not req.token_ids:
                results[i] = ValueError("empty prompt")
            else:
                valid.append(i)
        # group similar lengths together so one long prompt doesn't pad the
        # whole group's bucket (the dispatch buckets to the group max)
        valid.sort(key=lambda i: len(reqs[i].token_ids))
        B = self.cfg.max_batch_size
        for start in range(0, len(valid), B):
            group = valid[start : start + B]
            try:
                self._export_group(reqs, group, results, device)
            except Exception:  # noqa: BLE001 - page pressure / bucket overflow
                # fall back to singles: the failure may be group-induced
                # (scratch pages for N prompts at once) and per-item errors
                # must land on their own request
                log_throttled(
                    logger, "export-group-fallback",
                    "grouped prefill export failed; retrying %d request(s) "
                    "individually", len(group), exc_info=True,
                )
                for i in group:
                    try:
                        results[i] = self._prefill_export(reqs[i])
                    except Exception as exc:  # noqa: BLE001
                        results[i] = exc
        return results

    def _export_group(
        self,
        reqs: List[PreprocessedRequest],
        group: List[int],
        results: List[Any],
        device: bool = False,
    ) -> None:
        compile_sentry.set_entry("kv_export")
        ps = self.cfg.page_size
        allocated: List[List[int]] = []
        try:
            for i in group:
                n_pages = -(-len(reqs[i].token_ids) // ps)
                allocated.append(self.kv.allocator.alloc(n_pages))
        except Exception:
            for pages in allocated:
                self.kv.allocator.free(pages)
            raise
        try:
            items = [
                (
                    SeqState.from_request(
                        "export", reqs[i], self.sched.block_size
                    ),
                    list(reqs[i].token_ids),
                    pages,
                )
                for i, pages in zip(group, allocated)
            ]
            Bp = min(self._pad_batch(len(items)), self.cfg.max_batch_size)
            sampled = self._dispatch_full_prefill_batch(items, Bp)
            all_ids = np.concatenate(
                [np.asarray(p, np.int32) for p in allocated]
            )
            if device:
                # device-resident export: the gather materializes a copy of
                # the group's pages on device (freeing the scratch pages
                # below is safe), and only the first tokens come to host
                blob_all = self.kv.read_pages(jnp.asarray(all_ids))
            else:
                # one transfer per shard for the whole group's pages
                blob_all = assemble_blob(self.kv.read_pages(all_ids))
            firsts = np.asarray(jax.device_get(sampled))  # [Bp, 2 + 2N]
            off = 0
            for row, (i, pages) in enumerate(zip(group, allocated)):
                k = len(pages)
                results[i] = (blob_pages(blob_all, off, off + k), firsts[row])
                off += k
        finally:
            for pages in allocated:
                self.kv.allocator.free(pages)

    async def prefill_export_batch_stream(
        self,
        reqs: List[PreprocessedRequest],
        layers_per_chunk: Optional[int] = None,
    ) -> List[Any]:
        """Chunked, layer-pipelined :meth:`prefill_export_batch`: the batch
        prefill dispatches once, then each layer group is gathered on
        device, its device->host copy started asynchronously, and a
        :class:`KVExportStream` handed back BEFORE any blob materializes.
        The consumer streams chunk 0 onto the wire while chunks 1..N-1 are
        still transferring -- export-before-first-byte drops from the whole
        blob's transfer to one group's.

        ``layers_per_chunk`` pins the chunk granularity; None splits the
        stack into ~``DEFAULT_EXPORT_CHUNKS`` groups.  Returns one entry per
        request: a :class:`KVExportStream` or the per-request ``Exception``.
        Shares the dispatch site with the aggregated path, preserving
        disagg == aggregated output."""
        self._refuse("prefill_export")
        if not self._running:
            await self.start()
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._ex, self._prefill_export_batch_stream, reqs,
            layers_per_chunk,
        )

    def _prefill_export_batch_stream(
        self,
        reqs: List[PreprocessedRequest],
        layers_per_chunk: Optional[int] = None,
    ) -> List[Any]:
        results: List[Any] = [None] * len(reqs)
        valid: List[int] = []
        for i, req in enumerate(reqs):
            if not req.token_ids:
                results[i] = ValueError("empty prompt")
            else:
                valid.append(i)
        valid.sort(key=lambda i: len(reqs[i].token_ids))
        B = self.cfg.max_batch_size
        for start in range(0, len(valid), B):
            group = valid[start : start + B]
            try:
                self._export_group_stream(
                    reqs, group, results, layers_per_chunk
                )
            except Exception:  # noqa: BLE001 - page pressure, as in batch
                log_throttled(
                    logger, "export-stream-fallback",
                    "grouped streaming export failed; retrying %d "
                    "request(s) individually", len(group), exc_info=True,
                )
                for i in group:
                    try:
                        res = KVExportStream.from_blob(
                            *self._prefill_export(reqs[i])
                        )
                        res.shards = self.kv.shard_geometry
                        results[i] = res
                    except Exception as exc:  # noqa: BLE001
                        results[i] = exc
        return results

    def _export_group_stream(
        self,
        reqs: List[PreprocessedRequest],
        group: List[int],
        results: List[Any],
        layers_per_chunk: Optional[int] = None,
    ) -> None:
        """Executor thread: one padded prefill dispatch for the group, then
        per-layer-group device gathers with async host copies started; the
        scratch pages free as soon as the gathers are dispatched (device
        program order) and nothing blocks on the bulk transfer here --
        only the tiny sampled rows come to host."""
        compile_sentry.set_entry("kv_export")
        ps = self.cfg.page_size
        allocated: List[List[int]] = []
        try:
            for i in group:
                n_pages = -(-len(reqs[i].token_ids) // ps)
                allocated.append(self.kv.allocator.alloc(n_pages))
        except Exception:
            for pages in allocated:
                self.kv.allocator.free(pages)
            raise
        try:
            items = [
                (
                    SeqState.from_request(
                        "export", reqs[i], self.sched.block_size
                    ),
                    list(reqs[i].token_ids),
                    pages,
                )
                for i, pages in zip(group, allocated)
            ]
            Bp = min(self._pad_batch(len(items)), self.cfg.max_batch_size)
            sampled = self._dispatch_full_prefill_batch(items, Bp)
            all_ids = np.concatenate(
                [np.asarray(p, np.int32) for p in allocated]
            )
            L = self.model_cfg.num_layers
            spans = layer_chunk_spans(
                L, layers_per_chunk, DEFAULT_EXPORT_CHUNKS
            )
            ids_dev = jnp.asarray(all_ids)
            span_devs: List[Any] = []
            for lo, hi in spans:
                sl = self._fns.gather_layer_pages(
                    self.kv.pages,
                    jnp.asarray(np.arange(lo, hi, dtype=np.int32)),
                    ids_dev,
                )
                _start_host_copy(sl)
                span_devs.append(sl)
            firsts = np.asarray(jax.device_get(sampled))  # [Bp, 2 + 2N]
            shared = _GroupSpanExport(span_devs)
            off = 0
            for row, (i, pages) in enumerate(zip(group, allocated)):
                k = len(pages)
                results[i] = KVExportStream(
                    shape=blob_shape(self.kv.pages.shape, k),
                    dtype=str(self.kv.pages.dtype),
                    row=firsts[row],
                    spans=spans,
                    shards=self.kv.shard_geometry,
                    _group=shared,
                    _page_off=off,
                )
                off += k
        finally:
            for pages in allocated:
                self.kv.allocator.free(pages)

    async def export_blocks(
        self, seq_hashes: List[int]
    ) -> List[Tuple[int, np.ndarray, Dict[str, int]]]:
        """Export the longest resident prefix of ``seq_hashes`` as
        ``(hash, blob, meta)`` triples -- the donor side of cross-worker
        prefix onboarding (reference block_manager.rs:119-146 blockset
        export/import; G4).  Consults G1 (HBM pool, one bundled device
        transfer) then the offload tiers; stops at the first miss, because
        an importer can only use a contiguous prefix."""
        self._refuse("block_export")
        if not self._running:
            await self.start()
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._ex, self._export_blocks, seq_hashes
        )

    def _export_blocks(self, seq_hashes):
        out: List[Tuple[int, np.ndarray, Dict[str, int]]] = []
        pool = self.kv.allocator
        acquired: List[Any] = []
        if isinstance(pool, PagePool):
            try:
                for blk in pool.match(seq_hashes):
                    if pool.acquire(blk.sequence_hash) is None:
                        break
                    acquired.append(blk)
                if acquired:
                    all_ids = np.concatenate(
                        [np.asarray(b.pages, np.int32) for b in acquired]
                    )
                    blob_all = assemble_blob(self.kv.read_pages(all_ids))
                    off = 0
                    for blk in acquired:
                        k = len(blk.pages)
                        out.append(
                            (
                                blk.sequence_hash,
                                blob_pages(blob_all, off, off + k),
                                {
                                    "block_hash": blk.block_hash,
                                    "parent_sequence_hash": blk.parent_sequence_hash,
                                    "position": blk.position,
                                    "kv_dtype": str(self.kv.dtype),
                                },
                            )
                        )
                        off += k
            finally:
                for blk in acquired:
                    pool.release(blk.sequence_hash)
        # continue the chain into the offload tiers; the (possibly disk)
        # reads route through the offload thread -- this runs on the engine
        # executor, which may wait, but never does file I/O itself
        if self.offload_engine is not None:
            for h in seq_hashes[len(out) :]:
                hit = self.offload_engine.get_blocking(h)
                if hit is None:
                    break
                blob, meta = hit
                out.append((h, blob, meta.to_dict()))
        return out

    # -- metrics ------------------------------------------------------------

    def metrics(self) -> ForwardPassMetrics:
        alloc = self.kv.allocator
        hit_rate = (
            self._prefix_hits / self._prefix_lookups if self._prefix_lookups else 0.0
        )
        oe = self.offload_engine
        return ForwardPassMetrics(
            kv_active_blocks=alloc.used_pages,
            kv_total_blocks=alloc.num_pages - 1,
            num_requests_waiting=self.sched.num_waiting,
            gpu_cache_usage_perc=self.kv.usage,
            gpu_prefix_cache_hit_rate=hit_rate,
            request_active_slots=self.sched.num_active,
            request_total_slots=self.cfg.max_batch_size,
            # offload-plane warmth for KV-router placement: a worker whose
            # host tier holds blocks (and keeps hitting) beats a cold one
            host_tier_blocks=len(oe.host) if oe is not None else 0,
            disk_tier_blocks=(
                len(oe.disk) if oe is not None and oe.disk is not None else 0
            ),
            tier_hit_rate=oe.tier_hit_rate if oe is not None else 0.0,
        )

    @property
    def tokens_generated(self) -> int:
        return self._tokens_generated

    # -- the tick loop ------------------------------------------------------

    @hot_path
    def _entries_ready(self, entries: List[Any]) -> bool:
        """Non-blocking probe: have this generation's device results (and
        their async host copies) landed?  True means the commit's
        device_get is a copy, not a wait -- the async pipeline commits
        such generations immediately instead of carrying them."""
        for e in entries:
            if not _handles_ready(e.sampled):
                return False
            if (
                isinstance(e, InflightUnified)
                and e.spec_sampled is not None
                and not _handles_ready(e.spec_sampled)
            ):
                return False
            pfs = (
                e.entries
                if isinstance(e, InflightPrefillGroup)
                else e.finals
                if isinstance(e, InflightUnified)
                else [e] if isinstance(e, InflightPrefill) else []
            )
            for pf in pfs:
                if pf.prompt_lp is not None and not _handles_ready(
                    pf.prompt_lp
                ):
                    return False
        return True

    async def _emit_events(self, events: List[StepEvent]) -> None:
        """Hand a commit's events to the stream-fanout plane: the bounded
        worker queue in async mode (per-request ordering = the queue's
        FIFO; a full queue backpressures the tick), the direct in-tick
        fanout in serial mode (the exact legacy path)."""
        if not events:
            return
        q = self._fanout_q
        if q is not None:
            await q.put(events)
        else:
            self._dispatch(events)

    async def _fanout_worker(self) -> None:
        """Async-mode stream fanout: one FIFO consumer does the
        per-request queue puts (and the SLO/metrics notes inside
        ``_dispatch``) off the tick coroutine, so commit-to-client fanout
        cost never sits between two device dispatches.  Exits on the
        ``None`` sentinel ``stop()`` enqueues after the tick loop halts
        -- everything enqueued before the sentinel still delivers
        (drain-on-stop)."""
        assert self._fanout_q is not None
        while True:
            events = await self._fanout_q.get()
            if events is None:
                return
            # dynalint: disable=DT012 -- routes into the tick-phase
            # histogram (off-loop fanout contribution, the detok pattern)
            t0 = time.perf_counter()
            try:
                if isinstance(events, tuple) and events[0] == "error":
                    # a _fail_seq error frame riding the same FIFO as the
                    # token events it must not overtake
                    self._put_error(events[1], events[2])
                else:
                    self._dispatch(events)
            except Exception:  # fanout must never kill the worker
                logger.exception("stream fanout failed")
            if self.profiler.enabled:
                self.profiler.observe_phase(
                    # dynalint: disable=DT012 -- same histogram route
                    "fanout", time.perf_counter() - t0
                )

    async def _run(self) -> None:
        """The tick loop, software-pipelined over the device queue.

        Each iteration dispatches decode block i+1 *before* materializing
        block i's sampled tokens, so the ~RTT device->host transfer overlaps
        the next block's compute.  Batch-membership changes (admission,
        completion, revival) reach the device as per-lane row scatters
        (``_apply_dirty_rows``), never draining the pipeline: a drain per
        admission would serialize every block behind a device->host round
        trip.  Safety of the
        one-block lag rests on the device executing launches in order:
        writes from a lane whose request finished at commit time land before
        any later-dispatched prefill reuses its freed pages, and the
        later-dispatched row scatter deactivates the lane for subsequent
        blocks.

        With ``async_dispatch`` (the default), the loop is additionally
        DOUBLE-BUFFERED on the host side (ISSUE 13): up to
        ``_pipe_depth`` dispatch generations stay uncommitted, commits
        fire only when a generation's results have actually landed (or
        the pipeline is full -- the one blocking backpressure point), and
        stream fanout rides the bounded worker queue.  The host's plan/
        assemble/commit work therefore overlaps device compute instead of
        sitting serially between dispatches.  Scheduler state the next
        plan reads is the same speculative one-generation-behind view the
        one-deep pipeline always used -- commit's slot-snapshot guards
        and the stop-rule replay reconcile it, and a cancellation/
        preemption/stop landing between enqueue(N+1) and commit(N) rolls
        the stale generation's lanes back exactly like a stale decode
        block (the InflightVerify discipline).
        """
        import collections

        loop = asyncio.get_running_loop()
        assert self._wake is not None
        # FIFO of dispatched-but-uncommitted generations, oldest first;
        # each generation is one tick's entry list (the legacy ``pending``
        # is the depth-1 special case)
        inflight: "collections.deque[List[Any]]" = collections.deque()
        self._inflight = inflight
        prof = self.profiler
        while self._running:
            try:
                # tick-phase profiling: one record per working iteration,
                # marks attribute elapsed time to phases (disabled = one
                # attribute check here and a None check per site)
                tick = prof.begin_tick() if prof.enabled else None
                self._tick = tick
                self._read_tick_clock()
                self._process_cancellations()
                for work in self._process_deliveries():
                    if work[0] == "blob":
                        _, seq, first, lp_row = work
                        ev = await loop.run_in_executor(
                            self._ex, self._apply_external_kv, seq, first,
                            lp_row,
                        )
                        self._dispatch([ev])
                    elif work[0] == "chunks":
                        _, seq, parts = work
                        await loop.run_in_executor(
                            self._ex, self._apply_external_chunks, seq, parts
                        )
                    else:  # "commit": the chunked barrier cleared
                        _, seq, first, lp_row = work
                        ev = await loop.run_in_executor(
                            self._ex, self._apply_external_commit, seq,
                            first, lp_row,
                        )
                        self._dispatch([ev])
                for seq, rec in self._process_swaps():
                    # swap-in restore: scatter the parked KV back into the
                    # lane's pages (chunked, executor thread) and clear the
                    # barrier -- no token is emitted, the lane just resumes
                    await loop.run_in_executor(
                        self._ex, self._apply_swap_in, seq, rec
                    )
                if tick is not None:
                    tick.mark("onboard")
                if (
                    not self.sched.has_runnable_work
                    and not inflight
                    and not self._chunking
                    and not self.sched.mix_pending
                ):
                    # NOTE mix_pending: with the async pipeline a
                    # fully-committed tick can reach this gate while a
                    # mixed-mode chunked prefill still owes chunks (the
                    # serial loop always carried that tick's dispatch in
                    # ``pending``, masking the case)
                    watched = tick is not None
                    if tick is not None:
                        tick.discard()
                        self._tick = tick = None
                    self._wake.clear()
                    # bounded wait with parked lanes, so their timeouts
                    # still fire
                    await self._park(
                        1.0 if self._external or self._swapped else None,
                        watched,
                    )
                    continue
                self._drive_prefetch()
                if tick is not None:
                    tick.mark("onboard")
                # async mode: commit generations whose results ALREADY
                # landed before planning -- freed slots/pages and committed
                # stops reach this tick's plan instead of next tick's, and
                # preemption sees the same committed state the serial loop
                # would (swap eligibility must not shrink just because the
                # pipeline was on).  Non-blocking by construction: only
                # ready generations commit here.
                while (
                    self._pipe_depth > 1
                    and inflight
                    and self._entries_ready(inflight[0])
                ):
                    entries = inflight.popleft()
                    events = await loop.run_in_executor(
                        self._ex, self._commit_all, entries,
                        self._pipe_depth > 1 and bool(inflight),
                    )
                    await self._emit_events(events)
                    if tick is not None:
                        tick.mark("fanout")
                # K-granular admission (ISSUE 16): tell the budget planner
                # how many uncommitted multi-step tokens each decode lane
                # may be carrying across the pipeline before this plan's
                # admissions could possibly take effect
                self.sched.decode_inflight_tokens = (
                    self._pipe_depth
                    * min(
                        self._multistep_fixed or self._ms_ramp,
                        self._multistep_max,
                    )
                    if self._multistep
                    else 0
                )
                plan = self.sched.plan()
                if self.sched.num_active > 0:
                    # pre-grow pages to cover the in-flight block plus this
                    # tick's block (the host mirror lags the device by up to
                    # one uncommitted block); with speculating lanes slotted
                    # the floor also covers a verify dispatch's full draft
                    # span (spec-free serving keeps its exact old watermark
                    # -- the floor must not raise preemption pressure for
                    # workloads that never speculate)
                    # depth-scaled: every uncommitted generation may hold
                    # a full block's writes, plus this tick's block.  With
                    # multi-step decode armed a packed generation holds up
                    # to K writes per lane, so the floor covers whichever
                    # block shape is larger (K <= decode_block_size keeps
                    # the exact old watermark)
                    ms_block = self._multistep_max if self._multistep else 1
                    lookahead = (
                        (self._pipe_depth + 1)
                        * max(self.cfg.decode_block_size, ms_block)
                        + 1
                    )
                    if any(
                        s is not None and _spec_live(s)
                        for s in self.sched.slots
                    ):
                        from ..spec import MAX_DRAFT_TOKENS

                        lookahead = max(
                            lookahead,
                            (self._pipe_depth + 1) * (MAX_DRAFT_TOKENS + 1)
                            + 1,
                        )
                    preempted = self.sched.ensure_decode_capacity(
                        lookahead=lookahead,
                        chunk_pages=self.cfg.grow_chunk_pages,
                    )
                    if preempted:
                        self.obs.preemptions.inc(len(preempted))
                        if self.offload_engine is not None:
                            for s in preempted:
                                kind = (
                                    "swap"
                                    if s.request_id in self._swapped
                                    else "recompute"
                                )
                                self.offload_engine.metrics.preemptions.labels(
                                    kind
                                ).inc()
                self._revive_paused_lanes()
                fresh: List[Any] = []
                # mixed batching: admitted prompts pack into the decode
                # tick as ragged chunks of ONE unified dispatch.  Penalized
                # lanes force the classic tick (the unified step carries no
                # penalty histograms); pending mixed prefills then drain
                # through the classic chunk machinery (mixed chunk
                # boundaries stay page-aligned for exactly this handoff).
                mixed_ok = self._mixed_tick_ok()
                if not mixed_ok and self.sched.mix_pending:
                    self._drain_mixed_to_classic()
                if tick is not None:
                    tick.mark("plan")
                # advance chunked prefills: one chunk per seq per tick, so
                # decode blocks interleave below instead of stalling behind
                # one long prompt
                still_chunking: List[SeqState] = []
                for seq in self._chunking:
                    if (
                        seq.finish is not None
                        or seq.slot < 0
                        or self.sched.slots[seq.slot] is not seq
                        or not seq.prefilling
                    ):
                        continue  # cancelled / preempted mid-prefill
                    pf = await loop.run_in_executor(
                        self._ex, self._dispatch_chunk, seq
                    )
                    if pf is not None:
                        fresh.append(pf)  # final chunk sampled
                    else:
                        still_chunking.append(seq)
                self._chunking = still_chunking
                if tick is not None:
                    tick.mark("dispatch")
                # batch plain prefills by compiled shape: a burst of N
                # admissions costs one weight-streaming pass per shape
                # group instead of N (chunked-prefill candidates go one at
                # a time through _do_prefill; under mixed batching every
                # text prompt routes to the unified plane instead)
                groups: Dict[Tuple[int, int], List[Tuple[SeqState, int]]] = {}
                # park every chunk-bound lane BEFORE any dispatch: the
                # first sync of an admission burst can be a full device
                # rebuild (from inside the first lane's prefill), and a
                # lane not yet marked prefilling would be rebuilt ACTIVE
                # with placeholder state -- the next decode block would
                # then step it over a half-written cache and commit
                # garbage as its output (a multi-lane chunked-admission
                # corruption this ordering closes; test_mixed_batching
                # asserts the chunked batch == solo)
                for seq, prompt_len in plan.prefills:
                    if (
                        self._chunk_tokens is not None
                        and prompt_len - seq.cached_prompt_tokens
                        > self._chunk_tokens
                        and seq.mm_embeds is None
                    ):
                        seq.prefilling = True
                        seq.prefilled_tokens = seq.cached_prompt_tokens
                for seq, prompt_len in plan.prefills:
                    if seq.slot < 0 or self.sched.slots[seq.slot] is not seq:
                        continue  # preempted by this tick's capacity pass
                    if mixed_ok and seq.mm_embeds is None:
                        # soft-prompt lanes keep the classic dispatch (the
                        # unified step has no mm injection)
                        self.sched.queue_mixed_prefill(
                            seq, seq.cached_prompt_tokens
                        )
                        continue
                    cached = seq.cached_prompt_tokens
                    if (
                        self._chunk_tokens is not None
                        and prompt_len - cached > self._chunk_tokens
                    ):
                        pf = await loop.run_in_executor(
                            self._ex, self._do_prefill, seq, prompt_len
                        )
                        if pf is not None:
                            fresh.append(pf)
                        elif seq.prefilling:
                            self._chunking.append(seq)
                        continue
                    key = (
                        pick_bucket(self.buckets, prompt_len - cached),
                        pick_page_bucket(
                            max(cached // self.cfg.page_size, 1),
                            self.sched.max_pages,
                        ) if cached else 0,
                    )
                    groups.setdefault(key, []).append((seq, prompt_len))
                for items in groups.values():
                    pfs = await loop.run_in_executor(
                        self._ex, self._do_prefill_group, items
                    )
                    fresh.extend(pfs)
                if tick is not None:
                    tick.mark("dispatch")
                # folded speculation (ISSUE 15): on packed mixed ticks the
                # speculating lanes' verify columns ride the SAME unified
                # dispatch as decode rows + prefill chunks -- a
                # speculating tick is ONE device dispatch.  ``reserve``
                # keeps the dispatch's fresh-token budget honest about the
                # verify segments it is about to pack.
                fold_active = self._fold_spec and mixed_ok
                spec_reserve = (
                    self._spec_fold_reserve() if fold_active else 0
                )
                chunks = (
                    self.sched.form_mixed_chunks(
                        self._mixed_budget, self._chunk_tokens,
                        reserve_tokens=spec_reserve,
                    )
                    if mixed_ok
                    else []
                )
                if tick is not None:
                    tick.mark("assemble")
                ub = None
                # adaptive multi-step K (ISSUE 16): chunk/spec/admission
                # pressure collapses the next packed block to one step
                # (TTFT granularity); a pressure-free tick ramps K toward
                # the ceiling (ISSUE 42: as many steps as hide the loop's
                # own work) and fuses the whole block into one dispatch
                ms_k = (
                    self._multistep_plan_k(chunks, spec_reserve)
                    if self._multistep and mixed_ok
                    else 0
                )
                if chunks or spec_reserve:
                    # ONE dispatch serves the whole batch: every decode
                    # lane rides alongside the packed prefill chunks and
                    # (folded) the speculating lanes' verify segments
                    ub = await loop.run_in_executor(
                        self._ex, self._dispatch_unified, chunks,
                        fold_active,
                    )
                    if ub is not None:
                        fresh.append(ub)
                elif (
                    ms_k > 0
                    and self.sched.num_decode_runnable > 0
                    and self._has_steppable_lane(
                        [e for gen in inflight for e in gen]
                    )
                ):
                    # pure-decode tick with multi-step open: K decode
                    # iterations through the packed plane in one launch,
                    # replacing the classic fixed-width decode_block scan
                    # so admission granularity follows the controller
                    # (post-prefill multimodal lanes ride this like any
                    # text lane -- decode state carries no modality)
                    ub = await loop.run_in_executor(
                        self._ex, self._dispatch_unified, [], False, ms_k,
                    )
                    if ub is not None:
                        fresh.append(ub)
                        self._tick_fused = True
                        if ub.n_steps >= self._multistep_max:
                            self._ms_topped = True
                if ub is None and (
                    # no unified dispatch went out (or the spec candidates
                    # vanished between the loop-thread check and the
                    # executor hop): plain decode lanes must still get
                    # their block -- this branch is a fallthrough, not an
                    # elif, so that race can never starve them
                    self.sched.num_decode_runnable > 0
                    and self._has_steppable_lane(
                        [e for gen in inflight for e in gen]
                    )
                ):
                    blk = await loop.run_in_executor(self._ex, self._dispatch_block)
                    if blk is not None:
                        fresh.append(blk)
                if fresh:
                    inflight.append(fresh)
                # commit policy: the oldest generation commits when the
                # pipeline is past its depth (the ONE blocking
                # backpressure point -- its device_wait is the pacing
                # sync), when nothing new dispatched (drain: keep making
                # progress toward idle), or -- async mode -- when its
                # results have already landed (a non-blocking commit).
                # Serial mode (--no-async-dispatch) skips the readiness
                # probe, reproducing the legacy
                # dispatch-then-commit-previous loop exactly.
                allowed = self._pipe_depth if fresh else 0
                while inflight and (
                    len(inflight) > allowed
                    or (
                        self._pipe_depth > 1
                        and self._entries_ready(inflight[0])
                    )
                ):
                    entries = inflight.popleft()
                    # pipeline_busy only in ASYNC mode: the serial loop
                    # must keep the legacy ready->next-enqueue gap series
                    # (the --no-async-dispatch A/B baseline) even though
                    # the fresh generation is technically already queued
                    events = await loop.run_in_executor(
                        self._ex, self._commit_all, entries,
                        self._pipe_depth > 1 and bool(inflight),
                    )
                    await self._emit_events(events)
                    if tick is not None:
                        tick.mark("fanout")
                # CLASSIC speculative verify dispatches AFTER the commit
                # phase: a lane's next draft extends its post-commit
                # history, so each spec lane runs one
                # draft->verify->commit cycle per tick (the dispatch still
                # overlaps this tick's in-flight decode block on device).
                # With folding active the verify columns already rode the
                # unified dispatch above -- the standalone path serves
                # classic ticks (penalized lanes) and --no-fold-spec-verify.
                # The slot scan gates the executor hop so spec-free serving
                # pays nothing here.
                if not fold_active and any(
                    s is not None and _spec_live(s)
                    for s in self.sched.slots
                ):
                    vb = await loop.run_in_executor(
                        self._ex, self._dispatch_verify
                    )
                    if vb is not None:
                        if inflight:
                            inflight[-1].append(vb)
                        else:
                            inflight.append([vb])
                    if tick is not None:
                        tick.mark("dispatch")
                if tick is not None:
                    prof.finish_tick(tick)
                    self._tick = tick = None
                if not fresh and not inflight:
                    self._handle_stalled_admission()
                    # nothing dispatched and nothing in flight (e.g. waiting
                    # on slots held by parked lanes): don't spin the loop hot
                    await asyncio.sleep(0.001)
                # yield so enqueue/cancel callbacks interleave
                await asyncio.sleep(0)
            except asyncio.CancelledError:
                raise
            except Exception as e:  # engine must never die silently
                logger.exception("engine tick failed")
                if self._tick is not None:
                    self._tick.discard()
                self._tick = None
                inflight.clear()
                self._pending_injects.clear()
                self._chunking = []
                self.sched.mix_pending = []
                self._fail_all(f"engine error: {e}")
                self._dev = None  # full rebuild once work resumes
                self.sched.dirty_slots.clear()
                await asyncio.sleep(0.01)

    async def _park(self, timeout: Optional[float], watched: bool) -> None:
        """Wait for ``_wake`` (at most ``timeout`` seconds): nothing is
        runnable.  ``watched`` (the tick profiler is on) only records: the
        wait is written into the ``jax.profiler`` trace as ``dyn.parked``,
        in slices, because an annotation is kept only if it opens and
        closes inside a trace -- one that starts or stops mid-wait then
        loses a slice, not the whole wait.  It is no tick phase and enters
        no tick record.  Watched or not, the wait is counted into
        ``dynamo_engine_parked_seconds_total`` (``_count_parked``)."""
        assert self._wake is not None
        start = self._parked_since = time.monotonic()
        deadline = None if timeout is None else start + timeout
        try:
            while not self._wake.is_set():
                left = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if left is not None and left <= 0.0:
                    return
                parked = None
                if watched:
                    left = PARKED_SLICE_S if left is None else min(
                        left, PARKED_SLICE_S
                    )
                    parked = profiling.annotate(profiling.PARKED_ANNOTATION)
                try:
                    await asyncio.wait_for(self._wake.wait(), left)
                except asyncio.TimeoutError:
                    pass
                finally:
                    if parked is not None:
                        parked.__exit__(None, None, None)
        finally:
            self._count_parked(ended=True)

    def _count_parked(self, ended: bool = False) -> None:
        """Move the parked wait's seconds so far into
        ``dynamo_engine_parked_seconds_total``: where the wait ends
        (``_park``), and before every exposition of the registry, so that a
        scrape in the middle of a long wait sees the part that has passed
        (the lock: a scrape may come from another thread)."""
        with self._parked_lock:
            since = self._parked_since
            if since is None:
                return
            now = time.monotonic()
            self._parked_since = None if ended else now
            self.obs.parked_seconds.inc(max(now - since, 0.0))

    def _revive_paused_lanes(self) -> None:
        """A lane that hit its device-side limit self-deactivated; if growth
        since raised what its limit would be, mark the lane dirty so the next
        dispatch folds the raised limit (and ``active``) back in with a row
        scatter -- no pipeline drain (growth-only refreshes never touch
        ``active``)."""
        sched = self.sched
        limits = self._compute_limits()
        for b, seq in enumerate(sched.slots):
            if seq is None or seq.finish is not None:
                continue
            if (
                int(sched.seq_lens[b]) >= int(self._limit_host[b])
                and limits[b] > self._limit_host[b]
            ):
                sched.dirty_slots.add(b)

    def _mixed_tick_ok(self) -> bool:
        """Whether this tick may run the unified mixed-batch dispatch.

        Penalized lanes require the decode scan's device-resident penalty
        histograms (and prompt-penalized first-token logits), which the
        unified step deliberately does not carry -- one penalized lane in
        the batch reverts the whole tick to the classic paths, exactly the
        eligibility shape speculation uses (output is the contract, the
        packing is an optimization)."""
        if not self._mixed:
            return False
        return not any(
            s is not None and self._seq_penalized(s) for s in self.sched.slots
        )

    def _drain_mixed_to_classic(self) -> None:
        """Hand pending mixed prefills to the classic chunk machinery (a
        penalized lane turned the tick classic).  Safe because non-final
        mixed chunks always end page-aligned, which is the classic suffix
        path's restart requirement."""
        for seq in self.sched.mix_pending:
            if (
                seq.finish is None
                and seq.slot >= 0
                and self.sched.slots[seq.slot] is seq
                and seq.prefilling
                and seq not in self._chunking
            ):
                self._chunking.append(seq)
        self.sched.mix_pending = []

    def _decode_steps_of(self, pending: Iterable[Any]) -> int:
        """The most decode steps the dispatches in ``pending`` advance any
        one lane by."""
        steps = 0
        for e in pending:
            if isinstance(e, InflightBlock):
                steps += self.cfg.decode_block_size
            elif isinstance(e, InflightUnified):
                steps += e.n_steps
        return steps

    def _has_steppable_lane(self, pending: List[Any]) -> bool:
        """Whether any decode-runnable lane can still absorb a token once
        the in-flight work lands -- the guard that skips the decode
        dispatch on ticks that could only launch dead rows (e.g. the tail
        tick after every lane's token budget went in-flight: the old loop
        paid one wasted all-dead block per batch completion there)."""
        inflight = self._decode_steps_of(pending)
        sched = self.sched
        limits = self._compute_limits()
        for b, s in enumerate(sched.slots):
            if (
                s is None
                or s.finish is not None
                or s.awaiting_kv
                or s.prefilling
                or _spec_live(s)
            ):
                continue
            if int(limits[b]) > int(sched.seq_lens[b]) + inflight:
                return True
        return False

    def _spec_fold_reserve(self) -> int:
        """Fresh-token rows the speculating lanes would contribute to this
        tick's unified dispatch (1 committed-token column + the lane's
        draft budget each), 0 when no lane is verify-eligible right now.

        Loop-thread twin of ``_gather_spec_lanes``'s eligibility gates,
        INCLUDING the write-headroom gate -- a headroom-paused spec lane
        (growth pending, capacity cap) must not steer the tick into a
        unified dispatch that then has nothing to pack, or a chunk-less
        tick would skip the decode block and starve every plain lane.
        It decides (a) whether a chunk-less tick still needs the unified
        dispatch and (b) how many packed rows ``form_mixed_chunks`` must
        reserve.  An over-estimate (the drafter proposes fewer tokens
        than budgeted) only costs pad rows the packed fit absorbs."""
        total = 0
        limits: Optional[np.ndarray] = None
        for b, s in enumerate(self.sched.slots):
            if (
                s is None
                or s.finish is not None
                or not _spec_live(s)
                or s.spec.inflight
                or s.awaiting_kv
                or s.prefilling
                or b in self._pending_injects
                or s.num_generated + s.prior_generated < 1
            ):
                continue
            if limits is None:
                limits = self._compute_limits()
            if int(limits[b]) - int(self.sched.seq_lens[b]) < 1:
                continue  # no writable position (the _gather gate)
            total += 1 + s.spec.num_draft_tokens
        return total

    def _multistep_plan_k(self, chunks: List[Any], spec_reserve: int) -> int:
        """Decode steps to fuse into this tick's packed dispatch (ISSUE 16).

        The controller reads the same queue/lane state the scheduler
        plans from, so the decision is made once per tick on the loop
        thread with no device sync:

        * **Pressure collapses K to 1.**  Prefill chunks, speculating
          lanes, a non-empty admission queue, pending mixed prefills,
          classic chunk restarts, or pending spec injects all mean some
          lane wants the batch re-planned at single-token granularity --
          a fused block would hold admission (TTFT) hostage for K steps
          and would race the chunk machinery's KV writes.
        * **Fixed mode** (``DYN_MULTISTEP=<N>``) returns N whenever
          pressure-free -- the bench/ablation pin.
        * **Adaptive mode** ramps K geometrically (1, 2, 4, ...) per
          consecutive pressure-free tick, up to a ceiling: the smallest
          such K, at most ``multistep_max_k``, whose dispatch outlasts the
          loop's own work per tick by ``multistep.MARGIN`` (ISSUE 42).
          Fusing more than hides that work buys nothing, and a request
          admitted beside decoding lanes waits behind every fused step of
          the running dispatch and of the one queued behind it.  The two
          means the ceiling is worked out from are kept always on: a
          decode step's service from the dispatch record
          (``_record_service``), a decode-only tick's wall time less its
          blocked fetches from ``_read_tick_clock``.  Nothing here reads
          what the tick profiler or the span collector holds: the engine
          serves the same whether it is watched or not.  Until the widest
          block has run once (``_ms_topped``) the ceiling is
          ``multistep_max_k``, as it is across processes (``_ms_local``).

        The ramp (rather than an instant max) bounds the worst-case
        tokens a mid-block cancel/deadline discards right after a busy
        phase, while steady pure-decode traffic still converges to the
        ceiling in log2(K) ticks."""
        sched = self.sched
        pressure = (
            bool(chunks)
            or bool(spec_reserve)
            or bool(sched.waiting)
            or bool(sched.mix_pending)
            or bool(self._chunking)
            or bool(self._pending_injects)
            or any(
                s is not None
                and (s.prefilling or s.awaiting_kv or _spec_live(s))
                for s in sched.slots
            )
        )
        if pressure:
            self._ms_ramp = 1
            return 1
        if self._multistep_fixed is not None:
            return self._multistep_fixed
        ceiling = self._multistep_max
        if self._ms_topped and self._ms_local:
            ceiling = self._ms_ceiling.value()
        self.obs.observe_multistep_ceiling(
            ceiling, self._ms_ceiling.step_s, self._ms_ceiling.loop_s
        )
        k = min(self._ms_ramp, ceiling)
        self._ms_ramp = min(self._ms_ramp * 2, ceiling)
        return k

    def _read_tick_clock(self) -> None:
        """The tick loop's one always-on clock read, at each iteration's
        start: closes the iteration before (if it enqueued a decode-only
        dispatch and its readings count, its wall time less its blocked
        fetches is one reading of the loop's own work a tick, for the
        fused-step ceiling) and opens this one.  A parked or empty
        iteration enqueues nothing and is no reading."""
        # dynalint: disable=DT012 -- once a tick, profiler on or off
        now = time.perf_counter()
        if self._tick_fused and self._ms_reads:
            self._ms_ceiling.observe_loop(
                now - self._tick_began - self._fetch_wait
            )
        self._tick_began = now
        self._fetch_wait = 0.0
        self._tick_fused = False
        self._ms_reads = self._ms_topped

    def _handle_stalled_admission(self) -> None:
        """Nothing running, nothing admitted: requests whose prompts can never
        fit the page pool must fail instead of spinning the loop forever.

        Only fundamental capacity (the prompt plus the first decode-write
        page exceed the whole pool) fails a request -- a request that merely
        raced past this iteration's plan() gets admitted on the next tick.
        """
        sched = self.sched
        if sched.num_active > 0 or not sched.waiting:
            return
        head = sched.waiting[0]
        need = sched.min_total_pages(head)
        usable = sched.allocator.num_pages - 1
        if need <= usable:
            return  # admittable; plan() will take it next tick
        sched.waiting.popleft()
        self._fail_seq(
            head,
            f"request needs more KV pages than the pool holds "
            f"({len(head.prompt)} prompt tokens -> {need} pages, "
            f"pool has {usable} pages of {sched.cfg.page_size})",
        )

    def _fail_seq(self, seq: SeqState, message: str) -> None:
        if seq.finish is None:
            seq.finish = FinishReason.ERROR
        if tracing.collector.enabled:
            self._record_request_spans(seq)
        # a failed external request must not resurrect via a late delivery
        self._external.pop(seq.request_id, None)
        self._deliveries.pop(seq.request_id, None)
        self._chunked.pop(seq.request_id, None)
        self._external_deadline.pop(seq.request_id, None)
        self._cancel_prefetch(seq.request_id)
        if self._swapped.pop(seq.request_id, None) is not None:
            self.offload_engine.drop_swap(seq.request_id)
        if self._queues.get(seq.request_id) is None:
            return
        # async mode: the error + stream terminator ride the fanout queue
        # so they cannot overtake committed token events still waiting in
        # it (per-request ordering = the queue's FIFO).  A full queue
        # degrades to the inline put -- losing relative order beats losing
        # the error entirely.
        q = self._fanout_q
        if q is not None and self._running:
            # not during shutdown: a frame enqueued behind stop()'s None
            # sentinel would be dropped by the exiting worker (stop()
            # drains leftovers too, but the inline put is deterministic)
            try:
                q.put_nowait(("error", seq.request_id, message))
                return
            except asyncio.QueueFull:
                pass
        self._put_error(seq.request_id, message)

    def _put_error(self, request_id: str, message: str) -> None:
        """Designated error-frame emitter (TICK_COMMIT_HELPERS): the
        stream may have been torn down since the failure was enqueued."""
        queue = self._queues.get(request_id)
        if queue is not None:
            queue.put_nowait(Annotated.from_error(message))
            queue.put_nowait(None)

    def _fail_all(self, message: str) -> None:
        for seq in list(self.sched.waiting) + [
            s for s in self.sched.slots if s is not None
        ]:
            self._fail_seq(seq, message)
            self.sched.cancel(seq)

    def _process_cancellations(self) -> None:
        if not self._cancelled:
            return
        by_id = {}
        for s in self.sched.slots:
            if s is not None:
                by_id[s.request_id] = s
        for s in self.sched.waiting:
            by_id[s.request_id] = s
        for rid in list(self._cancelled):
            self._cancelled.discard(rid)
            self._external.pop(rid, None)
            self._deliveries.pop(rid, None)
            self._chunked.pop(rid, None)
            self._external_deadline.pop(rid, None)
            self._cancel_prefetch(rid)
            if self._swapped.pop(rid, None) is not None:
                self.offload_engine.drop_swap(rid)
            seq = by_id.get(rid)
            if seq is not None:
                # with the PagePool, cancel releases refs -- registered blocks
                # stay resident (no removed event until real eviction)
                if self.sched.pool is None:
                    self._publish_removed(seq)
                self.sched.cancel(seq)
                if tracing.collector.enabled:
                    self._record_request_spans(seq)

    # -- device work (executor thread) --------------------------------------

    @staticmethod
    def _norm_seed(so) -> int:
        """User seed -> device u32 with 0 reserved for 'unseeded' (a user
        seed of 0 is valid OpenAI input, so it maps into 1..2^32-1)."""
        if so is None or so.seed is None:
            return 0
        return (int(so.seed) % 0xFFFFFFFF) + 1

    def _sampling_arrays(self, seqs: List[Optional[SeqState]]) -> SamplingParams:
        n = len(seqs)
        temp = np.zeros((n,), np.float32)
        top_p = np.ones((n,), np.float32)
        top_k = np.zeros((n,), np.int32)
        seed = np.zeros((n,), np.uint32)
        freq = np.zeros((n,), np.float32)
        pres = np.zeros((n,), np.float32)
        rep = np.ones((n,), np.float32)
        for i, s in enumerate(seqs):
            if s is None:
                continue
            so = s.sampling
            if so.temperature is not None:
                temp[i] = so.temperature
            elif so.top_p is not None or so.top_k is not None:
                # unset temperature with explicit top_p/top_k means "sample":
                # default temperature 1.0, not greedy
                temp[i] = 1.0
            top_p[i] = so.top_p if so.top_p is not None else 1.0
            top_k[i] = so.top_k or 0
            seed[i] = self._norm_seed(so)
            freq[i] = so.frequency_penalty or 0.0
            pres[i] = so.presence_penalty or 0.0
            rep[i] = so.repetition_penalty or 1.0
        return SamplingParams(
            temperature=self._put_batch(temp),
            top_p=self._put_batch(top_p),
            top_k=self._put_batch(top_k),
            seed=self._put_batch(seed),
            freq=self._put_batch(freq),
            pres=self._put_batch(pres),
            rep=self._put_batch(rep),
        )

    @staticmethod
    def _sampling_needs_filters(so) -> bool:
        """Whether this request's settings engage the sorted filter path in
        ``sampling.sample_tokens`` (the trace-time ``use_filters`` switch at
        dispatch).  Lives next to ``_sampling_arrays`` so the None->0/1.0
        normalization and this predicate cannot drift apart: any filter
        added to SamplingParams + sample_tokens must be reflected in BOTH.

        Greedy rows (effective temperature 0) return the pre-filter argmax,
        so filters on a greedy request never change its output -- don't pay
        the sort for them."""
        has_filter = bool(so.top_k) or (so.top_p is not None and so.top_p < 1.0)
        if not has_filter:
            return False
        # effective temperature mirrors _sampling_arrays: explicit value
        # wins; unset with filters present means "sample at 1.0"
        temp = so.temperature if so.temperature is not None else 1.0
        return temp > 0.0

    def _next_rng(self) -> jax.Array:
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _put_batch(self, arr: np.ndarray) -> jax.Array:
        """Place a batch-major host array: sharded over ``dp`` on a mesh
        (when the leading dim divides), plain transfer otherwise.  Explicit
        placement keeps GSPMD from replicating per-lane compute across the
        dp groups."""
        if self.mesh is None:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.sharding import _compatible_spec

        spec = _compatible_spec(
            P(*(["dp"] + [None] * (arr.ndim - 1))), arr.shape, self.mesh
        )
        return jax.device_put(np.asarray(arr), NamedSharding(self.mesh, spec))

    @staticmethod
    def _pad_batch(n: int) -> int:
        """Pad a prefill group to a power-of-two batch so group size does
        not multiply compile-cache entries (dead rows write trash page 0)."""
        return pow2_bucket(n)

    def _dispatch_full_prefill_batch(
        self, items: List[Tuple[SeqState, List[int], List[int]]], Bp: int
    ) -> jax.Array:
        """Dispatch full-prompt (no prefix reuse) prefills + first-token
        samples for up to ``Bp`` lanes; rows past ``len(items)`` are dead
        (length 0, trash page).  This is THE full-prefill dispatch site --
        the single-request path and the disagg export path both call it, so
        they cannot diverge (the disagg-equals-aggregated invariant rests
        on identical dispatch here)."""
        compile_sentry.set_entry("prefill")
        ps = self.cfg.page_size
        bucket = pick_bucket(
            self.buckets, max(len(prompt) for _, prompt, _ in items)
        )
        n_pages = bucket // ps
        tokens = np.zeros((Bp, bucket), np.int32)
        lens = np.zeros((Bp,), np.int32)
        page_table = np.zeros((Bp, n_pages), np.int32)
        seqs: List[Optional[SeqState]] = [None] * Bp
        for i, (seq, prompt, pages) in enumerate(items):
            tokens[i, : len(prompt)] = prompt
            lens[i] = len(prompt)
            # the lane may hold growth pages beyond the prompt already
            # (loop-side ensure_decode_capacity runs before prefill
            # dispatch); prefill writes only within the prompt's pages
            k = min(len(pages), n_pages)
            page_table[i, :k] = pages[:k]
            seqs[i] = seq
        if any(s is not None and s.mm_embeds is not None for s in seqs):
            return self._dispatch_mm_prefill_batch(
                tokens, lens, page_table, seqs, Bp
            )
        routed = self._dispatch_parallel_prefill(
            tokens, lens, page_table, seqs, bucket
        )
        if routed is not None:
            return routed
        sampled, self.kv.pages = prefill_and_sample(
            self.params,
            self.model_cfg,
            self.kv.pages,
            self._put_batch(tokens),
            self._put_batch(lens),
            self._put_batch(page_table),
            self._next_rng(),
            self._sampling_arrays(seqs),
            self._lp_top(seqs),
            any(s is not None and self._seq_penalized(s) for s in seqs),
        )
        return sampled

    def _dispatch_mm_prefill_batch(
        self,
        tokens: np.ndarray,
        lens: np.ndarray,
        page_table: np.ndarray,
        seqs: List[Optional[SeqState]],
        Bp: int,
    ) -> jax.Array:
        """Soft-prompt (multimodal) full prefill: inject each lane's vision
        embeddings over its leading positions.  The soft-prompt length pads
        to a power-of-two bucket so compile-cache entries stay bounded."""
        compile_sentry.set_entry("prefill")
        from .step import prefill_mm_and_sample

        H = self.model_cfg.hidden_size
        mm_lens = [
            0 if s is None or s.mm_embeds is None else len(s.mm_embeds)
            for s in seqs
        ]
        M = pow2_bucket(max(mm_lens))  # >= 1, power of two
        mm = np.zeros((Bp, M, H), np.float32)
        mml = np.zeros((Bp,), np.int32)
        for i, s in enumerate(seqs):
            if s is not None and s.mm_embeds is not None:
                k = len(s.mm_embeds)
                mm[i, :k] = s.mm_embeds
                mml[i] = k
        sampled, self.kv.pages = prefill_mm_and_sample(
            self.params,
            self.model_cfg,
            self.kv.pages,
            self._put_batch(tokens),
            self._put_batch(lens),
            self._put_batch(page_table),
            self._put_batch(mm),
            self._put_batch(mml),
            self._next_rng(),
            self._sampling_arrays(seqs),
            self._lp_top(seqs),
            any(s is not None and self._seq_penalized(s) for s in seqs),
        )
        return sampled

    def _dispatch_parallel_prefill(
        self,
        tokens: np.ndarray,
        lens: np.ndarray,
        page_table: np.ndarray,
        seqs: List[Optional[SeqState]],
        bucket: int,
    ) -> Optional[jax.Array]:
        """Route a full prefill through ring attention (sp) or pipeline (pp)
        when the serving mesh has those axes and the shapes qualify; returns
        the sampled first tokens, or None to take the plain GSPMD path.

        sp wins when both axes exist (one dispatch can't compose both shard
        maps; sequence parallelism is the long-context lever, SURVEY.md 5.7).
        Shape guards mirror the step functions' own: ring needs the bucket
        divisible by sp (sliding windows mask over global positions); pp
        needs the layer count divisible by pp and the batch divisible by
        the microbatch count."""
        compile_sentry.set_entry("prefill")
        if self.mesh is None or (self._sp <= 1 and self._pp <= 1):
            return None
        Bp = tokens.shape[0]
        use_sp = self._sp > 1 and bucket % self._sp == 0
        use_pp = (
            not use_sp
            and self._pp > 1
            and self.model_cfg.num_layers % self._pp == 0
            and Bp % min(self._pp, Bp) == 0
        )
        if not use_sp and not use_pp:
            return None
        from .step import sample_step_packed

        if use_sp:
            from ..parallel.ring_attention import ring_prefill_step

            logits, self.kv.pages = ring_prefill_step(
                self.params, self.model_cfg, self.kv.pages,
                self._put_batch(tokens), self._put_batch(lens),
                self._put_batch(page_table), self.mesh,
            )
            self.sp_prefills += 1
        else:
            from ..parallel.pipeline_parallel import pp_prefill_step

            logits, self.kv.pages = pp_prefill_step(
                self.params, self.model_cfg, self.kv.pages,
                self._put_batch(tokens), self._put_batch(lens),
                self._put_batch(page_table), self.mesh,
                num_microbatches=min(self._pp, Bp),
            )
            self.pp_prefills += 1
        return sample_step_packed(
            logits, self._next_rng(), self._sampling_arrays(seqs),
            self._lp_top(seqs), positions=self._put_batch(lens),
        )

    def _dispatch_full_prefill(
        self, seq: SeqState, prompt: List[int], pages: List[int]
    ) -> jax.Array:
        """Single-lane wrapper over the shared batch dispatch (disagg
        export path)."""
        return self._dispatch_full_prefill_batch([(seq, prompt, pages)], 1)

    def _dispatch_suffix_prefill_batch(
        self, entries: List[Tuple[SeqState, int, int]], Bp: int
    ) -> jax.Array:
        """Suffix prefills (cached prefix resident) for up to ``Bp`` lanes;
        ``entries`` are (seq, prompt_len, cached) with page-aligned cached
        > 0.  The single-request and group paths share this builder."""
        compile_sentry.set_entry("prefill")
        ps = self.cfg.page_size
        bucket = pick_bucket(
            self.buckets, max(pl - c for _, pl, c in entries)
        )
        n_suffix_pages = bucket // ps
        prefix_P = pick_page_bucket(
            max(max(c for _, _, c in entries) // ps, 1), self.sched.max_pages
        )
        tokens = np.zeros((Bp, bucket), np.int32)
        offsets = np.zeros((Bp,), np.int32)
        suffix_lens = np.zeros((Bp,), np.int32)
        prefix_table = np.zeros((Bp, prefix_P), np.int32)
        suffix_table = np.zeros((Bp, n_suffix_pages), np.int32)
        seqs: List[Optional[SeqState]] = [None] * Bp
        for i, (seq, pl, cached) in enumerate(entries):
            sl = pl - cached
            tokens[i, :sl] = seq.prompt[cached:pl]
            offsets[i] = cached
            suffix_lens[i] = sl
            npp = cached // ps
            prefix_table[i, :npp] = seq.pages[:npp]
            k = min(len(seq.pages) - npp, n_suffix_pages)
            suffix_table[i, :k] = seq.pages[npp : npp + k]
            seqs[i] = seq
        sampled, self.kv.pages = prefill_suffix_and_sample(
            self.params,
            self.model_cfg,
            self.kv.pages,
            self._put_batch(tokens),
            self._put_batch(offsets),
            self._put_batch(suffix_lens),
            self._put_batch(prefix_table),
            self._put_batch(suffix_table),
            self._next_rng(),
            self._sampling_arrays(seqs),
            self._lp_top(seqs),
            any(s is not None and self._seq_penalized(s) for s in seqs),
        )
        return sampled

    def _lp_top(self, seqs) -> int:
        """Trace-time top-logprobs width for a dispatch: 8 when any live
        request asked for alternatives (OpenAI allows up to 5 completions /
        20 chat; widths bucket to {0, 8} so at most two executables exist
        per step shape -- requests above 8 are clamped, PARITY.md)."""
        for s in seqs:
            if s is not None and s.sampling is not None and s.sampling.logprobs:
                return 8
        return 0

    def _do_prefill(
        self, seq: SeqState, prompt_len: int
    ) -> Optional[InflightPrefill]:
        """Dispatch prefill + first-token sampling; inject the token into the
        device decode state.  No host round trip -- the token is committed
        later, materialized together with the next decode block.

        With a prefix-cache hit (scheduler matched resident blocks), only the
        prompt suffix is prefilled: queries start at position
        ``cached_prompt_tokens`` and attend to the reused pages.

        With chunked prefill configured and a long-enough remainder, only
        the first chunk dispatches here (no sample); the tick loop advances
        the rest via ``_dispatch_chunk`` (returns None in that case)."""
        self._note_prefetch_admission(seq)
        if seq.pending_onboard:
            self._apply_onboards(seq)
        # prefix-cache stats are token-weighted and counted once per request
        # (not per re-prefill after preemption)
        if not seq.stats_counted:
            seq.stats_counted = True
            self._prefix_lookups += prompt_len
            self._prefix_hits += seq.cached_prompt_tokens
            self.obs.prefix_lookups.inc(prompt_len)
            if seq.cached_prompt_tokens:
                self.obs.prefix_hits.inc(seq.cached_prompt_tokens)
        chunk = self._chunk_tokens
        start = seq.cached_prompt_tokens
        if (
            chunk is not None
            and prompt_len - start > chunk
            and seq.mm_embeds is None  # mm prompts prefill in one dispatch:
            # the soft-prompt injection indexes absolute positions from 0
        ):
            seq.prefilling = True
            seq.prefilled_tokens = start
            # the admission row must land (lane inactive while chunking)
            self._sync_device_state()
            return self._dispatch_chunk(seq)
        return self._finish_prefill(seq, prompt_len, start)

    @hot_path
    def _dispatch_chunk(self, seq: SeqState) -> Optional[InflightPrefill]:
        """Advance one page-aligned chunk of a chunked prefill (executor
        thread).  Intermediate chunks write KV and sample nothing; the final
        chunk runs the normal sample-and-inject path and re-activates the
        lane (dirty row ordered after the dispatch)."""
        compile_sentry.set_entry("prefill")
        prompt_len = len(seq.prompt)
        start = seq.prefilled_tokens
        chunk = self._chunk_tokens
        # chunk is None when a lane reaches here via _drain_mixed_to_classic
        # with chunking unconfigured: the rest of the prompt is one final
        # suffix dispatch (mixed chunk boundaries are page-aligned, which
        # is all the suffix restart requires)
        if chunk is None or prompt_len - start <= chunk:
            seq.prefilling = False
            pf = self._finish_prefill(seq, prompt_len, start)
            self.sched.dirty_slots.add(seq.slot)
            return pf
        ps = self.cfg.page_size
        suffix_len = chunk  # page-aligned by construction (__init__)
        bucket = pick_bucket(self.buckets, suffix_len)
        n_suffix_pages = bucket // ps
        n_prefix_pages = start // ps
        prefix_P = pick_page_bucket(
            max(n_prefix_pages, 1), self.sched.max_pages
        )
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :suffix_len] = seq.prompt[start : start + suffix_len]
        prefix_table = np.zeros((1, prefix_P), np.int32)
        prefix_table[0, :n_prefix_pages] = seq.pages[:n_prefix_pages]
        suffix_table = np.zeros((1, n_suffix_pages), np.int32)
        k = min(len(seq.pages) - n_prefix_pages, n_suffix_pages)
        suffix_table[0, :k] = seq.pages[n_prefix_pages : n_prefix_pages + k]
        _, self.kv.pages = prefill_suffix_and_sample(
            self.params,
            self.model_cfg,
            self.kv.pages,
            self._put_batch(tokens),
            self._put_batch(np.asarray([start], np.int32)),
            self._put_batch(np.asarray([suffix_len], np.int32)),
            self._put_batch(prefix_table),
            self._put_batch(suffix_table),
            self._next_rng(),
            self._sampling_arrays([seq]),
        )
        seq.prefilled_tokens = start + suffix_len
        seq.note_prefill(suffix_len)
        # no record of its own: its rows ride the next classic prefill's
        self._loose_prefill_rows += suffix_len
        self._steps += 1
        self.obs.observe_dispatch("chunk")
        if self._tick is not None:
            self._tick.note_dispatch("chunk")
        logger.debug(
            "prefill chunk id=%s %d..%d/%d", seq.request_id, start,
            seq.prefilled_tokens, prompt_len,
        )
        return None

    def _finish_prefill(
        self, seq: SeqState, prompt_len: int, cached: int
    ) -> InflightPrefill:
        compile_sentry.set_entry("prefill")
        if cached > 0:
            sampled = self._dispatch_suffix_prefill_batch(
                [(seq, prompt_len, cached)], 1
            )
            bucket = pick_bucket(self.buckets, prompt_len - cached)
        else:
            sampled = self._dispatch_full_prefill(seq, seq.prompt, seq.pages)
            bucket = pick_bucket(self.buckets, prompt_len)
        # bring decode state current (admission marked the lane dirty),
        # then inject the device-resident first token into its lane
        self._sync_device_state()
        tok = sampled[:, 0]  # device slice from the packed [1, C] row
        pf = InflightPrefill(
            sampled=sampled, tok=tok, seq=seq, slot=seq.slot,
            rows=prompt_len - cached + self._loose_prefill_rows,
        )
        self._loose_prefill_rows = 0
        if (
            seq.prompt_logprobs is not None
            and not seq.prompt_lp_sent
            and seq.prior_generated == 0  # resumes fold output into prompt
        ):
            pf.prompt_lp = self._dispatch_prompt_score(seq)
        self._pending_injects[seq.slot] = pf
        self._dev["tokens"] = self._fns.inject_token(
            self._dev["tokens"], seq.slot, tok
        )
        if self._dev.get("counts") is not None:
            self._dev["counts"] = self._fns.bump_counts(
                self._dev["counts"],
                jnp.asarray([seq.slot], jnp.int32), tok,
            )
        self._steps += 1
        self.obs.observe_dispatch("prefill")
        if self._tick is not None:
            self._tick.note_dispatch("prefill")
        seq.note_prefill(prompt_len - cached)
        logger.debug("prefill dispatched id=%s len=%d bucket=%d",
                     seq.request_id, prompt_len, bucket)
        return pf

    @hot_path
    def _do_prefill_group(
        self, items: List[Tuple[SeqState, int]]
    ) -> List["InflightPrefillGroup"]:
        """One batched prefill dispatch for same-shape admissions (executor
        thread): the whole group pays a single weight-streaming pass.

        All lanes share a suffix-length bucket and (when any lane has a
        cached prefix) a prefix-page bucket -- the tick loop groups by
        exactly those keys -- and the batch dimension pads to a power of
        two, so compile-cache entries stay O(buckets x log(batch)), not
        O(buckets x batch).  The array construction lives in the shared
        ``_dispatch_*_prefill_batch`` builders, the same dispatch sites the
        single-request and disagg-export paths use."""
        compile_sentry.set_entry("prefill")
        for seq, _pl in items:
            self._note_prefetch_admission(seq)
            if seq.pending_onboard:
                self._apply_onboards(seq)
            if not seq.stats_counted:
                seq.stats_counted = True
                self._prefix_lookups += len(seq.prompt)
                self._prefix_hits += seq.cached_prompt_tokens
                self.obs.prefix_lookups.inc(len(seq.prompt))
                if seq.cached_prompt_tokens:
                    self.obs.prefix_hits.inc(seq.cached_prompt_tokens)
        Bp = self._pad_batch(len(items))
        caches = [seq.cached_prompt_tokens for seq, _ in items]
        if not any(caches):
            sampled = self._dispatch_full_prefill_batch(
                [(seq, seq.prompt, seq.pages) for seq, _ in items], Bp
            )
        else:
            sampled = self._dispatch_suffix_prefill_batch(
                [(seq, pl, c) for (seq, pl), c in zip(items, caches)], Bp
            )
        self._sync_device_state()
        # one batched scatter for the whole group's first tokens: per-lane
        # inject_token dispatches were the dominant group overhead on a
        # high-RTT device link (pad rows carry slot=B and are dropped)
        slots = np.full((Bp,), self.cfg.max_batch_size, np.int32)
        for i, (seq, _pl) in enumerate(items):
            slots[i] = seq.slot
        self._dev["tokens"] = self._fns.inject_tokens(
            self._dev["tokens"], jnp.asarray(slots), sampled[:Bp, 0]
        )
        if self._dev.get("counts") is not None:
            self._dev["counts"] = self._fns.bump_counts(
                self._dev["counts"], jnp.asarray(slots), sampled[:Bp, 0]
            )
        entries: List[InflightPrefill] = []
        for i, (seq, pl) in enumerate(items):
            pf = InflightPrefill(
                sampled=sampled[i : i + 1],  # packed row (commit data)
                tok=sampled[i : i + 1, 0],  # device slice: inject re-apply
                seq=seq,
                slot=seq.slot,
                rows=pl - caches[i],
            )
            if (
                seq.prompt_logprobs is not None
                and not seq.prompt_lp_sent
                and seq.prior_generated == 0
            ):
                pf.prompt_lp = self._dispatch_prompt_score(seq)
            self._pending_injects[seq.slot] = pf
            seq.note_prefill(pl - caches[i])
            logger.debug(
                "prefill dispatched id=%s len=%d cached=%d (group of %d)",
                seq.request_id, pl, caches[i], len(items),
            )
            entries.append(pf)
        self._steps += 1
        self.obs.observe_dispatch("prefill")
        if self._tick is not None:
            self._tick.note_dispatch("prefill")
        _start_host_copy(sampled)
        # ONE group handle: commit fetches the [Bp] array in one transfer
        # instead of one round trip per lane's [1] slice
        return [InflightPrefillGroup(sampled=sampled, entries=entries)]

    def _compute_limits(self) -> np.ndarray:
        """Absolute per-lane cache-length caps from the host mirrors.

        ``seq_lens + remaining_budget`` is invariant under commits (each
        commit raises one and lowers the other equally), so this is correct
        even while a decode block is in flight."""
        sched = self.sched
        limit = np.zeros((self.cfg.max_batch_size,), np.int32)
        for b, seq in enumerate(sched.slots):
            if seq is None:
                continue
            limit[b] = min(
                int(sched.seq_lens[b]) + sched.remaining_budget(seq),
                self.cfg.max_seq_len - 1,
                # capacity cap: never write past the lane's allocated pages
                # (positions < len(pages)*page_size); the lane pauses there
                # until ensure_decode_capacity frees/grows pages
                len(seq.pages) * self.cfg.page_size,
            )
        return limit

    def _lane_stop_row(self, seq: Optional[SeqState]) -> np.ndarray:
        """Device-swallowable stop tokens for one lane (see
        ``_push_device_state``): only when the host rules coincide exactly."""
        E = self.cfg.device_stop_width
        row = np.full((E,), -1, np.int32)
        if seq is not None and seq.stop.min_tokens is None:
            ids = list(seq.stop.stop_token_ids_hidden or [])
            if not seq.stop.ignore_eos:
                ids += list(seq.eos_ids)
            for j, t in enumerate(ids[:E]):
                row[j] = t
        return row

    @hot_path
    def _apply_dirty_rows(self) -> None:
        """Fold mirror changes for dirty lanes into the device-resident state
        with per-row scatters (executor thread).

        This replaces the pipeline drain the engine used to pay on every
        batch-membership change: the scatters are dispatched after any
        in-flight decode blocks, which therefore run against the old rows --
        their stale lanes' output is discarded at commit (slot snapshots +
        ``seq.finish`` guards in ``Scheduler.commit_block``), and any pages
        a stale lane's tail writes touch are either still owned by it or are
        re-prefilled by a later-dispatched admission before reuse (device
        executes dispatches in order).  Correct only because dirty lanes
        never carry uncommitted in-flight decode progress: admission,
        release, revival and external-KV arrival all act on lanes that are
        parked, fresh, or committed-through."""
        compile_sentry.set_entry("kv_pages")
        sched = self.sched
        d = self._dev
        assert d is not None
        limits = self._compute_limits()
        dirty = sorted(sched.dirty_slots)
        # fixed G = max_batch_size: the rows are a few KB, so a single
        # always-warm executable beats per-burst-size pad buckets (a G
        # bucket first seen mid-serving would compile inside the measured
        # window; pad rows carry an out-of-range slot and drop)
        G = self.cfg.max_batch_size
        E = self.cfg.device_stop_width
        slots = np.full((G,), self.cfg.max_batch_size, np.int32)  # pad = drop
        rows = {
            "token": np.zeros((G,), np.int32),
            "seq_len": np.zeros((G,), np.int32),
            "limit": np.zeros((G,), np.int32),
            "active": np.zeros((G,), bool),
            "stop": np.full((G, E), -1, np.int32),
            "pages": np.zeros(
                (*sched.page_table.shape[:-2], G, sched.page_table.shape[-1]),
                np.int32,
            ),
            "temp": np.zeros((G,), np.float32),
            "top_p": np.ones((G,), np.float32),
            "top_k": np.zeros((G,), np.int32),
            "seed": np.zeros((G,), np.uint32),
            "freq": np.zeros((G,), np.float32),
            "pres": np.zeros((G,), np.float32),
            "rep": np.ones((G,), np.float32),
        }
        for i, b in enumerate(dirty):
            seq = sched.slots[b]
            slots[i] = b
            rows["token"][i] = sched.tokens[b]
            rows["seq_len"][i] = sched.seq_lens[b]
            rows["limit"][i] = limits[b]
            rows["active"][i] = (
                seq is not None
                and limits[b] > int(sched.seq_lens[b])
                and not seq.awaiting_kv
                and not seq.prefilling
                # live-spec lanes advance via verify columns; an
                # acceptance-disabled lane reverts to the decode scan here
                and not _spec_live(seq)
            )
            rows["stop"][i] = self._lane_stop_row(seq)
            rows["pages"][..., i, :] = sched.page_table[..., b, :]
            if seq is not None:
                so = seq.sampling
                if so.temperature is not None:
                    rows["temp"][i] = so.temperature
                elif so.top_p is not None or so.top_k is not None:
                    rows["temp"][i] = 1.0
                rows["top_p"][i] = so.top_p if so.top_p is not None else 1.0
                rows["top_k"][i] = so.top_k or 0
                rows["seed"][i] = self._norm_seed(so)
                rows["freq"][i] = so.frequency_penalty or 0.0
                rows["pres"][i] = so.presence_penalty or 0.0
                rows["rep"][i] = so.repetition_penalty or 1.0
            self._limit_host[b] = limits[b]
        samp = d["sampling"]
        (
            d["tokens"],
            d["seq_lens"],
            d["limit_lens"],
            d["active"],
            d["stop_ids"],
            d["page_table"],
            temp,
            top_p,
            top_k,
            seed,
            freq,
            pres,
            rep,
        ) = self._fns.update_lanes(
            d["tokens"],
            d["seq_lens"],
            d["limit_lens"],
            d["active"],
            d["stop_ids"],
            d["page_table"],
            samp.temperature,
            samp.top_p,
            samp.top_k,
            samp.seed,
            samp.freq,
            samp.pres,
            samp.rep,
            jnp.asarray(slots),
            rows,
        )
        d["sampling"] = SamplingParams(
            temperature=temp, top_p=top_p, top_k=top_k, seed=seed,
            freq=freq, pres=pres, rep=rep,
        )
        # penalty histograms: zero the flushed lanes, then re-seed each
        # penalized lane's row from its committed output history (a dirty
        # flush can hit a mid-request lane -- growth revival, external KV;
        # tokens of a still-uncommitted in-flight block are skipped, a
        # bounded one-block skew on a rare path)
        if d.get("counts") is not None and dirty:
            # the fixed-G padded slot array from above: a dirty-set-sized
            # array would compile one executable per distinct burst size
            # (pad slots are out of range; mode='drop' skips them), matching
            # update_lanes
            d["counts"] = self._fns.zero_count_rows(d["counts"], jnp.asarray(slots))
            for b in dirty:
                seq = sched.slots[b]
                if seq is None or not self._seq_penalized(seq):
                    continue
                toks, amts = self._penalty_history(seq)
                if not toks:
                    continue
                pad = pow2_bucket(len(toks))
                buf = np.zeros((pad,), np.int32)
                amounts = np.zeros((pad,), np.int32)
                buf[: len(toks)] = toks
                amounts[: len(toks)] = amts
                d["counts"] = self._fns.seed_count_rows(
                    d["counts"], jnp.int32(b), jnp.asarray(buf),
                    jnp.asarray(amounts),
                )
        # pending injects hold the real first token for lanes whose mirror
        # still has the placeholder; re-apply them on top of the row scatter
        # (batched: one scatter, not one dispatch per lane)
        injects: List[Tuple[int, Any]] = []
        for b in dirty:
            pf = self._pending_injects.get(b)
            if pf is not None:
                if sched.slots[b] is pf.seq and pf.seq.finish is None:
                    injects.append((b, pf.tok))
                else:
                    del self._pending_injects[b]
        if len(injects) == 1:
            b, samp = injects[0]
            d["tokens"] = self._fns.inject_token(d["tokens"], jnp.int32(b), samp)
        elif injects:
            d["tokens"] = self._fns.inject_tokens(
                d["tokens"],
                jnp.asarray(np.asarray([b for b, _ in injects], np.int32)),
                jnp.concatenate([s for _, s in injects]),
            )
        if injects and d.get("counts") is not None:
            # the re-applied first tokens follow the same rule as their
            # original injection: they are output, so they count (the lane
            # was just zeroed+reseeded above, so exactly once)
            d["counts"] = self._fns.bump_counts(
                d["counts"],
                jnp.asarray(np.asarray([b for b, _ in injects], np.int32)),
                jnp.concatenate([s for _, s in injects]),
            )
        sched.dirty_slots.clear()
        self._dev_version = sched.layout_version

    def _sync_device_state(self) -> None:
        """Bring the device-resident decode state current (executor thread):
        full rebuild only when none exists; otherwise per-lane row scatters
        for membership changes and a table/limit swap for page growth --
        neither drains the decode pipeline."""
        sched = self.sched
        if self._dev is None:
            self._push_device_state()
            return
        if sched.dirty_slots:
            self._apply_dirty_rows()
        if self._dev_growth != sched.growth_version:
            # growth-only refresh: swap the page table and raise the limits,
            # keeping tokens/seq_lens/active device-resident.  ``active`` is
            # left as the device carry: paused lanes revive through
            # _revive_paused_lanes marking them dirty.
            limit = self._compute_limits()
            # a lane that the dispatches still in flight may carry to the
            # limit they were issued under pauses there on the device.
            # Raised now, the host's copy would read the new limit before
            # the mirror reaches the old one, _revive_paused_lanes would
            # never see the pause and the lane would never run again (short
            # fused blocks walk a lane up to its limit a step or two at a
            # time, so the raise meets it there).  Such a lane keeps its
            # limit, pauses, and its revival folds the raise in with its row.
            ahead = self._decode_steps_of(
                e for gen in self._inflight or () for e in gen
            )
            heading = sched.seq_lens + ahead >= self._limit_host
            limit = np.where(
                heading, np.minimum(limit, self._limit_host), limit
            )
            # numpy copy for the same aliasing reason as _push_device_state
            self._dev["page_table"] = self._put_batch(sched.page_table.copy())
            self._dev["limit_lens"] = self._put_batch(limit)
            self._dev_growth = sched.growth_version
            self._limit_host = limit

    def _push_device_state(self) -> None:
        """Rebuild device-resident decode state from the scheduler mirrors."""
        compile_sentry.set_entry("kv_pages")
        sched = self.sched
        B = self.cfg.max_batch_size
        E = self.cfg.device_stop_width
        limit = self._compute_limits()
        active = np.zeros((B,), bool)
        stop_ids = np.full((B, E), -1, np.int32)
        for b, seq in enumerate(sched.slots):
            if seq is None:
                continue
            # a lane with no write headroom must not run: it would scatter
            # its next KV write to the trash page and emit a garbage token.
            # Lanes awaiting a remote prefill's KV stay parked until
            # delivery; live-spec lanes advance via verify columns (an
            # acceptance-disabled one is a plain decode lane again).
            active[b] = (
                limit[b] > int(sched.seq_lens[b])
                and not seq.awaiting_kv
                and not seq.prefilling
                and not _spec_live(seq)
            )
            # stop tokens the device may swallow itself (shared helper so
            # the full-rebuild and dirty-row paths cannot diverge)
            stop_ids[b] = self._lane_stop_row(seq)
        # COPY the scheduler mirrors with numpy (synchronous) before handing
        # them to JAX: on CPU, jnp.asarray aliases the numpy buffer zero-copy
        # and even jnp.array's copy can be performed asynchronously -- while
        # the scheduler mutates these arrays in place on later ticks.  An
        # async-dispatched decode block still queued on device would read the
        # *future* page table and scatter a dead lane's frozen write into a
        # page that now belongs to another sequence.  Harmless when every
        # reallocated page is re-prefilled; fatal once prefix reuse keeps
        # pages alive.  The .copy() is owned by JAX alone, so aliasing it is
        # safe.
        self._dev = {
            "tokens": self._put_batch(sched.tokens.copy()),
            "seq_lens": self._put_batch(sched.seq_lens.copy()),
            "limit_lens": self._put_batch(limit),
            "active": self._put_batch(active),
            "stop_ids": self._put_batch(stop_ids),
            "page_table": self._put_batch(sched.page_table.copy()),
            "sampling": self._sampling_arrays(list(sched.slots)),
        }
        # mirrors hold a placeholder for lanes whose prefilled first token is
        # still device-only; re-apply those injections
        for slot, pf in list(self._pending_injects.items()):
            if sched.slots[slot] is pf.seq and pf.seq.finish is None:
                self._dev["tokens"] = self._fns.inject_token(
                    self._dev["tokens"], slot, pf.tok
                )
            else:
                del self._pending_injects[slot]
        self._dev_version = sched.layout_version
        self._dev_growth = sched.growth_version
        self._limit_host = limit
        sched.dirty_slots.clear()

    def _output_tokens(self, seq: SeqState) -> List[int]:
        """Full committed output history for penalty accounting: tokens
        generated this life PLUS the tail that recompute preemption folded
        into the prompt (the last ``prior_generated`` prompt entries are
        previous lives' output -- vLLM keeps output_token_ids across
        preemption; this reconstructs the same set)."""
        folded = (
            list(seq.prompt[len(seq.prompt) - seq.prior_generated:])
            if seq.prior_generated
            else []
        )
        return folded + self.sched._generated_tokens(seq)

    @staticmethod
    def _seq_penalized(seq: SeqState) -> bool:
        so = seq.sampling
        return bool(
            so.frequency_penalty
            or so.presence_penalty
            or (so.repetition_penalty and so.repetition_penalty != 1.0)
        )

    def _penalty_history(self, seq: SeqState):
        """(tokens, amounts) for the packed histogram: committed output
        occurrences count 1, prompt-proper occurrences add PROMPT_FLAG
        (the prompt tail of length prior_generated is folded OUTPUT from
        recompute preemption, not prompt -- the single home of that
        invariant for both the device reseed and the host rebuild)."""
        from .sampling import PROMPT_FLAG

        out = self._output_tokens(seq)
        plen = len(seq.prompt) - seq.prior_generated
        ptoks = list(seq.prompt[:plen])
        return out + ptoks, [1] * len(out) + [PROMPT_FLAG] * len(ptoks)

    def _counts_host(self) -> np.ndarray:
        """Generated-token histograms rebuilt from scheduler state (lanes
        with penalties only; other rows stay zero and are never read)."""
        B = self.cfg.max_batch_size
        V = self.model_cfg.vocab_size
        counts = np.zeros((B, V), np.int32)
        for b, seq in enumerate(self.sched.slots):
            if seq is None or not self._seq_penalized(seq):
                continue
            toks, amounts = self._penalty_history(seq)
            if toks:
                np.add.at(
                    counts[b], np.asarray(toks, np.int64),
                    np.asarray(amounts, np.int64),
                )
        return counts

    def _live_page_bucket(self) -> int:
        """Power-of-two page-table width covering the longest slotted
        lane's allocation -- the ONE bucketing rule shared by the
        decode-block and verify dispatches (the classic paths) and by the
        unified dispatches of a pool whose kernels walk a grid over the
        table's width (narrow heads, int8: ``_packed_full_table`` false), so
        no two paths can compile against different table widths.  Where the
        kernels walk a work list the unified dispatches take the table
        whole and never ask.  The floor of 64 pages
        bounds the executable count: every width is one more executable
        for each packed shape and each fused K, compiled the first time a
        quiet moment leaves only short lanes in the batch (with a floor of
        8, short chat prompts at 3-7 requests/s compiled inside 5 of 8
        swept windows of 50 s on the chip, PERF.md PR 30), and what a
        narrower table saves is the kernels' grid steps over 56 pages."""
        live_pages = [
            len(s.pages) for s in self.sched.slots if s is not None and s.pages
        ]
        return pick_page_bucket(
            min(max(64, max(live_pages, default=1)), self.sched.max_pages),
            self.sched.max_pages,
        )

    @hot_path
    def _dispatch_block(self) -> Optional["InflightBlock"]:
        """Enqueue one decode block; does not wait for results."""
        compile_sentry.set_entry("decode_block")
        K = self.cfg.decode_block_size
        if self.sched.num_active == 0:
            return None  # everything was preempted
        self._sync_device_state()
        d = self._dev
        # Decode attention streams every page-table slot it is given, so the
        # dispatch narrows the table to a power-of-two bucket covering the
        # longest lane's allocated pages (growth lookahead included --
        # attention can never read past a lane's allocation).  Dead lanes'
        # rows are zeroed, so clamped gathers land on trash page 0.  Each
        # bucket is its own cached executable; the floor bounds the count.
        Pb = self._live_page_bucket()
        use_filters = any(
            s is not None and self._sampling_needs_filters(s.sampling)
            for s in self.sched.slots
        )
        use_penalties = any(
            s is not None and self._seq_penalized(s) for s in self.sched.slots
        )
        if use_penalties and d.get("counts") is None:
            d["counts"] = self._put_batch(self._counts_host())
            # pending first tokens are device-only (not yet in committed
            # history): fold them in so device and host views agree
            pend = [
                (slot, pf.tok)
                for slot, pf in self._pending_injects.items()
                if self.sched.slots[slot] is pf.seq
            ]
            if pend:

                d["counts"] = self._fns.bump_counts(
                    d["counts"],
                    jnp.asarray(
                        np.asarray([p[0] for p in pend], np.int32)
                    ),
                    jnp.concatenate([p[1] for p in pend]),
                )
        elif not use_penalties:
            d["counts"] = None  # free the 8MB-class buffer when unused
        tick = self._tick
        if tick is not None:
            tick.mark("assemble")
        (
            sampled,
            d["tokens"],
            d["seq_lens"],
            d["active"],
            self.kv.pages,
            self._rng,
            counts_out,
        ) = self._fns.decode_block(
            self.params,
            self.model_cfg,
            self.kv.pages,
            d["tokens"],
            d["seq_lens"],
            d["limit_lens"],
            d["active"],
            d["stop_ids"],
            d["page_table"][..., :Pb],
            self._rng,
            d["sampling"],
            K,
            use_filters,
            self._lp_top(self.sched.slots),
            d.get("counts"),
            use_penalties,
        )
        if use_penalties:
            d["counts"] = counts_out
        self._steps += 1
        self.obs.observe_dispatch("decode_block")
        self.obs.observe_multistep_k(1)
        _start_host_copy(sampled)
        if tick is not None:
            tick.note_dispatch("decode_block")
            tick.mark("dispatch")
        return InflightBlock(
            sampled=sampled, slots=list(self.sched.slots),
            n_decode=self.sched.num_decode_runnable, n_steps=K,
        )

    @hot_path
    def _dispatch_unified(
        self,
        chunks: List[Any],
        fold_spec: bool = False,
        num_steps: int = 0,
    ) -> Optional["InflightUnified"]:
        """Enqueue one unified ragged mixed-batch step (executor thread).

        Every decode lane contributes one query row read from the
        device-resident state (so unified steps pipeline exactly like
        decode blocks: dispatch i+1 goes out before step i's tokens
        materialize), and each :class:`~.scheduler.MixedChunk` contributes
        its prompt rows.  Final chunks sample the lane's first token on
        device and fold it into the decode state -- the unified analog of
        ``inject_token`` -- with an :class:`InflightPrefill` record minted
        for the pending-inject re-apply path and the echo+logprobs
        ride-along.  Host chunk bookkeeping advances at dispatch, exactly
        like ``_dispatch_chunk``, so next tick's formation never re-packs
        dispatched tokens.

        With ``fold_spec`` the tick's verify-eligible speculating lanes
        contribute ``1 + draft`` extra segments -- last committed token +
        host-proposed drafts -- scored in this SAME dispatch (ISSUE 15): a
        speculating tick pays ONE device launch, not decode + verify.
        Their per-column samples ride the returned record's
        ``spec_sampled`` handle and commit through the host accept walk at
        commit time.

        With ``num_steps >= 1`` (chunk-free, spec-free -- the tick loop
        only routes pure-decode multistep ticks here, with K from the
        adaptive controller) the dispatch runs the decode rows
        alone; for K > 1 it runs ``packed_unified_multistep``: K decode
        iterations fused into one launch, sampling and appending KV on
        device each step, so the host syncs one ``[B, K]`` token block
        per K generated tokens.  Commit replays the block through
        ``commit_block`` exactly like an :class:`InflightBlock`, so stop
        rules stay host-authoritative and mid-block cancels discard for
        free.  ``num_steps == 0`` (the default) marks a non-multistep
        call, where a chunk-less spec-less dispatch has nothing to pack.
        """
        compile_sentry.set_entry("packed_unified_step")
        sched = self.sched
        spec_lanes = self._gather_spec_lanes() if fold_spec else []
        if not chunks and not spec_lanes and num_steps <= 0:
            # the loop thread saw verify-eligible lanes that vanished
            # before the executor hop (cancel/preempt race): nothing to
            # dispatch -- plain decode lanes are better served by the
            # K-step block next tick
            return None
        num_steps = max(num_steps, 1)
        for ch in chunks:
            seq = ch.seq
            self._note_prefetch_admission(seq)
            if seq.pending_onboard:
                end = ch.start + ch.length
                self._apply_onboards(seq)
                if seq.cached_prompt_tokens < ch.start:
                    # onboard truncated (chaos/IO): the would-have-been-
                    # onboarded span must be recomputed, so widen this
                    # chunk back to the surviving cached prefix -- the
                    # classic path gets this ordering for free because it
                    # reads the start AFTER _apply_onboards
                    ch.start = seq.cached_prompt_tokens
                    ch.length = end - ch.start
                    ch.seq.prefilled_tokens = ch.start
            if not seq.stats_counted:
                seq.stats_counted = True
                self._prefix_lookups += len(seq.prompt)
                self._prefix_hits += seq.cached_prompt_tokens
                self.obs.prefix_lookups.inc(len(seq.prompt))
                if seq.cached_prompt_tokens:
                    self.obs.prefix_hits.inc(seq.cached_prompt_tokens)
        B = self.cfg.max_batch_size
        p_start = np.zeros((B,), np.int32)
        p_lens = np.zeros((B,), np.int32)
        p_sample = np.zeros((B,), bool)
        p_act = np.zeros((B,), bool)
        n_pf_tokens = 0
        final_chunks: List[Any] = []
        chunk_by_slot: Dict[int, Any] = {}
        for ch in chunks:
            b = ch.seq.slot
            chunk_by_slot[b] = ch
            p_start[b] = ch.start
            p_lens[b] = ch.length
            p_sample[b] = ch.final
            # live-spec lanes sample their first token here but stay
            # device-inactive: they advance via verify columns, and a
            # device-activated spec lane would be decoded TWICE (an
            # acceptance-disabled lane activates like any decode lane)
            p_act[b] = ch.final and not _spec_live(ch.seq)
            n_pf_tokens += ch.length
            # dispatch-ordered host bookkeeping (the _dispatch_chunk rule)
            ch.seq.prefilled_tokens = ch.start + ch.length
            ch.seq.note_prefill(ch.length)
            ch.seq.prefill_mixed = True
            if ch.final:
                ch.seq.prefilling = False
                final_chunks.append(ch)
        # folded verify segments: host-authoritative, exactly like the
        # standalone verify step -- base = committed cache length (rides
        # p_start), row 0 = last committed token, rows 1.. = the drafts.
        # ``inflight`` latches here (dispatch time), released at commit.
        v_host = np.zeros((B,), np.int32)
        n_spec_tokens = 0
        max_d = 0
        for seq, b, draft in spec_lanes:
            p_start[b] = sched.seq_lens[b]
            v_host[b] = 1 + len(draft)
            n_spec_tokens += 1 + len(draft)
            max_d = max(max_d, len(draft))
            seq.spec.inflight = True
        # verify columns pad to the MAX_DRAFT_TOKENS pow2 rule: the same
        # {1, 2, 3, 5, 9} set the standalone verify dispatch compiles
        s_spec = 0
        if spec_lanes:
            s_spec = 1 + (pow2_bucket(max_d) if max_d else 0)
        self._sync_device_state()
        d = self._dev
        # a work-list kernel has no step for a page group, and where the
        # packed launch walks one the fused steps' decode launch is the same
        # kernel (attention.decode_backend): every dispatch takes the whole
        # table, whose width is then no axis of any unified executable.  A
        # pool that keeps the grid kernels (narrow heads, int8) keeps the
        # bucket: they pay a step for every page group of the table's width
        Pb = (
            sched.max_pages
            if self._packed_full_table
            else self._live_page_bucket()
        )
        # decode-capable lanes contribute one fresh row each; the count
        # feeds the occupancy histograms
        dec_cap = np.zeros((B,), bool)
        for b, s in enumerate(sched.slots):
            dec_cap[b] = (
                s is not None
                and p_lens[b] == 0
                and v_host[b] == 0
                and s.finish is None
                and not s.awaiting_kv
                and not s.prefilling
                and not _spec_live(s)
            )
        n_decode = int(dec_cap.sum())
        if num_steps > 1 and n_decode == 0:
            # pure-decode multistep tick whose lanes vanished before the
            # executor hop (cancel/preempt race): nothing to fuse
            return None
        use_filters = any(
            s is not None and self._sampling_needs_filters(s.sampling)
            for s in sched.slots
        )
        top_n = self._lp_top(sched.slots)
        dispatch_meta: Dict[str, Any] = {}  # the dispatch annotation's stats
        # fully-packed layout (ISSUE 10): ONE flat token axis sized
        # pow2(real fresh tokens), so the trunk never pays for padding
        # every lane to the longest chunk.  Segments pack contiguously
        # in slot order.  Where the launch reads a lane's whole static
        # s_max window from its offset (the grid kernel, the XLA
        # composition, the latent kernels) the packed-axis pad also covers
        # the last live lane's window; the pair pools' work-list kernel
        # keeps its tiles inside the axis, and there the step runs the
        # rows it has (PackedShapeBudget's contract says which rule holds)
        q_host = np.where(
            dec_cap, 1, np.where(v_host > 0, v_host, p_lens)
        ).astype(np.int32)
        total = int(q_host.sum())
        s_nat = pow2_bucket(int(q_host.max()) if total else 1)
        seg_off = np.zeros((B,), np.int32)
        off = 0
        off_last = 0
        for b in range(B):
            ql = int(q_host[b])
            if ql == 0:
                continue
            seg_off[b] = off
            off_last = off
            off += ql
        # (Np, s_max, s_spec) through the executable-shape budget:
        # reuse or merge up into an already-minted triple instead of
        # compiling a fresh executable for every arrival pattern
        # (ISSUE 13 satellite, verify columns included since ISSUE
        # 15; the budget keeps total <= Np, and off_last + s_max <= Np
        # for a launch that reads windows)
        Np, s_max, s_spec = self._packed_shapes.fit(
            s_nat, off_last, total, s_spec
        )
        if not self._packed_fits(Np, s_max):
            raise RuntimeError(
                f"packed dispatch shape (Np={Np}, s_max={s_max}) "
                "exceeds what the packed attention kernel can hold; "
                "lower mixed_token_budget"
            )
        self.obs.observe_executable_shapes(len(self._packed_shapes))
        t_tokens = np.zeros((Np,), np.int32)
        t_lane = np.full((Np,), B, np.int32)
        t_rel = np.zeros((Np,), np.int32)
        t_dec = np.zeros((Np,), bool)
        spec_by_slot = {b: draft for _s, b, draft in spec_lanes}
        for b in range(B):
            ql = int(q_host[b])
            if ql == 0:
                continue
            o = int(seg_off[b])
            t_lane[o : o + ql] = b
            t_rel[o : o + ql] = np.arange(ql, dtype=np.int32)
            ch = chunk_by_slot.get(b)
            if ch is not None:
                t_tokens[o : o + ql] = ch.seq.prompt[
                    ch.start : ch.start + ql
                ]
            elif b in spec_by_slot:
                # verify segment: committed token + drafts (host
                # mirrors authoritative, the verify-dispatch rule)
                t_tokens[o] = sched.tokens[b]
                dr = spec_by_slot[b]
                if dr:
                    t_tokens[o + 1 : o + 1 + len(dr)] = dr
            else:
                t_dec[o] = True
        disp_tokens = Np + B * (num_steps - 1)
        self._dispatch_serial = serial = self._dispatch_serial + 1
        tick = self._tick
        if tick is not None:
            tick.mark("assemble")
        if tick is not None and tick.annotating:
            # a profiler trace is being taken: what the attention kernels
            # are asked to do, carried by the dispatch interval's
            # annotation: per live lane its fresh query rows and the
            # context its last row reads (host mirrors: a decode lane's
            # lags the device by the uncommitted generations), the fused
            # steps, the packed rows, the class of the step and the
            # dispatch's serial (the ``device_wait`` annotation that
            # fetches it names it again).  Lists are "|"-joined: a comma
            # cuts a trace stat.
            live = np.nonzero(q_host)[0]
            base = np.where(dec_cap, sched.seq_lens, p_start)
            dispatch_meta = {
                "q": "|".join(str(int(v)) for v in q_host[live]),
                "ctx": "|".join(
                    str(int(v)) for v in (base + q_host)[live]
                ),
                "k": num_steps,
                "np": Np,
                "pt": Pb,
                "step": "chunk" if n_pf_tokens else "decode",
                "d": serial,
            }
            # the launch as the dense pools' kernel walks it: its work
            # items, how many of them take the small tile, and how many
            # start on copies the item before them put in flight
            from ..ops.ragged_attention import packed_item_counts

            (
                dispatch_meta["items"],
                dispatch_meta["small"],
                dispatch_meta["chained"],
            ) = packed_item_counts(q_host[live], s_max)
            # which kernels the dispatch takes: the latent path of its
            # packed launch, and what attends the fused steps after the
            # first (read once, at construction)
            if self._latent_path is not None:
                dispatch_meta["latent"] = self._latent_path
            if num_steps > 1:
                dispatch_meta["decode"] = self._decode_backend
            if self.model_cfg.is_moe:
                # the layout the packed rows' expert MLPs take (a fused
                # block's later steps ask the same of the lanes)
                from .model import moe_layout

                dispatch_meta["moe"] = moe_layout(
                    self.params, self.model_cfg, Np
                )
            if self.model_cfg.has_conv:
                # which kernels the packed launch over the attention layers'
                # pool takes, and the lanes whose convolution layers resume
                # from a page's snapshot in this dispatch: a request's
                # first chunk after a prefix hit
                dispatch_meta["attn"] = self._packed_attn
                dispatch_meta["restored"] = sum(
                    1 for ch in chunks
                    if ch.start and ch.start == ch.seq.cached_prompt_tokens
                )
        if self.model_cfg.has_linear:
            # what the dispatch does with the snapshot pool rides the cache
            # into the step (kv_cache.DeltaKV.plan): every packed dispatch
            # writes it, so none reads the last one's
            plan = sched.state_plan(chunks)
            self.kv.pages = self.kv.pages.with_plan(jnp.asarray(plan))
            self.obs.observe_snapshot_slots(
                len(sched.state_slots), sched.state_slots.evictions)
            if self._delta_backend == "kernel":
                # chunks the step's launches run, a launch a linear layer
                from ..ops.gated_delta import chunks_of

                self.obs.gdn_chunks.inc(
                    self.model_cfg.kind_layers("linear") * chunks_of(
                        q_host, np.where(dec_cap, sched.seq_lens, p_start),
                        plan))
            if tick is not None and tick.annotating:
                dispatch_meta["attn"] = self._packed_attn
                dispatch_meta["gdn"] = self._delta_backend
                dispatch_meta["restored"] = int((plan[0] >= 0).sum())
                dispatch_meta["snapshots"] = int((plan[1] >= 0).sum())
                dispatch_meta["state_restored_tokens"] = sum(
                    ch.start for ch in chunks if plan[0, ch.seq.slot] >= 0)
                dispatch_meta["recompute_tokens"] = sum(
                    ch.seq.state_recompute for ch in chunks
                    if plan[0, ch.seq.slot] >= 0)
        operands = (
            self.params,
            self.model_cfg,
            self.kv.pages,
            d["tokens"],
            d["seq_lens"],
            d["limit_lens"],
            d["active"],
            d["stop_ids"],
            d["page_table"][..., :Pb],
            jnp.asarray(t_tokens),
            jnp.asarray(t_lane),
            jnp.asarray(t_rel),
            jnp.asarray(t_dec),
            self._put_batch(p_start),
            self._put_batch(p_lens),
            self._put_batch(p_sample),
            self._put_batch(p_act),
            self._put_batch(dec_cap),
            self._put_batch(seg_off),
            self._put_batch(v_host),
            self._rng,
            d["sampling"],
        )
        if num_steps > 1:
            # K decode iterations fused into the launch: packed is
            # [B, K, 2 + 2*top_n], row k = on-device step k's sample
            compile_sentry.set_entry("packed_unified_multistep")
            (
                packed,
                spec_packed,
                d["tokens"],
                d["seq_lens"],
                d["active"],
                self.kv.pages,
                self._rng,
                *reach,
            ) = self._fns.packed_unified_multistep(
                *operands, s_max, num_steps, s_spec, top_n, use_filters,
            )
        else:
            (
                packed,
                spec_packed,
                d["tokens"],
                d["seq_lens"],
                d["active"],
                self.kv.pages,
                self._rng,
                *reach,
            ) = self._fns.packed_unified_step(
                *operands, s_max, s_spec, top_n, use_filters,
            )
        # what the decode steps' expert MLPs read is counted over decode-only
        # dispatches: rows of a chunk or of a draft reach experts of their own
        decode_only = not (n_pf_tokens or spec_lanes)
        moe_reach = reach[0] if reach and decode_only else None
        # padded-token accounting: `used` real rows, `dispatched` what
        # actually ran -- the bench reports 1 - used/dispatched.  Multi-step
        # scan iterations each run (and use) one row per decode lane.
        used_tokens = n_pf_tokens + n_decode * num_steps + n_spec_tokens
        self.mixed_used_tokens += used_tokens
        self.mixed_dispatched_tokens += disp_tokens
        self.obs.observe_mixed_tokens(used_tokens, disp_tokens)
        finals: List[InflightPrefill] = []
        for ch in final_chunks:
            seq = ch.seq
            b = seq.slot
            pf = InflightPrefill(
                sampled=packed[b : b + 1],
                tok=packed[b : b + 1, 0],
                seq=seq,
                slot=b,
            )
            if (
                seq.prompt_logprobs is not None
                and not seq.prompt_lp_sent
                and seq.prior_generated == 0
            ):
                pf.prompt_lp = self._dispatch_prompt_score(seq)
            self._pending_injects[b] = pf
            finals.append(pf)
        self._steps += num_steps
        self.obs.observe_dispatch("unified")
        self.obs.observe_mixed(n_decode, n_pf_tokens)
        self.obs.observe_multistep_k(num_steps)
        _start_host_copy(packed)
        if spec_lanes:
            _start_host_copy(spec_packed)
        if moe_reach is not None:
            _start_host_copy(moe_reach)
        if tick is not None:
            tick.note_dispatch("unified")
            tick.mark("dispatch", **dispatch_meta)
        logger.debug(
            "unified dispatch: %d decode lanes + %d prefill tokens "
            "+ %d verify segments (%d chunks, %d final) Np=%d s_max=%d K=%d",
            n_decode, n_pf_tokens, len(spec_lanes), len(chunks),
            len(finals), Np, s_max, num_steps,
        )
        return InflightUnified(
            sampled=packed,
            slots=list(sched.slots),
            finals=finals,
            n_decode=n_decode,
            n_prefill_tokens=n_pf_tokens,
            spec_sampled=spec_packed if spec_lanes else None,
            spec_lanes=spec_lanes,
            n_steps=num_steps,
            np_rows=Np,
            used_rows=used_tokens,
            serial=serial,
            moe_reach=moe_reach,
        )

    # -- speculative decoding (spec/: draft on host, verify in one pass) ----

    def _gather_spec_lanes(self) -> List[Tuple[SeqState, int, List[int]]]:
        """Collect the verify-eligible speculating lanes with their drafts
        (executor thread) -- the ONE eligibility + drafting body behind
        both the folded unified dispatch and the standalone verify path,
        so the two cannot drift.

        Per eligible lane the proposal comes from the cross-tick draft
        pipeline first: ``SpecState.pending_draft`` was precomputed at
        the previous generation's commit (while that tick's device work
        and async host copies were in flight), so this dispatch-assembly
        path usually pays a list slice, not a drafter run -- the model
        drafter's device round trip in particular never sits between two
        tick dispatches.  A stale or missing precompute falls back to an
        inline propose.  Draft length clamps to the lane's write headroom
        so a draft can never outrun its pages or token budget.

        Eligibility gates keep the host mirrors authoritative: no verify
        while the lane's first token is device-only (pending inject),
        while parked (awaiting_kv / prefilling), or while a previous
        verify is in flight (the next draft must extend the post-commit
        history)."""
        from ..runtime import faults
        from ..spec import MAX_DRAFT_TOKENS

        sched = self.sched
        limits = self._compute_limits()
        lanes: List[Tuple[SeqState, int, List[int]]] = []
        # dynalint: disable=DT012 -- routes into dynamo_spec_draft_seconds
        t_draft0 = time.perf_counter()
        for b, seq in enumerate(sched.slots):
            if seq is None or not _spec_live(seq) or seq.finish is not None:
                continue
            st = seq.spec
            if (
                st.inflight
                or seq.awaiting_kv
                or seq.prefilling
                or b in self._pending_injects
                or seq.num_generated + seq.prior_generated < 1
            ):
                continue
            base = int(sched.seq_lens[b])
            headroom = int(limits[b]) - base
            if headroom < 1:
                continue  # no writable position; growth or preemption next
            n = min(st.num_draft_tokens, headroom - 1, MAX_DRAFT_TOKENS)
            draft: List[int] = []
            if n > 0 and seq.blocks is not None:
                history = seq.blocks.tokens
                got = st.take_pending_draft(len(history), n)
                if got is None:
                    got = list(st.drafter.propose(history, n))[:n]
                draft = got
                if (
                    draft
                    and faults.injector.enabled
                    and faults.injector.should_fire(
                        "spec.draft_corrupt", seq.request_id
                    )
                ):
                    # deterministic corruption: shift every proposed token
                    # off its value -- the accept walk must reject the
                    # whole column (a bad draft can only cost compute)
                    V = self.model_cfg.vocab_size
                    draft = [(t + 1) % V for t in draft]
            lanes.append((seq, b, draft))
        if lanes:
            self.spec_metrics.draft_latency.observe(
                # dynalint: disable=DT012 -- same histogram route
                max(time.perf_counter() - t_draft0, 0.0)
            )
        return lanes

    @hot_path
    def _dispatch_verify(self) -> Optional["InflightVerify"]:
        """Enqueue one batched multi-token verify for the speculating lanes
        (executor thread) -- the STANDALONE verify dispatch, serving
        classic ticks (penalized lanes) and fold-off engines.  Folded
        engines score verify columns inside the packed unified dispatch
        instead (``_dispatch_unified``); the two share
        :meth:`_gather_spec_lanes` and the commit-side accept walk.

        The scheduler packs each gathered lane's draft as extra columns
        next to its last committed token; one ``verify_and_sample``
        forward scores every column and the host accept walk runs at
        commit.  A lane with no proposal still rides along with zero
        draft columns -- its verify degenerates to a plain decode step,
        so speculation never stalls progress.
        """
        compile_sentry.set_entry("verify_and_sample")
        sched = self.sched
        lanes = self._gather_spec_lanes()
        if not lanes:
            return None
        max_d = max(len(draft) for _s, _b, draft in lanes)
        B = self.cfg.max_batch_size
        # pad the draft axis to a power of two so compile-cache entries
        # stay at {1, 1+1, 1+2, 1+4, 1+8} columns
        Dp = 0 if max_d == 0 else pow2_bucket(max_d)
        S = 1 + Dp
        tokens = np.zeros((B, S), np.int32)
        base_arr = np.zeros((B,), np.int32)
        n_tok = np.zeros((B,), np.int32)
        seqs: List[Optional[SeqState]] = [None] * B
        for seq, b, draft in lanes:
            tokens[b, 0] = sched.tokens[b]
            if draft:
                tokens[b, 1 : 1 + len(draft)] = draft
            base_arr[b] = sched.seq_lens[b]
            n_tok[b] = 1 + len(draft)
            seqs[b] = seq
            seq.spec.inflight = True
        Pb = self._live_page_bucket()
        use_filters = any(
            self._sampling_needs_filters(s.sampling) for s, _b, _d in lanes
        )
        # numpy copy of the page-table mirror for the same aliasing reason
        # as _push_device_state: the scheduler mutates it on later ticks
        sampled, self.kv.pages = self._fns.verify_and_sample(
            self.params,
            self.model_cfg,
            self.kv.pages,
            self._put_batch(tokens),
            self._put_batch(base_arr),
            self._put_batch(n_tok),
            self._put_batch(sched.page_table[..., :Pb].copy()),
            self._next_rng(),
            self._sampling_arrays(seqs),
            self._lp_top(seqs),
            use_filters,
        )
        self._steps += 1
        self.obs.observe_dispatch("verify")
        if self._tick is not None:
            self._tick.note_dispatch("verify")
        _start_host_copy(sampled)
        return InflightVerify(sampled=sampled, lanes=lanes)

    def _dispatch_prompt_score(self, seq: SeqState) -> Any:
        """Echo+logprobs: dispatch the prompt-scoring forward (no KV
        writes, step.score_prompt_step) alongside the lane's prefill; the
        packed rows materialize with the prefill commit.  One extra
        forward, paid only by requests that asked for prompt logprobs."""
        compile_sentry.set_entry("score_prompt_step")
        from .step import score_prompt_step

        prompt = seq.prompt
        bucket = pick_bucket(self.buckets, len(prompt))
        toks = np.zeros((1, bucket), np.int32)
        toks[0, : len(prompt)] = prompt
        lens = np.zeros((1,), np.int32)
        lens[0] = len(prompt)
        out = score_prompt_step(
            self.params,
            self.model_cfg,
            self.kv.pages,
            self._put_batch(toks),
            self._put_batch(lens),
            8 if seq.prompt_logprobs else 0,
        )
        self.obs.observe_dispatch("prompt_score")
        _start_host_copy(out)
        return out

    def _prompt_lp_entries(self, seq: SeqState, packed: np.ndarray) -> List[Any]:
        """Packed scoring rows [T, 2 + 2N] -> per-prompt-position entries
        ``[token_id, logprob|None, top|None]`` (position 0 carries None:
        nothing precedes it, the OpenAI prompt-logprobs shape)."""
        from .sampling import unpack_sampled_logprobs

        N = (packed.shape[-1] - 2) // 2
        _t, lps, tids, tlps = unpack_sampled_logprobs(packed, N)
        prompt = seq.prompt
        out: List[Any] = [[int(prompt[0]), None, None]]
        for j in range(1, len(prompt)):
            top = (
                [[int(i), float(l)] for i, l in zip(tids[j - 1], tlps[j - 1])]
                if N
                else None
            )
            out.append([int(prompt[j]), float(lps[j - 1]), top])
        return out

    # -- KV offload (G1 -> G2 -> G3 + swap; SURVEY.md 5.4) -----------------

    def _on_pool_evict(self, blk) -> None:
        """PagePool eviction hook: dispatch an async device slice of the
        block's pages before the free list reclaims them.  Device program
        order places the read before any reuse; the blocking materialize
        and the tier store run on the offload engine's dedicated thread --
        neither the tick loop nor the engine executor ever waits on them."""
        compile_sentry.set_entry("kv_pages")
        if self.offload_engine is None:
            return
        from ..offload import BlockMeta

        try:
            snap = self._fns.slice_block_pages(
                self.kv.pages, jnp.asarray(blk.pages, jnp.int32)
            )
            _start_host_copy(snap)
            meta = BlockMeta(
                block_hash=blk.block_hash,
                parent_sequence_hash=blk.parent_sequence_hash,
                position=blk.position,
                shards=self.kv.shard_geometry,
                kv_dtype=str(self.kv.dtype),
            )
            self.offload_engine.submit_evict(blk.sequence_hash, snap, meta)
        except Exception:
            # best-effort: a lost offload is a cache miss later, not an error
            logger.debug("offload snapshot failed", exc_info=True)

    def _drive_prefetch(self) -> None:
        """Issue tracked prefetch walks for the queue's admission window
        (loop thread, once per tick -- ISSUE 10).

        The walk promotes each request's offloaded prefix chain
        disk->host and pins it in the ring, so by the time the request
        reaches a slot, ``_match_prefix``'s tier lookup is a RAM hit and
        the onboard scatter dispatches with the admitting tick: the
        disk->host->HBM walk overlaps queue wait instead of TTFT.  Only
        the first ``_prefetch_window`` waiting requests are walked --
        queue position IS the prefetch priority."""
        oe = self.offload_engine
        if oe is None or self._prefetch_window == 0 or not self.sched.waiting:
            return
        pool = self.sched.pool
        count = 0
        for seq in self.sched.waiting:
            if count >= self._prefetch_window:
                break
            count += 1
            rid = seq.request_id
            if seq.blocks is None or seq.awaiting_kv:
                # external / swap-parked lanes admit with fresh pages
                # only and never consume onboards -- a pinned walk for
                # them is pure ring pressure
                continue
            # rid stays marked even when nothing is offloaded: rescanning
            # a fully-G1-resident 128k chain every tick would burn the
            # loop thread on no-op registry probes (a block evicted after
            # this scan is handled by the admission-time tier lookup)
            with self._prefetch_lock:
                if rid in self._prefetch_issued:
                    continue
                self._prefetch_issued.add(rid)
            max_blocks = max(
                0, (len(seq.prompt) - 1) // self.sched.block_size
            )
            hashes = [
                h
                for h in seq.blocks.sequence_hashes()[:max_blocks]
                if pool is None or not pool.is_registered(h)
            ]
            if hashes:
                oe.prefetch(hashes, request_id=rid)

    def _note_prefetch_admission(self, seq: SeqState) -> None:
        """Admission reached the request: settle its tracked prefetch --
        count staged blocks the admission consumes (``pending_onboard``
        tier hits), release the ring pins, record the overlap ratio.
        Must run BEFORE ``_apply_onboards`` drains the pending list."""
        oe = self.offload_engine
        if oe is None:
            return
        # atomic check-and-clear: an event-loop cancel racing this
        # executor-side settle must resolve to exactly one of the two
        # paths releasing the ring pins (dynalint DT014)
        with self._prefetch_lock:
            issued = seq.request_id in self._prefetch_issued
            self._prefetch_issued.discard(seq.request_id)
        if not issued:
            return
        consumed = [h for h, _p, _b, _m in seq.pending_onboard]
        seq.prefetch_hits = oe.finish_prefetch(seq.request_id, consumed)

    def _cancel_prefetch(self, rid: str) -> None:
        """A request left the queue without admitting (cancel / error):
        free its host-staged prefetch state (the ISSUE 10 leak fix)."""
        with self._prefetch_lock:
            issued = rid in self._prefetch_issued
            self._prefetch_issued.discard(rid)
        if issued and self.offload_engine is not None:
            self.offload_engine.cancel_prefetch(rid)

    def _offload_lookup(self, seq_hash: int):
        """Scheduler-facing tier lookup (``_match_prefix`` G1 -> G2 -> G3
        fall-through): RAM hits return immediately; disk-only hits kick an
        async promote and miss this admission (the queue-side prefetch in
        :meth:`generate` makes that case rare)."""
        hit = self.offload_engine.lookup(seq_hash)
        if hit is None:
            return None
        blob, meta, _tier = hit
        return blob, meta

    def _apply_onboards(self, seq: SeqState) -> None:
        """Scatter offload-tier hits into their pages and register them
        (executor thread, before the prefill dispatch that reads them).

        All of the admission's onboarded blocks ride ONE page-bucketed,
        layer-group-chunked scatter sequence -- the same
        ``scatter_layer_pages`` path the chunked external KV delivery uses
        -- so per-block dispatch overhead is paid once per admission and
        compile-cache entries stay O(page buckets x layer groups)."""
        compile_sentry.set_entry("kv_pages")
        from ..runtime import faults
        sched = self.sched
        if not seq.pending_onboard:
            return
        if faults.injector.enabled and faults.injector.should_fire(
            "onboard.truncate", seq.request_id
        ):
            self._abandon_onboards(seq)
            return
        pending, seq.pending_onboard = seq.pending_onboard, []
        ids = np.concatenate(
            [np.asarray(pages, np.int32) for _h, pages, _b, _m in pending]
        )
        blob = concat_blob_pages(
            [self._coerce_blob(blob_to_host(b)) for _h, _p, b, _m in pending]
        )
        bucket = pick_page_bucket(len(ids), self.sched.max_pages)
        ids_p = np.zeros((bucket,), np.int32)  # pad -> trash page 0
        ids_p[: len(ids)] = ids
        ids_dev = jnp.asarray(ids_p)
        padded = pad_page_axis(blob, bucket)
        L = blob_num_layers(blob.shape)
        # dynalint: disable=DT012 -- routes into dynamo_kv_onboard_seconds
        t0 = time.perf_counter()
        for lo, hi in layer_chunk_spans(L, None, DEFAULT_EXPORT_CHUNKS):
            self.kv.pages = self._fns.scatter_layer_pages(
                self.kv.pages,
                jnp.asarray(np.arange(lo, hi, dtype=np.int32)),
                ids_dev,
                as_device_blob(blob_layers(padded, lo, hi)),
            )
        self.offload_engine.record_onboard(
            # dynalint: disable=DT012 -- routes into dynamo_kv_onboard_seconds
            "prefix", blob.nbytes, time.perf_counter() - t0
        )
        for seq_hash, pages, _blob, meta in pending:
            if sched.pool.register(
                seq_hash,
                pages,
                block_hash=meta.block_hash,
                parent_sequence_hash=meta.parent_sequence_hash,
                position=meta.position,
            ):
                seq.held_blocks.append(seq_hash)
                for p in pages:
                    seq.owned_pages.remove(p)
            # register False: twin onboarded it concurrently; keep ownership

    def _abandon_onboards(self, seq: SeqState) -> None:
        """Onboard aborted (chaos/IO): fall back to recomputing the
        would-have-been-onboarded prefix.  The blocks' pages are already
        allocated at the right page-table positions, so they simply stay
        plain-owned and the (now longer) suffix prefill writes the prompt
        KV into them -- no pages move, no pages leak, nothing registers."""
        sched = self.sched
        seq.pending_onboard = []
        seq.cached_prompt_tokens = len(seq.held_blocks) * sched.block_size
        # re-derive which prompt blocks register after prefill: the
        # abandoned span is prefilled now, so it registers with the rest
        sched._queue_prompt_registrations(seq)
        if self.offload_engine is not None:
            self.offload_engine.onboard_fallbacks += 1
            self.offload_engine.metrics.onboard_fallbacks.labels(
                "truncate"
            ).inc()

    # -- swap-based preemption (offload the victim, restore on resume) ------

    def _swap_out(self, seq: SeqState) -> bool:
        """Scheduler ``swap_out`` hook (tick-loop thread, victim still
        slotted): snapshot the lane's committed KV and park the sequence.
        Declines -- recompute fallback -- whenever the lane's device state
        is not fully host-visible (mid-prefill, parked, uncommitted first
        token) or the swap budget is exhausted."""
        compile_sentry.set_entry("kv_pages")
        if self.offload_engine is None:
            return False
        if seq.awaiting_kv or seq.prefilling or seq.finish is not None:
            return False
        if seq.num_generated < 1 or seq.slot < 0:
            # nothing committed yet: the mirrors may hold a placeholder
            # token (pending inject) or no KV at all -- only a re-prefill
            # reproduces the stream
            return False
        if seq.blocks is None:
            # multimodal lanes opt out of block tracking, so the preemption
            # fold cannot reconstruct their token history; they keep the
            # classic recompute path
            return False
        if seq.slot in self._pending_injects:
            return False  # a device-only sampled token would be lost
        cache_len = int(self.sched.seq_lens[seq.slot])
        ps = self.cfg.page_size
        n_pages = -(-cache_len // ps)
        if cache_len <= 0 or n_pages > len(seq.pages):
            return False
        n_blocks = -(-n_pages // self.sched.pages_per_block)
        try:
            ids = jnp.asarray(np.asarray(seq.pages[:n_pages], np.int32))
            snap = self._fns.slice_block_pages(self.kv.pages, ids)
            _start_host_copy(snap)
        except Exception:
            logger.debug("swap snapshot dispatch failed", exc_info=True)
            return False
        if not self.offload_engine.swap_out(
            seq.request_id, snap, cache_len, n_blocks,
            shards=self.kv.shard_geometry,
        ):
            return False
        self._swapped[seq.request_id] = seq
        return True

    def _process_swaps(self) -> List[Tuple[SeqState, Any]]:
        """Tick-loop side of swap-in: hand back (seq, record) pairs whose
        restore is due (lane admitted + blob materialized).  Failed or
        chaos-truncated records fall back to recompute -- the lane (and
        its pages, if any) release cleanly and the request re-prefills."""
        if not self._swapped:
            return []
        from ..offload import SWAP_FAILED, SWAP_READY
        from ..runtime import faults

        out: List[Tuple[SeqState, Any]] = []
        for rid, seq in list(self._swapped.items()):
            if seq.finish is not None or not seq.awaiting_kv:
                # finished/cancelled, or a second preemption already
                # reverted the lane to the recompute path: drop the record
                self._swapped.pop(rid, None)
                self.offload_engine.drop_swap(rid)
                continue
            rec = self.offload_engine.poll_swap(rid)
            if rec is None or (rec.state == SWAP_FAILED and rec.dev is None):
                # no restorable copy anywhere: unpark onto recompute
                self._swap_recompute(seq, "copy_fail")
                continue
            if (rec.dev is None and rec.state != SWAP_READY) or seq.slot < 0:
                continue  # blob still materializing / lane not admitted
            if faults.injector.enabled and faults.injector.should_fire(
                "onboard.truncate", f"swap/{rid}"
            ):
                self._swap_recompute(seq, "truncate")
                continue
            self._swapped.pop(rid, None)
            out.append((seq, rec))
        return out

    def _swap_recompute(self, seq: SeqState, cause: str) -> None:
        """Swap restore impossible: unpark the sequence onto the recompute
        path.  Slot + pages (if admitted) release; the request re-prefills
        its folded prompt exactly as classic preemption would -- identical
        output, no leaked pages, one counted fallback."""
        rid = seq.request_id
        self._swapped.pop(rid, None)
        self.offload_engine.drop_swap(rid)
        self.offload_engine.swap_fallbacks += 1
        self.offload_engine.metrics.swap_fallbacks.labels(cause).inc()
        seq.awaiting_kv = False
        if seq.slot >= 0:
            self.sched._release_slot(seq)
            seq.slot = -1
            self.sched.waiting.appendleft(seq)
        # still waiting: plan() now treats it as a plain cold admission

    def _apply_swap_in(self, seq: SeqState, rec) -> None:
        """Executor thread: restore a parked lane's KV through the chunked
        scatter path and clear the resume barrier.

        Geometry: the snapshot covers ``cache_len`` committed positions =
        ``len(prompt) - 1`` after the preemption fold; admission already
        wrote ``tokens[b] = prompt[-1]``, so once ``seq_lens`` rewinds to
        ``cache_len`` the next decode block recomputes position P-1's KV
        and samples exactly the token the re-prefill would have -- swap on
        and off are token-identical.  The final ``block_until_ready`` is a
        deliberate sync: the lane cannot run before its KV lands, and the
        wait happens on the executor (never the event loop), yielding the
        true H2D throughput for the ``kv_onboard_gbps`` accounting."""
        compile_sentry.set_entry("kv_pages")
        rid = seq.request_id
        sched = self.sched
        try:
            # fast path: the retained device snapshot restores with a
            # device-to-device scatter -- no host link round trip (the
            # host link is far slower than HBM); the host blob serves
            # long parks whose device copy was
            # dropped for staging budget.  Read dev ONCE: the offload
            # thread may null it (budget trim) between a check and a
            # second read.
            dev = rec.dev
            blob = dev if dev is not None else rec.blob
            if blob is None:
                # dev was trimmed after _process_swaps saw it and the host
                # blob is not ready yet: retry next tick
                self._swapped[rid] = seq
                return
            if rec.shards != self.kv.shard_geometry:
                # snapshot from a differently-sharded pool (engine restart
                # with a new tp degree mid-park): the full-width blob is
                # still scatterable, but the device-side fast path aliases
                # the OLD layout -- recompute is the only safe restore
                self._swap_recompute(seq, "shard_geometry")
                return
            cache_len = rec.cache_len
            ps = self.cfg.page_size
            n_pages = -(-cache_len // ps)
            if (
                seq.slot < 0
                or sched.slots[seq.slot] is not seq
                or n_pages > len(seq.pages)
                or blob_num_pages(blob.shape) != n_pages
            ):
                self._swapped[rid] = seq  # re-examine next tick
                return
            bucket = pick_page_bucket(n_pages, sched.max_pages)
            ids = np.zeros((bucket,), np.int32)
            ids[:n_pages] = seq.pages[:n_pages]
            ids_dev = jnp.asarray(ids)
            # device-side fast-path snapshots are already in the pool's
            # domain; host blobs coerce (an old-dtype spill restores via
            # the shared conversion rule instead of corrupting the pool)
            if blob is not dev:
                blob = self._coerce_blob(blob)
            padded = pad_page_axis(blob, bucket)
            L = blob_num_layers(blob.shape)
            # dynalint: disable=DT012 -- routes into dynamo_kv_onboard_seconds
            t0 = time.perf_counter()
            for lo, hi in layer_chunk_spans(L, None, DEFAULT_EXPORT_CHUNKS):
                self.kv.pages = self._fns.scatter_layer_pages(
                    self.kv.pages,
                    jnp.asarray(np.arange(lo, hi, dtype=np.int32)),
                    ids_dev,
                    as_device_blob(blob_layers(padded, lo, hi)),
                )
            self.kv.pages.block_until_ready()
            self.offload_engine.record_onboard(
                # dynalint: disable=DT012 -- routes into dynamo_kv_onboard_seconds
                "swap", blob.nbytes, time.perf_counter() - t0
            )
        except Exception:
            logger.exception("swap-in restore failed for %s; recomputing", rid)
            self._swap_recompute(seq, "copy_fail")
            return
        self.offload_engine.drop_swap(rid)
        # barrier cleared: rewind the cache length to the restored KV and
        # wake the lane (admission wrote seq_lens = len(prompt); the last
        # prompt token's KV is rewritten by the lane's next decode step)
        sched.seq_lens[seq.slot] = cache_len
        sched.tokens[seq.slot] = seq.prompt[-1]
        seq.awaiting_kv = False
        sched.dirty_slots.add(seq.slot)

    def _dispatch_account(self, e: Any) -> "_DispatchAccount":
        """What the dispatch record keeps of one in-flight entry.  A unified
        dispatch is a ``chunk`` step if it carried prefill rows and a
        ``decode`` step if it carried none; the classic entries keep their
        kind as their class.  Counts the entry carries from its dispatch:
        nothing here walks the lanes."""
        if isinstance(e, InflightUnified):
            return _DispatchAccount(
                "chunk" if e.n_prefill_tokens > 0 else "decode",
                e.np_rows, e.n_steps, e.n_decode * e.n_steps,
                e.n_prefill_tokens, e.used_rows,
                e.np_rows + self.cfg.max_batch_size * (e.n_steps - 1),
            )
        if isinstance(e, InflightBlock):
            lane_steps = e.n_decode * e.n_steps
            return _DispatchAccount(
                "decode_block", 0, e.n_steps, lane_steps, 0, lane_steps,
                self.cfg.max_batch_size * e.n_steps,
            )
        if isinstance(e, InflightVerify):
            lanes = len(e.lanes)
            return _DispatchAccount("verify", 0, 1, 0, 0, lanes, lanes or 1)
        rows = (
            sum(pf.rows for pf in e.entries)
            if isinstance(e, InflightPrefillGroup)
            else e.rows
        )
        return _DispatchAccount("prefill", 0, 1, 0, rows, rows, rows or 1)

    def _service_shares(
        self, entries: List[Any], seconds: float
    ) -> List[Tuple["_DispatchAccount", float]]:
        """``seconds`` of device service over the entries of one commit, by
        rows x steps dispatched: ``(account, share)`` per entry.  A commit
        holds one entry nearly always."""
        accounts = [self._dispatch_account(e) for e in entries]
        weight = sum(a.weight for a in accounts)
        return [(a, seconds * a.weight / weight) for a in accounts]

    def _record_service(
        self, entries: List[Any], service: float, now: float
    ) -> None:
        """The dispatch record's one update a commit (executor thread):
        ``service`` seconds the device spent on ``entries``, which ended at
        this commit's clock ``now``.  Observes
        ``dynamo_engine_dispatch_service_seconds`` and its two counters per
        entry, advances the running totals a first token is split by, and
        (tick profiler on) files the entries under the tick's
        ``dispatch_records``.

        Service is the HOST's reading of the device's time: ``now`` less
        the later of the dispatch's enqueue and the previous commit's
        clock, so the time a dispatch queued behind the one before it (the
        loop is double-buffered) is the earlier dispatch's, not its own.
        No two readings overlap and their sum never exceeds the wall time;
        a host that fetches late makes one reading long and the next short
        and leaves the sum right.  It is NOT a device-side duration of one
        dispatch (read that from a device trace), and a classic chunked
        prefill's non-final chunks, which no commit fetches, fall into
        whatever commits next."""
        chunk_s, decode_s, rows, _ = self._served
        tick = self._tick
        for a, share in self._service_shares(entries, service):
            self.obs.observe_service(
                a.step, a.np_rows, share, a.steps, a.lane_steps
            )
            if a.carried_prefill:
                chunk_s += share
            else:
                decode_s += share
            if a.step == "decode" and self._ms_reads:
                self._ms_ceiling.observe_step(share / a.steps)
            rows += a.prefill_rows
            if tick is not None:
                tick.record.dispatch_records.append({
                    "step": a.step, "np": a.np_rows, "k": a.steps,
                    "rows": a.used_rows,
                    "service_ms": round(share * 1e3, 4),
                    "bundle": len(entries),
                })
        # dynalint: disable=DT014 -- one tuple swapped whole: the fanout
        # worker's read at a first token sees the record as of one commit
        # or the next, both of which lie inside that request's interval
        self._served = (chunk_s, decode_s, rows, now)

    def _service_mark(self) -> Tuple[float, float, int]:
        """The dispatch record's totals as of now, for a request's first
        admission (``Scheduler._try_admit`` copies them onto the request;
        loop thread, between the tick's executor hops, so the queue of
        uncommitted generations is still): service seconds of chunk steps,
        of decode-only dispatches, prefill rows committed.  A dispatch the
        device is serving right now is counted up to this moment (one clock
        read), so that its commit later credits the request with the part
        it waited for and no more."""
        chunk_s, decode_s, rows, floor = self._served
        head = self._inflight[0] if self._inflight else None
        if head:
            # dynalint: disable=DT012 -- once an admission: the record's
            # clock (perf_counter, like dispatched_at) beside admitted_s
            run = time.perf_counter() - max(head[0].dispatched_at, floor)
            if run > 0.0:
                for a, share in self._service_shares(head, run):
                    if a.carried_prefill:
                        chunk_s += share
                    else:
                        decode_s += share
        return chunk_s, decode_s, rows

    @hot_path
    def _commit_all(
        self, entries: List[Any], pipeline_busy: bool = False
    ) -> List[StepEvent]:
        """Materialize and commit pending prefills/blocks/verifies in
        dispatch order (one bundled device_get instead of one round trip
        per handle).  ``pipeline_busy`` notes that OTHER dispatch
        generations are still queued on device behind this one -- the
        dispatch-gap accounting then records a zero gap (the device was
        never idle) instead of arming the ready->enqueue stopwatch."""
        compile_sentry.set_entry("commit")
        # the commit walk owns the tick domain's hottest shared state
        # (scheduler lanes, KV pages, inflight entries): armed, assert the
        # declared confinement -- executor thread or the serialized tick
        # coroutine, never a foreign thread
        thread_sentry.assert_role("tick", what="JaxEngine._commit_all")
        from .sampling import unpack_sampled_logprobs

        tick = self._tick
        if tick is not None:
            # close the loop->executor hop under "dispatch" so the
            # device_wait below measures only the blocked fetch
            tick.mark("dispatch")
        handles = [e.sampled for e in entries]
        # echo+logprobs scoring rows and folded-verify column handles ride
        # the same bundled transfer
        lp_refs: List[Tuple[Any, int]] = []
        spec_refs: List[Tuple[Any, int]] = []
        reach_at: List[int] = []  # the counts of experts read and held
        for e in entries:
            if isinstance(e, InflightUnified) and e.spec_sampled is not None:
                spec_refs.append((e, len(handles)))
                handles.append(e.spec_sampled)
            if isinstance(e, InflightUnified) and e.moe_reach is not None:
                reach_at.append(len(handles))
                handles.append(e.moe_reach)
            pfs = (
                e.entries
                if isinstance(e, InflightPrefillGroup)
                else e.finals
                if isinstance(e, InflightUnified)
                else [e] if isinstance(e, InflightPrefill) else []
            )
            for pf in pfs:
                if pf.prompt_lp is not None:
                    lp_refs.append((pf, len(handles)))
                    handles.append(pf.prompt_lp)
        # dynalint: disable=DT012 -- with the commit clock below, the time
        # this tick sat blocked in the fetch (the fused-step ceiling's
        # reading of the loop's own work leaves it out)
        fetch_began = time.perf_counter()
        if jax.process_count() > 1:
            # multi-host mesh (v5e pod): a batch-sharded result's shards
            # live partly on other processes, so a plain device_get raises
            # on non-addressable arrays.  process_allgather is a collective
            # -- safe because serving runs SPMD-lockstep across processes
            # (every process commits the same dispatch sequence).
            from jax.experimental import multihost_utils

            mats = [
                multihost_utils.process_allgather(h, tiled=True)
                for h in handles
            ]
        else:
            # dynalint: disable=DT004 -- the pipeline's ONE designed sync point:
            # block i's results materialize here while block i+1 computes
            mats = jax.device_get(handles)
        # mats are host-resident np arrays (device_get / allgather output):
        # no further np.asarray wrapping, which would read as a sync here
        # dynalint: disable=DT012 -- the commit clock: one read serves every
        # entry's dispatch->commit latency observe (dynamo_engine_step_latency)
        # and the dispatch record's service time
        now = time.perf_counter()
        self._fetch_wait += now - fetch_began
        # the device's time on what this commit fetched: from the later of
        # its enqueue and the commit before, which it queued behind
        service = max(
            now - max(entries[0].dispatched_at, self._served[3]), 0.0
        )
        if tick is not None:
            # inside a profiler trace the interval names what it fetched
            # and how long the device served it
            fetched = {
                "d": "|".join(
                    str(e.serial) for e in entries
                    if isinstance(e, InflightUnified)
                ),
                "svc_us": int(service * 1e6),
            } if tick.annotating else {}
            tick.mark("device_wait", **fetched)
            if pipeline_busy:
                # another generation is already queued on device: results
                # landing here imply zero device idle -- record the gap
                # as such instead of timing ready->next-enqueue
                tick.note_zero_gap()
            else:
                self.profiler.note_results_ready()
        lp_mats = {id(pf): mats[i] for pf, i in lp_refs}
        spec_mats = {id(e): mats[i] for e, i in spec_refs}
        events: List[StepEvent] = []

        def commit_prefill(pf: InflightPrefill, row: np.ndarray) -> None:
            # row: packed [2 + 2N] (token | lp bits | top ids | top lps)
            seq = pf.seq
            if self._pending_injects.get(pf.slot) is pf:
                del self._pending_injects[pf.slot]
            if (
                seq.finish is not None
                or seq.slot != pf.slot
                or self.sched.slots[pf.slot] is not seq
                or seq.num_generated > 0
            ):
                return  # preempted/cancelled before the commit landed
            N = (row.shape[-1] - 2) // 2
            tok, lp, tids, tlps = unpack_sampled_logprobs(row, N)
            top = (
                [[int(i), float(l)] for i, l in zip(tids, tlps)] if N else None
            )
            if seq.prior_generated > 0:
                # this prefill resumed a recompute-preempted lane: the
                # folded prompt's uncached span is pure resume work
                self.resume_prefill_tokens += (
                    len(seq.prompt) - seq.cached_prompt_tokens
                )
                self.resume_prefill_seconds += max(now - pf.dispatched_at, 0.0)
            ev = self.sched.commit_prefill_token(seq, int(tok), float(lp), top)
            plp = lp_mats.get(id(pf))
            if plp is not None and not seq.prompt_lp_sent:
                ev.prompt_logprobs = self._prompt_lp_entries(seq, plp[0])
                seq.prompt_lp_sent = True
            events.append(ev)

        self._record_service(entries, service, now)
        for i in reach_at:
            self.obs.observe_moe_reach(int(mats[i][0]), int(mats[i][1]))
        for e, mat in zip(entries, mats):
            if isinstance(e, InflightPrefillGroup):
                for i, pf in enumerate(e.entries):
                    commit_prefill(pf, mat[i])  # [Bp, 2 + 2N]
                self.obs.observe_step("prefill", now - e.dispatched_at)
            elif isinstance(e, InflightPrefill):
                commit_prefill(e, mat[0])
                self.obs.observe_step("prefill", now - e.dispatched_at)
            elif isinstance(e, InflightUnified):
                # mat: packed [B, 2 + 2N] (single-step) or [B, K, 2 + 2N]
                # (multi-step) -- decode columns AND final prefill columns
                # commit through the same block replay, so the stop rules
                # cannot diverge between the lanes of one dispatch
                N = (mat.shape[-1] - 2) // 2
                toks, lps, tids, tlps = unpack_sampled_logprobs(mat, N)
                final_slots = {pf.slot: pf for pf in e.finals}
                for pf in e.finals:
                    if self._pending_injects.get(pf.slot) is pf:
                        del self._pending_injects[pf.slot]
                if e.n_steps > 1:
                    # the K-block replay discards uncommitted steps of
                    # lanes cancelled/preempted mid-block (the commit
                    # guards), and each of the K-1 device-internal step
                    # boundaries had zero host-visible idle by
                    # construction -- record them as such so the gap
                    # profile reflects the fused dispatch
                    unified_events = self.sched.commit_block(
                        toks, e.slots, lps,
                        tids if N else None, tlps if N else None,
                    )
                    if tick is not None:
                        for _ in range(e.n_steps - 1):
                            tick.note_zero_gap()
                else:
                    unified_events = self.sched.commit_block(
                        toks[:, None], e.slots, lps[:, None],
                        tids[:, None] if N else None,
                        tlps[:, None] if N else None,
                    )
                for ev in unified_events:
                    # slot-keyed (commit events only fire for lanes still
                    # resident, so ev.seq.slot is its dispatch-time lane);
                    # the identity guard covers slot reuse after preempt
                    pf = final_slots.get(ev.seq.slot)
                    if pf is None or pf.seq is not ev.seq:
                        continue
                    seq = pf.seq
                    if seq.prior_generated > 0:
                        # this dispatch completed a recompute-preempted
                        # lane's re-prefill: pure resume work
                        self.resume_prefill_tokens += (
                            len(seq.prompt) - seq.cached_prompt_tokens
                        )
                        self.resume_prefill_seconds += max(
                            now - e.dispatched_at, 0.0
                        )
                    plp = lp_mats.get(id(pf))
                    if plp is not None and not seq.prompt_lp_sent:
                        ev.prompt_logprobs = self._prompt_lp_entries(
                            seq, plp[0]
                        )
                        seq.prompt_lp_sent = True
                events.extend(unified_events)
                sp = spec_mats.get(id(e))
                if sp is not None:
                    # folded verify columns commit AFTER the dispatch's
                    # decode/prefill columns (disjoint lane sets): same
                    # accept walk as the standalone path
                    events.extend(
                        self._commit_spec_columns(
                            e.spec_lanes, sp, e.dispatched_at, now
                        )
                    )
                    self.spec_metrics.folded_steps.inc()
                self.obs.observe_step("unified", now - e.dispatched_at)
            elif isinstance(e, InflightVerify):
                events.extend(
                    self._commit_spec_columns(
                        e.lanes, mat, e.dispatched_at, now
                    )
                )
                self.obs.observe_step("verify", now - e.dispatched_at)
            else:
                arr = mat  # [B, K, 2 + 2N]
                N = (arr.shape[-1] - 2) // 2
                toks, lps, tids, tlps = unpack_sampled_logprobs(arr, N)
                events.extend(
                    self.sched.commit_block(
                        toks, e.slots, lps,
                        tids if N else None, tlps if N else None,
                    )
                )
                self.obs.observe_step("decode_block", now - e.dispatched_at)
        alloc = self.kv.allocator
        self.obs.observe_kv(
            alloc.used_pages, alloc.num_pages - 1, self.kv.bytes_per_token
        )
        if self.sched.two_kind:
            self.obs.observe_kv_kinds(
                {"full": alloc, "window": self.kv.window_allocator},
                self.sched.resident_context_tokens,
                self.sched.window_released,
            )
        if tick is not None:
            tick.mark("commit")
        return events

    def _commit_spec_columns(
        self,
        lanes: List[Tuple[SeqState, int, List[int]]],
        arr: np.ndarray,  # packed [B, S, 2 + 2N] target samples per column
        dispatched_at: float,
        now: float,
    ) -> List[StepEvent]:
        """Host accept walk over one verify dispatch's packed columns --
        the ONE commit body behind the standalone ``InflightVerify`` and
        the folded unified record, so the two paths cannot drift.

        Committed tokens are the TARGET samples: the verified draft
        prefix plus the bonus token at the first mismatch; trailing
        columns are marked dead for the host replay.  A lane
        preempted/cancelled since dispatch discards its whole column (the
        existing speculative-rollback path -- resume re-derives these
        tokens deterministically)."""
        from ..spec import longest_accepted
        from .sampling import unpack_sampled_logprobs

        events: List[StepEvent] = []
        N = (arr.shape[-1] - 2) // 2
        toks, lps, tids, tlps = unpack_sampled_logprobs(arr, N)
        for seq, slot, draft in lanes:
            st = seq.spec
            if st is not None:
                st.inflight = False
            if (
                seq.finish is not None
                or seq.slot != slot
                or self.sched.slots[slot] is not seq
                or seq.awaiting_kv
            ):
                continue
            col = toks[slot]
            m = longest_accepted(draft, col)
            column = np.full((col.shape[0],), -1, np.int32)
            column[: m + 1] = col[: m + 1]
            ev = self.sched._commit_lane_column(
                seq, column, lps[slot],
                tids[slot] if N else None,
                tlps[slot] if N else None,
            )
            if st is not None:
                # accepted counts only verified drafts that actually
                # COMMITTED: the stop-rule replay can finish the lane
                # mid-column, and acceptance must not exceed emitted
                # tokens (a verified-but-swallowed stop token is
                # conservatively uncounted)
                accepted = min(m, len(ev.tokens))
                st.drafted += len(draft)
                st.accepted += accepted
                st.verify_steps += 1
                self.spec_drafted += len(draft)
                self.spec_accepted += accepted
                if draft:
                    self.spec_metrics.drafted.labels(st.kind).inc(len(draft))
                    if accepted:
                        self.spec_metrics.accepted.labels(st.kind).inc(
                            accepted
                        )
            if ev.finished is not None:
                seq.finish = ev.finished
                self.sched._release_slot(seq)
            elif st is not None:
                self._spec_post_commit(seq, st)
            if ev.tokens or ev.finished is not None:
                events.append(ev)
        self.spec_verify_steps += 1
        self.spec_metrics.verify_steps.inc()
        if self.spec_drafted:
            self.spec_metrics.accept_rate.set(
                self.spec_accepted / self.spec_drafted
            )
        self.spec_metrics.verify_latency.observe(
            max(now - dispatched_at, 0.0)
        )
        return events

    def _spec_post_commit(self, seq: SeqState, st: Any) -> None:
        """After a lane's verify columns commit: acceptance-aware
        auto-disable, then the cross-tick draft pipeline's precompute.

        Auto-disable first: once the lane has drafted past the warmup and
        its acceptance sits under the floor, speculation turns OFF for
        the request -- the lane reverts to the plain decode scan (its
        mirror row folds back with ``active`` True on the next dirty-row
        scatter) with no output change, because committed tokens were
        always the target model's.

        Otherwise, propose the NEXT generation's draft right here at
        commit -- this runs while the pipeline's other generations and
        their async host copies are still in flight, so the proposal
        (including a model drafter's device round trip) overlaps device
        work instead of sitting on the next tick's dispatch-assembly
        path.  Stamped with the history length; preempt/cancel/rollback
        invalidates it by construction (``SpecState.take_pending_draft``).
        """
        if (
            self._spec_auto_disable
            and st.enabled
            and st.drafted >= self._spec_disable_after
            and st.accept_rate < self._spec_min_accept
        ):
            st.enabled = False
            st.auto_disabled = True
            st.pending_draft = None
            self.spec_auto_disabled += 1
            self.spec_metrics.auto_disabled.inc()
            self.spec_metrics.enabled_frac.set(self.spec_enabled_frac)
            self.sched.dirty_slots.add(seq.slot)
            logger.debug(
                "speculation auto-disabled for %s: accept %.3f < %.3f "
                "after %d drafted",
                seq.request_id, st.accept_rate, self._spec_min_accept,
                st.drafted,
            )
            return
        if not st.enabled or seq.blocks is None:
            return
        n = st.num_draft_tokens
        if n <= 0:
            return
        history = seq.blocks.tokens
        try:
            st.pending_draft = (
                len(history),
                list(st.drafter.propose(history, n))[:n],
            )
        except Exception:
            # a drafter crash must cost a proposal, never the request
            st.pending_draft = None
            logger.debug("draft precompute failed", exc_info=True)

    # -- event/output dispatch (loop thread) --------------------------------

    def _dispatch(self, events: List[StepEvent]) -> None:
        # with the PagePool active, stored/removed events flow from the
        # registry itself (register/evict via _emit_kv_event), so the router
        # index mirrors actual cache residency; the direct per-completion /
        # per-finish publishes below are the no-pool fallback
        pool = self.sched.pool
        for ev in events:
            queue = self._queues.get(ev.seq.request_id)
            if ev.tokens:
                self._tokens_generated += len(ev.tokens)
                self.obs.tokens.inc(len(ev.tokens))
                ev.seq.token_commits += 1
                if not ev.seq.first_token_s:
                    # first token: the service leg (first admission ->
                    # here) ends; observed once per request, and the SLO
                    # plane gets the same queue-wait / service split, the
                    # attribution a TTFT miss is classified with
                    seq = ev.seq
                    seq.first_token_s = now_m = time.monotonic()
                    adm = seq.admitted_s or now_m
                    self.obs.first_token_service.observe(now_m - adm)
                    mark = seq.served_at_admission
                    if mark is not None:
                        # what it waited behind: the record's totals now
                        # less those at its admission.  Committed service
                        # only: what follows the commit that brought the
                        # token (its fanout) is no_dispatch's
                        chunk_s, decode_s, rows, _ = self._served
                        chunk_s -= mark[0]
                        decode_s -= mark[1]
                        rows -= mark[2]
                        idle_s = max(now_m - adm - chunk_s - decode_s, 0.0)
                        seq.first_token_wait = (
                            chunk_s, decode_s, idle_s, rows
                        )
                        self.obs.observe_first_token_wait(
                            chunk_s, decode_s, idle_s,
                            seq.prefill_tokens, rows,
                        )
                    if slo.tracker.enabled:
                        slo.tracker.note_first_token(
                            seq.request_id,
                            queue_s=adm - seq.arrival_s,
                            service_s=now_m - adm,
                        )
            if ev.completed_blocks and pool is None:
                self._publish_stored(ev.seq, ev.completed_blocks)
            if queue is None:
                continue
            if ev.tokens:
                # one stream item carries the whole coalesced batch of tokens
                # (a decode block's worth); consumers iterate token_ids
                out = LLMEngineOutput(token_ids=list(ev.tokens))
                want = ev.seq.sampling.logprobs
                if want is not None and ev.logprobs:
                    out.logprobs = list(ev.logprobs)
                    if want > 0 and ev.top_logprobs is not None:
                        out.top_logprobs = [t[:want] for t in ev.top_logprobs]
                if ev.prompt_logprobs is not None:
                    out.prompt_logprobs = ev.prompt_logprobs
                queue.put_nowait(Annotated.from_data(out.to_dict()))
            if ev.finished is not None:
                # backstop for paths that never cross a prefill-dispatch
                # site (disagg external lanes): any prefetch state still
                # tracked at finish is released here (pins freed, bytes
                # counted wasted)
                self._cancel_prefetch(ev.seq.request_id)
                out = LLMEngineOutput.finished(ev.finished)
                if not ev.tokens and ev.prompt_logprobs is not None:
                    # first token finished the request outright (swallowed
                    # stop): the prompt logprobs must still ship
                    out.prompt_logprobs = ev.prompt_logprobs
                st = ev.seq.spec
                if st is not None:
                    # per-choice acceptance observability: the finish item
                    # carries the stats (usage extension downstream), the
                    # request span carries spec_accept_rate
                    out.spec = {
                        "drafted_tokens": st.drafted,
                        "accepted_tokens": st.accepted,
                        "acceptance_rate": round(st.accept_rate, 6),
                        "drafter": st.kind,
                        "auto_disabled": st.auto_disabled,
                    }
                    if tracing.collector.enabled:
                        with tracing.span(
                            "engine.spec", ev.seq.request_id
                        ) as sp:
                            sp.set(
                                spec_accept_rate=round(st.accept_rate, 6),
                                spec_drafted=st.drafted,
                                spec_accepted=st.accepted,
                                spec_verify_steps=st.verify_steps,
                            )
                queue.put_nowait(Annotated.from_data(out.to_dict()))
                queue.put_nowait(None)
                if pool is None:
                    self._publish_removed(ev.seq)
                if tracing.collector.enabled:
                    self._record_request_spans(ev.seq)

    def _record_request_spans(self, seq: SeqState) -> None:
        """Write a finished request's stamps out as spans (tracing on
        only; once per request): ``engine.request`` from arrival to now
        under the request id's binding (``http.request`` / ingress), and
        under it the stages that tile it -- ``engine.queue``,
        ``engine.prefill``, ``engine.decode``, one ``engine.preempted``
        per interval.  Nothing is recorded while the request is served:
        the stamps are floats on its ``SeqState``."""
        if seq.stages_recorded:
            return
        seq.stages_recorded = True
        end_s = time.monotonic()
        rid = seq.request_id
        root = tracing.record_span(
            "engine.request", rid, seq.arrival_s, end_s,
            prompt_tokens=len(seq.prompt) - seq.prior_generated,
            cached_tokens=seq.cached_prompt_tokens,
            output_tokens=seq.prior_generated + seq.num_generated,
            dispatches=max(seq.prefill_chunks - 1, 0) + seq.token_commits,
            preemptions=len(seq.preempted) + bool(seq.preempted_at),
            finish=seq.finish.value if seq.finish is not None else None,
            **(
                {
                    "state_restored": seq.state_page is not None,
                    "state_page": seq.state_page,
                }
                if self.model_cfg.has_conv else {}
            ),
            **(
                {
                    "state_restored_tokens": seq.cached_prompt_tokens,
                    "recompute_tokens": seq.state_recompute,
                }
                if self.model_cfg.has_linear else {}
            ),
        )
        for stage, lo, hi in seq.stage_segments(end_s):
            attrs: Dict[str, Any] = {}
            if self.model_cfg.is_mla and stage in ("prefill", "decode"):
                attrs["attn"] = "latent"
            elif self.model_cfg.two_kind and stage in ("prefill", "decode"):
                attrs["attn"] = "window+full"
            elif self.model_cfg.has_conv and stage in ("prefill", "decode"):
                attrs["attn"] = "conv+full"
            elif self.model_cfg.has_linear and stage in ("prefill", "decode"):
                attrs["attn"] = "linear+full"
            if stage == "prefill":
                attrs = {
                    **attrs,
                    "chunks": seq.prefill_chunks,
                    "prompt_tokens_computed": seq.prefill_tokens,
                    "cached": seq.cached_prompt_tokens,
                    "kv_prefetch_hits": seq.prefetch_hits,
                    "mixed": seq.prefill_mixed,
                }
                if seq.first_token_wait is not None:
                    in_chunk, in_decode, idle, rows_all = seq.first_token_wait
                    attrs.update(
                        in_chunk_steps_ms=round(in_chunk * 1e3, 3),
                        in_decode_steps_ms=round(in_decode * 1e3, 3),
                        no_dispatch_ms=round(idle * 1e3, 3),
                        chunk_rows_all=rows_all,
                    )
            tracing.record_span(
                "engine." + stage, rid, lo, hi, parent=root, **attrs
            )

    def _emit_kv_event(self, event: Dict[str, Any]) -> None:
        """PagePool event_sink -> the externally-wired kv_event_sink.

        Registration fires inside commit calls on the executor thread while
        eviction fires on the loop thread; sinks (KvEventPublisher.emit uses
        an asyncio.Queue) are not thread-safe, so off-loop emissions hop to
        the engine's event loop."""
        sink = self.kv_event_sink
        if sink is None:
            return
        loop = self._loop
        if loop is None:
            sink(event)
            return
        try:
            on_loop = asyncio.get_running_loop() is loop
        except RuntimeError:
            on_loop = False
        if on_loop:
            sink(event)
        else:
            try:
                loop.call_soon_threadsafe(sink, event)
            except RuntimeError:
                pass  # loop already closed during shutdown

    def _emit_kv_holdings(self, delta) -> None:
        """Offload-plane holdings_cb -> the externally-wired
        kv_holdings_sink (fleet KV economy).

        Deltas fire on the offload / kv-remote threads; the sink
        (KvHoldingsPublisher.emit uses an asyncio.Queue) is not
        thread-safe, so emissions hop to the engine's loop exactly like
        ``_emit_kv_event``.  Tuple rows ``(hash, tier|None, nbytes)``
        become wire rows ``{"sequence_hash", "tier", "nbytes"}``."""
        sink = self.kv_holdings_sink
        if sink is None:
            return
        event = {
            "type": "holdings",
            "delta": [
                {"sequence_hash": int(h), "tier": tier, "nbytes": int(n)}
                for h, tier, n in delta
            ],
        }
        loop = self._loop
        if loop is None:
            sink(event)
            return
        try:
            on_loop = asyncio.get_running_loop() is loop
        except RuntimeError:
            on_loop = False
        if on_loop:
            sink(event)
        else:
            try:
                loop.call_soon_threadsafe(sink, event)
            except RuntimeError:
                pass  # loop already closed during shutdown

    def attach_remote_kv(
        self, store, *, worker_id: int = 0, namespace: str = "dynamo"
    ) -> None:
        """Arm the G4 remote tier on the offload plane (fleet KV economy).

        ``store`` is any blob store with put/get (offload.InMemoryBlobStore,
        runtime.transports.client.HubBlobClient).  No-op unless the offload
        plane and a parsed ``kv_remote`` spec are both armed."""
        if self.offload_engine is None or self.kv_remote_spec is None:
            return
        self.offload_engine.attach_remote(
            store,
            worker_id=worker_id,
            namespace=str(self.kv_remote_spec.get("namespace", namespace)),
            mirror=bool(self.kv_remote_spec.get("mirror", True)),
        )

    def _publish_stored(self, seq: SeqState, blocks: List[TokenBlock]) -> None:
        if self.kv_event_sink is None:
            return
        self.kv_event_sink(
            {
                "type": "stored",
                "blocks": [
                    {
                        "block_hash": b.block_hash,
                        "sequence_hash": b.sequence_hash,
                        "parent_sequence_hash": b.parent_sequence_hash,
                        "position": b.position,
                    }
                    for b in blocks
                ],
            }
        )

    def _publish_removed(self, seq: SeqState) -> None:
        if self.kv_event_sink is None or seq.blocks is None:
            return
        hashes = seq.blocks.sequence_hashes()
        if hashes:
            self.kv_event_sink({"type": "removed", "sequence_hashes": hashes})
