"""Continuous-batching scheduler: host-side state feeding fixed-shape device
steps.

Behavioral spec comes from the reference mocker scheduler / KV manager split
(lib/llm/src/mocker/scheduler.rs:185, kv_manager.rs:55) and vLLM-style
continuous batching, re-shaped for XLA: the device sees a fixed-capacity
decode batch (``max_batch_size`` lanes) and bucket-padded prefill shapes;
all variability -- admission, slot assignment, page growth, stop conditions,
preemption -- lives here on the host.

The scheduler is sans-IO: it owns numpy mirrors of the device-side batch
arrays (tokens / seq_lens / page_table) and pure-Python bookkeeping; the
engine drives it and runs the actual jitted steps.
"""

from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import numpy as np

from ..block_manager import PagePool
from ..spec.drafter import spec_live
from ..protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from ..tokens.sequence import TokenBlock, TokenBlockSequence
from .kv_cache import OutOfPages, PageAllocator


@dataclass
class KVAdmitConfig:
    """KV-budget admission model (ROADMAP item 5 / FlowKV): admit against
    a *predicted KV-page* commitment instead of slot count, so one 128k
    prompt neither grabs a slot it cannot feed nor blocks the queue while
    short traffic could still fit.

    The predictor charges each request its peak pages -- sequence length
    plus decode headroom (``remaining_budget``, optionally capped by
    ``headroom_tokens``) -- against ``util * pool - reserve_pages``.  A
    head that does not fit is *skipped over* (short traffic keeps
    admitting, up to ``max_skips`` per pass) until it has aged past
    ``floor_s`` seconds; from then on no request passes it, so freed
    pages accumulate for the head instead of feeding newcomers -- the
    fairness floor in both directions.  Admission order changes; token
    streams never do.

    Armed via ``SchedulerConfig.kv_admit`` (engine:
    ``EngineConfig.kv_admit_budget`` / ``DYN_KV_ADMIT_BUDGET``)."""

    # fraction of the (trash-page-excluded) pool the predictor may commit
    util: float = 0.9
    # cap on the predicted decode headroom per request, tokens; None =
    # the request's full remaining token budget (max_tokens-capped)
    headroom_tokens: Optional[int] = None
    # pages withheld from the predictor (swap-restore / onboard slack)
    reserve_pages: int = 0
    # fairness floor: once the queue head has waited this long, nothing
    # skips past it
    floor_s: float = 2.0
    # max requests admitted past a blocked head per planning pass
    max_skips: int = 4


def parse_kv_admit_spec(spec: Any) -> Optional[KVAdmitConfig]:
    """Parse a ``DYN_KV_ADMIT_BUDGET`` value into a :class:`KVAdmitConfig`
    (None = slot-count admission).

    Grammar: ``0``/``off`` disarms, ``1``/``on`` arms the defaults, or a
    comma-separated ``k=v`` list::

        DYN_KV_ADMIT_BUDGET=util=0.9,headroom=256,reserve=16,floor_s=2,skips=4
    """
    if spec is None:
        return None
    if isinstance(spec, KVAdmitConfig):
        return spec
    if isinstance(spec, bool):
        return KVAdmitConfig() if spec else None
    s = str(spec).strip()
    if not s or s.lower() in ("0", "off", "false", "no"):
        return None
    out = KVAdmitConfig()
    if s.lower() in ("1", "on", "true", "yes"):
        return out
    for clause in filter(None, (c.strip() for c in s.split(","))):
        k, sep, v = clause.partition("=")
        k = k.strip().lower()
        if not sep:
            raise ValueError(f"malformed DYN_KV_ADMIT_BUDGET clause {clause!r}")
        if k not in ("util", "headroom", "reserve", "floor_s", "skips"):
            raise ValueError(f"unknown DYN_KV_ADMIT_BUDGET key {k!r}")
        try:
            if k == "util":
                out.util = float(v)
            elif k == "headroom":
                out.headroom_tokens = int(v)
            elif k == "reserve":
                out.reserve_pages = int(v)
            elif k == "floor_s":
                out.floor_s = float(v)
            elif k == "skips":
                out.max_skips = int(v)
        except ValueError as e:
            raise ValueError(f"bad DYN_KV_ADMIT_BUDGET value {clause!r}") from e
    return out


@dataclass
class SchedulerConfig:
    max_batch_size: int = 8
    max_seq_len: int = 2048
    page_size: int = 16
    # max prompts prefilled per tick (each prefill is one async device
    # dispatch); None = as many as there are free slots.  Uncapped admission
    # fills the decode batch in one tick, so a burst of N prompts costs one
    # partially-occupied decode block instead of N
    max_prefill_per_tick: Optional[int] = None
    # KV block size for router-visible block identity (token hashing); usually
    # equals page_size but decoupled (reference recommends 128 for routing).
    block_size: Optional[int] = None
    # data-parallel groups of the serving mesh: slot b belongs to dp group
    # b // (max_batch_size / dp_groups), because the engine's decode-state
    # arrays shard batch-major over ``dp``.  Admission balances lanes
    # across groups (see _free_slot) so one dp shard never carries the
    # whole batch while its peers idle -- per-chip throughput under
    # partial load depends on it.  1 = no mesh, first-free admission.
    dp_groups: int = 1
    # KV-budget admission (None = legacy slot-count admission); see
    # KVAdmitConfig.  Changes which tick a request admits on, never its
    # tokens.
    kv_admit: Optional[KVAdmitConfig] = None


@dataclass
class SeqState:
    """One in-flight request."""

    request_id: str
    prompt: List[int]
    stop: StopConditions
    sampling: SamplingOptions
    eos_ids: List[int]
    arrival_s: float = field(default_factory=time.monotonic)
    slot: int = -1
    # page_table view: shared (reused) pages first, then owned pages
    pages: List[int] = field(default_factory=list)
    blocks: Optional[TokenBlockSequence] = None  # router-visible block identity
    # llava-style soft prompt: [T_img, hidden] f32 rows injected over the
    # first T_img prompt positions at prefill (None = text-only)
    mm_embeds: Optional[Any] = None
    num_generated: int = 0
    # tokens generated before the last preemption (already streamed to the
    # client); stop-condition accounting uses prior_generated + num_generated
    prior_generated: int = 0
    finish: Optional[FinishReason] = None
    # number of prompt tokens whose KV was reused from a prefix-cache match
    cached_prompt_tokens: int = 0
    # registry refs this sequence holds (reused prefix + own registered blocks)
    held_blocks: List[int] = field(default_factory=list)
    # pages allocated to (and freed by) this sequence alone
    owned_pages: List[int] = field(default_factory=list)
    # a two-kind cache (window and full layers): the window pool's page of
    # each of ``pages``' positions, 0 where none is held (not reached yet,
    # or let go behind the window); the window pool's registry refs by
    # block position; the first block not yet let go
    wpages: List[int] = field(default_factory=list)
    w_held: Dict[int, int] = field(default_factory=dict)
    w_lo: int = 0
    # completed blocks whose final token's KV is not yet written (it lands
    # with the next decode step); registered once the cache catches up
    pending_register: List[TokenBlock] = field(default_factory=list)
    # offload-tier hits awaiting their device scatter: (seq_hash, pages,
    # blob, meta) -- the engine scatters + registers them at prefill time
    pending_onboard: List[Any] = field(default_factory=list)
    # prefix-cache stats are counted once per request (first admission)
    stats_counted: bool = False
    # disaggregation: prompt KV arrives from a remote prefill worker; the
    # lane holds pages but stays inactive until delivery
    awaiting_kv: bool = False
    # chunked prefill: prompt tokens whose KV has been dispatched so far;
    # the lane stays decode-inactive while prefilling is True
    prefilled_tokens: int = 0
    prefilling: bool = False
    # speculative decoding: the request's knobs (SpeculationOptions | None)
    # and, once the engine arms the lane, its live spec.SpecState.  A lane
    # with spec armed is DEVICE-inactive for the decode scan -- it advances
    # through the engine's batched verify dispatches instead, driven from
    # the host mirrors.
    speculation: Optional[Any] = None
    spec: Optional[Any] = None
    # a trunk with convolution layers: the page whose snapshot the last
    # admission resumed from (None: it started empty), and whether its hit
    # was shortened because the registry held the whole prompt
    state_page: Optional[int] = None
    state_walked_back: bool = False
    # a trunk with gated delta-rule layers: the block whose snapshot the
    # admission resumes from, held in the slot table until the lane's first
    # chunk is dispatched (None: none, or restored already); the tokens of
    # the hit behind that block, computed again
    state_block: Optional[int] = None
    state_recompute: int = 0
    # echo+logprobs: top-N prompt logprobs to compute at first prefill
    prompt_logprobs: Optional[int] = None
    prompt_lp_sent: bool = False
    # queue-side prefetch accounting: offloaded prefix blocks found
    # host-staged at admission because the prefetch walk promoted them
    # during queue wait (engine._note_prefetch_admission; span attr +
    # dynamo_kv_prefetch_hits)
    prefetch_hits: int = 0
    # request stages (time.monotonic(), like arrival_s; 0.0 = not yet):
    # when the process received the request (the Context's stamp), the
    # FIRST admission (a re-admission after preemption keeps it), the first
    # token committed, and the intervals spent preempted (queued again
    # after losing the lane).  Read once per request: the stage histograms
    # (runtime/metrics.py), the SLO plane's first-token note, and -- with
    # tracing on -- the engine.* spans written when the request finishes.
    created_s: float = 0.0
    admitted_s: float = 0.0
    first_token_s: float = 0.0
    # the engine's dispatch record at the first admission (service seconds
    # of chunk steps, of decode-only dispatches, prefill rows committed;
    # ``JaxEngine._service_mark``), and at the first token what the request
    # waited behind: seconds in chunk steps, in decode-only dispatches, in
    # no dispatch, and every request's prefill rows committed meanwhile.
    # Copied and observed once each; nothing schedules by them.
    served_at_admission: Optional[Tuple[float, float, int]] = None
    first_token_wait: Optional[Tuple[float, float, float, int]] = None
    preempted_at: float = 0.0
    preempted: List[Tuple[float, float]] = field(default_factory=list)
    # span attributes: prefill dispatches that carried this prompt, the
    # prompt tokens they computed, commits that brought tokens, and whether
    # the prompt rode the unified mixed dispatch
    prefill_chunks: int = 0
    prefill_tokens: int = 0
    token_commits: int = 0
    prefill_mixed: bool = False
    stages_recorded: bool = False

    @property
    def seq_len(self) -> int:
        return len(self.prompt) + self.num_generated

    def note_prefill(self, tokens: int) -> None:
        """One prefill dispatch carried ``tokens`` of this prompt."""
        self.prefill_chunks += 1
        self.prefill_tokens += tokens

    def stage_segments(self, end_s: float) -> List[Tuple[str, float, float]]:
        """``(stage, start, end)`` pieces that tile arrival -> ``end_s``:
        ``queue`` to the first admission, ``prefill`` to the first token,
        ``decode`` after it, each cut around the ``preempted`` intervals
        (one still open at ``end_s`` closes there).  Stages a request never
        reached are absent."""
        adm = self.admitted_s or end_s
        first = self.first_token_s or end_s
        stages = [
            ("queue", self.arrival_s, adm),
            ("prefill", adm, first),
            ("decode", first, end_s),
        ]
        gaps = list(self.preempted)
        if self.preempted_at:
            gaps.append((self.preempted_at, end_s))
        out: List[Tuple[str, float, float]] = []
        for name, lo, hi in stages:
            for g_lo, g_hi in gaps:
                g_lo, g_hi = max(g_lo, lo), min(g_hi, hi)
                if g_lo >= g_hi:
                    continue
                if g_lo > lo:
                    out.append((name, lo, g_lo))
                out.append(("preempted", g_lo, g_hi))
                lo = g_hi
            if hi > lo:
                out.append((name, lo, hi))
        return out

    @classmethod
    def from_request(cls, request_id: str, req: PreprocessedRequest, block_size: int) -> "SeqState":
        import numpy as np

        mm = None
        if req.mm_embeds:
            mm = np.asarray(req.mm_embeds, np.float32)
        return cls(
            request_id=request_id,
            prompt=list(req.token_ids),
            stop=req.stop_conditions,
            sampling=req.sampling_options,
            eos_ids=list(req.eos_token_ids),
            # multimodal prompts opt out of prefix caching: the block hash
            # chain is computed over token ids, and the placeholder ids for
            # embedding positions would alias across different images
            blocks=(
                None
                if mm is not None
                else TokenBlockSequence(req.token_ids, block_size=block_size)
            ),
            mm_embeds=mm,
            speculation=req.speculation,
            prompt_logprobs=req.prompt_logprobs,
        )


@dataclass
class TickPlan:
    """What the engine must execute this tick."""

    # prompts to prefill: (seq, bucket_len) -- each is one prefill dispatch
    prefills: List[Tuple[SeqState, int]] = field(default_factory=list)


@dataclass
class MixedChunk:
    """One lane's contribution of prompt tokens to a unified mixed-batch
    dispatch: ``final`` means the chunk completes the prompt, so the
    dispatch samples the lane's first token."""

    seq: SeqState
    start: int  # first prompt position this chunk covers
    length: int  # tokens in the chunk
    final: bool


@dataclass
class StepEvent:
    """Per-request outcome of a tick (tokens emitted and/or finished).

    ``tokens`` carries every token the tick emitted for the request -- a
    whole decode block's worth coalesces into ONE event (commit_block), so
    downstream per-event costs (queue put, consumer wakeup, SSE frame build)
    are paid per block, not per token.  Order within the list is emission
    order."""

    seq: SeqState
    tokens: List[int] = field(default_factory=list)
    finished: Optional[FinishReason] = None
    completed_blocks: List[TokenBlock] = field(default_factory=list)
    # aligned with ``tokens`` when the dispatch carried logprob data:
    # chosen-token logprobs, and per-token top-N alternatives as
    # [[token_id, logprob], ...] (None when the dispatch ran without tops)
    logprobs: List[float] = field(default_factory=list)
    top_logprobs: Optional[List[List[List[float]]]] = None
    # echo+logprobs: per-prompt-position [token_id, logprob|None, top|None]
    # entries, attached by the engine to the request's first event
    prompt_logprobs: Optional[List[Any]] = None

    @property
    def token(self) -> Optional[int]:
        """Single-token view for the prefill/first-token paths (and tests)."""
        return self.tokens[0] if self.tokens else None


class Scheduler:
    def __init__(
        self,
        cfg: SchedulerConfig,
        allocator: PageAllocator,
        window_allocator: Optional[PageAllocator] = None,
        window: int = 0,
    ) -> None:
        self.cfg = cfg
        self.allocator = allocator
        # a two-kind cache (kv_cache.KindKV): ``allocator`` hands out the
        # full layers' pages, which a sequence keeps while it runs, and
        # ``window_allocator`` the window layers', which it lets go as they
        # fall behind ``window`` keys of the next row it computes
        self.window_allocator = window_allocator
        # set by an engine whose trunk has convolution layers
        self.conv_state = False
        # set by an engine whose trunk has gated delta-rule layers: the
        # table of the snapshot pool (kv_cache.StateSlots)
        self.state_slots: Optional[Any] = None
        self.window = int(window)
        self.window_released = 0  # window pages let go behind the window
        self.block_size = cfg.block_size or cfg.page_size
        # prefix-cache reuse runs when the allocator is a PagePool (has a
        # sequence-hash registry) and router blocks align to whole pages
        self.pool: Optional[PagePool] = (
            allocator
            if isinstance(allocator, PagePool)
            and self.block_size % cfg.page_size == 0
            else None
        )
        self.pages_per_block = self.block_size // cfg.page_size
        self.wpool: Optional[PagePool] = (
            window_allocator
            if self.pool is not None and isinstance(window_allocator, PagePool)
            else None
        )
        # G2/G3 offload lookup: fn(seq_hash) -> (blob, meta) | None, wired
        # by the engine when offload tiers are configured
        self.offload_lookup: Optional[Any] = None
        # swap-based preemption hook: fn(seq) -> bool, wired by the engine
        # when the offload plane is armed.  Called with the victim still
        # slotted (pages intact) so the engine can dispatch the device
        # snapshot before the slot release frees them; True parks the
        # sequence for a KV restore instead of a re-prefill.
        self.swap_out: Optional[Any] = None
        self.preempt_swap = 0
        self.preempt_recompute = 0
        # KV-budget admission (None = slot-count): counters back the
        # long-context bench and the starvation tests
        self.kv_admit = cfg.kv_admit
        self.admit_skips = 0  # admissions that passed a blocked head
        self.admit_blocked = 0  # passes whose head did not fit the budget
        # K-granular admission (ISSUE 16): tokens a decode lane may grow
        # by before the scheduler can react again -- the engine sets this
        # to its multi-step K x pipeline depth each tick, so the budget
        # planner charges every decode-phase lane at least that much
        # uncommitted in-flight growth and an admission decision can never
        # be invalidated by a block that was already dispatched
        self.decode_inflight_tokens = 0
        # observability hook (engine/metrics.EngineMetrics): the scheduler
        # stays sans-IO -- it only pokes gauges the engine wired in
        self.metrics: Optional[Any] = None
        # the engine's dispatch record, read where a request is first
        # admitted and copied onto the request; the scheduler decides
        # nothing by it
        self.service_mark: Optional[
            Callable[[], Tuple[float, float, int]]
        ] = None
        B = cfg.max_batch_size
        self.max_pages = cfg.max_seq_len // cfg.page_size
        self.waiting: Deque[SeqState] = collections.deque()
        self.slots: List[Optional[SeqState]] = [None] * B
        # slotted lanes whose prompt KV the mixed-batch plane still owes
        # (unified ragged dispatches pack their chunks; see
        # form_mixed_chunks)
        self.mix_pending: List[SeqState] = []
        # numpy mirrors of the device batch arrays
        self.tokens = np.zeros((B,), np.int32)
        self.seq_lens = np.zeros((B,), np.int32)
        # [B, P]; over a two-kind cache [2, B, P]: full, window (kind-major,
        # so that a lane's row and a width's slice are what they are in one)
        self.page_table = np.zeros(
            (2, B, self.max_pages) if self.two_kind else (B, self.max_pages),
            np.int32,
        )
        # layout_version: slot membership changed (admission / release /
        # preemption).  growth_version: pages were appended to live lanes --
        # the engine refreshes the device page table and limits, keeping the
        # decode pipeline running.  dirty_slots: lanes whose mirrors changed
        # (admission/release); the engine folds them into the device-resident
        # decode state with per-row scatters instead of a full rebuild, so
        # the decode pipeline never drains for batch-membership changes.
        self.layout_version = 0
        self.growth_version = 0
        self.dirty_slots: set = set()

    # -- queue/observability -------------------------------------------------

    @property
    def two_kind(self) -> bool:
        return self.window_allocator is not None

    @property
    def resident_context_tokens(self) -> int:
        """Tokens of context the cache keeps: the running sequences' lengths
        and the reusable (registered, unreferenced) blocks of the pool a
        prefix match walks (the full pool of a two-kind cache)."""
        held = sum(s.seq_len for s in self.slots if s is not None)
        idle = self.pool.num_inactive if self.pool is not None else 0
        return held + idle * self.block_size

    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    @property
    def num_active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    @property
    def num_runnable(self) -> int:
        """Slotted lanes the device can actually step (parked awaiting_kv /
        mid-chunked-prefill lanes hold a slot + pages but must not spin
        decode blocks)."""
        return sum(
            1
            for s in self.slots
            if s is not None and not s.awaiting_kv and not s.prefilling
        )

    @property
    def num_decode_runnable(self) -> int:
        """Runnable lanes the decode SCAN should step: actively
        speculating lanes are excluded -- they advance via the engine's
        verify columns (host-mirror driven), and a decode block over
        only-spec lanes would burn a dispatch on dead rows.  A lane whose
        speculation auto-disabled is a plain decode lane again and counts
        (``spec.drafter.spec_live``: the same predicate the engine's
        eligibility sites consult)."""
        return sum(
            1
            for s in self.slots
            if s is not None
            and not s.awaiting_kv
            and not s.prefilling
            and not spec_live(s.spec)
        )

    @property
    def has_work(self) -> bool:
        return self.num_active > 0 or len(self.waiting) > 0

    @property
    def has_runnable_work(self) -> bool:
        """Work the tick loop can make progress on *right now*; a batch of
        only parked lanes sleeps until a delivery (or timeout) wakes it."""
        return self.num_runnable > 0 or len(self.waiting) > 0

    def enqueue(self, seq: SeqState) -> None:
        if not seq.prompt:
            raise ValueError("empty prompt (zero tokens after preprocessing)")
        if len(seq.prompt) > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt of {len(seq.prompt)} tokens exceeds max_seq_len "
                f"{self.cfg.max_seq_len}"
            )
        self.waiting.append(seq)

    # -- admission -----------------------------------------------------------

    def remaining_budget(self, seq: SeqState) -> int:
        """Tokens the sequence may still emit (max_tokens / max_seq_len caps)."""
        produced = seq.prior_generated + seq.num_generated
        by_max = (
            seq.stop.max_tokens - produced
            if seq.stop.max_tokens is not None
            else self.cfg.max_seq_len
        )
        by_len = self.cfg.max_seq_len - seq.seq_len
        return max(0, min(by_max, by_len))

    def min_total_pages(self, seq: SeqState) -> int:
        """Smallest page count that lets the sequence make forward progress:
        the prompt KV plus, when at least one decode step must run, the write
        slot for the next token.  (A single-token request samples its only
        token from the prefill logits and never decodes.)"""
        n = len(seq.prompt)
        if self.remaining_budget(seq) >= 2:
            n += 1
        return -(-n // self.cfg.page_size)

    def plan(self) -> TickPlan:
        """Admit waiting requests into free slots (page permitting), then
        decide whether a decode step runs.

        With ``kv_admit`` unset the queue admits strictly FIFO against
        slot count + the physical page floor.  With it set, admission
        runs the KV-budget model (:class:`KVAdmitConfig`): predicted
        peak pages gate each candidate, and a head that does not fit is
        skipped over -- bounded by the fairness floor -- so short
        traffic and one long prompt make progress together."""
        plan = TickPlan()
        cap = self.cfg.max_prefill_per_tick
        if self.kv_admit is not None:
            self._plan_budget(plan, cap)
        else:
            while self.waiting and (cap is None or len(plan.prefills) < cap):
                slot = self._free_slot()
                if slot is None:
                    break
                if not self._try_admit(self.waiting[0], plan, slot):
                    break
                self.waiting.popleft()
        # decode dispatch gating lives in the engine tick loop, keyed on
        # num_decode_runnable AFTER this tick's lane parking: a tick whose
        # slots hold only parked / mid-prefill / speculating lanes must
        # not pay a device dispatch for dead rows
        if self.metrics is not None:
            self.metrics.observe_sched(len(self.waiting), self.num_active)
        return plan

    def _try_admit(self, seq: SeqState, plan: TickPlan, slot: int) -> bool:
        """Admit one request into ``slot`` if the physical page floor
        allows; returns False (state untouched) otherwise.  The one
        admission body both planners share."""
        # remote-prefilled prompts arrive as one full-prompt KV blob; a
        # shared reused prefix would be overwritten by the scatter, so
        # external admissions take fresh pages only (reuse is the local
        # prefill path's optimization)
        cached_pages = [] if seq.awaiting_kv else self._match_prefix(seq)
        if seq.awaiting_kv:
            seq.cached_prompt_tokens = 0
        n_pages = -(-len(seq.prompt) // self.cfg.page_size)
        # admission needs room for the prompt *and* the first decode
        # write, with one page of headroom per active seq for growth;
        # reused prefix pages are already resident and cost nothing
        need = self.min_total_pages(seq) - len(cached_pages)
        if self.allocator.free_pages < need + self.num_active or (
            # window pages are taken a chunk at a time (form_mixed_chunks):
            # admission asks only that a lane's most can be had now
            self.two_kind
            and self.window_allocator.free_pages
            < min(need, self.window_lane_pages(0)) + self.num_active
        ):
            self._unmatch_prefix(seq)
            return False
        fresh = self.allocator.alloc(n_pages - len(cached_pages))
        if self.two_kind:
            seq.wpages += [0] * (n_pages - len(seq.wpages))
        # onboard pages were allocated inside _match_prefix and stay
        # plain-owned until the engine registers them post-scatter
        onboard = [
            p for _h, pgs, _b, _m in seq.pending_onboard for p in pgs
        ]
        seq.owned_pages = onboard + fresh
        seq.pages = cached_pages + fresh
        seq.slot = slot
        # the first admission ends the queue-wait leg (observed here, once
        # per request); a re-admission after preemption keeps that stamp
        # and closes the preempted interval instead
        now = time.monotonic()
        if not seq.admitted_s:
            seq.admitted_s = now
            if self.metrics is not None:
                self.metrics.queue_wait.observe(now - seq.arrival_s)
            if self.service_mark is not None:
                seq.served_at_admission = self.service_mark()
        elif seq.preempted_at:
            seq.preempted.append((seq.preempted_at, now))
            seq.preempted_at = 0.0
        self.slots[slot] = seq
        self._write_slot_arrays(seq)
        self._queue_prompt_registrations(seq)
        if self.conv_state:
            self._note_state_admission(seq)
        if self.state_slots is not None:
            self._note_snapshot_admission(seq)
        if not seq.awaiting_kv:
            plan.prefills.append((seq, len(seq.prompt)))
        # awaiting_kv lanes hold their pages and stay device-inactive
        # until the remote prefill delivers (engine.deliver_external)
        return True

    def _note_state_admission(self, seq: SeqState) -> None:
        """Where an admission's convolution layers start: empty at position
        0, or from the snapshot that rides the last page of its hit (the
        step reads it off the chunk's first position; nothing is copied).
        A hit ends on a page because a block is whole pages."""
        n = seq.cached_prompt_tokens
        if n % self.cfg.page_size:
            raise RuntimeError(
                f"a prefix hit of {n} tokens does not end on a page: the "
                "convolution layers have no snapshot to resume from"
            )
        seq.state_page = seq.pages[n // self.cfg.page_size - 1] if n else None
        m = self.metrics
        if m is not None and m.state_restores is not None:
            (m.state_restores if n else m.state_resets).inc()
            if seq.state_walked_back:
                m.state_walkbacks.inc()

    def _note_snapshot_admission(self, seq: SeqState) -> None:
        """Where an admission's delta-rule layers start: empty at position
        0, or from the snapshot ``_match_prefix`` walked the hit back to."""
        m = self.metrics
        if m is None or m.state_restores is None:
            return
        (m.state_restores if seq.state_block is not None else m.state_resets).inc()
        if seq.state_recompute:
            m.state_walkbacks.inc()
            m.snapshot_recompute_tokens.inc(seq.state_recompute)

    def state_plan(self, chunks: List[MixedChunk]) -> np.ndarray:
        """What a packed dispatch of ``chunks`` does with the snapshot pool
        (``kv_cache.DeltaKV.plan``, ``[3, B]``: the slot a lane restores
        from, the slot it writes a snapshot to, the position it is taken
        at).  A lane restores in the first chunk after its hit; a
        prefilling lane takes a snapshot where its chunk ends on a block,
        and at the last whole block of its prompt (the deepest a later hit
        can reach: a token is always left to compute).  A snapshot a block
        already has is used, not taken again."""
        slots, bs = self.state_slots, self.block_size
        plan = np.full((3, self.cfg.max_batch_size), -1, np.int32)
        for ch in chunks:
            seq, b = ch.seq, ch.seq.slot
            if seq.state_block is not None:
                plan[0, b] = slots.slot_of(seq.state_block)
                slots.release(seq.state_block)
                seq.state_block = None
            elif ch.start and ch.start == seq.cached_prompt_tokens:
                raise RuntimeError(
                    f"lane {b} resumes at {ch.start} and no snapshot is held "
                    "for it: the delta-rule layers have no state to start from"
                )
            if seq.blocks is None:
                continue
            at = ch.start + ch.length
            if ch.final:
                at = (len(seq.prompt) - 1) // bs * bs
            if at <= ch.start or at % bs:
                continue
            slot = slots.take(seq.blocks.sequence_hashes()[at // bs - 1])
            if slot is not None:
                plan[1, b], plan[2, b] = slot, at
                if self.metrics is not None and self.metrics.state_snapshots:
                    self.metrics.state_snapshots.inc()
        return plan

    def predicted_pages(self, seq: SeqState) -> int:
        """Predicted peak KV pages for a request under the budget model, in
        the pool ``allocator`` hands out (over a two-kind cache the full
        layers' pool: a lane's window pages are bounded by
        :meth:`window_lane_pages` whatever its length):
        current sequence length (the prompt, for a queued request) plus
        decode headroom -- the remaining token budget, optionally capped
        by ``headroom_tokens``.  Never below what the sequence already
        holds, never above the per-lane page ceiling."""
        adm = self.kv_admit
        remaining = self.remaining_budget(seq)
        head = remaining
        if adm is not None and adm.headroom_tokens is not None:
            head = min(head, adm.headroom_tokens)
        if (
            seq.slot is not None
            and not seq.prefilling
            and not seq.awaiting_kv
        ):
            # a decode-phase lane has up to decode_inflight_tokens of
            # uncommitted multi-step growth in flight: charge at least
            # that (still capped by what it may legally emit), even when
            # headroom_tokens clamps tighter
            head = max(head, min(self.decode_inflight_tokens, remaining))
        n = min(seq.seq_len + head, self.cfg.max_seq_len)
        pages = -(-n // self.cfg.page_size)
        return max(min(pages, self.max_pages), len(seq.pages))

    def _plan_budget(self, plan: TickPlan, cap: Optional[int]) -> None:
        """KV-budget admission pass (see :class:`KVAdmitConfig`)."""
        adm = self.kv_admit
        now = time.monotonic()
        usable = self.allocator.num_pages - 1  # trash page excluded
        budget = max(int(usable * adm.util) - adm.reserve_pages, 1)
        committed = sum(
            self.predicted_pages(s) for s in self.slots if s is not None
        )

        # fairness floor: an aged head stops all skip-ahead, so pages
        # freed by completions accumulate for it instead of feeding
        # newcomers behind it.  Evaluated against the CURRENT head at
        # each gating point -- an aged head that admits mid-pass must
        # not leave its stale flag gating the requests behind it.
        def head_aged() -> bool:
            return (
                bool(self.waiting)
                and now - self.waiting[0].arrival_s > adm.floor_s
            )

        skips = 0
        i = 0
        while i < len(self.waiting) and (
            cap is None or len(plan.prefills) < cap
        ):
            slot = self._free_slot()
            if slot is None:
                break
            seq = self.waiting[i]
            need = self.predicted_pages(seq)
            # an empty batch always admits its head: a request whose
            # prediction exceeds the whole budget must still run alone
            # (the engine fails truly-impossible prompts separately)
            fits = committed + need <= budget or (
                self.num_active == 0 and i == 0
            )
            if fits and self._try_admit(seq, plan, slot):
                del self.waiting[i]
                committed += need
                continue
            if i == 0:
                self.admit_blocked += 1
            if head_aged() or skips >= adm.max_skips:
                break
            skips += 1
            self.admit_skips += 1
            i += 1

    # -- mixed-batch formation (unified ragged dispatch) ---------------------

    def queue_mixed_prefill(self, seq: SeqState, start: int) -> None:
        """Hand an admitted (slotted) prompt to the mixed-batch plane: the
        lane parks ``prefilling`` (decode-inactive) and its prompt tokens
        are packed into unified dispatches chunk by chunk, FIFO across
        lanes, under the per-dispatch token budget."""
        seq.prefilling = True
        seq.prefilled_tokens = start
        # a re-admitted (preemption-recomputed) lane may still have a stale
        # entry from its previous life; one entry per seq keeps one chunk
        # per lane per dispatch
        if seq not in self.mix_pending:
            self.mix_pending.append(seq)

    def form_mixed_chunks(
        self, budget: int, chunk_cap: Optional[int] = None,
        reserve_tokens: int = 0,
    ) -> List[MixedChunk]:
        """Pack pending prefill work into this tick's unified dispatch.

        ``budget`` is the dispatch's total fresh-token budget
        (``DYN_MIXED_TOKEN_BUDGET``): every decode-runnable lane costs one
        token, ``reserve_tokens`` rows are withheld for the tick's folded
        speculative-verify segments (the engine's spec-fold reserve -- a
        verify column is a fresh row like any other under the packed
        layout), the remainder goes to prefill chunks in arrival order.  At
        least one prompt token always packs when prefill work is pending,
        so a decode batch as wide as the budget can never starve
        admission.  ``chunk_cap`` bounds one lane's chunk (the
        ``prefill_chunk_tokens`` knob); chunk lengths are otherwise ragged
        -- the dispatch pads the query axis to a pow2 bucket, so the
        executable-shape set stays O(log(budget)) no matter the arrival
        pattern (tested in test_mixed_batching).

        Non-final chunk boundaries are rounded DOWN to a page multiple:
        a drained lane (``_drain_mixed_to_classic``) resumes through the
        classic suffix machinery, whose prefix page table covers whole
        pages only -- a mid-page boundary would leave the partial page's
        keys unreachable on restart.  Starts stay aligned by induction
        (admission starts at the page-aligned prefix-cache boundary).
        When alignment rounds the head lane's chunk to zero, one full
        page packs anyway (slight budget overshoot beats starvation).
        """
        ps = self.cfg.page_size
        left = max(budget - self.num_decode_runnable - reserve_tokens, 1)
        chunks: List[MixedChunk] = []
        still: List[SeqState] = []
        seen: set = set()
        for seq in self.mix_pending:
            if (
                seq.finish is not None
                or seq.slot < 0
                or self.slots[seq.slot] is not seq
                or not seq.prefilling
                or id(seq) in seen
            ):
                continue  # cancelled / preempted mid-prefill / dup: drop
            seen.add(id(seq))
            remaining = len(seq.prompt) - seq.prefilled_tokens
            if remaining <= 0:  # defensive; final chunk clears prefilling
                seq.prefilling = False
                self.dirty_slots.add(seq.slot)
                continue
            take = min(remaining, left) if left > 0 else 0
            if chunk_cap is not None:
                take = min(take, chunk_cap)
            if take < remaining:
                # non-final: keep the boundary page-aligned for the
                # classic-path handoff (start is aligned by induction)
                take = (seq.prefilled_tokens + take) // ps * ps \
                    - seq.prefilled_tokens
                if take <= 0 and not chunks:
                    take = min(ps, remaining)
            if take > 0 and self.two_kind and not self._reserve_chunk_window(
                seq, seq.prefilled_tokens, take
            ):
                take = 0  # the window pool is dry: the lane waits a tick
            if take > 0:
                chunks.append(
                    MixedChunk(
                        seq=seq,
                        start=seq.prefilled_tokens,
                        length=take,
                        final=(take == remaining),
                    )
                )
                left -= take
                if take < remaining:
                    still.append(seq)
            else:
                still.append(seq)
        self.mix_pending = still
        return chunks

    def _match_prefix(self, seq: SeqState) -> List[int]:
        """Acquire the longest resident prefix of the prompt's blocks; returns
        the reused pages (front of the page table).  Reuse is capped below the
        full prompt so prefill always has at least one token to process.

        After the G1 (HBM) match ends, the chain continues into the offload
        tiers: a G2/G3 hit allocates fresh pages now and defers the device
        scatter + registration to the engine (``seq.pending_onboard``) --
        those pages stay plain-owned until the scatter is dispatched, so no
        other request can match a block whose contents haven't landed."""
        seq.cached_prompt_tokens = 0
        if self.pool is None or seq.blocks is None:
            return []
        max_blocks = max(0, (len(seq.prompt) - 1) // self.block_size)
        every = seq.blocks.sequence_hashes()
        hashes = every[:max_blocks]
        matched = self.pool.match(hashes)
        if self.conv_state:
            # the registry holds the whole prompt: the hit walks back a
            # block (a token is left to compute, and the convolution
            # layers' state exists at page ends only)
            seq.state_walked_back = (
                len(every) > max_blocks
                and len(matched) == max_blocks
                and self.pool.is_registered(every[max_blocks])
            )
        if self.wpool is not None:
            matched = matched[: self._window_tail_boundary(hashes, len(matched))]
        if self.state_slots is not None:
            # the hit walks back to the deepest block that has a live
            # snapshot, as if the prompt had matched no further: the rows
            # behind it pass through every layer again, on pages of the
            # lane's own
            keep = next(
                (i for i in range(len(matched), 0, -1)
                 if matched[i - 1].sequence_hash in self.state_slots), 0)
            seq.state_recompute = (len(matched) - keep) * self.block_size
            matched = matched[:keep]
        pages: List[int] = []
        for blk in matched:
            got = self.pool.acquire(blk.sequence_hash)
            if got is None:  # raced away (defensive; single-threaded today)
                break
            seq.held_blocks.append(blk.sequence_hash)
            pages.extend(blk.pages)
        n_matched = len(seq.held_blocks)
        if self.state_slots is not None and n_matched:
            seq.state_block = seq.held_blocks[-1]
            self.state_slots.hold(seq.state_block)
        if self.wpool is not None:
            # the window layers' share of the hit: the blocks that hold the
            # last ``window - 1`` tokens before the boundary, and no others
            ppb = self.pages_per_block
            seq.wpages = [0] * len(pages)
            seq.w_lo = max(0, n_matched - self._window_blocks)
            for i in range(seq.w_lo, n_matched):
                blk = self.wpool.acquire(hashes[i])
                seq.w_held[i] = hashes[i]
                seq.wpages[i * ppb : (i + 1) * ppb] = blk.pages
        if self.offload_lookup is not None:
            for h in hashes[n_matched:]:
                if self.pool.is_registered(h):
                    break  # re-resident meanwhile; stop the offload chain
                hit = self.offload_lookup(h)
                if hit is None:
                    break
                blob, meta = hit
                try:
                    got_pages = self.allocator.alloc(self.pages_per_block)
                except OutOfPages:
                    break
                seq.pending_onboard.append((h, got_pages, blob, meta))
                pages.extend(got_pages)
        seq.cached_prompt_tokens = (
            n_matched + len(seq.pending_onboard)
        ) * self.block_size
        return pages

    @property
    def _window_blocks(self) -> int:
        """Blocks that hold the ``window - 1`` keys before a block boundary
        (what the row at the boundary reads of a window layer's cache)."""
        return -(-(self.window - 1) // self.block_size)

    def window_lane_pages(self, chunk_tokens: int) -> int:
        """The most window-pool pages a lane holds: the window behind the
        next row, a chunk of ``chunk_tokens`` in flight, and one page for a
        boundary inside a page."""
        return -(-(self.window - 1 + chunk_tokens) // self.cfg.page_size) + 1

    def _window_tail_boundary(self, hashes: List[int], n_full: int) -> int:
        """The longest ``n <= n_full`` blocks of a prompt that the window
        layers can resume at: the window pool still has the blocks holding
        the last ``window - 1`` tokens before ``n`` (a hit of ``n`` blocks
        needs the full layers' pages for all of them, the window layers'
        for that tail only).  Where the pool has taken a tail back, the
        match walks back to the longest boundary whose tail is resident."""
        need, run, best = self._window_blocks, 0, 0
        for i in range(n_full):
            run = run + 1 if self.wpool.is_registered(hashes[i]) else 0
            if run >= min(i + 1, need):
                best = i + 1
        return best

    def _free_window_pages(self, seq: SeqState, lo: int, hi: int) -> int:
        """Let go of the window pages of block positions ``[lo, hi)``: a
        registered block's reference (it stays reusable until the pool
        takes it back), an unregistered page to the free list.  Returns the
        pages let go."""
        ppb = self.pages_per_block
        n = 0
        for b in range(lo, hi):
            span = seq.wpages[b * ppb : (b + 1) * ppb]
            h = seq.w_held.pop(b, None)
            if h is not None:
                self.wpool.release(h)
            else:
                self.window_allocator.free([p for p in span if p])
            n += sum(1 for p in span if p)
            seq.wpages[b * ppb : (b + 1) * ppb] = [0] * len(span)
        if hi > lo and seq.slot >= 0 and self.slots[seq.slot] is seq:
            self.page_table[1, seq.slot, lo * ppb : hi * ppb] = 0
        return n

    def release_window_behind(self, seq: SeqState, next_row: int) -> None:
        """Drop the window pool's blocks that lie wholly behind the window
        of ``next_row``, the next position the lane computes (a prefill
        chunk's start, a decode lane's cache length on the host, which the
        device is never behind).  No row still to be dispatched reads them.
        A step already dispatched may: that is safe because dispatches run
        in order on one stream, and whoever is handed the page next writes
        it in a dispatch that comes later.  The device's copy of the table
        keeps the stale entry until its next refresh; the window mask hides
        it either way."""
        first_key = next_row - (self.window - 1)
        behind = min(max(first_key, 0) // self.block_size,
                     len(seq.wpages) // self.pages_per_block)
        if behind > seq.w_lo:
            self.window_released += self._free_window_pages(
                seq, seq.w_lo, behind)
            seq.w_lo = behind

    def _reserve_chunk_window(self, seq: SeqState, start: int, take: int) -> bool:
        """Window pages for the chunk ``[start, start + take)`` of a
        prefilling lane, after registering what earlier chunks completed
        and letting go what lies behind ``start``'s window."""
        ps = self.cfg.page_size
        self._register_ready(seq, cache_len=start)
        self.release_window_behind(seq, start)
        idx = [
            i for i in range(start // ps, -(-(start + take) // ps))
            if not seq.wpages[i]
        ]
        try:
            got = self.window_allocator.alloc(len(idx))
        except OutOfPages:
            return False
        for i, p in zip(idx, got):
            seq.wpages[i] = p
            self.page_table[1, seq.slot, i] = p
        if idx:
            self.growth_version += 1
        return True

    def _drop_state_hold(self, seq: SeqState) -> None:
        if seq.state_block is not None:
            self.state_slots.release(seq.state_block)
            seq.state_block = None

    def _unmatch_prefix(self, seq: SeqState) -> None:
        self._drop_state_hold(seq)
        for h in seq.held_blocks:
            self.pool.release(h)
        seq.held_blocks = []
        if self.two_kind:
            self._free_window_pages(
                seq, 0, len(seq.wpages) // self.pages_per_block)
            seq.wpages, seq.w_lo = [], 0
        for _h, pages, _blob, _meta in seq.pending_onboard:
            self.allocator.free(pages)
        seq.pending_onboard = []
        seq.cached_prompt_tokens = 0

    def _queue_prompt_registrations(self, seq: SeqState) -> None:
        """Prompt blocks beyond the reused prefix register once prefill's KV
        writes are committed (the catch-up in ``_register_ready``)."""
        if self.pool is None or seq.blocks is None:
            return
        n_reused = seq.cached_prompt_tokens // self.block_size
        n_prompt_blocks = len(seq.prompt) // self.block_size
        seq.pending_register = list(seq.blocks.blocks[n_reused:n_prompt_blocks])

    def _free_slot(self) -> Optional[int]:
        dp = self.cfg.dp_groups
        B = self.cfg.max_batch_size
        if dp <= 1 or B % dp:
            for i, s in enumerate(self.slots):
                if s is None:
                    return i
            return None
        # dp-balanced admission: pick the first free slot of the
        # least-occupied dp group (ties -> lowest group, preserving the
        # deterministic first-free order within a group).  The decode batch
        # shards batch-major over dp, so an unbalanced fill would leave
        # whole chips stepping empty lanes while one group saturates.
        per = B // dp
        best: Optional[int] = None
        best_load = per + 1
        for g in range(dp):
            lanes = self.slots[g * per : (g + 1) * per]
            load = sum(1 for s in lanes if s is not None)
            if load >= per or load >= best_load:
                continue
            best = g * per + next(
                i for i, s in enumerate(lanes) if s is None
            )
            best_load = load
        return best

    def _write_slot_arrays(self, seq: SeqState) -> None:
        b = seq.slot
        self.page_table[..., b, :] = 0
        if self.two_kind:
            self.page_table[0, b, : len(seq.pages)] = seq.pages
            self.page_table[1, b, : len(seq.wpages)] = seq.wpages
        else:
            self.page_table[b, : len(seq.pages)] = seq.pages
        self.seq_lens[b] = len(seq.prompt)
        self.tokens[b] = seq.prompt[-1] if seq.prompt else 0
        self.layout_version += 1
        self.dirty_slots.add(b)

    # -- decode bookkeeping --------------------------------------------------

    def ensure_decode_capacity(
        self, lookahead: int = 1, chunk_pages: int = 0
    ) -> List[SeqState]:
        """Grow page tables so each active sequence can absorb up to
        ``lookahead`` more tokens, never growing past the lane's remaining
        token budget (max_tokens / max_seq_len).  When growth is needed,
        over-allocate by ``chunk_pages`` so the page table (and the device
        copy of it) changes every few blocks instead of every block.

        Growth is best-effort: a lane that cannot reach the full lookahead
        pauses at its allocated capacity (the device-side ``limit_lens`` cap
        keeps it from writing past its pages) and retries next tick.
        Preemption only triggers when a lane lacks room for even one more
        token -- then the youngest lane is evicted (possibly the lane
        itself).  Returns the preempted sequences (moved back to the head of
        the waiting queue, pages freed)."""
        ps = self.cfg.page_size
        preempted: List[SeqState] = []
        for seq in [s for s in self.slots if s is not None]:
            if seq.slot < 0:
                continue  # became a preemption victim earlier this pass
            cache_len = int(self.seq_lens[seq.slot])
            if (
                self.two_kind
                and not seq.prefilling
                and seq.prefilled_tokens >= len(seq.prompt)
            ):
                # a decoding lane (its whole prompt dispatched): a lane
                # admitted this tick still owes its chunks their window
                self.release_window_behind(seq, cache_len)
            budget = max(self.remaining_budget(seq), 1)
            # max cache length the lane can ever use (limit_lens semantics:
            # the final token's KV is never read, and position max_seq_len-1
            # is the last writable slot)
            useful = min(cache_len + budget, self.cfg.max_seq_len - 1)
            want_tokens = min(cache_len + lookahead, useful)
            need_tokens = min(cache_len + 1, useful)
            want = min(-(-want_tokens // ps), self.max_pages)
            need = min(-(-need_tokens // ps), self.max_pages)
            if len(seq.pages) < want:
                want = min(want + chunk_pages, -(-useful // ps), self.max_pages)
            while len(seq.pages) < want:
                try:
                    page = self._grow_page(seq)
                except OutOfPages:
                    if len(seq.pages) >= need:
                        break  # best effort met; lane pauses at capacity
                    victim = self._pick_preemption_victim()
                    if victim is None or victim is seq:
                        # cannot make room; preempt this one
                        self._preempt(seq)
                        preempted.append(seq)
                        break
                    self._preempt(victim)
                    preempted.append(victim)
                    continue
                seq.pages.append(page)
                seq.owned_pages.append(page)
                if self.two_kind:
                    self.page_table[:, seq.slot, len(seq.pages) - 1] = (
                        page, seq.wpages[-1])
                else:
                    self.page_table[seq.slot, len(seq.pages) - 1] = page
                self.growth_version += 1
        return preempted

    def _grow_page(self, seq: SeqState) -> int:
        """One more page for a decoding lane; over a two-kind cache one in
        each pool or none (the window pool's first: its page goes on
        ``seq.wpages``, so that the two lists stay one length)."""
        if not self.two_kind:
            return self.allocator.alloc(1)[0]
        wpage = self.window_allocator.alloc(1)[0]
        try:
            page = self.allocator.alloc(1)[0]
        except OutOfPages:
            self.window_allocator.free([wpage])
            raise
        seq.wpages.append(wpage)
        return page

    def _pick_preemption_victim(self) -> Optional[SeqState]:
        """Preempt the most recently arrived active sequence (reference
        vLLM-style recompute preemption favors older requests)."""
        active = [s for s in self.slots if s is not None]
        if not active:
            return None
        return max(active, key=lambda s: s.arrival_s)

    def _preempt(self, seq: SeqState) -> None:
        # swap-based preemption: snapshot the lane's KV (engine hook, must
        # run while the pages are still allocated so the device read is
        # ordered before any reuse) and park the sequence for a restore;
        # recompute -- fold + re-prefill -- remains the fallback whenever
        # the hook declines (tiers full, lane mid-prefill, chaos)
        swapped = False
        if self.swap_out is not None and seq.finish is None:
            try:
                swapped = bool(self.swap_out(seq))
            except Exception:
                import logging

                logging.getLogger("dynamo.offload").exception(
                    "swap-out hook failed for %s; recomputing", seq.request_id
                )
        self._release_slot(seq)
        # fold generated tokens into the prompt so the resume -- whether a
        # KV restore or a re-prefill -- reproduces the full sequence
        # deterministically (stop/penalty accounting shares this bookkeeping)
        seq.prompt = seq.prompt + self._generated_tokens(seq)
        seq.prior_generated += seq.num_generated
        seq.num_generated = 0
        seq.slot = -1
        seq.preempted_at = time.monotonic()
        if swapped:
            # parked exactly like a disagg external lane: holds pages at
            # admission, stays device-inactive until the engine's swap-in
            # delivery clears the barrier (an external lane keeps its own
            # pre-existing awaiting_kv)
            seq.awaiting_kv = True
            self.preempt_swap += 1
        else:
            self.preempt_recompute += 1
        self.waiting.appendleft(seq)

    def _generated_tokens(self, seq: SeqState) -> List[int]:
        if seq.blocks is None:
            return []
        all_tokens = seq.blocks.tokens
        return list(all_tokens[len(seq.prompt) :])

    def _release_slot(self, seq: SeqState) -> None:
        seq.prefilling = False
        seq.prefilled_tokens = 0
        self._drop_state_hold(seq)
        if seq.slot >= 0:
            b = seq.slot
            self.slots[b] = None
            self.page_table[..., b, :] = 0
            self.seq_lens[b] = 0
            self.tokens[b] = 0
            self.layout_version += 1
            self.dirty_slots.add(b)
        if self.two_kind:
            self._free_window_pages(
                seq, 0, -(-len(seq.wpages) // self.pages_per_block))
            seq.wpages, seq.w_lo = [], 0
        # registered blocks outlive the sequence (refcount drops; the block
        # turns inactive-reusable at zero); only exclusively-owned pages and
        # never-registered completions return to the free list
        if self.pool is not None:
            self.allocator.free(seq.owned_pages)
            for h in seq.held_blocks:
                self.pool.release(h)
            seq.held_blocks = []
            seq.pending_register = []
            seq.pending_onboard = []  # pages were owned; freed above
            seq.pages = []
            seq.owned_pages = []
        elif seq.pages:
            self.allocator.free(seq.pages)
            seq.pages = []
            seq.owned_pages = []

    # -- per-token postprocessing -------------------------------------------

    def commit_tokens(self, sampled: np.ndarray) -> List[StepEvent]:
        """Apply one decode step's sampled tokens [B]; returns per-seq events.

        Stop-condition semantics follow the reference backend jail
        (lib/llm/src/backend.rs): eos finishes unless ignore_eos; hidden stop
        token ids finish without emitting the token.
        """
        events: List[StepEvent] = []
        for b, seq in enumerate(self.slots):
            if seq is None:
                continue
            token = int(sampled[b])
            ev = self._commit_token(seq, token)
            events.append(ev)
            if ev.finished is not None:
                seq.finish = ev.finished
                self._release_slot(seq)
        return events

    def _commit_lane_column(
        self,
        seq: SeqState,
        column: np.ndarray,
        lps: Optional[np.ndarray] = None,  # [K] chosen-token logprobs
        top_ids: Optional[np.ndarray] = None,  # [K, N]
        top_lps: Optional[np.ndarray] = None,  # [K, N]
    ) -> StepEvent:
        """Commit one lane's K sampled tokens as a single coalesced event.

        Host-side replay of the device loop for one lane: per token the
        exact stop-condition rules run (``_commit_token``); ``-1`` marks a
        step the device already knew was dead.  Once the lane finishes, the
        rest of the column was speculative decode and is discarded."""
        tokens: List[int] = []
        blocks: List[TokenBlock] = []
        logprobs: List[float] = []
        tops: Optional[List[List[List[float]]]] = (
            [] if top_ids is not None else None
        )
        finished: Optional[FinishReason] = None
        for k, raw in enumerate(column.tolist()):
            if raw < 0:
                continue
            ev = self._commit_token(seq, raw)
            if ev.tokens:
                tokens.extend(ev.tokens)
                if lps is not None:
                    logprobs.append(float(lps[k]))
                if tops is not None:
                    tops.append(
                        [
                            [int(i), float(l)]
                            for i, l in zip(top_ids[k], top_lps[k])
                        ]
                    )
            blocks.extend(ev.completed_blocks)
            if ev.finished is not None:
                finished = ev.finished
                break
        return StepEvent(
            seq=seq, tokens=tokens, finished=finished, completed_blocks=blocks,
            logprobs=logprobs, top_logprobs=tops,
        )

    def commit_block(
        self,
        sampled: np.ndarray,
        slot_snapshot: Optional[List[Optional[SeqState]]] = None,
        lps: Optional[np.ndarray] = None,  # [B, K] chosen-token logprobs
        top_ids: Optional[np.ndarray] = None,  # [B, K, N]
        top_lps: Optional[np.ndarray] = None,  # [B, K, N]
    ) -> List[StepEvent]:
        """Apply a device-decoded block of raw sampled tokens [B, K].

        Each live lane's column commits through ``_commit_lane_column``,
        which replays the device stop rules token by token but returns ONE
        coalesced event for the block -- the per-event downstream cost
        (queue put, consumer wakeup, SSE frame) is paid per block per lane,
        not per token, which is what keeps large-batch decode off the host's
        critical path.

        ``slot_snapshot`` is the slot list captured when the block was
        dispatched -- with pipelined blocks a slot may have been released (or
        even re-assigned) since, and those lanes' tokens must not be
        attributed to the new occupant.
        """
        events: List[StepEvent] = []
        B, K = sampled.shape
        slots_at_entry = (
            list(slot_snapshot) if slot_snapshot is not None else list(self.slots)
        )
        for b in range(B):
            seq = slots_at_entry[b]
            if seq is None or seq.finish is not None or seq.slot != b:
                continue
            if seq.prefilling or seq.awaiting_kv:
                # a parked lane's column is placeholder garbage by
                # construction (the lane is device-inactive, rows are -1);
                # a lane re-parked since the dispatch (preempt + re-admit
                # into the same slot) must not have stale columns
                # attributed to its new life
                continue
            ev = self._commit_lane_column(
                seq, sampled[b],
                lps[b] if lps is not None else None,
                top_ids[b] if top_ids is not None else None,
                top_lps[b] if top_lps is not None else None,
            )
            if ev.finished is not None:
                seq.finish = ev.finished
                self._release_slot(seq)
            if ev.tokens or ev.finished is not None:
                events.append(ev)
        return events

    def commit_prefill_token(
        self,
        seq: SeqState,
        token: int,
        logprob: Optional[float] = None,
        top: Optional[List[List[float]]] = None,
    ) -> StepEvent:
        """Apply the first token sampled from prefill logits."""
        ev = self._commit_token(seq, token)
        if ev.tokens:
            if logprob is not None:
                ev.logprobs = [logprob]
            if top is not None:
                ev.top_logprobs = [top]
        if ev.finished is not None:
            seq.finish = ev.finished
            self._release_slot(seq)
        return ev

    def _commit_token(self, seq: SeqState, token: int) -> StepEvent:
        stop = seq.stop
        # total tokens streamed to the client, across preemptions
        n_gen = seq.prior_generated + seq.num_generated + 1

        hidden_stop = stop.stop_token_ids_hidden or []
        is_eos = token in seq.eos_ids
        min_ok = stop.min_tokens is None or n_gen >= stop.min_tokens

        if token in hidden_stop and min_ok:
            return StepEvent(seq=seq, finished=FinishReason.STOP)
        if is_eos and not stop.ignore_eos and min_ok:
            return StepEvent(seq=seq, finished=FinishReason.EOS)

        seq.num_generated += 1
        completed: List[TokenBlock] = []
        if seq.blocks is not None:
            blk = seq.blocks.append(token)
            if blk is not None:
                completed.append(blk)
        b = seq.slot
        self.tokens[b] = token
        # seq_lens mirrors the *cache* length: the KV of the newest token is
        # written by the upcoming decode step at exactly this position
        # (decode_step positions = seq_lens).
        self.seq_lens[b] = seq.seq_len - 1
        if self.pool is not None:
            seq.pending_register.extend(completed)
            self._register_ready(seq)

        finished: Optional[FinishReason] = None
        if stop.max_tokens is not None and n_gen >= stop.max_tokens:
            finished = FinishReason.LENGTH
        elif seq.seq_len >= self.cfg.max_seq_len:
            finished = FinishReason.LENGTH
        return StepEvent(
            seq=seq, tokens=[token], finished=finished, completed_blocks=completed
        )

    def _register_ready(
        self, seq: SeqState, cache_len: Optional[int] = None
    ) -> None:
        """Register completed blocks whose KV is fully written.

        A block ending at token position ``end`` is committable once the
        cache length reaches ``end``: the decode step that consumed the
        block's final token wrote its KV (commit implies the write was
        dispatched, and the device executes dispatches in order, so any
        later prefill that reuses the block reads it complete).  Over a
        two-kind cache a prefilling lane registers what its dispatched
        chunks completed (``cache_len``: the tokens dispatched), before its
        window pages fall behind the window, and each block goes into both
        pools: the window pool's copy is what lets a later hit resume at
        the block's boundary.
        """
        if cache_len is None:
            cache_len = int(self.seq_lens[seq.slot])
        ppb = self.pages_per_block
        while seq.pending_register:
            blk = seq.pending_register[0]
            end = (blk.position + 1) * self.block_size
            if end > cache_len:
                break
            seq.pending_register.pop(0)
            start = blk.position * ppb
            pages = seq.pages[start : start + ppb]
            if len(pages) < ppb:
                break  # table shorter than the block span (defensive)
            if self.pool.register(
                blk.sequence_hash,
                pages,
                block_hash=blk.block_hash,
                parent_sequence_hash=blk.parent_sequence_hash,
                position=blk.position,
            ):
                # ownership moves to the registry; this seq keeps a ref
                seq.held_blocks.append(blk.sequence_hash)
                for p in pages:
                    seq.owned_pages.remove(p)
            wpages = seq.wpages[start : start + ppb]
            if (
                self.wpool is not None
                and len(wpages) == ppb
                and all(wpages)
                and blk.position not in seq.w_held
                and self.wpool.register(blk.sequence_hash, wpages)
            ):
                seq.w_held[blk.position] = blk.sequence_hash
            # register() == False: identical block already registered by a
            # concurrent twin; keep plain ownership of our duplicate pages

    def cancel(self, seq: SeqState) -> None:
        if seq.slot >= 0:
            self._release_slot(seq)
        elif seq in self.waiting:
            self.waiting.remove(seq)
        seq.finish = FinishReason.CANCELLED
