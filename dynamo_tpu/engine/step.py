"""Jitted engine steps: prefill, decode, sample.

Everything under jit runs with static shapes; variability is absorbed by

- **prefill length buckets** (powers of two, multiples of page_size),
- a **fixed-capacity decode batch** (inactive lanes attend to nothing and
  scatter to the trash page),
- per-request sampling settings as arrays.

The KV buffer is donated on every step so XLA aliases it in place -- the
cache never copies.  Compiled executables are cached per entry shape, so the
first request in a bucket pays compile cost once (persistent compilation
cache applies across processes).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as att
from .config import ModelConfig
from .model import (
    Params,
    lm_logits,
    moe_counts_reached,
    moe_layout,
    transformer,
)
from .sampling import (
    PROMPT_FLAG,
    SamplingParams,
    apply_penalties,
    pack_sampled_logprobs,
    sample_tokens,
    token_logprobs,
)


def _prompt_penalized_logits(
    logits: jax.Array,  # [B, V]
    tokens: jax.Array,  # [B, T] the tokens this dispatch carries
    seq_lens: jax.Array,  # [B] valid lengths
    sampling: SamplingParams,
) -> jax.Array:
    """Repetition-penalize first-token logits over the dispatch's own
    prompt tokens (HF semantics penalize the prompt from the very first
    sample; frequency/presence are output-only and out_count stays 0
    here, so the shared apply_penalties call leaves them inert).  A
    suffix-prefill dispatch carries only the suffix, so a cached prefix
    is not penalized for this ONE token -- the decode histogram covers
    every later step exactly."""
    B, T = tokens.shape
    valid = (jnp.arange(T)[None, :] < seq_lens[:, None]).astype(jnp.int32)
    seen = jnp.zeros(logits.shape, jnp.int32).at[
        jnp.arange(B)[:, None], tokens
    ].add(valid * PROMPT_FLAG, mode="drop")
    return apply_penalties(
        logits, seen, sampling.freq, sampling.pres, sampling.rep
    )


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("kv_pages",))
def prefill_step(
    params: Params,
    cfg: ModelConfig,
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    tokens: jax.Array,  # [B, T] bucket-padded prompts
    seq_lens: jax.Array,  # [B] true prompt lengths (0 = inactive lane)
    page_table: jax.Array,  # [B, P]
) -> Tuple[jax.Array, jax.Array]:
    """Run full prompts, write their KV pages, return last-token logits.

    Returns (logits [B, V] f32, updated kv_pages).
    """
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def attn_fn(q, k, v, kv, layer, kind=None):
        lv = att.layer_view(cfg, kv, page_table, layer, kind)
        out = att.prefill_attention_dispatch(q, k, v, seq_lens, lv.window)
        new_kv = att.write_prefill_kv(lv.kv, k, v, lv.table, lv.layer)
        return out, lv.put(new_kv)

    hidden, kv_pages = transformer(params, cfg, tokens, positions, kv_pages, attn_fn)
    last = jnp.clip(seq_lens - 1, 0, T - 1)
    hidden_last = jnp.take_along_axis(hidden, last[:, None, None], axis=1)[:, 0]
    return lm_logits(params, cfg, hidden_last), kv_pages


def _state_fn(cfg: ModelConfig, conv_fn, delta_fn):
    """The callback of a trunk's layers that hold state, by their kind."""
    return {"conv": conv_fn, "linear": delta_fn}.get(cfg.state_kind)


def _decode_once(
    params: Params,
    cfg: ModelConfig,
    kv_pages: jax.Array,
    tokens: jax.Array,  # [B] last sampled token per slot
    seq_lens: jax.Array,  # [B] tokens already in cache (new token's position)
    page_table: jax.Array,  # [B, P]
    active: Optional[jax.Array] = None,  # [B] bool: lanes the step advances;
    # read by a trunk's convolution layers (a frozen lane's K/V write
    # repeats itself, a shift of its convolution state would not) and by
    # the expert MLP's grouped layout, which reads no expert for a lane
    # that has stopped (nobody reads such a lane's result)
    count_reached: bool = False,
) -> Tuple[jax.Array, ...]:
    """One unjitted decode step.  Returns (logits [B,V], kv), and with
    ``count_reached`` the experts its grouped expert MLPs read and held
    (``model.transformer``)."""
    positions = seq_lens.astype(jnp.int32)  # new token position (0-indexed)
    # the buffers take no mask: the other layouts' steps stay as they were
    grouped = moe_layout(params, cfg, tokens.shape[0]) == "grouped"

    def conv_fn(z, taps, kv, layer):
        if active is None:
            from .kv_cache import refuse

            refuse(cfg, "unmasked_decode_step")
        out, kv = att.decode_conv_mix(
            z[:, 0], taps, kv, layer, page_table, positions, active
        )
        return out[:, None], kv

    def delta_fn(u, taps, g, beta, kv, layer):
        if active is None:
            from .kv_cache import refuse

            refuse(cfg, "unmasked_decode_step")
        out, kv = att.decode_delta_mix(
            cfg, u[:, 0], taps, g[:, 0], beta[:, 0], kv, layer, active
        )
        return out[:, None], kv

    def attn_fn(q, k, v, kv, layer, kind=None):
        # q/k/v arrive [B, 1, H, D]; squeeze the singleton time axis.
        q1, k1, v1 = q[:, 0], k[:, 0], v[:, 0]
        lv = att.layer_view(cfg, kv, page_table, layer, kind)
        new_kv = att.write_decode_kv(
            lv.kv, k1, v1, lv.table, positions, lv.layer
        )
        out = att.decode_attention_dispatch(
            q1, new_kv, lv.table, positions + 1, lv.layer, lv.window,
            lv.suffix,
        )
        return out[:, None], lv.put(new_kv)

    hidden, kv_pages, *reach = transformer(
        params, cfg, tokens, positions, kv_pages, attn_fn,
        row_valid=active if grouped else None,
        conv_fn=_state_fn(cfg, conv_fn, delta_fn),
        count_reached=count_reached,
    )
    return (lm_logits(params, cfg, hidden), kv_pages, *reach)


decode_step = partial(jax.jit, static_argnames=("cfg",), donate_argnames=("kv_pages",))(
    _decode_once
)


def _decode_block(
    params: Params,
    cfg: ModelConfig,
    kv_pages: jax.Array,
    tokens: jax.Array,  # [B] last committed token per lane
    seq_lens: jax.Array,  # [B] cache length (position of the incoming token)
    limit_lens: jax.Array,  # [B] cache length at which a lane must stop
    active: jax.Array,  # [B] bool
    stop_ids: jax.Array,  # [B, E] device-checked stop tokens (-1 = pad)
    page_table: jax.Array,  # [B, P] (pre-grown for num_steps of growth)
    rng: jax.Array,
    sampling: SamplingParams,
    num_steps: int,
    use_filters: bool = True,
    top_n: int = 0,
    counts: jax.Array = None,  # [B, V] i32 generated-token histograms
    use_penalties: bool = False,
) -> Tuple[jax.Array, ...]:
    """Run ``num_steps`` decode+sample iterations entirely on device.

    The TPU-native decode loop: ONE host dispatch and ONE device->host
    transfer per K tokens instead of per token -- decode state (last token,
    cache lengths, active mask) lives on device between blocks; the host
    only intervenes when batch membership changes (admission / completion /
    page growth).

    Lanes self-deactivate on device when they sample a ``stop_ids`` token or
    reach ``limit_lens``; the host re-derives the authoritative stop reason
    from the raw sampled matrix with the exact same rules (scheduler
    ``_commit_token``), so device masking is purely an optimization that
    stops dead lanes from burning HBM bandwidth.

    Returns ``(packed [B, num_steps, 2 + 2*top_n], tokens, seq_lens,
    active, kv_pages, rng)``: packed rows carry (raw token | chosen
    logprob | top-N ids | top-N logprobs) per sampling.pack_sampled_logprobs
    -- one int32 array, one device->host transfer, logprobs always
    available (token at [..., 0] is ``-1`` for lanes the device already
    knew were dead).  Everything except ``packed`` stays device-resident
    for the next block.
    """

    if counts is None:
        # dummy carry so the scan signature is stable; never read
        counts = jnp.zeros((tokens.shape[0], 1), jnp.int32)

    def live_step(carry):
        tokens, seq_lens, active, rng, kv, counts = carry
        logits, kv = _decode_once(
            params, cfg, kv, tokens, seq_lens, page_table, active
        )
        rng, sub = jax.random.split(rng)
        if use_penalties:
            # frequency/presence over the lane's generated-token histogram
            # (raw logits; sample_tokens applies temperature after)
            logits_s = apply_penalties(
                logits, counts, sampling.freq, sampling.pres, sampling.rep
            )
        else:
            logits_s = logits
        # seeded lanes key their noise by the position being FILLED
        # (seq_lens + 1): distinct from the prefill-sampled first token's
        # key (= prompt length) and from every other step of the request
        sampled = sample_tokens(
            logits_s, sub, sampling, use_filters, positions=seq_lens + 1
        )
        # logprobs report the RAW model distribution (protocol contract),
        # penalties included only in what gets sampled
        lp, top_ids, top_lps = token_logprobs(logits, sampled, top_n)
        hit_stop = jnp.any(sampled[:, None] == stop_ids, axis=1)
        emit = active & ~hit_stop  # stop tokens are swallowed, not emitted
        new_seq = seq_lens + emit.astype(jnp.int32)
        new_active = emit & (new_seq < limit_lens)
        new_tokens = jnp.where(emit, sampled, tokens)
        out = jnp.where(active, sampled, -1)  # -1 = lane was already dead
        packed = pack_sampled_logprobs(out, lp, top_ids, top_lps)
        if use_penalties:
            B = tokens.shape[0]
            counts = counts.at[jnp.arange(B), sampled].add(
                emit.astype(jnp.int32), mode="drop"
            )
        return (new_tokens, new_seq, new_active, rng, kv, counts), packed

    def dead_step(carry):
        # every lane is dead: skip the weight stream entirely.  Tail steps
        # after the last lane finishes (and speculative blocks dispatched
        # while a short request's commit is still in flight) would otherwise
        # each pay a full per-step weight read for no output.
        B = carry[0].shape[0]
        packed = jnp.full((B, 2 + 2 * top_n), -1, jnp.int32)
        return carry, packed

    def body(carry, _):
        active = carry[2]
        return jax.lax.cond(jnp.any(active), live_step, dead_step, carry)

    (tokens, seq_lens, active, rng, kv_pages, counts), packed = jax.lax.scan(
        body, (tokens, seq_lens, active, rng, kv_pages, counts), None,
        length=num_steps,
    )
    return (
        packed.transpose(1, 0, 2), tokens, seq_lens, active, kv_pages, rng,
        counts,
    )


# the serving entry point: the raw implementation re-jits with explicit
# in/out shardings for multichip meshes (parallel.sharding.make_sharded_steps)
decode_block = partial(
    jax.jit,
    static_argnames=("cfg", "num_steps", "use_filters", "top_n",
                     "use_penalties"),
    donate_argnames=("kv_pages", "counts"),
)(_decode_block)


def _verify_and_sample(
    params: Params,
    cfg: ModelConfig,
    kv_pages: jax.Array,
    tokens: jax.Array,  # [B, S]: last committed token | draft columns (padded)
    base: jax.Array,  # [B] cache length; column j sits at position base + j
    n_tokens: jax.Array,  # [B] valid columns (1 + draft len; 0 = inactive)
    page_table: jax.Array,  # [B, P] (bucketed)
    rng: jax.Array,
    sampling: SamplingParams,
    top_n: int = 0,
    use_filters: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Batched multi-token verify: score every speculating lane's draft
    columns in ONE forward pass and sample the target token at every
    position.

    Column j carries (for j=0) the lane's last committed token and (j>0)
    draft token j; its KV lands at position ``base + j`` and its logits
    sample the token for position ``base + j + 1`` -- the exact
    position-keying of the decode scan (``decode_block``: a step at cache
    length q samples with ``positions = q + 1``), so greedy and seeded
    lanes produce bit-identical tokens to plain decode.  The host accept
    walk (engine ``_commit_all``) keeps the longest prefix where draft j
    equals the sampled target j-1, plus the bonus token at the first
    mismatch; the rest of the column is speculative garbage the next
    step's writes overwrite.

    Attention reuses the prefix-suffix dispatch: the resident cache
    (positions < base, token-granular mask, no page alignment needed) is
    the prefix; the S fresh columns attend causally among themselves.

    Returns (packed [B, S, 2 + 2*top_n], kv_pages) -- one int32 transfer
    carrying token | logprob | top-N per column (pack_sampled_logprobs
    layout shared with every other sampling site).
    """
    B, S = tokens.shape
    positions = base[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]

    def attn_fn(q, k, v, kv, layer, kind=None):
        lv = att.layer_view(cfg, kv, page_table, layer, kind)
        out = att.prefill_prefix_attention_dispatch(
            q, k, v, lv.kv, lv.layer, lv.table, base, n_tokens, lv.window,
        )
        new_kv = att.write_spec_kv(
            lv.kv, k, v, lv.table, base, n_tokens, lv.layer
        )
        return out, lv.put(new_kv)

    hidden, kv_pages = transformer(
        params, cfg, tokens, positions, kv_pages, attn_fn
    )
    logits = lm_logits(params, cfg, hidden)  # [B, S, V]
    subs = jax.random.split(rng, S)
    cols = []
    for j in range(S):  # S <= 1 + MAX_DRAFT_TOKENS: unrolled, tiny
        lj = logits[:, j]
        sampled = sample_tokens(
            lj, subs[j], sampling, use_filters, positions=base + 1 + j
        )
        lp, top_ids, top_lps = token_logprobs(lj, sampled, top_n)
        cols.append(pack_sampled_logprobs(sampled, lp, top_ids, top_lps))
    return jnp.stack(cols, axis=1), kv_pages


verify_and_sample = partial(
    jax.jit,
    static_argnames=("cfg", "top_n", "use_filters"),
    donate_argnames=("kv_pages",),
)(_verify_and_sample)


def _mixed_sample_epilogue(
    logits: jax.Array,  # [B, V] last-row logits per lane
    base: jax.Array,  # [B]
    q_lens: jax.Array,  # [B]
    is_pf: jax.Array,  # [B] bool
    p_start: jax.Array,  # [B]
    p_lens: jax.Array,  # [B]
    p_sample: jax.Array,  # [B] bool
    p_activate: jax.Array,  # [B] bool
    tokens: jax.Array,  # [B]
    seq_lens: jax.Array,  # [B]
    limit_lens: jax.Array,  # [B]
    active: jax.Array,  # [B] bool
    stop_ids: jax.Array,  # [B, E]
    rng: jax.Array,
    sampling: SamplingParams,
    top_n: int,
    use_filters: bool,
) -> Tuple[jax.Array, ...]:
    """The packed unified step's epilogue: sampling + device bookkeeping
    over per-lane last-row logits.

    Mirrors ``decode_block``'s live_step for decode lanes and the inject
    path for final-chunk lanes (host replay at commit re-derives the
    authoritative stop reason from ``packed``).  A final chunk hands the
    lane to decode with the SAME state the classic path's admission
    mirror + inject would produce: cache length = prompt length (the
    sampled token's KV lands at exactly that position on the next decode
    step), last token = the sample."""
    rng, sub = jax.random.split(rng)
    sampled = sample_tokens(
        logits, sub, sampling, use_filters, positions=base + q_lens
    )
    lp, top_ids, top_lps = token_logprobs(logits, sampled, top_n)
    final_pf = is_pf & p_sample
    live = active | final_pf
    hit_stop = jnp.any(sampled[:, None] == stop_ids, axis=1)
    emit = live & ~hit_stop
    new_seq = jnp.where(
        final_pf,
        p_start + p_lens,
        seq_lens + (emit & ~is_pf).astype(jnp.int32),
    )
    new_active = emit & (new_seq < limit_lens) & (~final_pf | p_activate)
    new_tokens = jnp.where(emit, sampled, tokens)
    out = jnp.where(live, sampled, -1)
    packed = pack_sampled_logprobs(out, lp, top_ids, top_lps)
    return packed, new_tokens, new_seq, new_active, rng


def _spec_columns_epilogue(
    params: Params,
    cfg: ModelConfig,
    hidden: jax.Array,  # [Np, H] packed trunk output
    base: jax.Array,  # [B] committed cache length per lane
    seg_off: jax.Array,  # [B] lane's segment offset into the packed axis
    v_lens: jax.Array,  # [B] verify columns per lane (0 = not speculating)
    rng: jax.Array,
    sampling: SamplingParams,
    s_spec: int,  # static column width (1 + pow2(draft), budget-merged)
    top_n: int,
    use_filters: bool,
) -> jax.Array:
    """Folded-verify sampling: the per-column half of
    :func:`_verify_and_sample` over the packed layout.

    Column ``j`` of a speculating lane sits at packed row ``seg_off + j``
    (its KV landed at ``base + j`` via the shared packed write) and its
    logits sample the target token for position ``base + j + 1`` -- the
    exact position-keying of the standalone verify step and the decode
    scan, so greedy and seeded lanes are bit-identical to the
    two-dispatch path.  All ``B x s_spec`` columns sample in ONE
    vectorized call (sampling params repeat per column; per-request
    seeded noise is a pure function of (seed, position), so column
    batching cannot perturb it).  Invalid columns (j >= v_lens,
    non-speculating lanes) report token ``-1``.

    Returns packed [B, s_spec, 2 + 2*top_n] int32."""
    B = base.shape[0]
    Np = hidden.shape[0]
    cols = jnp.arange(s_spec, dtype=jnp.int32)
    idx = jnp.clip(seg_off[:, None] + cols[None, :], 0, Np - 1)  # [B, S]
    rows = hidden[idx.reshape(-1)]  # [B*S, H]
    logits = lm_logits(params, cfg, rows)  # [B*S, V]
    positions = (base[:, None] + 1 + cols[None, :]).reshape(-1)
    tiled = SamplingParams(
        *(
            jnp.repeat(leaf, s_spec, axis=0) if leaf is not None else None
            for leaf in sampling
        )
    )
    sampled = sample_tokens(logits, rng, tiled, use_filters, positions=positions)
    lp, top_ids, top_lps = token_logprobs(logits, sampled, top_n)
    valid = (cols[None, :] < v_lens[:, None]).reshape(-1)
    out = jnp.where(valid, sampled, -1)
    return pack_sampled_logprobs(out, lp, top_ids, top_lps).reshape(
        B, s_spec, -1
    )


def _packed_unified_step(
    params: Params,
    cfg: ModelConfig,
    kv_pages: jax.Array,
    tokens: jax.Array,  # [B] device-resident last committed token per lane
    seq_lens: jax.Array,  # [B] cache length (next decode write position)
    limit_lens: jax.Array,  # [B] cache length at which a lane must stop
    active: jax.Array,  # [B] bool: decode lanes the scan would step
    stop_ids: jax.Array,  # [B, E] device-checked stop tokens (-1 = pad)
    page_table: jax.Array,  # [B, P] (bucketed)
    t_tokens: jax.Array,  # [Np] packed fresh tokens (prefill chunk rows,
    # and a speculating lane's last-committed token + draft columns)
    t_lane: jax.Array,  # [Np] lane per packed token (B = padding)
    t_rel: jax.Array,  # [Np] row index within the lane's segment
    t_dec: jax.Array,  # [Np] bool: row carries a decode lane's query (its
    # token is read from the device-resident ``tokens`` vector)
    p_start: jax.Array,  # [B] chunk start position (0 on decode lanes;
    # the committed cache length on speculating lanes -- host mirrors are
    # authoritative for them, exactly like the standalone verify step)
    p_lens: jax.Array,  # [B] chunk length; 0 = decode / spec / idle lane
    p_sample: jax.Array,  # [B] bool: final chunk -> sample first token
    p_activate: jax.Array,  # [B] bool: final chunk also joins decode
    dec_cap: jax.Array,  # [B] bool: host packed a decode row for the lane
    seg_off: jax.Array,  # [B] lane's segment offset into the packed axis
    v_lens: jax.Array,  # [B] folded-verify columns (1 + draft len; 0 =
    # lane not speculating this dispatch)
    rng: jax.Array,
    sampling: SamplingParams,
    s_max: int,  # static per-lane window capacity: pow2 of the longest
    # segment where the launch reads windows, maybe less where it walks
    # items (bucketing.PackedShapeBudget's contract); attention alone reads it
    s_spec: int = 0,  # static folded-verify column width (0 = spec-free
    # dispatch: the program is exactly the pre-fold one, no spec sampler
    # and no extra rng split, so spec-free serving compiles and runs the
    # identical executable it always did)
    top_n: int = 0,
    use_filters: bool = True,
) -> Tuple[jax.Array, ...]:
    """ONE ragged mixed prefill+decode dispatch over the whole batch, on a
    flat ``[Np]`` token axis (ISSUE 10 + folded verify, ISSUE 15).

    The continuous-batching step (ROADMAP item 2, *Ragged Paged
    Attention*): decode lanes contribute one query row, chunked-prefill
    lanes their chunk's rows, speculating lanes their verify columns --
    all served by a single attention dispatch per layer, so an admitted
    prompt never stalls the decode batch behind a separate prefill
    launch.  The trunk runs exactly the packed rows -- ``Np = pow2(total
    fresh tokens)``, never every lane padded to the longest chunk -- and
    resolves each row's lane through ``t_lane`` / ``seg_off``.  Segments
    pack contiguously in slot order; a decode lane's token is read from
    the device-resident ``tokens`` vector on device (``t_dec``), so host
    assembly never waits on an uncommitted step and steps pipeline
    without a host round trip.  A decode lane that self-deactivated on
    device masks its row to the trash page.

    Per-lane geometry: row ``j`` of lane ``b`` sits at absolute position
    ``base[b] + j`` where ``base`` is ``p_start`` for prefill (and
    speculating) lanes and ``seq_lens`` for decode lanes.  Sampling keys
    positions exactly like the classic paths -- ``base + q_len`` is
    ``seq_lens + 1`` for a decode lane (the decode-scan identity) and the
    prompt length for a final prefill chunk (the prefill-sample identity)
    -- so greedy and seeded lanes are token-identical to them.  Decode
    lanes replay ``decode_block``'s one-step update on device (stop-token
    swallow, limit deactivation) so the next pipelined dispatch sees
    consistent state; final-chunk lanes fold their sampled first token
    into the decode state the way ``inject_token`` would; intermediate
    chunks write KV only (:func:`_mixed_sample_epilogue`).  The host
    replay at commit stays authoritative for all stop rules.

    A speculating lane (``v_lens > 0``) contributes ``1 + draft`` rows:
    row 0 its last committed token, rows 1.. the host-proposed drafts.
    Attention (resident prefix ``< base`` + causal fresh rows) and the
    token-granular KV scatter are the SAME packed calls every other
    segment takes -- verify columns stopped being a dispatch and became
    a layout.  Their per-column target samples come from
    :func:`_spec_columns_epilogue` and commit through the host accept
    walk; the single-token epilogue ignores them (``active`` is False
    and ``p_lens`` is 0 on spec lanes, so ``live`` never fires).

    Returns ``(packed [B, 2 + 2*top_n], spec_packed [B, s_spec, 2 +
    2*top_n], tokens, seq_lens, active, kv_pages, rng)``: packed rows
    carry (raw token | logprob | tops), the token ``-1`` for lanes that
    sampled nothing (idle, mid-chunk); ``spec_packed`` is zero-width when
    ``s_spec == 0``.  A step of so few rows that its expert MLPs read only
    the experts a row reaches (``model.moe_counts_reached``, asked at trace
    time) returns an eighth: ``[2] int32``, the experts read and the
    experts held, summed over its layers."""
    B = tokens.shape[0]
    Np = t_tokens.shape[0]
    is_pf = p_lens > 0
    if s_spec > 0:
        is_sp = v_lens > 0
        q_lens = jnp.where(
            is_pf,
            p_lens,
            jnp.where(is_sp, v_lens, (dec_cap & active).astype(jnp.int32)),
        )
        base = jnp.where(is_pf | is_sp, p_start, seq_lens).astype(jnp.int32)
    else:
        q_lens = jnp.where(is_pf, p_lens, (dec_cap & active).astype(jnp.int32))
        base = jnp.where(is_pf, p_start, seq_lens).astype(jnp.int32)
    lane_c = jnp.clip(t_lane, 0, B - 1)
    tok_flat = jnp.where(t_dec, tokens[lane_c], t_tokens)
    pos = base[lane_c] + t_rel
    valid = (t_lane < B) & (t_rel < q_lens[lane_c])
    positions = jnp.where(valid, pos, 0)

    def attn_fn(q, k, v, kv, layer, kind=None):
        if cfg.is_mla:
            out, new_kv = att.latent_packed_attention_dispatch(
                q[0], k[0], kv, layer, page_table, base, seg_off, q_lens,
                t_lane, t_rel, pos, valid, s_max,
            )
            return out[None], new_kv
        # rows first: a dense pool's kernel reads every key from the pool;
        # the other paths read the pool below ``base`` and are none the
        # wiser
        lv = att.layer_view(cfg, kv, page_table, layer, kind)
        new_kv = att.write_packed_kv(
            lv.kv, k[0], v[0], lv.table, t_lane, pos, valid, lv.layer
        )
        out = att.packed_ragged_attention_dispatch(
            q[0], k[0], v[0], new_kv, lv.layer, lv.table, base, seg_off,
            q_lens, t_lane, t_rel, s_max, lv.window, lv.suffix,
        )
        return out[None], lv.put(new_kv)

    def conv_fn(z, taps, kv, layer):
        out, kv = att.packed_conv_mix(
            z[0], taps, kv, layer, page_table, base, seg_off, q_lens, t_lane,
            pos, valid,
        )
        return out[None], kv

    def delta_fn(u, taps, g, beta, kv, layer):
        out, kv = att.packed_delta_mix(
            cfg, u[0], taps, g[0], beta[0], kv, layer, base, seg_off, q_lens,
        )
        return out[None], kv

    hidden, kv_pages, *reach = transformer(
        params, cfg, tok_flat[None], positions[None], kv_pages, attn_fn,
        row_valid=valid[None], conv_fn=_state_fn(cfg, conv_fn, delta_fn),
        count_reached=moe_counts_reached(params, cfg, Np),
    )
    if s_spec > 0:
        rng, spec_sub = jax.random.split(rng)
        spec_packed = _spec_columns_epilogue(
            params, cfg, hidden[0], base, seg_off, v_lens, spec_sub,
            sampling, s_spec, top_n, use_filters,
        )
    else:
        spec_packed = jnp.zeros((B, 0, 2 + 2 * top_n), jnp.int32)
    last = jnp.clip(seg_off + q_lens - 1, 0, Np - 1)
    hidden_last = hidden[0, last]  # [B, H]
    logits = lm_logits(params, cfg, hidden_last)  # [B, V]
    packed, new_tokens, new_seq, new_active, rng = _mixed_sample_epilogue(
        logits, base, q_lens, is_pf, p_start, p_lens, p_sample, p_activate,
        tokens, seq_lens, limit_lens, active, stop_ids, rng, sampling,
        top_n, use_filters,
    )
    return (
        packed, spec_packed, new_tokens, new_seq, new_active, kv_pages, rng,
        *reach,
    )


packed_unified_step = partial(
    jax.jit,
    static_argnames=("cfg", "s_max", "s_spec", "top_n", "use_filters"),
    donate_argnames=("kv_pages", "tokens", "seq_lens", "active"),
)(_packed_unified_step)


def _packed_unified_multistep(
    params: Params,
    cfg: ModelConfig,
    kv_pages: jax.Array,
    tokens: jax.Array,  # [B] device-resident last committed token per lane
    seq_lens: jax.Array,  # [B] cache length (next decode write position)
    limit_lens: jax.Array,  # [B] cache length at which a lane must stop
    active: jax.Array,  # [B] bool
    stop_ids: jax.Array,  # [B, E]
    page_table: jax.Array,  # [B, P] (pre-grown for num_steps of growth)
    t_tokens: jax.Array,  # [Np]
    t_lane: jax.Array,  # [Np]
    t_rel: jax.Array,  # [Np]
    t_dec: jax.Array,  # [Np] bool
    p_start: jax.Array,  # [B]
    p_lens: jax.Array,  # [B]
    p_sample: jax.Array,  # [B] bool
    p_activate: jax.Array,  # [B] bool
    dec_cap: jax.Array,  # [B] bool
    seg_off: jax.Array,  # [B]
    v_lens: jax.Array,  # [B]
    rng: jax.Array,
    sampling: SamplingParams,
    s_max: int,
    num_steps: int,
    s_spec: int = 0,
    top_n: int = 0,
    use_filters: bool = True,
) -> Tuple[jax.Array, ...]:
    """``num_steps`` decode iterations through the packed unified path in
    ONE device dispatch (the multi-step decode tentpole): step 0 is the
    full :func:`_packed_unified_step`, steps 1..K-1 scan
    :func:`_decode_block`'s live/dead decode step over the device-resident
    state the epilogue folded -- on-device sampling, per-step KV append
    through the paged pool, stop-flag detection -- so the host syncs one
    ``[B, K, 2 + 2*top_n]`` packed block per K tokens and replays the
    authoritative stop rules at commit (``Scheduler.commit_block``),
    exactly like the classic ``decode_block``.

    rng identity: step 0 splits exactly like a lone packed dispatch and
    each scan step splits once, matching K sequential single-step
    dispatches key-for-key -- greedy, seeded, AND unseeded-temperature
    lanes are token-identical to K=1 (asserted in tier-1).

    Frozen lanes (dead, speculating, mid-chunk) re-write the KV their
    device row already describes: KV at a position is a pure function of
    (token, position, committed prefix), so the repeated stale write is
    idempotent -- the same argument that makes ``decode_block``'s masked
    dead lanes safe.  Lanes past their page allocation self-pause via
    ``limit_lens`` before the table runs out (the engine pre-grows
    ``num_steps`` tokens of lookahead).

    The engine dispatches ``num_steps > 1`` only on chunk-free, spec-free
    ticks (the adaptive-K controller collapses to 1 under prefill or
    speculation pressure), but the scan is correct for any dispatch: a
    final-chunk lane activated by step 0's epilogue keeps decoding inside
    the block, which is how post-prefill lanes ride multi-step.

    Returns the :func:`_packed_unified_step` contract with ``packed``
    widened to ``[B, num_steps, 2 + 2*top_n]`` (row 0 = step 0; ``-1``
    tokens mark steps a lane was already dead for); the count of experts
    read and held, where either part returns one, is the sum over the
    steps that ran."""
    packed0, spec_packed, tokens, seq_lens, active, kv_pages, rng, *reach = (
        _packed_unified_step(
            params, cfg, kv_pages, tokens, seq_lens, limit_lens, active,
            stop_ids, page_table, t_tokens, t_lane, t_rel, t_dec, p_start,
            p_lens, p_sample, p_activate, dec_cap, seg_off, v_lens, rng,
            sampling, s_max, s_spec, top_n, use_filters,
        )
    )
    count_tail = moe_counts_reached(params, cfg, tokens.shape[0])
    if count_tail and not reach:
        reach = [jnp.zeros((2,), jnp.int32)]

    def live_step(carry):
        tokens, seq_lens, active, rng, kv, *reach = carry
        logits, kv, *step_reach = _decode_once(
            params, cfg, kv, tokens, seq_lens, page_table, active, count_tail
        )
        if count_tail:
            reach = [reach[0] + step_reach[0]]
        rng, sub = jax.random.split(rng)
        sampled = sample_tokens(
            logits, sub, sampling, use_filters, positions=seq_lens + 1
        )
        lp, top_ids, top_lps = token_logprobs(logits, sampled, top_n)
        hit_stop = jnp.any(sampled[:, None] == stop_ids, axis=1)
        emit = active & ~hit_stop
        new_seq = seq_lens + emit.astype(jnp.int32)
        new_active = emit & (new_seq < limit_lens)
        new_tokens = jnp.where(emit, sampled, tokens)
        out = jnp.where(active, sampled, -1)
        packed = pack_sampled_logprobs(out, lp, top_ids, top_lps)
        return (new_tokens, new_seq, new_active, rng, kv, *reach), packed

    def dead_step(carry):
        B = carry[0].shape[0]
        packed = jnp.full((B, 2 + 2 * top_n), -1, jnp.int32)
        return carry, packed

    def body(carry, _):
        return jax.lax.cond(jnp.any(carry[2]), live_step, dead_step, carry)

    (tokens, seq_lens, active, rng, kv_pages, *reach), tail = jax.lax.scan(
        body, (tokens, seq_lens, active, rng, kv_pages, *reach), None,
        length=num_steps - 1,
    )
    packed = jnp.concatenate(
        [packed0[:, None], tail.transpose(1, 0, 2)], axis=1
    )
    return (
        packed, spec_packed, tokens, seq_lens, active, kv_pages, rng, *reach
    )


packed_unified_multistep = partial(
    jax.jit,
    static_argnames=(
        "cfg", "s_max", "num_steps", "s_spec", "top_n", "use_filters"
    ),
    donate_argnames=("kv_pages", "tokens", "seq_lens", "active"),
)(_packed_unified_multistep)


@partial(jax.jit, static_argnames=("cfg", "top_n"))
def score_prompt_step(
    params: Params,
    cfg: ModelConfig,
    kv_pages: jax.Array,  # read-only: trunk signature, never written
    tokens: jax.Array,  # [B, T] bucket-padded prompt
    seq_lens: jax.Array,  # [B] true prompt length (0 = pad lane)
    top_n: int = 0,
) -> jax.Array:
    """Per-position next-token logprobs over a prompt (echo+logprobs).

    The scoring half of the verify path without the KV writes: run the
    trunk causally, take logits at every position, and report the logprob
    of the token that actually FOLLOWS it (entry j scores prompt token
    j+1; the last entry is meaningless and dropped by the host).  Shares
    :func:`~..sampling.token_logprobs`/``pack_sampled_logprobs`` with the
    verify and decode sites, so all three report the same raw-model
    distribution.  The logits projection runs in position chunks so the
    transient buffer is [B, <=512, V] instead of [B, T, V] -- a
    max_seq_len prompt over a large vocab must not be able to OOM the
    device (and thereby fail the whole batch) from one echo+logprobs
    request.

    Returns packed [B, T, 2 + 2*top_n] int32.
    """
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def attn_fn(q, k, v, kv, layer, kind=None):
        window = att.layer_view(cfg, None, None, layer, kind).window
        return att.prefill_attention_dispatch(q, k, v, seq_lens, window), kv

    hidden, _ = transformer(params, cfg, tokens, positions, kv_pages, attn_fn)
    targets = jnp.roll(tokens, -1, axis=1)  # target[j] = tokens[j + 1]
    chunk = min(T, 512)  # ragged tail chunk handled via logits.shape
    parts = []
    for lo in range(0, T, chunk):
        logits = lm_logits(params, cfg, hidden[:, lo : lo + chunk])
        span = logits.shape[1]
        tgt = targets[:, lo : lo + chunk].reshape(B * span)
        lp, top_ids, top_lps = token_logprobs(
            logits.reshape(B * span, -1), tgt, top_n
        )
        parts.append(
            pack_sampled_logprobs(tgt, lp, top_ids, top_lps).reshape(
                B, span, -1
            )
        )
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


@jax.jit
def sample_step(
    logits: jax.Array, rng: jax.Array, params: SamplingParams
) -> jax.Array:
    return sample_tokens(logits, rng, params)


@partial(jax.jit, static_argnames=("top_n",))
def sample_step_packed(
    logits: jax.Array,
    rng: jax.Array,
    params: SamplingParams,
    top_n: int = 0,
    positions=None,  # [B] i32: step identity for per-request seeds
    sample_logits=None,  # penalized logits to SAMPLE from (logprobs
    # always report the raw model distribution in ``logits``)
) -> jax.Array:
    """Sample + logprob packing: [B, 2 + 2*top_n] int32 (token | chosen
    logprob bits | top ids | top logprob bits) -- the layout every engine
    sampling site shares (sampling.pack_sampled_logprobs)."""
    src = logits if sample_logits is None else sample_logits
    sampled = sample_tokens(src, rng, params, positions=positions)
    lp, top_ids, top_lps = token_logprobs(logits, sampled, top_n)
    return pack_sampled_logprobs(sampled, lp, top_ids, top_lps)


@partial(
    jax.jit, static_argnames=("cfg", "top_n", "use_penalties"),
    donate_argnames=("kv_pages",),
)
def prefill_and_sample(
    params: Params,
    cfg: ModelConfig,
    kv_pages: jax.Array,
    tokens: jax.Array,
    seq_lens: jax.Array,
    page_table: jax.Array,
    rng: jax.Array,
    sampling: SamplingParams,
    top_n: int = 0,
    use_penalties: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Prefill + first-token sampling fused into one dispatch.

    Returns (packed [B, 2 + 2*top_n], kv) -- token at [:, 0], chosen/top
    logprobs bitcast alongside.  The handle stays on device so the first
    token can be injected into the decode state without a host round trip
    (engine._do_prefill)."""
    logits, kv_pages = prefill_step(params, cfg, kv_pages, tokens, seq_lens, page_table)
    pen = (
        _prompt_penalized_logits(logits, tokens, seq_lens, sampling)
        if use_penalties
        else None
    )
    return (
        sample_step_packed(
            logits, rng, sampling, top_n, positions=seq_lens,
            sample_logits=pen,
        ),
        kv_pages,
    )


@partial(
    jax.jit, static_argnames=("cfg", "top_n", "use_penalties"),
    donate_argnames=("kv_pages",),
)
def prefill_mm_and_sample(
    params: Params,
    cfg: ModelConfig,
    kv_pages: jax.Array,
    tokens: jax.Array,  # [B, T]; positions < mm_len[b] are placeholders
    seq_lens: jax.Array,
    page_table: jax.Array,
    mm_embeds: jax.Array,  # [B, M, H] f32 soft-prompt rows
    mm_len: jax.Array,  # [B] rows valid per lane (0 = text-only lane)
    rng: jax.Array,
    sampling: SamplingParams,
    top_n: int = 0,
    use_penalties: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Multimodal prefill: llava-style soft-prompt injection over the first
    ``mm_len`` positions, then the standard causal prefill + sample.  A
    separate executable from :func:`prefill_and_sample` so text-only serving
    never pays the injection (or a recompile) for a feature it doesn't
    use."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def attn_fn(q, k, v, kv, layer, kind=None):
        lv = att.layer_view(cfg, kv, page_table, layer, kind)
        out = att.prefill_attention_dispatch(q, k, v, seq_lens, lv.window)
        new_kv = att.write_prefill_kv(lv.kv, k, v, lv.table, lv.layer)
        return out, lv.put(new_kv)

    hidden, kv_pages = transformer(
        params, cfg, tokens, positions, kv_pages, attn_fn,
        mm=(mm_embeds, mm_len),
    )
    last = jnp.clip(seq_lens - 1, 0, T - 1)
    hidden_last = jnp.take_along_axis(hidden, last[:, None, None], axis=1)[:, 0]
    logits = lm_logits(params, cfg, hidden_last)
    pen = (
        _prompt_penalized_logits(logits, tokens, seq_lens, sampling)
        if use_penalties
        else None
    )
    return (
        sample_step_packed(
            logits, rng, sampling, top_n, positions=seq_lens,
            sample_logits=pen,
        ),
        kv_pages,
    )


@partial(
    jax.jit, static_argnames=("cfg", "top_n", "use_penalties"),
    donate_argnames=("kv_pages",),
)
def prefill_suffix_and_sample(
    params: Params,
    cfg: ModelConfig,
    kv_pages: jax.Array,
    tokens: jax.Array,  # [B, T] bucket-padded suffix tokens
    offset: jax.Array,  # [B] cached prefix length (page-aligned)
    suffix_lens: jax.Array,  # [B] true suffix length
    prefix_table: jax.Array,  # [B, Pp] reused-prefix pages (bucketed, 0-padded)
    suffix_table: jax.Array,  # [B, T//page_size] pages the suffix writes into
    rng: jax.Array,
    sampling: SamplingParams,
    top_n: int = 0,
    use_penalties: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Prefix-cache restart: prefill only the suffix, attending to the
    resident prefix pages; sample the first token (engine-side prefix reuse,
    reference block_manager/pool.rs match + vLLM prefix caching semantics).

    Returns (packed [B, 2 + 2*top_n], kv) -- token at [:, 0]."""
    B, T = tokens.shape
    positions = offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]

    def attn_fn(q, k, v, kv, layer):
        out = att.prefill_prefix_attention_dispatch(
            q, k, v, kv, layer, prefix_table, offset, suffix_lens,
            cfg.sliding_window or 0,
        )
        new_kv = att.write_prefill_kv(kv, k, v, suffix_table, layer)
        return out, new_kv

    hidden, kv_pages = transformer(params, cfg, tokens, positions, kv_pages, attn_fn)
    last = jnp.clip(suffix_lens - 1, 0, T - 1)
    hidden_last = jnp.take_along_axis(hidden, last[:, None, None], axis=1)[:, 0]
    logits = lm_logits(params, cfg, hidden_last)
    pen = (
        _prompt_penalized_logits(logits, tokens, suffix_lens, sampling)
        if use_penalties
        else None
    )
    return (
        sample_step_packed(
            logits, rng, sampling, top_n, positions=offset + suffix_lens,
            sample_logits=pen,
        ),
        kv_pages,
    )


@partial(jax.jit, static_argnames=("cfg",))
def embed_step(
    params: Params,
    cfg: ModelConfig,
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D] -- read-only here
    tokens: jax.Array,  # [B, T] bucket-padded inputs
    seq_lens: jax.Array,  # [B] true input lengths (0 = pad lane)
) -> jax.Array:
    """Pooled-embedding forward: run the trunk, mean-pool the final hidden
    states over valid positions, L2-normalize.  Serves /v1/embeddings
    (reference: http/service/openai.rs:212 delegates to embedding engines;
    here the first-party trunk doubles as the embedder).  KV is passed only
    to satisfy the trunk signature -- the attn callback never writes, no
    pages are allocated, and the returned buffer is discarded (NOT donated).

    Returns [B, H] f32 unit vectors (zero rows for pad lanes)."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def attn_fn(q, k, v, kv, layer, kind=None):
        window = att.layer_view(cfg, None, None, layer, kind).window
        return att.prefill_attention_dispatch(q, k, v, seq_lens, window), kv

    hidden, _ = transformer(params, cfg, tokens, positions, kv_pages, attn_fn)
    valid = (
        jnp.arange(T)[None, :] < seq_lens[:, None]
    )  # [B, T]
    hidden = hidden.astype(jnp.float32) * valid[:, :, None]
    denom = jnp.maximum(seq_lens[:, None].astype(jnp.float32), 1.0)
    pooled = jnp.sum(hidden, axis=1) / denom  # [B, H] mean over valid
    norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
    return pooled / jnp.maximum(norm, 1e-9)


def _inject_token(tokens: jax.Array, slot: jax.Array, token: jax.Array) -> jax.Array:
    """Scatter a freshly-prefilled lane's first token into the device-resident
    decode token vector (dynamic slot index -> one cached executable)."""
    return tokens.at[slot].set(token[0])


inject_token = partial(jax.jit, donate_argnames=("tokens",))(_inject_token)


def _inject_tokens(
    tokens: jax.Array,  # [B]
    slots: jax.Array,  # [G] lane indices; out-of-range rows are pad (dropped)
    toks: jax.Array,  # [G]
) -> jax.Array:
    """Batched :func:`inject_token`: one scatter for a whole prefill group
    instead of one dispatch per lane (the per-lane dispatches were the
    dominant group overhead on a high-RTT device link).  Pad rows carry an
    out-of-range slot and are dropped by the scatter."""
    return tokens.at[slots].set(toks, mode="drop")


inject_tokens = partial(jax.jit, donate_argnames=("tokens",))(_inject_tokens)

# donated decode-state arrays of the lane-scatter path: the one donation
# list shared by the module jit below and the sharded re-jit
UPDATE_LANES_DONATED = (
    "tokens", "seq_lens", "limit_lens", "active", "stop_ids",
    "page_table", "temp", "top_p", "top_k", "seed", "freq", "pres",
    "rep",
)


def _update_lanes(
    tokens: jax.Array,  # [B]
    seq_lens: jax.Array,  # [B]
    limit_lens: jax.Array,  # [B]
    active: jax.Array,  # [B] bool
    stop_ids: jax.Array,  # [B, E]
    page_table: jax.Array,  # [B, P]
    temp: jax.Array,  # [B]
    top_p: jax.Array,  # [B]
    top_k: jax.Array,  # [B]
    seed: jax.Array,  # [B] u32
    freq: jax.Array,  # [B] f32
    pres: jax.Array,  # [B] f32
    rep: jax.Array,  # [B] f32
    slots: jax.Array,  # [G] lane indices; out-of-range rows are pad (dropped)
    rows: dict,  # stacked per-lane values: token [G], stop [G, E], pages [G, P], ...
) -> Tuple[jax.Array, ...]:
    """Fold G lanes' host-side state into the device-resident decode state
    with ONE dispatch.

    This is how batch membership changes (admission, completion, revival,
    external-KV arrival) reach the device WITHOUT draining the decode
    pipeline: the scatter is dispatched after any in-flight decode blocks,
    so those blocks run against the old state (their stale lanes' output is
    discarded at commit via slot snapshots) and every later block sees the
    new lanes.  Batched because per-lane scatter calls each pay their own
    host->device row transfer -- an admission burst of G lanes costs G
    transfers; stacking the rows pays one.  The engine always calls this at G = max_batch_size
    (rows are a few KB), so exactly ONE executable exists per engine and
    no burst size can trigger a compile inside a serving window; unused
    rows carry an out-of-range slot and drop."""
    return (
        tokens.at[slots].set(rows["token"], mode="drop"),
        seq_lens.at[slots].set(rows["seq_len"], mode="drop"),
        limit_lens.at[slots].set(rows["limit"], mode="drop"),
        active.at[slots].set(rows["active"], mode="drop"),
        stop_ids.at[slots].set(rows["stop"], mode="drop"),
        # [B, P], or a two-kind cache's [2, B, P]: a lane's row in both
        (
            page_table.at[slots].set(rows["pages"], mode="drop")
            if page_table.ndim == 2
            else page_table.at[:, slots].set(rows["pages"], mode="drop")
        ),
        temp.at[slots].set(rows["temp"], mode="drop"),
        top_p.at[slots].set(rows["top_p"], mode="drop"),
        top_k.at[slots].set(rows["top_k"], mode="drop"),
        seed.at[slots].set(rows["seed"], mode="drop"),
        freq.at[slots].set(rows["freq"], mode="drop"),
        pres.at[slots].set(rows["pres"], mode="drop"),
        rep.at[slots].set(rows["rep"], mode="drop"),
    )


update_lanes = partial(jax.jit, donate_argnames=UPDATE_LANES_DONATED)(
    _update_lanes
)


def _zero_count_rows(counts: jax.Array, slots: jax.Array) -> jax.Array:
    """Zero the generated-token histograms of re-assigned lanes (penalty
    state; out-of-range pad slots drop)."""
    return counts.at[slots].set(0, mode="drop")


zero_count_rows = partial(jax.jit, donate_argnames=("counts",))(
    _zero_count_rows
)


def _bump_counts(
    counts: jax.Array,  # [B, V]
    slots: jax.Array,  # [G] lane indices (out-of-range pads drop)
    toks: jax.Array,  # [G] token ids (device values fine)
) -> jax.Array:
    """Count injected first tokens into the penalty histograms: prefill-
    sampled tokens never pass through the decode scan's own increment."""
    return counts.at[slots, toks].add(1, mode="drop")


bump_counts = partial(jax.jit, donate_argnames=("counts",))(_bump_counts)


def _seed_count_rows(
    counts: jax.Array,  # [B, V]
    slot: jax.Array,  # scalar i32
    toks: jax.Array,  # [Tpad] history tokens (pow2-padded)
    amounts: jax.Array,  # [Tpad] i32 per-token increment (0 = pad;
    # 1 = generated occurrence; PROMPT_FLAG = prompt occurrence)
) -> jax.Array:
    """Rebuild one lane's packed histogram from its prompt + committed
    output history (mid-request dirty flushes zero the row first)."""
    return counts.at[slot, toks].add(amounts, mode="drop")


seed_count_rows = partial(jax.jit, donate_argnames=("counts",))(
    _seed_count_rows
)


# Shape bucketing lives in engine/bucketing.py (the ONE home of every
# pow2/pad rule); re-exported here for the existing import sites.
from .bucketing import (  # noqa: E402,F401
    pick_bucket,
    pick_page_bucket,
    pow2_bucket,
    prefill_buckets,
)

# ---------------------------------------------------------------------------
# Compile budgets (runtime/compile_sentry.py, dynalint DT017/DT018's
# runtime complement).  Each key is a dispatch-plane entry label (the
# engine's compile_sentry.set_entry sites); each value is the ceiling on
# XLA compile events that entry may trigger in one process.  The numbers
# derive from the declared shape sets -- exceeding one means a shape
# leaked past the bucketing helpers:
#
# - decode_block: page buckets (pow2 over live pages, <= ~6 in practice)
#   x the use_filters flag.
# - packed_unified_step: PackedShapeBudget caps the live
#   (Np, s_max, s_spec) set at 16 (DYN_PACKED_SHAPES); top_n / filter
#   variants ride the same budget's headroom.
# - packed_unified_multistep: the packed set x the K ramp {1, 2, 4, 8}
#   (each K is a distinct lax.scan length, i.e. a distinct executable).
# - prefill: pow2 length buckets (prefill_buckets: log2(max_len/page)
#   entries) x batch-shape variants of the batched/suffix/mm planes.
# - verify_and_sample: draft-length buckets x page buckets.
# - commit: the fixed family of small epilogue jits (inject_token/s,
#   update_lanes, bump/seed/zero counts) x a couple of shapes each.
# - kv_pages / kv_export: scatter/slice/gather page ops over page-count
#   buckets (pick_page_bucket) and layer-range chunks.
#
# Budgets are per-process totals, enforced only when DYN_COMPILE_SENTRY=1
# (tier-1 arms it around the engine tests after compile_sentry.reset()).
COMPILE_BUDGET = {
    "decode_block": 12,
    "packed_unified_step": 24,
    "packed_unified_multistep": 96,
    "prefill": 32,
    "verify_and_sample": 16,
    "score_prompt_step": 12,
    "embed_step": 12,
    "commit": 48,
    "kv_pages": 48,
    "kv_export": 32,
}

from ..runtime import compile_sentry as _compile_sentry  # noqa: E402

_compile_sentry.register_budgets(COMPILE_BUDGET)
