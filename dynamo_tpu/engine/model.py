"""Llama-family transformer as pure JAX functions over a stacked-params pytree.

Design (TPU-first, not a torch translation):

- **Stacked layers + ``lax.scan``**: every per-layer weight is stored with a
  leading ``[num_layers, ...]`` axis and the layer loop is a ``lax.scan``.
  One layer gets traced/compiled once regardless of depth -- an 80-layer
  70B compiles in the same time as a 2-layer test model.
- **Params are a flat dict pytree** (no framework Module state); sharding is
  applied by annotating the pytree leaves with ``NamedSharding`` at load
  time (see dynamo_tpu.parallel.sharding) and letting GSPMD propagate.
- **Weights are stored ``[in, out]``** so the forward is ``x @ W`` (row-major
  matmuls map directly onto the MXU); the safetensors loader transposes from
  torch's ``[out, in]``.

RoPE matches the HF ``rotate_half`` convention so HF checkpoints reproduce
logits bit-for-band (validated against transformers' torch CPU reference in
tests/test_engine_model.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .quant import mat

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array, dtype: Any = None) -> Params:
    """Random-init a full parameter pytree (tests/benchmarks; real serving
    loads safetensors via dynamo_tpu.engine.weights)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    L = cfg.num_layers
    H = cfg.hidden_size
    D = cfg.head_dim
    Hq, Hkv = cfg.num_heads, cfg.num_kv_heads
    I = cfg.intermediate_size

    keys = iter(jax.random.split(key, 16))

    def w(k, shape, scale=None):
        scale = scale if scale is not None else (1.0 / jnp.sqrt(shape[-2] if len(shape) > 1 else shape[-1]))
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    layers: Dict[str, Any] = {
        "wq": w(next(keys), (L, H, Hq * D)),
        "wk": w(next(keys), (L, H, Hkv * D)),
        "wv": w(next(keys), (L, H, Hkv * D)),
        "wo": w(next(keys), (L, Hq * D, H)),
        "input_norm": jnp.ones((L, H), dtype),
        "post_norm": jnp.ones((L, H), dtype),
    }
    if cfg.attention_bias:
        layers["bq"] = jnp.zeros((L, Hq * D), dtype)
        layers["bk"] = jnp.zeros((L, Hkv * D), dtype)
        layers["bv"] = jnp.zeros((L, Hkv * D), dtype)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, D), dtype)
        layers["k_norm"] = jnp.ones((L, D), dtype)
    if cfg.is_moe:
        E = cfg.num_experts
        layers["router"] = w(next(keys), (L, H, E))
        layers["w_gate"] = w(next(keys), (L, E, H, I))
        layers["w_up"] = w(next(keys), (L, E, H, I))
        layers["w_down"] = w(next(keys), (L, E, I, H))
    else:
        layers["w_gate"] = w(next(keys), (L, H, I))
        layers["w_up"] = w(next(keys), (L, H, I))
        layers["w_down"] = w(next(keys), (L, I, H))

    params: Params = {
        "embed": w(next(keys), (cfg.vocab_size, H), scale=0.02),
        "layers": layers,
        "final_norm": jnp.ones((H,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(next(keys), (H, cfg.vocab_size))
    return params


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def rms_norm(
    x: jax.Array, weight: jax.Array, eps: float, offset: bool = False
) -> jax.Array:
    """RMSNorm; ``offset=True`` multiplies by (1 + w) (Gemma convention,
    whose checkpoints store weights centered at zero)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    if offset:
        w = 1.0 + w
    return (x * w).astype(dt)


def _activate(x: jax.Array, hidden_act: str) -> jax.Array:
    if hidden_act == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def rope_cos_sin(
    positions: jax.Array,
    head_dim: int,
    theta: float,
    scaling: Optional[tuple] = None,
) -> Tuple[jax.Array, jax.Array]:
    """HF convention: inv_freq over even dims, angles ``pos * inv_freq``,
    cos/sin tiled as [freqs, freqs].

    ``scaling`` = ("llama3", factor, low_freq_factor, high_freq_factor,
    original_max_position) applies Llama-3.1's frequency-dependent
    stretch: long-wavelength components slow by ``factor``, short ones
    stay, the band between interpolates smoothly (matches HF
    ``_compute_llama3_parameters``)."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if scaling is not None:
        kind, factor, low_f, high_f, orig_max = scaling
        if kind != "llama3":  # config validates; belt and braces
            raise ValueError(f"unknown rope scaling {kind!r}")
        wavelen = 2.0 * jnp.pi / inv_freq
        low_wavelen = orig_max / low_f
        high_wavelen = orig_max / high_f
        smooth = (orig_max / wavelen - low_f) / (high_f - low_f)
        smoothed = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
        inv_freq = jnp.where(
            wavelen > low_wavelen,
            inv_freq / factor,
            jnp.where(wavelen < high_wavelen, inv_freq, smoothed),
        )
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., D/2]
    emb = jnp.concatenate([angles, angles], axis=-1)  # [..., D]
    return jnp.cos(emb), jnp.sin(emb)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [..., heads, D]; cos/sin: [..., D] (broadcast over heads)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    return (x.astype(jnp.float32) * cos + rotated.astype(jnp.float32) * sin).astype(
        x.dtype
    )


def _dense_mlp(lp: Params, x: jax.Array, hidden_act: str = "silu") -> jax.Array:
    gate = _activate(x @ mat(lp["w_gate"]), hidden_act)
    return (gate * (x @ mat(lp["w_up"]))) @ mat(lp["w_down"])


def _moe_mlp_dense(lp: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Reference dense-dispatch MoE: every expert computes every token,
    weighted combine.  O(E*N) compute -- kept only as the ground truth the
    sparse dispatch is validated against in tests."""
    orig_shape = x.shape
    H = orig_shape[-1]
    xf = x.reshape(-1, H)  # [N, H]
    router_logits = (xf @ lp["router"]).astype(jnp.float32)  # [N, E]
    topw, topi = jax.lax.top_k(router_logits, cfg.num_experts_per_tok)
    topw = jax.nn.softmax(topw, axis=-1).astype(x.dtype)  # [N, K]
    one_hot = jax.nn.one_hot(topi, cfg.num_experts, dtype=x.dtype)  # [N, K, E]
    combine = jnp.einsum("nk,nke->ne", topw, one_hot)  # [N, E]
    gate = jax.nn.silu(jnp.einsum("nh,ehi->eni", xf, mat(lp["w_gate"])))
    up = jnp.einsum("nh,ehi->eni", xf, mat(lp["w_up"]))
    down = jnp.einsum("eni,eih->enh", gate * up, mat(lp["w_down"]))  # [E, N, H]
    out = jnp.einsum("enh,ne->nh", down, combine)
    return out.reshape(orig_shape)


# Rows N of a step at and above which a no-drop expert MLP takes the grouped
# product.  Set from chip_smoke.py's "moe_grouped" line (one v5e, Mixtral
# widths, the whole expert MLP of one layer, ms; my chip run, PR 27):
#      N   capacity  grouped
#     32     3.844    3.932    both stream all eight experts' weights once,
#    128     3.900    4.013    and the buffers need no sort
#    256     4.384    4.042    C passes the ridge (about 240 rows): -7.8%
#    512     8.114    4.262
#   1024    16.078    5.946    (4.593 with half the rows masked as padding)
_GROUPED_MIN_ROWS = 256

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _moe_capacity(cfg: ModelConfig, N: int) -> int:
    """Rows of an expert's capacity buffer for a step of N rows: perfect
    balance is N*K/E; ``moe_capacity_factor`` leaves headroom."""
    K, E = cfg.num_experts_per_tok, cfg.num_experts
    C = int(max(1, -(-N * K * cfg.moe_capacity_factor // E)))
    return min(C, N * K)


def _moe_takes_grouped(lp: Params, N: int, C: int) -> bool:
    """Trace-time choice of the expert MLP's layout, read off the input.

    Grouped product (`ops.grouped_matmul`) when the capacity asked for
    holds every token (``C >= N``: no assignment can drop, so the buffers
    would compute the dropless result and the grouped product computes
    the same one over ``N*K`` rows instead of ``E*N``), the step is at
    least ``_GROUPED_MIN_ROWS`` rows, and the trace runs on one device.
    Capacity buffers otherwise: ``C < N`` asks for GShard's drops; on a
    mesh the buffers' leading E axis is what GSPMD shards over ``ep``
    (and a Mosaic kernel cannot be partitioned); int8 expert weights
    dequantize inside the einsum's read, which a kernel operand cannot;
    and on the chip the kernel wants widths that tile to 128 lanes."""
    from ..ops.grouped_matmul import kernel_fits
    from .attention import _context_mesh, _on_tpu
    from .quant import QuantizedTensor

    w = lp["w_gate"]
    if C < N or N < _GROUPED_MIN_ROWS or _context_mesh() is not None:
        return False
    if isinstance(w, QuantizedTensor):
        return False
    return not _on_tpu() or kernel_fits(w.shape[-2], w.shape[-1])


def _moe_grouped(
    lp: Params,
    xf: jax.Array,  # [N, H]
    topw: jax.Array,  # [N, K] combine weights
    topi: jax.Array,  # [N, K] expert of each assignment
    row_valid: Optional[jax.Array],  # [N] bool, or None: every row counts
    layer: Optional[jax.Array],  # index, where lp holds the layers' stack
) -> jax.Array:
    """The dropless expert MLP over the ``N*K`` routed rows, sorted by
    expert: three grouped products, each row against its own expert's
    matrix.  Rows a step marks invalid (padding of a packed dispatch) are
    sorted behind the last group, where the kernel never goes, and come
    back zero."""
    from ..ops.grouped_matmul import _ROW_TILE, grouped_matmul
    from .attention import _on_tpu

    product = partial(grouped_matmul, layer=layer, kernel=_on_tpu())
    N, K = topi.shape
    E = lp["w_gate"].shape[-3]
    key = topi.reshape(-1)  # [N*K] expert id per assignment
    if row_valid is not None:
        key = jnp.where(jnp.repeat(row_valid, K), key, E)
    # place of each assignment in expert order (stable), from the same
    # running count the capacity path slots with; class E holds the invalid
    onehot = jax.nn.one_hot(key, E + 1, dtype=jnp.int32)  # [NK, E+1]
    counts = jnp.sum(onehot, axis=0)
    slot = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1
    dest = (jnp.cumsum(counts) - counts)[key] + slot  # a permutation of NK
    M = -(-N * K // _ROW_TILE) * _ROW_TILE  # whole row tiles, no pad copy
    order = jnp.zeros((M,), jnp.int32).at[dest].set(
        jnp.arange(N * K, dtype=jnp.int32), unique_indices=True
    )
    rows = xf[order // K]  # [M, H]; rows past N*K repeat token 0, unread
    sizes = counts[:E]
    gate = jax.nn.silu(product(rows, lp["w_gate"], sizes))
    up = product(rows, lp["w_up"], sizes)
    down = product(gate * up, lp["w_down"], sizes)  # [M, H]
    per_assign = down[dest].reshape(N, K, -1)  # un-sort
    if row_valid is not None:  # behind the groups the kernel stored nothing
        per_assign = jnp.where(row_valid[:, None, None], per_assign, 0)
    return jnp.sum(per_assign * topw[:, :, None], axis=1)


def _moe_mlp(
    lp: Params,
    x: jax.Array,
    cfg: ModelConfig,
    row_valid: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """Sparse MoE MLP: top-k routing, then one of two layouts of the same
    expert products, chosen at trace time (:func:`_moe_takes_grouped`).
    ``lp`` holds one layer's expert weights ``[E, ., .]``; where
    :func:`scan_layers` found that the step takes the grouped product, the
    layers' stack ``[L, E, ., .]``, with ``layer`` the index into it.

    **Grouped (dropless).**  Where ``cfg.moe_capacity_factor`` is ``E/K``
    or more the capacity holds every token, nothing can drop, and a
    single-device step of enough rows multiplies only the ``N*K`` routed
    rows, grouped by expert (:func:`_moe_grouped`).  ``row_valid`` ([...]
    bool, the leading shape of ``x``) marks rows whose result nobody
    reads; only this layout uses it, to skip them.

    **Capacity buffers (GShard/Switch).**  Tokens are packed into fixed
    [E, C, H] per-expert buffers (C = capacity), each expert runs a
    batched matmul over its buffer, and the combine scatters results back
    weighted by the router.  Compute is O(N*K*capacity_factor), shapes are
    static (jit), and the leading E axis of the buffers/weights shards
    over the ``ep`` mesh axis -- GSPMD turns the pack/unpack into an
    all_to_all over ICI (SURVEY.md 2.8: EP is first-party here,
    engine-internal in the reference).  Assignments that overflow an
    expert's capacity are dropped (their combine weight contributes
    nothing), the standard GShard behavior; the default capacity factor
    leaves headroom so drops need an adversarially skewed batch.
    """
    orig_shape = x.shape
    H = orig_shape[-1]
    E = cfg.num_experts
    K = cfg.num_experts_per_tok
    xf = x.reshape(-1, H)  # [N, H]
    N = xf.shape[0]

    router_logits = (xf @ lp["router"]).astype(jnp.float32)  # [N, E]
    topw, topi = jax.lax.top_k(router_logits, K)
    topw = jax.nn.softmax(topw, axis=-1).astype(x.dtype)  # [N, K]

    C = _moe_capacity(cfg, N)
    if _moe_takes_grouped(lp, N, C):
        valid = None if row_valid is None else row_valid.reshape(-1)
        out = _moe_grouped(lp, xf, topw, topi, valid, layer)
        return out.reshape(orig_shape)

    flat_expert = topi.reshape(-1)  # [N*K] expert id per assignment
    flat_w = topw.reshape(-1)  # [N*K]
    token_of = jnp.arange(N * K, dtype=jnp.int32) // K  # [N*K]

    # slot of each assignment within its expert's buffer (stable order)
    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)  # [NK, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot  # running count where routed
    slot = jnp.sum(pos, axis=1) - 1  # [N*K]
    keep = slot < C
    dispatch = jnp.where(keep, flat_expert * C + slot, E * C)  # OOB = drop

    buf = jnp.zeros((E * C, H), xf.dtype)
    buf = buf.at[dispatch].set(xf[token_of], mode="drop")
    buf = buf.reshape(E, C, H)

    gate = jax.nn.silu(jnp.einsum("ech,ehi->eci", buf, mat(lp["w_gate"])))
    up = jnp.einsum("ech,ehi->eci", buf, mat(lp["w_up"]))
    down = jnp.einsum("eci,eih->ech", gate * up, mat(lp["w_down"]))  # [E, C, H]

    per_assign = down.reshape(E * C, H).at[jnp.minimum(dispatch, E * C - 1)].get(
        mode="fill", fill_value=0
    )  # [N*K, H]
    per_assign = per_assign * (flat_w * keep.astype(flat_w.dtype))[:, None]
    out = jax.ops.segment_sum(per_assign, token_of, num_segments=N)
    return out.reshape(orig_shape)


# ---------------------------------------------------------------------------
# transformer trunk
# ---------------------------------------------------------------------------

# An attention callback receives (q, k, v, kv_pages, layer) -- the FULL
# stacked KV buffer plus the layer index -- and returns (attn_out,
# kv_pages).  Writes scatter into kv_pages at the layer index, so the scan
# over layers updates one carried buffer in place; threading per-layer
# slices through scan ys instead would rewrite the whole multi-GB cache
# every step (measured 2.7 ms/step on a 1.1B model).  q/k/v carry head
# dims: q [.., Hq, D], k/v [.., Hkv, D].
AttnFn = Callable[
    [jax.Array, jax.Array, jax.Array, jax.Array, jax.Array],
    Tuple[jax.Array, jax.Array],
]


def transformer_layer(
    lp: Params,
    x: jax.Array,  # [B, T, H]
    cos: jax.Array,  # [B, T, D]
    sin: jax.Array,
    cfg: ModelConfig,
    attn_fn: AttnFn,
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    layer: jax.Array,  # scalar i32 layer index into kv_pages
    row_valid: Optional[jax.Array] = None,  # [B, T] bool: rows anyone reads
) -> Tuple[jax.Array, jax.Array]:
    """One decoder layer (norm -> attention -> norm -> MLP, residuals).
    Shared by the single-device layer scan and the pipeline-parallel stage
    loop so the math cannot diverge.  ``row_valid`` lets the expert MLP
    skip a packed dispatch's padding rows (:func:`_moe_mlp`)."""
    B, T, _ = x.shape
    D = cfg.head_dim
    h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps, cfg.rms_norm_offset)
    q = h @ mat(lp["wq"])
    k = h @ mat(lp["wk"])
    v = h @ mat(lp["wv"])
    if "bq" in lp:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    q = q.reshape(B, T, cfg.num_heads, D)
    k = k.reshape(B, T, cfg.num_kv_heads, D)
    v = v.reshape(B, T, cfg.num_kv_heads, D)
    if cfg.qk_norm:  # Qwen3: per-head RMSNorm before RoPE
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    attn, kv_pages = attn_fn(q, k, v, kv_pages, layer)
    x = x + attn.reshape(B, T, cfg.num_heads * D) @ mat(lp["wo"])
    h2 = rms_norm(x, lp["post_norm"], cfg.rms_norm_eps, cfg.rms_norm_offset)
    if cfg.is_moe:
        x = x + _moe_mlp(lp, h2, cfg, row_valid, layer)
    else:
        x = x + _dense_mlp(lp, h2, cfg.hidden_act)
    return x, kv_pages


def scan_layers(
    lp_stack: Params,
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    x: jax.Array,  # [B, T, H]
    cos: jax.Array,
    sin: jax.Array,
    cfg: ModelConfig,
    attn_fn: AttnFn,
    row_valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Scan ``transformer_layer`` over the stacked weights.

    kv_pages rides the scan CARRY and each layer scatters into its slice in
    place; making it a scanned input/stacked output would copy the whole
    cache every call (see AttnFn note above).  Shared by the single-device
    trunk and the pipeline-parallel stage loop (which passes its
    stage-local weight/KV stacks)."""
    L = kv_pages.shape[0]
    # Where the expert MLP takes the grouped kernel, the experts' weights
    # stay whole and the kernel indexes the stack by layer: a custom call
    # cannot fuse the scan's slice, which would then be a copy of every
    # expert's matrices in every layer of every step.
    whole: Params = {}
    N = x.shape[0] * x.shape[1]
    if cfg.is_moe and _moe_takes_grouped(lp_stack, N, _moe_capacity(cfg, N)):
        whole = {k: lp_stack[k] for k in _EXPERT_WEIGHTS}
        lp_stack = {k: v for k, v in lp_stack.items() if k not in whole}

    def layer(carry, scanned):
        x, kv = carry
        lp, idx = scanned
        x, kv = transformer_layer(
            {**lp, **whole}, x, cos, sin, cfg, attn_fn, kv, idx, row_valid
        )
        return (x, kv), None

    (x, kv_pages), _ = jax.lax.scan(
        layer, (x, kv_pages), (lp_stack, jnp.arange(L, dtype=jnp.int32))
    )
    return x, kv_pages


def transformer(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, T] or [B] int32
    positions: jax.Array,  # same leading shape as tokens
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    attn_fn: AttnFn,
    mm: "Optional[Tuple[jax.Array, jax.Array]]" = None,
    row_valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Run the trunk; returns (hidden [.., H], updated kv_pages).

    ``row_valid`` (bool, shaped like ``tokens``) marks the rows whose
    hidden state anyone reads; a step that pads its rows passes it so the
    expert MLP can leave the padding out (:func:`_moe_mlp`).

    ``mm = (mm_embeds [B, M, H], mm_len [B])`` injects a llava-style soft
    prompt: lane b's first ``mm_len[b]`` positions take rows from
    ``mm_embeds`` instead of the token-embedding lookup (the vision
    projector's output lands here; reference examples/multimodal
    encode_worker -> prefill embedding splice)."""
    squeeze = tokens.ndim == 1
    if squeeze:
        tokens = tokens[:, None]
        positions = positions[:, None]

    D = cfg.head_dim
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    if cfg.scale_embeddings:  # Gemma: sqrt(hidden) in the embed dtype
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
    if mm is not None:
        mm_embeds, mm_len = mm
        M = mm_embeds.shape[1]
        T = x.shape[1]
        inj = jnp.zeros_like(x)
        k = min(M, T)
        inj = inj.at[:, :k].set(mm_embeds[:, :k].astype(x.dtype))
        pos_t = jnp.arange(T, dtype=jnp.int32)
        take = pos_t[None, :] < jnp.minimum(mm_len, k)[:, None]  # [B, T]
        x = jnp.where(take[:, :, None], inj, x)
    cos, sin = rope_cos_sin(positions, D, cfg.rope_theta, cfg.rope_scaling)  # [B, T, D]

    if squeeze and row_valid is not None:
        row_valid = row_valid[:, None]
    x, new_kv_pages = scan_layers(
        params["layers"], kv_pages, x, cos, sin, cfg, attn_fn, row_valid
    )

    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps, cfg.rms_norm_offset)
    if squeeze:
        x = x[:, 0]
    return x, new_kv_pages


def lm_logits(params: Params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    if cfg.tie_word_embeddings:
        w = params["embed"].T
    else:
        w = mat(params["lm_head"])
    return (hidden @ w).astype(jnp.float32)
