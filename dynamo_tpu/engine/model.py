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

import math
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

    def w(k, shape, scale=None):
        scale = scale if scale is not None else (1.0 / jnp.sqrt(shape[-2] if len(shape) > 1 else shape[-1]))
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    if cfg.state_kind:
        return _init_conv_trunk(
            cfg, iter(jax.random.split(key, 24 + 6 * cfg.lead_layers)), w, dtype
        )
    keys = iter(jax.random.split(key, 24))
    if cfg.is_mla:
        R, C = cfg.q_lora_rank, cfg.kv_lora_rank
        layers: Dict[str, Any] = {
            "wq_a": w(next(keys), (L, H, R)),
            "q_a_norm": jnp.ones((L, R), dtype),
            "wq_b": w(next(keys), (L, R, Hq * D)),
            "wkv_a": w(next(keys), (L, H, C + cfg.qk_rope_head_dim)),
            "kv_a_norm": jnp.ones((L, C), dtype),
            "wkv_b": w(
                next(keys),
                (L, C, Hq * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            ),
            "wo": w(next(keys), (L, Hq * cfg.v_head_dim, H)),
        }
    else:
        layers = {
            "wq": w(next(keys), (L, H, Hq * D)),
            "wk": w(next(keys), (L, H, Hkv * D)),
            "wv": w(next(keys), (L, H, Hkv * D)),
            "wo": w(next(keys), (L, Hq * D, H)),
        }
    layers["input_norm"] = jnp.ones((L, H), dtype)
    layers["post_norm"] = jnp.ones((L, H), dtype)
    if cfg.attention_bias:
        layers["bq"] = jnp.zeros((L, Hq * D), dtype)
        layers["bk"] = jnp.zeros((L, Hkv * D), dtype)
        layers["bv"] = jnp.zeros((L, Hkv * D), dtype)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, D), dtype)
        layers["k_norm"] = jnp.ones((L, D), dtype)
    if cfg.is_moe:
        E = cfg.experts_held
        layers["router"] = w(next(keys), (L, H, cfg.num_experts))
        layers["w_gate"] = w(next(keys), (L, E, H, I))
        layers["w_up"] = w(next(keys), (L, E, H, I))
        layers["w_down"] = w(next(keys), (L, E, I, H))
        if cfg.num_shared_experts:
            Is = I * cfg.num_shared_experts
            layers["ws_gate"] = w(next(keys), (L, H, Is))
            layers["ws_up"] = w(next(keys), (L, H, Is))
            layers["ws_down"] = w(next(keys), (L, Is, H))
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


def _init_conv_trunk(cfg: ModelConfig, keys, w, dtype) -> Params:
    """The tree of a trunk with layers that hold state (``scan_layers``):
    the periods' stack with each kind's operator under ``"attn"`` and
    ``"conv"`` / ``"linear"`` (``cfg.state_kind``), and the layers in front
    of the periods as a tuple under ``"lead"``."""
    H, D, I, E = cfg.hidden_size, cfg.head_dim, cfg.intermediate_size, cfg.num_experts
    Hq, Hkv = cfg.num_heads, cfg.num_kv_heads

    def operator(kind, lead):
        if kind == "conv":
            return {
                "conv_in": w(next(keys), (*lead, H, 3 * H)),
                "conv_taps": w(next(keys), (*lead, 3, H), scale=0.5),
                "conv_out": w(next(keys), (*lead, H, H)),
            }
        if kind == "linear":
            Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
            dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
            return {
                "gdn_in": w(next(keys), (*lead, H, 2 * Hk * dk + 2 * Hv * dv)),
                "gdn_ba": w(next(keys), (*lead, H, 2 * Hv)),
                "gdn_taps": w(
                    next(keys), (*lead, 4, cfg.linear_conv_width), scale=0.5),
                # memories of about 8 to 500 tokens, a head
                "gdn_a_log": jnp.broadcast_to(
                    jnp.linspace(-6.0, -2.0, Hv, dtype=jnp.float32),
                    (*lead, Hv)).astype(dtype),
                "gdn_dt_bias": jnp.ones((*lead, Hv), dtype),
                "gdn_norm": jnp.ones((*lead, dv), dtype),
                "gdn_out": w(next(keys), (*lead, Hv * dv, H)),
            }
        gate = 2 if cfg.attn_output_gate else 1
        return {
            "wq": w(next(keys), (*lead, H, gate * Hq * D)),
            "wk": w(next(keys), (*lead, H, Hkv * D)),
            "wv": w(next(keys), (*lead, H, Hkv * D)),
            "wo": w(next(keys), (*lead, Hq * D, H)),
            "q_norm": unit((*lead, D)),
            "k_norm": unit((*lead, D)),
        }

    def unit(shape):  # a norm weight that multiplies by one
        return (jnp.zeros if cfg.rms_norm_offset else jnp.ones)(shape, dtype)

    def norms(lead):
        return {
            "input_norm": unit((*lead, H)),
            "post_norm": unit((*lead, H)),
        }

    Il = cfg.lead_intermediate_size
    lead = tuple(
        {
            **norms(()), **operator(kind, ()),
            "w_gate": w(next(keys), (H, Il)),
            "w_up": w(next(keys), (H, Il)),
            "w_down": w(next(keys), (Il, H)),
        }
        for kind in cfg.lead_pattern or ()
    )
    L = cfg.num_layers - len(lead)
    Eh, kind = cfg.experts_held, cfg.state_kind
    layers: Dict[str, Any] = {
        **norms((L,)),
        "router": w(next(keys), (L, H, E)),
        "w_gate": w(next(keys), (L, Eh, H, I)),
        "w_up": w(next(keys), (L, Eh, H, I)),
        "w_down": w(next(keys), (L, Eh, I, H)),
        "attn": operator(
            "full", (cfg.kind_layers("full") - cfg.lead_kind_layers("full"),)
        ),
        kind: operator(
            kind, (cfg.kind_layers(kind) - cfg.lead_kind_layers(kind),)
        ),
    }
    if cfg.router_bias:
        layers["router_bias"] = w(next(keys), (L, E), scale=0.1)
    if cfg.num_shared_experts:
        Is = I * cfg.num_shared_experts
        layers["ws_gate"] = w(next(keys), (L, H, Is))
        layers["ws_up"] = w(next(keys), (L, H, Is))
        layers["ws_down"] = w(next(keys), (L, Is, H))
        if cfg.shared_expert_gate:
            layers["ws_router"] = w(next(keys), (L, H, 1))
    params: Params = {
        "embed": w(next(keys), (cfg.vocab_size, H), scale=0.02),
        "layers": layers,
        "lead": lead,
        "final_norm": unit((H,)),
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
    factor_cs = 1.0
    if scaling is not None and scaling[0] == "yarn":
        # YaRN: dimensions that turn more than beta_fast times over the
        # original context keep their frequency, those that turn less than
        # beta_slow times are interpolated by ``factor``, a linear ramp
        # between; cos/sin carry mscale/mscale_all_dim (HF
        # ``_compute_yarn_parameters`` with both mscale keys)
        from .config import _yarn_mscale

        _, factor, orig_max, b_fast, b_slow, mscale, mscale_all = scaling
        half = head_dim // 2

        def corr_dim(rot):
            return head_dim * math.log(orig_max / (rot * 2 * math.pi)) / (
                2 * math.log(theta)
            )

        low = max(math.floor(corr_dim(b_fast)), 0)
        high = min(math.ceil(corr_dim(b_slow)), head_dim - 1)
        ramp = jnp.clip(
            (jnp.arange(half, dtype=jnp.float32) - low)
            / max(high - low, 0.001),
            0.0, 1.0,
        )
        inv_freq = inv_freq / factor * ramp + inv_freq * (1.0 - ramp)
        factor_cs = _yarn_mscale(factor, mscale) / _yarn_mscale(
            factor, mscale_all
        )
    elif scaling is not None:
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
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    if factor_cs != 1.0:
        cos, sin = cos * factor_cs, sin * factor_cs
    return cos, sin


def apply_rope_interleaved(
    x: jax.Array, cos: jax.Array, sin: jax.Array
) -> jax.Array:
    """x: [..., heads, D] with the rotated pairs at (2i, 2i+1)
    (``rope_interleave``); cos/sin: [..., D] as :func:`rope_cos_sin` tiles
    them (the first half is one angle a pair)."""
    half = x.shape[-1] // 2
    c = cos[..., None, :half]
    s = sin[..., None, :half]
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    even, odd = xf[..., 0], xf[..., 1]
    out = jnp.stack([even * c - odd * s, odd * c + even * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


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
    topw, topi = _route(lp, xf, cfg)
    # an absent expert's index falls outside one_hot's range: a zero row
    one_hot = jax.nn.one_hot(
        topi - cfg.local_expert_offset, cfg.experts_held, dtype=x.dtype
    )  # [N, K, E held]
    combine = jnp.einsum("nk,nke->ne", topw, one_hot)  # [N, E]
    gate = jax.nn.silu(jnp.einsum("nh,ehi->eni", xf, mat(lp["w_gate"])))
    up = jnp.einsum("nh,ehi->eni", xf, mat(lp["w_up"]))
    down = jnp.einsum("eni,eih->enh", gate * up, mat(lp["w_down"]))  # [E, N, H]
    out = jnp.einsum("enh,ne->nh", down, combine)
    return (out + _shared_experts(lp, xf, cfg)).reshape(orig_shape)


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


# A step under that takes the grouped product all the same where it routes
# fewer assignments than the router has experts, ``N*K < num_experts``: under
# one row an expert on average, so most matrices have no row, the grouped
# product does not read a group with no row, and the buffers' einsum reads
# every held expert whatever was routed.  chip_smoke.py's "moe_grouped_held"
# line (one v5e; top-4 of 128 experts, 32 held, H 4096, I 2048; the whole
# routed expert MLP of one layer, ms; my chip run, PR 51):
#      N   N*K   capacity  grouped  (half the rows idle lanes)
#      8    32     2.228    0.789    0.471    the buffers stream 32 x 50 MB,
#     16    64     2.234    0.659    0.466    the groups 50 MB an expert
#     32   128     2.255    1.740    1.005    reached (about 7, 13, 20 of 32)
# The rule leaves N = 32 the buffers although the groups still win there: a
# step with rows enough to reach every expert is the case _GROUPED_MIN_ROWS
# was measured on (8 of 8 reached from 32 rows on), and no served shape
# lies between.
def _moe_reaches_few(cfg: ModelConfig, N: int) -> bool:
    return N * cfg.num_experts_per_tok < cfg.num_experts


_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _route(lp: Params, xf: jax.Array, cfg: ModelConfig):
    """Top-k routing over the router's whole width: ``(weights [N, K],
    published expert index [N, K])``.  The softmax over the chosen logits
    equals a softmax over all of them renormalised over the chosen
    (``norm_topk_prob``), so Mixtral and mistral4 share it.  The logits
    come out of the product in float32: rounded to bfloat16 first, two
    experts a few thousandths apart change places, and a changed expert is
    the largest error a token can meet (on the chip at 32k tokens, top-4 of
    128: the 90th percentile of the log-probability error against the
    float32 reference read 0.044-0.067 with bfloat16 logits, PERF.md)."""
    router_logits = jnp.dot(
        xf, lp["router"], preferred_element_type=jnp.float32
    )  # [N, E]
    if cfg.router_score == "sigmoid":
        # every expert scored on its own; the bias decides who is chosen
        # and weighs nothing: the weights are the unbiased scores of the
        # chosen over their sum (lfm2_moe).  All in float32
        scores = jax.nn.sigmoid(router_logits)
        choice = scores
        if cfg.router_bias:
            choice = scores + lp["router_bias"].astype(jnp.float32)
        _, topi = jax.lax.top_k(choice, cfg.num_experts_per_tok)
        topw = jnp.take_along_axis(scores, topi, axis=-1)
        topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-6)
    else:
        topw, topi = jax.lax.top_k(router_logits, cfg.num_experts_per_tok)
        topw = jax.nn.softmax(topw, axis=-1)
    if cfg.routed_scaling_factor != 1.0:
        topw = topw * cfg.routed_scaling_factor
    return topw.astype(xf.dtype), topi


def _shared_experts(lp: Params, xf: jax.Array, cfg: ModelConfig):
    """The shared experts' part: a dense SwiGLU every token takes (0 where
    the configuration has none)."""
    if "ws_gate" not in lp:
        return 0
    gate = _activate(xf @ mat(lp["ws_gate"]), cfg.hidden_act)
    out = (gate * (xf @ mat(lp["ws_up"]))) @ mat(lp["ws_down"])
    if "ws_router" in lp:  # qwen3_next: the shared expert has a gate
        score = jnp.dot(
            xf, lp["ws_router"], preferred_element_type=jnp.float32)
        out = out * jax.nn.sigmoid(score).astype(out.dtype)
    return out


def _moe_capacity(cfg: ModelConfig, N: int) -> int:
    """Rows of an expert's capacity buffer for a step of N rows: perfect
    balance is N*K/E; ``moe_capacity_factor`` leaves headroom."""
    K, E = cfg.num_experts_per_tok, cfg.num_experts
    C = int(max(1, -(-N * K * cfg.moe_capacity_factor // E)))
    return min(C, N * K)


def _moe_takes_grouped(lp: Params, cfg: ModelConfig, N: int) -> bool:
    """Trace-time choice of the expert MLP's layout for a step of ``N``
    rows, read off the input (``lp``: a layer's weights or the layers'
    stack).

    Grouped product (`ops.grouped_matmul`) when the capacity asked for
    holds every token (``C >= N``: no assignment can drop, so the buffers
    would compute the dropless result and the grouped product computes
    the same one over ``N*K`` rows instead of ``E*N``), the trace runs on
    one device, and the step is at least ``_GROUPED_MIN_ROWS`` rows or
    reaches few of its experts (:func:`_moe_reaches_few`).
    Capacity buffers otherwise: ``C < N`` asks for GShard's drops; on a
    mesh the buffers' leading E axis is what GSPMD shards over ``ep``
    (and a Mosaic kernel cannot be partitioned); int8 expert weights
    dequantize inside the einsum's read, which a kernel operand cannot;
    and on the chip the kernel wants widths that tile to 128 lanes."""
    from ..ops.grouped_matmul import kernel_fits
    from .attention import _context_mesh, _on_tpu
    from .quant import QuantizedTensor

    w = lp["w_gate"]
    if _moe_capacity(cfg, N) < N or _context_mesh() is not None:
        return False
    if N < _GROUPED_MIN_ROWS and not _moe_reaches_few(cfg, N):
        return False
    if isinstance(w, QuantizedTensor):
        return False
    return not _on_tpu() or kernel_fits(w.shape[-2], w.shape[-1])


def moe_layout(params: Params, cfg: ModelConfig, N: int) -> Optional[str]:
    """``"grouped"`` or ``"capacity"``: the layout the trunk's expert MLP
    takes in a step of ``N`` rows (None: no routed experts).  The one
    trace-time question, for a step that hands the grouped layout its
    idle lanes and for the engine's ``dispatch`` annotation."""
    if not cfg.is_moe:
        return None
    grouped = _moe_takes_grouped(params["layers"], cfg, N)
    return "grouped" if grouped else "capacity"


def moe_counts_reached(params: Params, cfg: ModelConfig, N: int) -> bool:
    """Whether a step of ``N`` rows returns the count of the experts it
    read: where it reaches few of them and takes the grouped layout for
    that, the count is what the layout saved.  A step of enough rows to
    reach every expert returns what it always did."""
    return _moe_reaches_few(cfg, N) and moe_layout(params, cfg, N) == "grouped"


def _moe_grouped(
    lp: Params,
    xf: jax.Array,  # [N, H]
    topw: jax.Array,  # [N, K] combine weights
    topi: jax.Array,  # [N, K] expert of each assignment
    row_valid: Optional[jax.Array],  # [N] bool, or None: every row counts
    layer: Optional[jax.Array],  # index, where lp holds the layers' stack
    local: bool = False,  # topi may name experts this process does not hold
) -> Tuple[jax.Array, jax.Array]:
    """The dropless expert MLP over the ``N*K`` routed rows, sorted by
    expert: three grouped products, each row against its own expert's
    matrix.  Rows a step marks invalid (padding of a packed dispatch, a
    lane that has stopped) are sorted behind the last group, where the
    kernel never goes, and come back zero.  So are assignments to an expert
    held elsewhere (``local``: ``topi`` already counts from this process's
    first expert).  Returns the result and the groups' sizes ``[E]``: a
    group with a row is an expert whose matrices the products read."""
    from ..ops.grouped_matmul import _ROW_TILE, grouped_matmul
    from .attention import _on_tpu

    product = partial(grouped_matmul, layer=layer, kernel=_on_tpu())
    N, K = topi.shape
    E = lp["w_gate"].shape[-3]
    key = topi.reshape(-1)  # [N*K] expert id per assignment
    counted = None  # [N*K] bool: assignments some group holds
    if row_valid is not None:
        counted = jnp.repeat(row_valid, K)
    if local:
        here = (key >= 0) & (key < E)
        counted = here if counted is None else counted & here
    if counted is not None:
        key = jnp.where(counted, key, E)
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
    if counted is not None:  # behind the groups the kernel stored nothing
        per_assign = jnp.where(
            counted.reshape(N, K)[:, :, None], per_assign, 0
        )
    return jnp.sum(per_assign * topw[:, :, None], axis=1), sizes


def _moe_mlp(
    lp: Params,
    x: jax.Array,
    cfg: ModelConfig,
    row_valid: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """The sparse MoE MLP's result alone (:func:`_moe_mlp_counted`)."""
    return _moe_mlp_counted(lp, x, cfg, row_valid, layer)[0]


def _moe_mlp_counted(
    lp: Params,
    x: jax.Array,
    cfg: ModelConfig,
    row_valid: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Sparse MoE MLP: top-k routing, then one of two layouts of the same
    expert products, chosen at trace time (:func:`_moe_takes_grouped`).
    ``lp`` holds one layer's expert weights ``[E, ., .]``; where
    :func:`scan_layers` found that the step takes the grouped product, the
    layers' stack ``[L, E, ., .]``, with ``layer`` the index into it.
    Returns the result and, from the grouped layout, its groups' sizes
    ``[E]`` (an expert with no row is not read; the buffers read every one:
    None).

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
    E = cfg.experts_held
    K = cfg.num_experts_per_tok
    xf = x.reshape(-1, H)  # [N, H]
    N = xf.shape[0]

    topw, topi = _route(lp, xf, cfg)  # [N, K]
    # experts held elsewhere (a deployment's other chips): routing is over
    # the router's whole width, only this process's experts compute, and
    # the sum goes on without the absent ones' part
    local = cfg.experts_held != cfg.num_experts
    if local:
        topi = topi - cfg.local_expert_offset
    shared = _shared_experts(lp, xf, cfg)

    if _moe_takes_grouped(lp, cfg, N):
        valid = None if row_valid is None else row_valid.reshape(-1)
        out, sizes = _moe_grouped(lp, xf, topw, topi, valid, layer, local)
        return (out + shared).reshape(orig_shape), sizes

    C = _moe_capacity(cfg, N)
    flat_expert = topi.reshape(-1)  # [N*K] expert id per assignment
    held = True
    if local:
        held = (flat_expert >= 0) & (flat_expert < E)
        flat_expert = jnp.where(held, flat_expert, E)  # one_hot: a zero row
    flat_w = topw.reshape(-1)  # [N*K]
    token_of = jnp.arange(N * K, dtype=jnp.int32) // K  # [N*K]

    # slot of each assignment within its expert's buffer (stable order)
    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)  # [NK, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot  # running count where routed
    slot = jnp.sum(pos, axis=1) - 1  # [N*K]
    keep = (slot < C) & held
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
    return (out + shared).reshape(orig_shape), None


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


def _latent_attention(
    lp: Params,
    h: jax.Array,  # [B, T, H] normed input
    cos: jax.Array,  # [B, T, rope_dim]
    sin: jax.Array,
    cfg: ModelConfig,
    attn_fn: AttnFn,
    kv_pages: jax.Array,  # [L, 1, num_pages, page, 1, C + R]
    layer: jax.Array,
    q_factor: Optional[jax.Array],  # [B, T] or None
) -> Tuple[jax.Array, jax.Array]:
    """Latent attention (MLA) in the absorbed form, over a cache that holds
    one row ``[c_kv | RoPE(k_r)]`` a token and nothing else.

    Head ``i``'s keys are ``[c_kv W^K_i | RoPE(k_r)]`` and its values
    ``c_kv W^V_i``; since ``q_nope . (c_kv W^K_i) = (q_nope W^K_i^T) .
    c_kv``, the queries are carried into the latent space instead and every
    head attends to the SAME cached row: multi-query attention whose one
    "KV head" is the row, keys the whole row (C + R wide), values its
    first C columns.  That is the contract every ``attn_fn`` already
    serves -- it gets ``(q~ [.., Hq, C+R], row [.., 1, C+R], row)``, writes
    the row once (a latent pool has one side) and returns ``[.., Hq, >=C]``
    -- so the up-projection ``W^V`` runs after attention, on ``Hq`` rows a
    token and not on every cached token.  The softmax scale (with YaRN's
    factor) and the position-dependent query factor are folded into ``q~``
    against the ``(C+R)^-0.5`` an attention call applies on its own."""
    B, T, _ = h.shape
    Hq = cfg.num_heads
    C, R = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    N, V = cfg.qk_nope_head_dim, cfg.v_head_dim
    rope = apply_rope_interleaved if cfg.rope_interleave else apply_rope
    c_q = rms_norm(h @ mat(lp["wq_a"]), lp["q_a_norm"], cfg.rms_norm_eps)
    q = (c_q @ mat(lp["wq_b"])).reshape(B, T, Hq, N + R)
    kv_a = h @ mat(lp["wkv_a"])  # [B, T, C + R]
    c_kv = rms_norm(kv_a[..., :C], lp["kv_a_norm"], cfg.rms_norm_eps)
    k_r = rope(kv_a[..., None, C:], cos, sin)  # [B, T, 1, R]
    row = jnp.concatenate([c_kv[..., None, :], k_r], axis=-1)  # [B, T, 1, C+R]
    w_kvb = mat(lp["wkv_b"]).reshape(C, Hq, N + V)
    # absorbed and scaled in float32, rounded to the compute type once
    q_lat = jnp.einsum(
        "bthn,chn->bthc", q[..., :N], w_kvb[..., :N],
        preferred_element_type=jnp.float32,
    )
    q_rot = rope(q[..., N:].astype(jnp.float32), cos, sin)
    fold = cfg.attn_softmax_scale * (C + R) ** 0.5
    if q_factor is not None:
        fold = fold * q_factor[..., None, None]
    q_abs = (jnp.concatenate([q_lat, q_rot], axis=-1) * fold).astype(h.dtype)
    out, kv_pages = attn_fn(q_abs, row, row, kv_pages, layer)
    attn = jnp.einsum("bthc,chv->bthv", out[..., :C], w_kvb[..., N:])
    return attn.reshape(B, T, Hq * V), kv_pages


def _packed_heads_attention(q, k, v, cfg, attn_fn, kv_pages, layer):
    """``attn_fn`` over a pool whose rows hold ``cfg.kv_head_pack`` KV heads
    (config.kv_head_pack): a query sits in its KV head's part of the row
    with zeros in the others', so its scores over the row are its own
    head's; the value rows come back whole and the query takes its part.
    The scale the attention call applies is the row's, ``(pack D)^-0.5``:
    the part's ones carry ``sqrt(pack)``, in float32, so the query is
    rounded once."""
    B, T, Hq, D = q.shape
    Hkv, pack = cfg.num_kv_heads, cfg.kv_head_pack
    part = jax.nn.one_hot(
        jnp.arange(Hq) // (Hq // Hkv) % pack, pack, dtype=jnp.float32
    )  # [Hq, pack]
    q = (q[..., None, :] * (part * pack ** 0.5)[:, :, None]).astype(q.dtype)
    attn, kv_pages = attn_fn(
        q.reshape(B, T, Hq, pack * D),
        k.reshape(B, T, Hkv // pack, pack * D),
        v.reshape(B, T, Hkv // pack, pack * D),
        kv_pages, layer,
    )
    attn = jnp.sum(
        attn.reshape(B, T, Hq, pack, D) * part.astype(attn.dtype)[:, :, None],
        axis=-2,
    )
    return attn, kv_pages


# A convolution callback receives (z [B, T, H], taps [3, H], kv_pages, layer)
# -- the rows of ``B (.) X``, one layer's filter, the cache with the
# convolution layers' state on it (kv_cache.ConvKV) and the layer's index
# among the convolution layers -- and returns (mixed [B, T, H], kv_pages):
# each row mixed with its two predecessors, the state left for the next
# step.  Only the steps that carry that state have one (step.py).
ConvFn = Callable[
    [jax.Array, jax.Array, Any, jax.Array], Tuple[jax.Array, Any]
]


def _conv_operator(
    lp: Params, h: jax.Array, cfg: ModelConfig, conv_fn: Optional[ConvFn],
    kv_pages, layer,
) -> Tuple[jax.Array, Any]:
    """The gated short convolution (lfm2_moe): ``[B | C | X] = W_in h``,
    ``z = B (.) X``, a causal depthwise 3-tap filter over ``z``
    (``conv_fn``), ``W_out (C (.) conv)``."""
    if conv_fn is None:
        from .kv_cache import refuse

        refuse(cfg, "classic_step")
    b, c, x = jnp.split(h @ mat(lp["conv_in"]), 3, axis=-1)
    mixed, kv_pages = conv_fn(b * x, lp["conv_taps"], kv_pages, layer)
    return (c * mixed) @ mat(lp["conv_out"]), kv_pages


# A delta callback is the convolution callback of a trunk with gated
# delta-rule layers.  It receives (u [B, T, C], taps [4, C], g [B, T, Hv],
# beta [B, T, Hv], kv_pages, layer): the rows of ``[q | k | v]`` before
# their convolution, one layer's filter, the log-decay and the step size a
# value head in float32, the cache with the lanes' state on it
# (kv_cache.DeltaKV) and the layer's index among the linear layers.  It
# returns (o [B, T, Hv, dv] float32, kv_pages): every row's read of its
# head's state after the row's own update, the state left for the next
# step.  Only the steps that carry that state have one (step.py).
DeltaFn = Callable[..., Tuple[jax.Array, Any]]


def delta_columns(heads: int, widths: Tuple[int, ...]):
    """The columns of a published projection that reads a head at a time
    ``[part_0 | part_1 | ..]`` (``widths`` of the parts within one head;
    ``W_qkvz``: ``(dk, dk, r dv, r dv)`` a key head, ``W_ba``: ``(r, r)``, the
    query projection's ``[query | gate]``: ``(D, D)`` a query head), in the
    order the tree keeps them: every head's ``part_0``, then every head's
    ``part_1``, ...  ``w[:, delta_columns(..)]`` is a loader's one step."""
    import numpy as np

    per = sum(widths)
    starts = np.cumsum((0, *widths[:-1]))
    return np.concatenate([
        (np.arange(heads)[:, None] * per + s0 + np.arange(w)[None, :]).reshape(-1)
        for s0, w in zip(starts, widths)
    ])


def _gated_delta_operator(
    lp: Params, h: jax.Array, cfg: ModelConfig, delta_fn: Optional[DeltaFn],
    kv_pages, layer,
) -> Tuple[jax.Array, Any]:
    """The gated delta rule (qwen3_next): ``[q | k | v | z] = W_qkvz h``,
    ``[b | a] = W_ba h``; ``beta = sigmoid(b)``, ``g = -exp(A_log)
    softplus(a + dt_bias)`` in float32; ``delta_fn`` runs the convolution
    over ``[q | k | v]`` and the recurrence; ``W_o (w rms(o) silu(z))``
    with the norm over a head's values.  The tree holds both projections'
    columns in that order, every key head's ``q`` first (value head ``i r +
    j`` is key head ``i``'s ``j``-th): the published tensors interleave
    them a key head at a time, which a loader undoes once
    (``delta_columns``), where a split of every step's rows a head at a
    time has XLA copy the layers' weights into a layout of its own (432 MB
    beside a step at Qwen3-Next's cut, compiled for a described v5e)."""
    if delta_fn is None:
        from .kv_cache import refuse

        refuse(cfg, "classic_step")
    B, T, _ = h.shape
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    C = 2 * Hk * dk + Hv * dv
    qkvz = h @ mat(lp["gdn_in"])
    u, z = qkvz[..., :C], qkvz[..., C:]
    ba = (h @ mat(lp["gdn_ba"])).astype(jnp.float32)
    b, a = ba[..., :Hv], ba[..., Hv:]
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(lp["gdn_a_log"].astype(jnp.float32)) * jax.nn.softplus(
        a + lp["gdn_dt_bias"].astype(jnp.float32)
    )
    o, kv_pages = delta_fn(u, lp["gdn_taps"], g, beta, kv_pages, layer)
    o = rms_norm(o, lp["gdn_norm"], cfg.rms_norm_eps).astype(h.dtype)
    o = o.astype(jnp.float32) * jax.nn.silu(
        z.reshape(B, T, Hv, dv).astype(jnp.float32)
    )
    return o.astype(h.dtype).reshape(B, T, Hv * dv) @ mat(lp["gdn_out"]), kv_pages


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
    q_factor: Optional[jax.Array] = None,  # [B, T] per-position query scale
    kind: Optional[str] = None,  # "sliding" | "full" | "conv" by layer_pattern
    conv_fn: Optional[ConvFn] = None,
    op_layer: Optional[jax.Array] = None,  # index among its kind's layers,
    # where the cache holds the kinds apart from the stack (has_conv)
    reach: Optional[jax.Array] = None,  # [2] i32 running count, or None
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """One decoder layer (norm -> attention -> norm -> MLP, residuals).
    Shared by the single-device layer scan and the pipeline-parallel stage
    loop so the math cannot diverge.  ``row_valid`` lets the expert MLP
    skip the rows nobody reads (:func:`_moe_mlp_counted`); a step that
    counts what its grouped expert MLPs read hands ``reach`` (experts
    whose matrices were read, experts held) and gets it back with this
    layer's added, as the third result.  In a trunk
    of window and full layers ``kind`` is this layer's, known at trace
    time: ``cos``/``sin`` are its kind's table, and ``attn_fn`` is handed
    the kind to pick pool, page table and window by
    (``attention.layer_view``)."""
    if kind is not None:
        attn_fn = partial(attn_fn, kind=kind)
    B, T, _ = x.shape
    D = cfg.head_dim
    h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps, cfg.rms_norm_offset)
    # where the cache holds the kinds apart, this layer's place among its own
    layer_in_cache = layer if op_layer is None else op_layer
    if kind == "conv":  # the kind chooses the operator
        op, kv_pages = _conv_operator(
            lp, h, cfg, conv_fn, kv_pages, layer_in_cache
        )
        x = x + op
    elif kind == "linear":
        op, kv_pages = _gated_delta_operator(
            lp, h, cfg, conv_fn, kv_pages, layer_in_cache
        )
        x = x + op
    elif cfg.is_mla:
        attn, kv_pages = _latent_attention(
            lp, h, cos, sin, cfg, attn_fn, kv_pages, layer, q_factor
        )
        x = x + attn @ mat(lp["wo"])
    else:
        q = h @ mat(lp["wq"])
        k = h @ mat(lp["wk"])
        v = h @ mat(lp["wv"])
        if "bq" in lp:
            q = q + lp["bq"]
            k = k + lp["bk"]
            v = v + lp["bv"]
        gate = None
        if cfg.attn_output_gate:  # [every head's query | every head's gate]
            q, gate = jnp.split(q, 2, axis=-1)
            gate = gate.reshape(B, T, cfg.num_heads, D)
        q = q.reshape(B, T, cfg.num_heads, D)
        k = k.reshape(B, T, cfg.num_kv_heads, D)
        v = v.reshape(B, T, cfg.num_kv_heads, D)
        if cfg.qk_norm:  # Qwen3, LFM2: per-head RMSNorm before RoPE
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps, cfg.rms_norm_offset)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps, cfg.rms_norm_offset)
        R = cfg.rope_dim
        if R < D:  # partial_rotary_factor: the head's first columns turn
            q = jnp.concatenate(
                [apply_rope(q[..., :R], cos, sin), q[..., R:]], axis=-1)
            k = jnp.concatenate(
                [apply_rope(k[..., :R], cos, sin), k[..., R:]], axis=-1)
        else:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        if cfg.kv_head_pack > 1:
            attn, kv_pages = _packed_heads_attention(
                q, k, v, cfg, attn_fn, kv_pages, layer_in_cache
            )
        else:
            attn, kv_pages = attn_fn(q, k, v, kv_pages, layer_in_cache)
        if gate is not None:
            attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                attn.dtype
            )
        x = x + attn.reshape(B, T, cfg.num_heads * D) @ mat(lp["wo"])
    h2 = rms_norm(x, lp["post_norm"], cfg.rms_norm_eps, cfg.rms_norm_offset)
    # a layer in front of the periods may have a dense MLP in a routed
    # model (lfm2_moe): told by its weights
    if cfg.is_moe and "router" in lp:
        out, sizes = _moe_mlp_counted(lp, h2, cfg, row_valid, layer)
        x = x + out
        if reach is not None and sizes is not None:
            reach = reach + jnp.stack([jnp.sum(sizes > 0), sizes.size])
    else:
        x = x + _dense_mlp(lp, h2, cfg.hidden_act)
    return x, kv_pages, reach


def scan_layers(
    lp_stack: Params,
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    x: jax.Array,  # [B, T, H]
    cos: jax.Array,
    sin: jax.Array,
    cfg: ModelConfig,
    attn_fn: AttnFn,
    row_valid: Optional[jax.Array] = None,
    q_factor: Optional[jax.Array] = None,
    rope_by_kind: Optional[Dict[str, Tuple[jax.Array, jax.Array]]] = None,
    conv_fn: Optional[ConvFn] = None,
    reach: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Scan ``transformer_layer`` over the stacked weights; ``reach`` rides
    the carry beside the cache (None: a carry with nothing in it).

    kv_pages rides the scan CARRY and each layer scatters into its slice in
    place; making it a scanned input/stacked output would copy the whole
    cache every call (see AttnFn note above).  Shared by the single-device
    trunk and the pipeline-parallel stage loop (which passes its
    stage-local weight/KV stacks).

    A trunk of window and full layers (``cfg.layer_pattern``) scans over
    its periods: the body runs the period's layers in order, each with its
    kind known at trace time, its ``(cos, sin)`` from ``rope_by_kind``.
    Where the kinds differ in operator (``cfg.has_conv``) the stack holds
    what every layer has (norms, experts) and under ``"attn"`` and
    ``"conv"`` each kind's operator, stacked over that kind's layers."""
    # layers of the stack in hand (a latent pool holds two layers a slab)
    L = lp_stack["input_norm"].shape[0]
    # Where the expert MLP takes the grouped kernel, the experts' weights
    # stay whole and the kernel indexes the stack by layer: a custom call
    # cannot fuse the scan's slice, which would then be a copy of every
    # expert's matrices in every layer of every step.
    whole: Params = {}
    N = x.shape[0] * x.shape[1]
    if cfg.is_moe and _moe_takes_grouped(lp_stack, cfg, N):
        whole = {k: lp_stack[k] for k in _EXPERT_WEIGHTS}
        lp_stack = {k: v for k, v in lp_stack.items() if k not in whole}

    def layer(carry, scanned):
        x, kv, reach = carry
        lp, idx = scanned
        return transformer_layer(
            {**lp, **whole}, x, cos, sin, cfg, attn_fn, kv, idx, row_valid,
            q_factor, reach=reach,
        ), None

    pattern = cfg.layer_pattern
    if pattern is None:
        carry, _ = jax.lax.scan(
            layer, (x, kv_pages, reach),
            (lp_stack, jnp.arange(L, dtype=jnp.int32)),
        )
        return carry

    def at(stack, idx):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, False), stack
        )

    ops: Dict[str, Params] = {}
    if cfg.state_kind:
        ops = {"full": lp_stack["attn"], cfg.state_kind: lp_stack[cfg.state_kind]}
        lp_stack = {
            k: v for k, v in lp_stack.items() if k not in ("attn", cfg.state_kind)
        }

    def period(carry, first):
        # a layer's weights are sliced out of the whole stack by its own
        # index, as the scan above slices its scanned operand
        x, kv, reach = carry
        for j, kind in enumerate(pattern):
            idx = first + j
            lp = at(lp_stack, idx)
            op_layer = None
            if ops:
                # the layer's place among its kind's: whole periods before
                # it, its rank in its own, the layers in front of the scan
                rank = first // len(pattern) * pattern.count(kind) + (
                    pattern[:j].count(kind)
                )
                lp = {**lp, **at(ops[kind], rank)}
                op_layer = rank + cfg.lead_kind_layers(kind)
            c, s = rope_by_kind[kind]
            x, kv, reach = transformer_layer(
                {**lp, **whole}, x, c, s, cfg, attn_fn, kv, idx, row_valid,
                q_factor, kind, conv_fn, op_layer, reach,
            )
        return (x, kv, reach), None

    carry, _ = jax.lax.scan(
        period, (x, kv_pages, reach),
        jnp.arange(0, L, len(pattern), dtype=jnp.int32),
    )
    return carry


def transformer(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, T] or [B] int32
    positions: jax.Array,  # same leading shape as tokens
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    attn_fn: AttnFn,
    mm: "Optional[Tuple[jax.Array, jax.Array]]" = None,
    row_valid: Optional[jax.Array] = None,
    conv_fn: Optional[ConvFn] = None,
    count_reached: bool = False,
) -> Tuple[jax.Array, ...]:
    """Run the trunk; returns (hidden [.., H], updated kv_pages).

    ``count_reached`` asks for a third result, ``[2] int32``: over the
    layers whose expert MLP took the grouped layout, the experts whose
    matrices were read and the experts held (:func:`moe_counts_reached`
    says of which steps that is worth asking).

    ``conv_fn`` serves a trunk's convolution layers (:data:`ConvFn`); a
    step that has none cannot run such a trunk, and says so.

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
    ropes = None
    if cfg.layer_pattern is None:
        cos, sin = rope_cos_sin(
            positions, cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling
        )  # [B, T, D]
    else:  # one table a kind of layer, built once a step
        ropes = {
            kind: rope_cos_sin(positions, cfg.rope_dim, *cfg.kind_rope(kind))
            for kind in sorted(set(cfg.layer_pattern + (cfg.lead_pattern or ())))
            if kind not in ("conv", "linear")
        }
        # a convolution layer, a delta-rule layer rotate nothing
        ropes["conv"] = ropes["linear"] = (None, None)
        cos = sin = None
    q_factor = None
    if cfg.query_pos_scaling is not None:
        beta, orig_max = cfg.query_pos_scaling
        q_factor = 1.0 + beta * jnp.log1p(
            (positions // orig_max).astype(jnp.float32)
        )

    if squeeze and row_valid is not None:
        row_valid = row_valid[:, None]
    reach = jnp.zeros((2,), jnp.int32) if count_reached else None
    # layers in front of the periods, each with its own shapes: unrolled
    seen: Dict[str, int] = {}
    for kind, lp in zip(cfg.lead_pattern or (), params.get("lead", ())):
        c, s = ropes[kind]
        x, kv_pages, reach = transformer_layer(
            lp, x, c, s, cfg, attn_fn, kv_pages, None, row_valid, q_factor,
            kind, conv_fn, jnp.int32(seen.get(kind, 0)), reach,
        )
        seen[kind] = seen.get(kind, 0) + 1
    x, new_kv_pages, reach = scan_layers(
        params["layers"], kv_pages, x, cos, sin, cfg, attn_fn, row_valid,
        q_factor, ropes, conv_fn, reach,
    )

    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps, cfg.rms_norm_offset)
    if squeeze:
        x = x[:, 0]
    if count_reached:
        return x, new_kv_pages, reach
    return x, new_kv_pages


def lm_logits(params: Params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    if cfg.tie_word_embeddings:
        w = params["embed"].T
    else:
        w = mat(params["lm_head"])
    return (hidden @ w).astype(jnp.float32)
