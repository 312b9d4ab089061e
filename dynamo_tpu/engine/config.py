"""Model architecture config for the first-party JAX engine.

Covers the Llama family surface (Llama 2/3, Mistral, Qwen2 via
``attention_bias``, Mixtral/DeepSeek-style MoE via ``num_experts``, Gemma
via ``rms_norm_offset``/``gelu``/``scale_embeddings``, Phi-3 via fused
qkv/gate_up splitting in the loader, Qwen3 via ``qk_norm``) -- the model
families the reference serves through vLLM/TRT-LLM configs (reference
examples/llm/configs/*.yaml, examples/tensorrt_llm/configs).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
import math
from typing import Any, Dict, Optional, Tuple


def _yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor: ``0.1 * mscale * ln(factor) + 1``."""
    if factor <= 1.0 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_position: int = 4096
    tie_word_embeddings: bool = False
    attention_bias: bool = False  # Qwen2-style qkv bias
    # MoE (Mixtral-style); num_experts == 0 means dense MLP
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # headroom of the capacity path's per-expert buffers over perfect
    # balance (GShard capacity factor): C = ceil(N*K*factor/E) rows an
    # expert, assignments past capacity are dropped.  At E/K or above C
    # holds every token, which means "no drop": a single-device engine then
    # serves its wider steps through the dropless grouped product, which
    # has no capacity (model._moe_mlp reads the path off the input)
    moe_capacity_factor: float = 2.0
    # Gemma-family switches: RMSNorm multiplies by (1 + w), the MLP uses
    # tanh-approximated GELU, and embeddings scale by sqrt(hidden)
    rms_norm_offset: bool = False
    hidden_act: str = "silu"  # "silu" | "gelu_tanh"
    scale_embeddings: bool = False
    # Qwen3-family: per-head RMSNorm on q and k before RoPE
    qk_norm: bool = False
    # Llama-3.1 style frequency-dependent RoPE scaling, stored as a hashable
    # tuple ("llama3", factor, low_freq_factor, high_freq_factor,
    # original_max_position) -- ModelConfig rides jit as a static arg
    rope_scaling: Optional[tuple] = None
    # sliding-window attention (Mistral/Phi3); None/0 = full attention
    sliding_window: Optional[int] = None
    # latent attention (MLA; mistral4): kv_lora_rank > 0 switches the layer
    # to low-rank queries (q_lora_rank) and one cached row per token of
    # [c_kv (kv_lora_rank) | RoPE(k_r) (qk_rope_head_dim)] shared by every
    # head -- no K/V pair and no head axis in the cache (kv_geometry)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False  # rotate (2i, 2i+1) pairs, not halves
    # queries at position p scale by 1 + beta * ln(1 + floor(p / orig_max))
    # (``llama_4_scaling_beta``, stored as (beta, original_max_position))
    query_pos_scaling: Optional[tuple] = None
    # experts held by this process: ``num_experts`` stays the router's
    # width (routing is over every published expert); 0 = all of them.
    # Assignments to an absent expert contribute nothing here: their
    # owner adds them in a deployment's exchange
    num_local_experts: int = 0
    local_expert_offset: int = 0
    # shared experts: dense SwiGLUs of the expert width added to every token
    num_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    # per-layer attention kinds (mellum): the shortest period of the model's
    # ``layer_types``, each entry "sliding" (attends the last
    # ``sliding_window`` keys) or "full".  None = every layer is the one
    # kind ``sliding_window`` says.  A period holding both kinds gives the
    # cache two pools (kv_cache.KindKV): a window layer's pages behind the
    # window are let go, a full layer's are kept
    layer_pattern: Optional[Tuple[str, ...]] = None
    # RoPE by kind of layer, ``((kind, theta, rope_scaling), ...)``; None =
    # ``rope_theta`` / ``rope_scaling`` for every layer
    rope_by_kind: Optional[Tuple[Tuple[str, float, Optional[tuple]], ...]] = None
    # gated short-convolution layers (lfm2_moe): a ``layer_pattern`` entry
    # "conv" is a layer whose operator mixes a token with its two
    # predecessors by a depthwise 3-tap filter over ``B (.) X`` and gates the
    # result (model._conv_operator) instead of attending.  Such a layer
    # touches no page: the cache holds the attention layers only, and beside
    # them the two rows a sequence carries (kv_cache.ConvKV)
    #
    # layers in front of the periods (``num_dense_layers``): their kinds, in
    # order; each has a dense SwiGLU of ``lead_intermediate_size`` where the
    # periods' layers have experts.  ``num_layers`` counts them too
    lead_pattern: Optional[Tuple[str, ...]] = None
    lead_intermediate_size: int = 0
    # the router's score: "softmax" over the chosen logits, or "sigmoid" of
    # every logit, the chosen renormalised by their sum; ``router_bias``: a
    # per-expert bias added for the choice only, never to a weight
    router_score: str = "softmax"
    router_bias: bool = False
    # gated delta-rule layers (qwen3_next): a ``layer_pattern`` entry
    # "linear" is a layer whose operator keeps a matrix a value head,
    # ``S [linear_key_head_dim, linear_value_head_dim]`` in float32, that
    # every token decays and updates (model._gated_delta_operator), behind a
    # causal depthwise convolution of 4 taps over ``[q | k | v]``.  Such a
    # layer touches no page: the cache holds the attention layers only, the
    # lane holds the state, and a pool of slots holds the few snapshots a
    # prefix hit can resume from (kv_cache.DeltaKV)
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    # the share of a head's width RoPE rotates (its first columns)
    partial_rotary_factor: float = 1.0
    # the query projection carries a gate as wide as the head beside every
    # head's query; the attention's result is multiplied by its sigmoid
    attn_output_gate: bool = False
    # the shared expert's result is multiplied by sigmoid(w_sg . x)
    shared_expert_gate: bool = False
    # activation dtype for compute; params may be stored differently
    dtype: str = "bfloat16"

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def experts_held(self) -> int:
        return self.num_local_experts or self.num_experts

    @property
    def two_kind(self) -> bool:
        """Window layers and full layers in one trunk: two pools of pages."""
        p = self.layer_pattern or ()
        return "sliding" in p and "full" in p

    @property
    def has_conv(self) -> bool:
        """Convolution layers in the trunk: state beside the pages."""
        return "conv" in (self.layer_pattern or ()) + (self.lead_pattern or ())

    @property
    def has_linear(self) -> bool:
        """Gated delta-rule layers in the trunk: a matrix state in the lane,
        snapshots in a pool of slots."""
        return "linear" in (self.layer_pattern or ())

    @property
    def state_kind(self) -> Optional[str]:
        """The kind of the trunk's layers that hold state and no page
        ("conv", "linear"), or None: the stack holds such a kind's operator
        and the attention layers' apart (model.scan_layers), and the pair
        pool holds the attention layers alone."""
        return "conv" if self.has_conv else "linear" if self.has_linear else None

    @property
    def linear_conv_width(self) -> int:
        """Channels of a linear layer's convolution: ``[q | k | v]``."""
        return (
            2 * self.linear_num_key_heads * self.linear_key_head_dim
            + self.linear_num_value_heads * self.linear_value_head_dim
        )

    @property
    def lead_layers(self) -> int:
        return len(self.lead_pattern or ())

    def lead_kind_layers(self, kind: str) -> int:
        """Layers of ``kind`` in front of the periods."""
        return (self.lead_pattern or ()).count(kind)

    def kind_layers(self, kind: str) -> int:
        """Layers of ``kind`` in the whole trunk."""
        p = self.layer_pattern
        periods = (self.num_layers - self.lead_layers) // len(p)
        return self.lead_kind_layers(kind) + periods * p.count(kind)

    def kind_window(self, kind: Optional[str]) -> int:
        """The window a layer of ``kind`` attends (0 = every key); ``None``
        is a trunk of one kind."""
        if kind == "full":
            return 0
        return self.sliding_window or 0

    def kind_rope(self, kind: Optional[str]) -> Tuple[float, Optional[tuple]]:
        """``(theta, rope_scaling)`` of a layer of ``kind``."""
        for k, theta, scaling in self.rope_by_kind or ():
            if k == kind:
                return theta, scaling
        return self.rope_theta, self.rope_scaling

    @property
    def rope_dim(self) -> int:
        """Width of the rotated part of a query/key."""
        if self.is_mla:
            return self.qk_rope_head_dim
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def kv_geometry(self) -> Tuple[int, int, int, int]:
        """``(slabs, sides, heads, width)``: the pool is ``[slabs, sides,
        pages, page, heads, width]``.  A K/V pair per KV head per layer; or
        a latent cache (MLA): one row ``[c_kv | RoPE(k_r)]`` a token a
        layer, no pair and no head axis, two layers' rows side by side in
        one slab row (``kv_cache.LatentKV``: 2 x 320 values is a whole
        number of the chip's 128-lane tiles, 320 is not)."""
        if self.is_mla:
            row = self.kv_lora_rank + self.qk_rope_head_dim
            return -(-self.num_layers // 2), 1, 1, 2 * row
        if self.state_kind:  # the attention layers alone hold pages
            return (self.kind_layers("full"), 2, self.pool_kv_heads,
                    self.pool_head_dim)
        return self.num_layers, 2, self.num_kv_heads, self.head_dim

    @property
    def kv_head_pack(self) -> int:
        """KV heads that share one row of the pool.  A trunk with convolution
        layers whose heads are narrower than the chip's 128 lanes packs as
        many as make a row of whole lanes (LFM2: two heads of 64): a pool of
        64-wide rows is laid out pages-minor by XLA and copied whole around
        every kernel call (1.6 GB twice a step at LFM2's cut, compiled for a
        described v5e), and Mosaic will not slice such a page for a DMA.  The
        attention layer (``model._packed_heads_attention``) puts a query into
        its KV head's part of the row, zeros elsewhere, so every attention
        path sees an ordinary pair pool of ``pool_kv_heads`` heads of
        ``pool_head_dim``.  1 everywhere else, narrow heads or not: a
        one-kind or two-kind pool is also what tensor parallelism shards by
        KV head, what the int8 pool scales a head at a time, and what
        offload, G4 and disaggregation ship as ``[.., Hkv, D]`` blobs, all of
        which read ``num_kv_heads`` and ``head_dim``; this trunk refuses
        each of them by name (``kv_cache.KV_REFUSALS``), so its pool
        is free to differ.  Packing another family's pool is theirs to
        follow first."""
        d = self.head_dim
        if not self.has_conv or d >= 128 or 128 % d:
            return 1
        return math.gcd(128 // d, self.num_kv_heads)

    @property
    def pool_kv_heads(self) -> int:
        return self.num_kv_heads // self.kv_head_pack

    @property
    def pool_head_dim(self) -> int:
        return self.head_dim * self.kv_head_pack

    @property
    def kv_values_per_token(self) -> int:
        """Values a cached token takes over all layers (a two-kind cache:
        while the token lies inside the window; behind it only the full
        layers' share stays, ``kv_cache.PagedKVCache.bytes_per_token``)."""
        slabs, sides, heads, width = self.kv_geometry
        return slabs * sides * heads * width

    @property
    def attn_softmax_scale(self) -> float:
        """Scale of the attention scores: head_dim^-0.5, times YaRN's
        ``mscale_all_dim`` factor squared where the configuration has one."""
        scale = self.head_dim ** -0.5
        rs = self.rope_scaling
        if rs is not None and rs[0] == "yarn":
            m = _yarn_mscale(rs[1], rs[6])
            scale *= m * m
        return scale

    def validate_tp(self, tp: int) -> None:
        """Fail fast when a tensor-parallel degree cannot shard this
        architecture's attention heads.  ``num_heads % tp`` must be 0 for
        the column-parallel qkv split; kv heads that do not divide fall
        back to replicated KV (``_compatible_spec``) -- legal, but the
        decode hot path then pays a cross-chip gather per step, so it is
        an error here rather than a silent 10x regression.  Serving a GQA
        model at tp > num_kv_heads requires head-replication machinery
        this engine does not carry."""
        if tp <= 1:
            return
        if self.is_mla:
            raise ValueError(
                f"tp={tp}: a latent cache (MLA, kv_lora_rank="
                f"{self.kv_lora_rank}) has one row a token and no head axis "
                "to shard; serve it data-parallel (dp) with the experts "
                "split across chips"
            )
        if self.num_heads % tp:
            raise ValueError(
                f"tp={tp} does not divide num_heads={self.num_heads}"
            )
        if self.num_kv_heads % tp:
            raise ValueError(
                f"tp={tp} does not divide num_kv_heads={self.num_kv_heads}: "
                "the paged KV pool would replicate across the tp group and "
                "every decode step would pay a cross-chip gather"
            )

    @classmethod
    def tiny(cls, **overrides: Any) -> "ModelConfig":
        """A CI-sized config: runs in milliseconds on CPU, same code paths."""
        base = dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            max_position=512,
            dtype="float32",
        )
        base.update(overrides)
        return cls(**base)

    @classmethod
    def llama3_8b(cls) -> "ModelConfig":
        return cls(
            vocab_size=128256,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=500000.0,
            max_position=8192,
        )

    @classmethod
    def llama3_70b(cls) -> "ModelConfig":
        return cls(
            vocab_size=128256,
            hidden_size=8192,
            intermediate_size=28672,
            num_layers=80,
            num_heads=64,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=500000.0,
            max_position=8192,
        )

    @classmethod
    def mixtral_8x7b(cls) -> "ModelConfig":
        return cls(
            vocab_size=32000,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=1000000.0,
            max_position=32768,
            num_experts=8,
            num_experts_per_tok=2,
        )

    SUPPORTED_MODEL_TYPES = (
        "llama", "mistral", "qwen2", "mixtral", "gemma", "phi3", "qwen3",
        "mistral4", "mellum", "lfm2_moe", "qwen3_next",
    )

    @classmethod
    def from_hf_config(cls, cfg: Dict[str, Any]) -> "ModelConfig":
        """Build from a HuggingFace ``config.json`` dict (llama/mistral/qwen2/
        mixtral/gemma/phi3/qwen3 architectures).

        Unknown model types raise instead of loading silently: e.g. gemma2
        carries extra pre/post_feedforward_layernorm tensors the assembler
        would skip, producing garbage output with no error."""
        mt = cfg.get("model_type")
        if mt is not None and mt not in cls.SUPPORTED_MODEL_TYPES:
            raise ValueError(
                f"unsupported model_type {mt!r}; supported: "
                f"{', '.join(cls.SUPPORTED_MODEL_TYPES)}"
            )
        # RoPE scaling: llama3 frequency-dependent scaling is implemented;
        # anything else (yarn, longrope, linear, dynamic) must fail loudly
        # for EVERY model type -- loading a scaled checkpoint with plain
        # RoPE produces garbage at long context with no error
        rope_scaling: Optional[tuple] = None
        rs = cfg.get("rope_scaling") or cfg.get("rope_parameters") or None
        if mt == "mistral4":
            return cls._from_mistral4(cfg, rs or {})
        if mt == "mellum":
            return cls._from_mellum(cfg, rs or {})
        if mt == "lfm2_moe":
            return cls._from_lfm2_moe(cfg)
        if mt == "qwen3_next":
            return cls._from_qwen3_next(cfg)
        if rs is not None:
            rs_type = rs.get("rope_type") or rs.get("type")
            if rs_type == "llama3":
                rope_scaling = (
                    "llama3",
                    float(rs["factor"]),
                    float(rs["low_freq_factor"]),
                    float(rs["high_freq_factor"]),
                    int(rs["original_max_position_embeddings"]),
                )
            elif rs_type not in (None, "default"):
                raise ValueError(
                    f"rope_scaling type {rs_type!r} is not supported"
                    " (implemented: llama3)"
                )
        # sliding-window attention: mistral/phi3 enable by presence; the
        # qwen families gate it behind use_sliding_window, whose HF default
        # is False -- a missing key must DISABLE for them or this engine
        # would window checkpoints HF attends fully
        window = cfg.get("sliding_window") or None
        if mt in ("qwen2", "qwen3") and not cfg.get("use_sliding_window", False):
            window = None
        elif window is not None and cfg.get("use_sliding_window") is False:
            window = None
        if window is not None:
            # HF qwen2 windows only layers >= max_window_layers; this engine
            # windows uniformly.  mwl >= num_layers means NO layer windows
            # (disable); 0 < mwl < num_layers is a genuine per-layer mix --
            # fail loudly, not silently-different logits
            mwl = cfg.get("max_window_layers")
            if mwl is not None:
                if mwl >= cfg["num_hidden_layers"]:
                    window = None
                elif mwl > 0:
                    raise ValueError(
                        f"per-layer sliding window (max_window_layers={mwl} <"
                        f" num_hidden_layers={cfg['num_hidden_layers']}) is"
                        f" not supported for model_type {mt!r}: a trunk of"
                        " window and full layers is stated through"
                        " layer_types (model_type 'mellum')"
                    )
        hidden = cfg["hidden_size"]
        heads = cfg["num_attention_heads"]
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim", hidden // heads),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-5)),
            max_position=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            attention_bias=bool(
                cfg.get("attention_bias", False)
                or cfg.get("model_type") == "qwen2"
            ),
            num_experts=cfg.get("num_local_experts", 0),
            num_experts_per_tok=cfg.get("num_experts_per_tok", 2),
            rms_norm_offset=cfg.get("model_type") == "gemma",
            hidden_act=(
                "gelu_tanh"
                if cfg.get("hidden_act", cfg.get("hidden_activation"))
                in ("gelu_pytorch_tanh", "gelu_tanh")
                or cfg.get("model_type") == "gemma"
                else "silu"
            ),
            scale_embeddings=cfg.get("model_type") == "gemma",
            qk_norm=cfg.get("model_type") == "qwen3",
            rope_scaling=rope_scaling,
            sliding_window=window,
        )

    @classmethod
    def _from_mistral4(cls, cfg: Dict[str, Any], rs: Dict[str, Any]) -> "ModelConfig":
        """``mistral4``: latent attention (MLA), routed experts of width
        ``moe_intermediate_size`` with shared experts in every layer, YaRN.
        ``n_routed_experts`` is what this process holds; ``router_experts``
        (default: the same) the router's published width, with
        ``expert_offset`` the first held expert's published index."""
        rs_type = rs.get("rope_type") or rs.get("type")
        if rs_type == "yarn":
            rope_scaling: Optional[tuple] = (
                "yarn",
                float(rs["factor"]),
                int(rs["original_max_position_embeddings"]),
                float(rs.get("beta_fast", 32)),
                float(rs.get("beta_slow", 1)),
                float(rs.get("mscale", 1)),
                float(rs.get("mscale_all_dim", 0)),
            )
        elif rs_type in (None, "default"):
            rope_scaling = None
        else:
            raise ValueError(
                f"rope_scaling type {rs_type!r} is not supported for mistral4"
                " (implemented: yarn)"
            )
        if cfg.get("first_k_dense_replace", 0):
            raise ValueError(
                "mistral4 with first_k_dense_replace > 0 (dense layers before"
                " the expert layers) is not supported"
            )
        if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
            raise ValueError("mistral4 grouped routing (n_group > 1) is not supported")
        if not cfg.get("norm_topk_prob", True):
            raise ValueError("mistral4 without norm_topk_prob is not supported")
        if cfg.get("sliding_window"):
            raise ValueError("mistral4 with a sliding window is not supported")
        nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        if cfg.get("qk_head_dim", nope + rope) != nope + rope:
            raise ValueError("qk_head_dim != qk_nope_head_dim + qk_rope_head_dim")
        held = cfg["n_routed_experts"]
        width = cfg.get("router_experts", held)
        offset = cfg.get("expert_offset", 0)
        if offset < 0 or offset + held > width:
            raise ValueError(
                f"experts {offset}..{offset + held - 1} lie outside the "
                f"router's {width}"
            )
        beta = rs.get("llama_4_scaling_beta")
        heads = cfg["num_attention_heads"]
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["moe_intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=heads,
            head_dim=nope + rope,
            rope_theta=float(rs.get("rope_theta", cfg.get("rope_theta", 10000.0))),
            rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
            max_position=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            num_experts=width,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            num_local_experts=held if held != width else 0,
            local_expert_offset=offset,
            num_shared_experts=cfg.get("n_shared_experts", 0),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            rope_scaling=rope_scaling,
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=nope,
            qk_rope_head_dim=rope,
            v_head_dim=cfg["v_head_dim"],
            rope_interleave=bool(cfg.get("rope_interleave", False)),
            query_pos_scaling=(
                (float(beta), int(rs["original_max_position_embeddings"]))
                if beta else None
            ),
        )

    @staticmethod
    def _yarn_tuple(rs: Dict[str, Any], where: str) -> Optional[tuple]:
        """The ``("yarn", ...)`` tuple :func:`model.rope_cos_sin` takes, from
        a rope section of type ``default`` (None) or ``yarn``; any other
        type raises by name.  An ``attention_factor`` is carried as the
        ``mscale`` that yields it (``0.1 mscale ln(factor) + 1``) with
        ``mscale_all_dim`` 0: it multiplies cos and sin, so the scores take
        its square, as the source's rope code does."""
        rs_type = rs.get("rope_type") or rs.get("type")
        if rs_type in (None, "default"):
            return None
        if rs_type != "yarn":
            raise ValueError(
                f"rope type {rs_type!r} ({where}) is not supported"
                " (implemented: default, yarn)"
            )
        factor = float(rs["factor"])
        mscale = float(rs.get("mscale", 1))
        mscale_all = float(rs.get("mscale_all_dim", 0))
        af = rs.get("attention_factor")
        if af is not None and factor > 1.0:
            mscale, mscale_all = (float(af) - 1.0) / (0.1 * math.log(factor)), 0.0
        return (
            "yarn", factor, int(rs["original_max_position_embeddings"]),
            float(rs.get("beta_fast", 32)), float(rs.get("beta_slow", 1)),
            mscale, mscale_all,
        )

    @staticmethod
    def _shortest_period(kinds) -> int:
        """The shortest ``p`` with ``kinds[i] == kinds[i % p]`` throughout; a
        trunk cut inside a period has one, and is not whole repetitions of
        it."""
        n = len(kinds)
        return next(
            p for p in range(1, n + 1)
            if all(kinds[i] == kinds[i % p] for i in range(n))
        )

    @classmethod
    def _from_mellum(cls, cfg: Dict[str, Any], rs: Dict[str, Any]) -> "ModelConfig":
        """``mellum``: ``layer_types`` as whole periods of
        ``sliding_attention`` / ``full_attention`` layers, RoPE parameters by
        kind of layer, every MLP sparse (``num_experts`` of width
        ``moe_intermediate_size``, ``num_experts_per_tok`` a token, no
        shared expert)."""
        L = cfg["num_hidden_layers"]
        names = {"sliding_attention": "sliding", "full_attention": "full"}
        types = cfg.get("layer_types") or ["full_attention"] * L
        for t in types:
            if t not in names:
                raise ValueError(
                    f"mellum layer_types entry {t!r} is not supported"
                    f" (implemented: {', '.join(names)})"
                )
        if len(types) != L:
            raise ValueError(
                f"mellum layer_types has {len(types)} entries for"
                f" num_hidden_layers={L}"
            )
        kinds = [names[t] for t in types]
        period = cls._shortest_period(kinds)
        if L % period:
            raise ValueError(
                f"mellum layer_types is not whole periods: its shortest"
                f" period has {period} layers, num_hidden_layers={L}"
            )
        pattern = tuple(kinds[:period])
        window = cfg.get("sliding_window") or None
        if "sliding" in pattern and not window:
            raise ValueError(
                "mellum layer_types has sliding_attention layers and no"
                " sliding_window"
            )
        if any(t != "sparse" for t in cfg.get("mlp_layer_types") or []):
            raise ValueError(
                "mellum mlp_layer_types with a dense entry is not supported"
                " (every MLP sparse)"
            )
        if not cfg.get("norm_topk_prob", True):
            raise ValueError("mellum without norm_topk_prob is not supported")
        # rope_parameters: one section a kind of layer, or one flat section
        by_kind = []
        for src, kind in names.items():
            if kind not in pattern:
                continue
            sec = rs.get(src)
            if not isinstance(sec, dict):
                sec = rs
            by_kind.append((
                kind,
                float(sec.get("rope_theta", cfg.get("rope_theta", 10000.0))),
                cls._yarn_tuple(sec, f"rope_parameters of {src}"),
            ))
        if len(set(pattern)) == 1:
            window = window if pattern[0] == "sliding" else None
        heads = cfg["num_attention_heads"]
        hidden = cfg["hidden_size"]
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["moe_intermediate_size"],
            num_layers=L,
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim", hidden // heads),
            rope_theta=by_kind[0][1],
            rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
            max_position=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            num_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            rope_scaling=by_kind[0][2] if len(by_kind) == 1 else None,
            sliding_window=window,
            layer_pattern=pattern if len(set(pattern)) > 1 else None,
            rope_by_kind=tuple(by_kind) if len(by_kind) > 1 else None,
        )

    @classmethod
    def _from_lfm2_moe(cls, cfg: Dict[str, Any]) -> "ModelConfig":
        """``lfm2_moe``: ``layer_types`` of ``conv`` (gated 3-tap short
        convolution) and ``full_attention`` layers (GQA, a norm over each
        head of q and k before RoPE); the first ``num_dense_layers`` layers
        have a dense SwiGLU of ``intermediate_size``, every other an expert
        MLP of ``moe_intermediate_size`` routed by sigmoid scores, the
        choice made with a per-expert bias (``use_expert_bias``).  The
        layers after the dense ones have to be whole periods."""
        L = cfg["num_hidden_layers"]
        names = {"conv": "conv", "full_attention": "full"}
        types = cfg.get("layer_types") or ["full_attention"] * L
        for t in types:
            if t not in names:
                raise ValueError(
                    f"lfm2_moe layer_types entry {t!r} is not supported"
                    f" (implemented: {', '.join(names)})"
                )
        if len(types) != L:
            raise ValueError(
                f"lfm2_moe layer_types has {len(types)} entries for"
                f" num_hidden_layers={L}"
            )
        if cfg.get("conv_bias", False):
            raise ValueError("lfm2_moe with conv_bias is not supported")
        if cfg.get("conv_L_cache", 3) != 3:
            raise ValueError(
                f"lfm2_moe conv_L_cache={cfg['conv_L_cache']} is not supported"
                " (implemented: 3, a filter over a token and its two"
                " predecessors)"
            )
        if not cfg.get("norm_topk_prob", True):
            raise ValueError("lfm2_moe without norm_topk_prob is not supported")
        if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
            raise ValueError("lfm2_moe grouped routing (n_group > 1) is not supported")
        lead = int(cfg.get("num_dense_layers", 0))
        if not 0 <= lead < L:
            raise ValueError(
                f"lfm2_moe num_dense_layers={lead} leaves no expert layer of"
                f" num_hidden_layers={L}"
            )
        kinds = [names[t] for t in types]
        rest = kinds[lead:]
        period = cls._shortest_period(rest)
        if len(rest) % period:
            raise ValueError(
                f"lfm2_moe layer_types after the {lead} dense layers is not"
                f" whole periods: its shortest period has {period} layers,"
                f" {len(rest)} layers follow"
            )
        if "full" not in kinds:
            raise ValueError(
                "lfm2_moe layer_types without a full_attention layer is not"
                " supported (the page pool needs a layer)"
            )
        plain = not lead and set(rest) == {"full"}
        heads = cfg["num_attention_heads"]
        hidden = cfg["hidden_size"]
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["moe_intermediate_size"],
            num_layers=L,
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim") or hidden // heads,
            rope_theta=float(cfg.get("rope_theta", 1000000.0)),
            rms_norm_eps=float(cfg.get("norm_eps", 1e-5)),
            max_position=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=cfg.get("tie_word_embeddings", True),
            num_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            qk_norm=True,
            layer_pattern=None if plain else tuple(rest[:period]),
            lead_pattern=tuple(kinds[:lead]) or None,
            lead_intermediate_size=cfg["intermediate_size"] if lead else 0,
            router_score="sigmoid",
            router_bias=bool(cfg.get("use_expert_bias", False)),
        )

    @classmethod
    def _from_qwen3_next(cls, cfg: Dict[str, Any]) -> "ModelConfig":
        """``qwen3_next``: periods of ``full_attention_interval`` layers, the
        last of each gated softmax attention (a norm over each head of q and
        k, RoPE over ``partial_rotary_factor`` of the head, the result times
        the sigmoid of a gate the query projection carries), the others
        gated delta-rule layers (``linear_*``); every MLP ``num_experts``
        routed experts of ``moe_intermediate_size`` (softmax over all, the
        ``num_experts_per_tok`` largest renormalised) beside a shared expert
        with a sigmoid gate; RMSNorm weights centred at zero.
        ``num_experts`` is what this process holds; ``router_experts``
        (default: the same) the router's published width, ``expert_offset``
        the first held expert's published index (the ``mistral4`` keys)."""
        L = cfg["num_hidden_layers"]
        interval = int(cfg.get("full_attention_interval", 4))
        if cfg.get("mlp_only_layers"):
            raise ValueError(
                f"qwen3_next mlp_only_layers={cfg['mlp_only_layers']} is not"
                " supported (every MLP routed)"
            )
        if cfg.get("decoder_sparse_step", 1) != 1:
            raise ValueError(
                f"qwen3_next decoder_sparse_step={cfg['decoder_sparse_step']}"
                " is not supported (implemented: 1, every MLP routed)"
            )
        if cfg.get("linear_conv_kernel_dim", 4) != 4:
            raise ValueError(
                f"qwen3_next linear_conv_kernel_dim="
                f"{cfg['linear_conv_kernel_dim']} is not supported"
                " (implemented: 4, a filter over a token and its three"
                " predecessors)"
            )
        if interval < 1 or L % interval:
            raise ValueError(
                f"qwen3_next num_hidden_layers={L} is not whole periods of"
                f" full_attention_interval={interval}"
            )
        types = cfg.get("layer_types")
        want = [
            "full_attention" if (i + 1) % interval == 0 else "linear_attention"
            for i in range(L)
        ]
        if types is not None and list(types) != want:
            raise ValueError(
                "qwen3_next layer_types differs from what"
                f" full_attention_interval={interval} lays out"
            )
        if not cfg.get("norm_topk_prob", True):
            raise ValueError("qwen3_next without norm_topk_prob is not supported")
        if cfg.get("use_sliding_window") or cfg.get("rope_scaling"):
            raise ValueError(
                "qwen3_next with a sliding window or rope_scaling is not"
                " supported"
            )
        I = cfg["moe_intermediate_size"]
        shared = int(cfg.get("shared_expert_intermediate_size", 0))
        if shared % I:
            raise ValueError(
                f"qwen3_next shared_expert_intermediate_size={shared} is not a"
                f" multiple of moe_intermediate_size={I}"
            )
        hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
        if hv % hk:
            raise ValueError(
                f"qwen3_next linear_num_value_heads={hv} is not a multiple of"
                f" linear_num_key_heads={hk}"
            )
        held = cfg["num_experts"]
        width = cfg.get("router_experts", held)
        offset = cfg.get("expert_offset", 0)
        if offset < 0 or offset + held > width:
            raise ValueError(
                f"experts {offset}..{offset + held - 1} lie outside the "
                f"router's {width}"
            )
        heads = cfg["num_attention_heads"]
        hidden = cfg["hidden_size"]
        pattern = ("linear",) * (interval - 1) + ("full",)
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=I,
            num_layers=L,
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim") or hidden // heads,
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
            max_position=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            num_experts=width,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            num_local_experts=held if held != width else 0,
            local_expert_offset=offset,
            num_shared_experts=shared // I,
            shared_expert_gate=shared > 0,
            rms_norm_offset=True,
            qk_norm=True,
            layer_pattern=pattern if interval > 1 else None,
            linear_num_key_heads=hk,
            linear_num_value_heads=hv,
            linear_key_head_dim=cfg["linear_key_head_dim"],
            linear_value_head_dim=cfg["linear_value_head_dim"],
            partial_rotary_factor=float(cfg.get("partial_rotary_factor", 1.0)),
            attn_output_gate=True,
        )

    @classmethod
    def from_pretrained(cls, model_path: str) -> "ModelConfig":
        cfg_json = os.path.join(model_path, "config.json")
        if os.path.exists(cfg_json):
            with open(cfg_json) as f:
                return cls.from_hf_config(json.load(f))
        # GGUF checkpoint: the architecture config lives in its metadata
        from ..llm.gguf import find_gguf_file, gguf_model_config

        gguf = find_gguf_file(model_path)
        if gguf is not None:
            return gguf_model_config(gguf)
        raise FileNotFoundError(
            f"{model_path}: no config.json and no .gguf file"
        )
