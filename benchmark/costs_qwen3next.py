"""The qwen3_next family's counts (``ctx["model_costs"]``; README, "A
configuration"): gated delta-rule layers and gated attention layers in
periods of ``full_attention_interval``, every MLP ``router_experts`` routed
experts of ``moe_intermediate_size`` of which this chip holds ``num_experts``,
``num_experts_per_tok`` a token, beside a gated shared expert; an untied
head; a cache that holds the attention layers alone, the linear layers'
state in the lanes and in a pool of snapshot slots.  No JAX."""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

from .costs_attn import keys_read, pairs  # (query, key) arithmetic, no keys of a family

CHUNK = 64  # rows of a chunk of the delta rule (the published chunk)


def layers_of(cfg: Dict[str, Any], kind: str) -> int:
    """Layers of ``kind`` (``linear`` or ``attention``)."""
    full = cfg["num_hidden_layers"] // cfg.get("full_attention_interval", 4)
    return full if kind == "attention" else cfg["num_hidden_layers"] - full


def conv_width(cfg: Dict[str, Any]) -> int:
    """Channels of a linear layer's convolution, ``[q | k | v]``."""
    return (2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
            + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def state_values(cfg: Dict[str, Any]) -> int:
    """Values of one layer's recurrent state: a matrix a value head."""
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"])


def operator_params(cfg: Dict[str, Any], kind: str) -> int:
    """Parameters of one layer's operator."""
    h = cfg["hidden_size"]
    if kind == "linear":
        hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
        c = conv_width(cfg)
        return (h * (c + hv * dv) + h * 2 * hv + cfg["linear_conv_kernel_dim"] * c
                + hv * dv * h + 2 * hv + dv)
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return h * hq * 2 * d + 2 * h * hkv * d + hq * d * h + 2 * d


def expert_params(cfg: Dict[str, Any]) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: Dict[str, Any]) -> int:
    h = cfg["hidden_size"]
    return 3 * h * cfg.get("shared_expert_intermediate_size", 0) + h


def held_rows_and_experts(tokens: float, cfg: Dict[str, Any]) -> Tuple[float, float]:
    """Of ``tokens`` routed tokens: the assignments that go to a held expert,
    and the held experts that get at least one, both in the mean of a router
    that spreads its ``num_experts_per_tok`` choices evenly over its width."""
    e, width = cfg["num_experts"], cfg.get("router_experts", cfg["num_experts"])
    assignments = tokens * cfg["num_experts_per_tok"]
    return assignments * e / width, e * (1.0 - (1.0 - 1.0 / width) ** assignments)


def weight_bytes(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """Bytes of the weights one forward step has to stream: every layer's
    operator, router and shared expert, the slice of the head, and **the
    held experts a step's rows reach**, counted for the step most passes are:
    a decode step of the configuration's lanes (16 lanes route 160
    assignments over 512: 34 of the 128 held experts in the mean).  A chunk
    step of 2048 rows reaches all 128 and is counted as if it reached those
    34, so the share this feeds can only read low, never bytes no step moves
    (ledger 51 left longdoc-open reading all held experts a pass)."""
    h = cfg["hidden_size"]
    lanes = cfg.get("engine", {}).get("max_batch_size", 1)
    _rows, reached = held_rows_and_experts(lanes, cfg)
    total = h * cfg["vocab_size"]
    total += layers_of(cfg, "linear") * operator_params(cfg, "linear")
    total += layers_of(cfg, "attention") * operator_params(cfg, "attention")
    total += cfg["num_hidden_layers"] * (
        h * cfg.get("router_experts", cfg["num_experts"]) + shared_params(cfg)
        + reached * expert_params(cfg))
    return float(dtype_bytes) * total


def resident_bytes(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """Bytes of every weight the chip holds."""
    h = cfg["hidden_size"]
    total = 2 * h * cfg["vocab_size"]
    total += layers_of(cfg, "linear") * operator_params(cfg, "linear")
    total += layers_of(cfg, "attention") * operator_params(cfg, "attention")
    total += cfg["num_hidden_layers"] * (
        h * cfg.get("router_experts", cfg["num_experts"]) + shared_params(cfg)
        + cfg["num_experts"] * expert_params(cfg) + 2 * h)
    return float(dtype_bytes) * (total + h)


def kv_bytes_per_token(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """Bytes a token takes in the pool: a K/V pair a KV head an ATTENTION
    layer; a linear layer keeps nothing a token."""
    return 2.0 * layers_of(cfg, "attention") * cfg["num_key_value_heads"] * cfg[
        "head_dim"] * dtype_bytes


def state_bytes_per_snapshot(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """Bytes of one snapshot (and of one lane's state): a float32 matrix a
    value head and the convolution's three rows, a linear layer."""
    return float(layers_of(cfg, "linear")) * (
        4 * state_values(cfg)
        + dtype_bytes * (cfg["linear_conv_kernel_dim"] - 1) * conv_width(cfg))


def forward_passes(op_counts: Dict[str, int], cfg: Dict[str, Any]) -> float:
    """Forward passes among a trace's device events: every attention layer
    of a pass runs one attention kernel (packed or decode), a linear layer
    none."""
    kernels = sum(n for label, n in op_counts.items() if "attention" in label)
    return kernels / layers_of(cfg, "attention")


def attn_launch(qs: Iterable[int], ctxs: Iterable[int], cfg: Dict[str, Any],
                dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one launch of one attention layer over its
    lanes, packed or decode: a lane brings ``q`` fresh rows whose last reads
    ``ctx`` keys.  Every query head multiplies (2 D a key for the scores, 2 D
    for the values); keys and values are read once a KV head, the queries
    read and the output written once."""
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    flops = nbytes = 0.0
    for q, ctx in zip(qs, ctxs):
        flops += 4.0 * hq * d * pairs(q, ctx)
        nbytes += float(dtype_bytes) * d * (2 * hkv * keys_read(q, ctx) + 2 * hq * q)
    return flops, nbytes


def gdn_chunk_launch(qs: Iterable[int], cfg: Dict[str, Any],
                     dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one linear layer of a packed step over its
    lanes' segments of ``q`` rows.  A segment of more than one row runs in
    ``ceil(q / 64)`` chunks a value head, each: ``K K^T`` and ``Q K^T`` (2 C C
    dk each), the ten products of ``(I - A)^-1`` (2 C^3 each), ``T (beta V)``
    and ``W V''`` (2 C C dv each), ``T (beta e^G K)`` (2 C C dk), ``K' S``, ``Q
    S`` and ``Kd^T V''`` (2 C dk dv each); a segment of one row takes the
    recurrence's one step (three products of dk dv a head).  Bytes: a row's
    ``[q | k | v]`` read and its ``o`` written in float32, a lane's state read
    and written once in float32."""
    hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    c = CHUNK
    a_chunk = 2.0 * c * c * (2 * dk + 2 * dv + dk) + 20.0 * c ** 3 + 6.0 * c * dk * dv
    flops = nbytes = 0.0
    for q in qs:
        if q <= 0:
            continue
        flops += hv * (6.0 * dk * dv if q == 1 else -(-q // c) * a_chunk)
        nbytes += q * (dtype_bytes * conv_width(cfg) + 4.0 * hv * dv)
        nbytes += 2 * 4.0 * state_values(cfg)
    return flops, nbytes


def gdn_decode_launch(lanes: int, cfg: Dict[str, Any],
                      dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one linear layer of a fused decode step over
    ``lanes`` lanes: the recurrence's one step a lane."""
    return gdn_chunk_launch([1] * lanes, cfg, dtype_bytes)


def held_grouped_launch(tokens: int, cfg: Dict[str, Any],
                        dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one grouped product of a step that routes
    ``tokens`` valid tokens: the rows to held experts multiply, and an
    expert's matrix is read only if a row reached it."""
    h, i = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows, experts = held_rows_and_experts(tokens, cfg)
    return 2.0 * rows * h * i, float(dtype_bytes) * (experts * h * i + rows * (h + i))
