"""The lfm2_moe family's counts (``ctx["model_costs"]``; README, "A
configuration"): gated short-convolution layers and grouped-query attention
layers by ``layer_types``, the first ``num_dense_layers`` layers with a dense
SwiGLU of ``intermediate_size``, every other with ``num_experts`` experts of
``moe_intermediate_size``, ``num_experts_per_tok`` a token; a head tied to the
embedding; a cache that holds the attention layers alone, and beside a page
the convolution layers' two rows at its end.  No JAX."""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

from .costs_attn import keys_read, pairs  # (query, key) arithmetic, no keys of a family

KINDS = {"conv": "conv", "full_attention": "attention"}


def layers_of(cfg: Dict[str, Any], kind: str) -> int:
    """Layers of ``kind`` (``conv`` or ``attention``)."""
    return sum(1 for t in cfg["layer_types"] if KINDS[t] == kind)


def head_dim(cfg: Dict[str, Any]) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def operator_params(cfg: Dict[str, Any], kind: str) -> int:
    """Parameters of one layer's operator: the convolution's two projections
    and three taps a channel, or the attention's four projections and the
    two norms over a head."""
    h = cfg["hidden_size"]
    if kind == "conv":
        return h * 3 * h + h * h + cfg.get("conv_L_cache", 3) * h
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    return 2 * h * hq * d + 2 * h * hkv * d + 2 * d


def mlp_params(cfg: Dict[str, Any], dense: bool) -> int:
    h = cfg["hidden_size"]
    if dense:
        return 3 * h * cfg["intermediate_size"]
    e = cfg["num_experts"]
    bias = e if cfg.get("use_expert_bias") else 0
    return 3 * h * cfg["moe_intermediate_size"] * e + h * e + bias


def weight_bytes(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """Bytes of the weights one forward step has to stream: every layer's
    operator and MLP (at the cell's batch every expert has a row routed to
    it) and the head, which is the embedding read whole; the embedding's own
    read is a gather."""
    dense = int(cfg.get("num_dense_layers", 0))
    total = cfg["hidden_size"] * cfg["vocab_size"]
    for i, t in enumerate(cfg["layer_types"]):
        total += operator_params(cfg, KINDS[t]) + mlp_params(cfg, i < dense)
    return float(dtype_bytes) * total


def kv_bytes_per_token(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """Bytes a token takes in the pool: a K/V pair a KV head an ATTENTION
    layer; a convolution layer keeps nothing a token."""
    return 2.0 * layers_of(cfg, "attention") * cfg["num_key_value_heads"] * head_dim(
        cfg) * dtype_bytes


def state_bytes_per_page(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """Bytes of the snapshot that rides a page: the rows ``B (.) X`` at the
    page's last ``conv_L_cache - 1`` positions, a convolution layer."""
    return float(dtype_bytes) * layers_of(cfg, "conv") * (
        cfg.get("conv_L_cache", 3) - 1) * cfg["hidden_size"]


def forward_passes(op_counts: Dict[str, int], cfg: Dict[str, Any]) -> float:
    """Forward passes among a trace's device events: every attention layer
    of a pass runs one attention kernel (packed or decode), a convolution
    layer none."""
    kernels = sum(n for label, n in op_counts.items() if "attention" in label)
    return kernels / layers_of(cfg, "attention")


def attn_launch(qs: Iterable[int], ctxs: Iterable[int], cfg: Dict[str, Any],
                dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one launch of one attention layer over its
    lanes, packed or decode (a decode lane brings one row): a lane brings
    ``q`` fresh rows whose last reads ``ctx`` keys.  Every query head
    multiplies (2 D a key for the scores, 2 D for the values); keys and
    values are read once a KV head, the queries read and the output written
    once.  What a launch does beyond that (over a pool of two heads a row it
    multiplies a query against both, and masks blocks) is the kernel's to
    avoid."""
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    flops = nbytes = 0.0
    for q, ctx in zip(qs, ctxs):
        flops += 4.0 * hq * d * pairs(q, ctx)
        nbytes += float(dtype_bytes) * d * (2 * hkv * keys_read(q, ctx) + 2 * hq * q)
    return flops, nbytes


def grouped_matmul(r: int, cfg: Dict[str, Any], dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one grouped expert product of ``r`` routed
    rows: ``2 r H I`` operations; the rows read and the result written once,
    all the experts' matrices read once."""
    h, i, e = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    return 2.0 * r * h * i, float(dtype_bytes) * (e * h * i + r * h + r * i)
