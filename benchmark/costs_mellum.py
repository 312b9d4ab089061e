"""The mellum family's counts (``ctx["model_costs"]``; README, "A
configuration"): grouped-query attention in window layers and full layers
(``layer_types``), every MLP sparse (``num_experts`` of width
``moe_intermediate_size``, ``num_experts_per_tok`` a token), and a cache of
two pools: a full layer keeps every token's keys and values, a window layer
the last ``sliding_window`` tokens'.  No JAX."""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

from .costs_attn import keys_read, pairs  # (query, key) arithmetic, no keys of a family

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def layers_of(cfg: Dict[str, Any], kind: str) -> int:
    """Layers of ``kind`` (``window`` or ``full``)."""
    types = cfg.get("layer_types") or ["full_attention"] * cfg["num_hidden_layers"]
    return sum(1 for t in types if KINDS[t] == kind)


def layer_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one layer's matrices, by part."""
    h, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    return {
        "attention": 2 * h * hq * d + 2 * h * hkv * d,
        "router": h * cfg["num_experts"],
        "experts": 3 * h * cfg["moe_intermediate_size"] * cfg["num_experts"],
    }


def weight_bytes(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """Bytes of the weights one forward step has to stream: every layer's
    attention, router and experts (at the cell's batch every expert has a
    row routed to it) and the output head; the embedding is a gather."""
    layer = sum(layer_params(cfg).values())
    return float(dtype_bytes) * (
        cfg["num_hidden_layers"] * layer + cfg["hidden_size"] * cfg["vocab_size"])


def kind_kv_bytes_per_token(cfg: Dict[str, Any], kind: str, dtype_bytes: int = 2) -> float:
    """Bytes a token takes in the pool of ``kind``: a K/V pair a KV head a
    layer of that kind."""
    return 2.0 * layers_of(cfg, kind) * cfg["num_key_value_heads"] * cfg[
        "head_dim"] * dtype_bytes


def kv_bytes_per_token(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """A token inside the window: both pools hold it.  Behind the window
    only ``kind_kv_bytes_per_token(cfg, "full")`` stays."""
    return kind_kv_bytes_per_token(cfg, "full", dtype_bytes) + kind_kv_bytes_per_token(
        cfg, "window", dtype_bytes)


def forward_passes(op_counts: Dict[str, int], cfg: Dict[str, Any]) -> float:
    """Forward passes among a trace's device events: every layer of a pass
    runs one attention kernel (packed or decode, window or full)."""
    kernels = sum(n for label, n in op_counts.items() if "attention" in label)
    return kernels / cfg["num_hidden_layers"]


def attn_launch(qs: Iterable[int], ctxs: Iterable[int], cfg: Dict[str, Any],
                kind: str, dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one packed launch of one layer of ``kind``
    over its lanes: a lane brings ``q`` fresh rows whose last reads ``ctx``
    keys; in a window layer a row reads the last ``sliding_window`` of its
    keys.  Every query head multiplies (2 D a key for the scores, 2 D for
    the values); keys and values are read once a KV head, the queries read
    and the output written once."""
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    window = cfg["sliding_window"] if kind == "window" else 0
    flops = nbytes = 0.0
    for q, ctx in zip(qs, ctxs):
        flops += 4.0 * hq * d * pairs(q, ctx, window)
        nbytes += float(dtype_bytes) * d * (
            2 * hkv * keys_read(q, ctx, window) + 2 * hq * q)
    return flops, nbytes


def grouped_matmul(r: int, cfg: Dict[str, Any], dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one grouped expert product of ``r`` result
    rows: ``2 r H I`` operations; the rows read and the result written
    once, all the experts' matrices read once."""
    h, i, e = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    return 2.0 * r * h * i, float(dtype_bytes) * (e * h * i + r * h + r * i)


def resident_bytes(pages: Dict[str, float], cfg: Dict[str, Any], page_size: int,
                   dtype_bytes: int = 2) -> float:
    """Bytes of the pages that are live or reusable, ``pages`` by pool
    (``full``, ``window``)."""
    return sum(
        n * page_size * kind_kv_bytes_per_token(cfg, kind, dtype_bytes)
        for kind, n in pages.items())
