"""The mistral4 family's counts (``ctx["model_costs"]``; README, "A
configuration"): latent attention, routed experts of width
``moe_intermediate_size`` of which this chip holds ``n_routed_experts``, a
router over ``router_experts``, shared experts.  No JAX."""

from __future__ import annotations

from typing import Any, Dict


def layer_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one layer's matrices, by part."""
    h, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    r, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    i = cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    return {
        "attention": h * r + r * hq * (dn + dr) + h * (c + dr)
        + c * hq * (dn + dv) + hq * dv * h,
        "shared_experts": 3 * h * i * cfg.get("n_shared_experts", 0),
        "router": h * cfg.get("router_experts", held),
        "held_experts": 3 * h * i * held,
    }


def weight_bytes(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """Bytes of the weights one forward step has to stream: every layer's
    attention, shared experts, router and held experts (at the cells' batch
    every held expert has a row routed to it) and the slice of the output
    head; the embedding is a gather."""
    layer = sum(layer_params(cfg).values())
    return float(dtype_bytes) * (
        cfg["num_hidden_layers"] * layer + cfg["hidden_size"] * cfg["vocab_size"])


def resident_bytes(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """Bytes of every weight the chip holds: the streamed ones and the
    slice of the embedding."""
    return weight_bytes(cfg, dtype_bytes) + float(dtype_bytes) * (
        cfg["hidden_size"] * cfg["vocab_size"])


def kv_bytes_per_token(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """One latent row a layer: ``kv_lora_rank + qk_rope_head_dim`` values,
    no K/V pair, no head axis."""
    return float(dtype_bytes) * cfg["num_hidden_layers"] * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def forward_passes(op_counts: Dict[str, int], cfg: Dict[str, Any]) -> float:
    """Forward passes among a trace's device events: every layer of a pass
    runs one attention kernel (``latent_packed_attention``, or
    ``latent_decode_attention`` in a fused decode step)."""
    kernels = sum(n for label, n in op_counts.items() if "attention" in label)
    return kernels / cfg["num_hidden_layers"]
