"""What a kernel has to do: operations and bytes from shapes, the table of
peaks, and the least time the chip could take.  No JAX.

``peaks``, ``matmul``, ``roofline_seconds`` and ``expert_matmul`` are the
arithmetic every family shares (``ctx["costs"]``).  The last three functions
are the Mistral family's counts (dense or expert MLP under
``intermediate_size``, one attention kernel a layer): the module a
configuration names under ``"costs"`` (``ctx["model_costs"]``) has these
three, and both configurations of that family name this one."""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, Any]:
    """The peaks of a device kind; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in benchmark/peaks.json"
        )
    return table[device_kind]


def matmul(m: int, k: int, n: int, batch: int = 1, dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of ``batch`` products [m, k] @ [k, n]: every
    operand read once and the result written once."""
    flops = 2.0 * batch * m * k * n
    nbytes = float(dtype_bytes) * batch * (m * k + k * n + m * n)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: Dict[str, Any]) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def expert_matmul(e: int, c: int, h: int, i: int) -> Tuple[float, float]:
    """One of the three expert products over buffers [E, C, .]: [C, H] @
    [H, I] or [C, I] @ [I, H] for each expert; the two have the same cost."""
    return matmul(c, h, i, batch=e)


def weight_bytes(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    """Bytes of the weights one forward step has to stream: every layer
    (all experts: at these batch sizes every expert has a token routed to
    it, whether the product goes through capacity buffers or is grouped by
    expert) and the output head; the embedding is a gather."""
    h = cfg["hidden_size"]
    i = cfg["intermediate_size"]
    hq = cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads", hq)
    d = cfg.get("head_dim", h // hq)
    e = cfg.get("num_local_experts", 0)
    attn = h * (hq * d) * 2 + h * (hkv * d) * 2
    mlp = 3 * h * i * max(e, 1) + h * e
    return float(dtype_bytes) * (
        cfg["num_hidden_layers"] * (attn + mlp) + h * cfg["vocab_size"]
    )


def kv_bytes_per_token(cfg: Dict[str, Any], dtype_bytes: int = 2) -> float:
    h = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads", hq)
    d = cfg.get("head_dim", h // hq)
    return 2.0 * cfg["num_hidden_layers"] * hkv * d * dtype_bytes


def forward_passes(op_counts: Dict[str, int], cfg: Dict[str, Any]) -> float:
    """Forward passes among a trace's device events (``op_counts``: events
    by operation label): every layer of a pass runs one attention kernel
    (the packed ragged kernel, or the paged decode kernel of a fused decode
    step), so passes = attention events / layers."""
    kernels = sum(n for label, n in op_counts.items() if "attention" in label)
    return kernels / cfg["num_hidden_layers"]
