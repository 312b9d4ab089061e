"""What a kernel has to do: operations and bytes from shapes, the table of
peaks, and the least time the chip could take.  No JAX."""

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
    (all experts: buffers of capacity C = N route tokens to all of them at
    these batch sizes) and the output head; the embedding is a gather."""
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
