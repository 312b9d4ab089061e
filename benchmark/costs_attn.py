"""What the packed ragged attention kernel cannot avoid: operations and
bytes of one launch (one layer) from the lanes' ``(query, context)`` pairs.
No JAX.

A lane brings ``q`` fresh rows; its last row reads ``ctx`` keys, so the
rows sit at positions ``ctx - q .. ctx - 1``.  A row at position ``p`` reads
the keys ``0 .. p`` (causal, also within the fresh block), clipped to the
last ``window`` of them where the model has a sliding window.  Every query
head multiplies (2 x D operations a key for the scores, 2 x D for the
values); keys and values are read once per KV head (grouped-query
attention), the queries read and the output written once.  Padding rows,
masked blocks and a second read of any key are the kernel's to avoid, and
are not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple


def _sum_to(n: int) -> int:
    return n * (n + 1) // 2


def pairs(q: int, ctx: int, window: int = 0) -> int:
    """(query row, key) pairs: the sum over the rows of min(p + 1, window)."""
    lo, hi = ctx - q + 1, ctx  # p + 1 over the rows
    if not window or hi <= window:
        return _sum_to(hi) - _sum_to(lo - 1)
    if lo > window:
        return q * window
    return _sum_to(window) - _sum_to(lo - 1) + (hi - window) * window


def keys_read(q: int, ctx: int, window: int = 0) -> int:
    """Distinct key positions some row reads: from the first row's earliest
    visible key to the last row's own."""
    first = max(0, ctx - q + 1 - window) if window else 0
    return ctx - first


def lane(q: int, ctx: int, cfg: Dict[str, Any], dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one lane in one layer."""
    hq = cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads", hq)
    d = cfg.get("head_dim", cfg["hidden_size"] // hq)
    window = cfg.get("sliding_window") or 0
    flops = 4.0 * hq * d * pairs(q, ctx, window)
    nbytes = float(dtype_bytes) * d * (
        2 * hkv * keys_read(q, ctx, window) + 2 * hq * q)
    return flops, nbytes


def launch(qs: Iterable[int], ctxs: Iterable[int], cfg: Dict[str, Any]) -> Tuple[float, float]:
    """(operations, bytes) of one launch over all its lanes."""
    flops = nbytes = 0.0
    for q, ctx in zip(qs, ctxs):
        f, b = lane(q, ctx, cfg)
        flops += f
        nbytes += b
    return flops, nbytes
