"""What the latent-attention kernels and the held experts' grouped product
cannot avoid: operations and bytes of one launch (one layer) from its
shapes, for a configuration of the mistral4 family.  No JAX.

**Absorbed form** (the form both kernels of this program compute,
``dynamo_tpu/ops/latent_attention.py``).  A cached token is one row of ``W =
kv_lora_rank + qk_rope_head_dim`` values, shared by every head.  A (query
row, key) pair costs every head ``2 W`` operations for its score and ``2 C``
for the values (``C = kv_lora_rank``): at the published widths 32 x (640 +
512) = 36 864 operations on a 640-byte row.  Rows at positions ``ctx - q ..
ctx - 1`` read keys ``0 .. p`` (causal); a lane's keys are read once, its
queries read (``Hq W`` a row) and its output written (``Hq C`` a row) once.
The up-projections before and after attention are matrix products of the
trunk, not of the kernel, and are not counted here.  A second read of a key
(the kernel walks a lane's keys once per block of query rows), the k_r tile
of the neighbouring layer that rides along, padding and masked blocks are
the kernel's to avoid.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

from .costs_attn import pairs


def widths(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """(heads, row width W, value width C)."""
    c = cfg["kv_lora_rank"]
    return cfg["num_attention_heads"], c + cfg["qk_rope_head_dim"], c


def absorbed_lane(q: int, ctx: int, cfg: Dict[str, Any], dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one lane of ``q`` fresh rows whose last row
    reads ``ctx`` keys, in one layer."""
    hq, w, c = widths(cfg)
    flops = float(hq) * pairs(q, ctx) * (2 * w + 2 * c)
    nbytes = float(dtype_bytes) * (ctx * w + hq * q * (w + c))
    return flops, nbytes


def absorbed_launch(qs: Iterable[int], ctxs: Iterable[int], cfg: Dict[str, Any]) -> Tuple[float, float]:
    """(operations, bytes) of one launch over all its lanes."""
    flops = nbytes = 0.0
    for q, ctx in zip(qs, ctxs):
        f, b = absorbed_lane(q, ctx, cfg)
        flops += f
        nbytes += b
    return flops, nbytes


def decode_launch(ctxs: Iterable[int], cfg: Dict[str, Any]) -> Tuple[float, float]:
    """(operations, bytes) of one decode launch: one query row a lane,
    ``ctx x 640 B`` of rows a lane at the published widths."""
    ctxs = list(ctxs)
    return absorbed_launch([1] * len(ctxs), ctxs, cfg)


def held_rows_and_experts(tokens: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    """Of ``tokens`` routed tokens: the assignments that go to a held expert,
    and the held experts that get at least one, both in the mean of a router
    that spreads its ``num_experts_per_tok`` choices evenly over its width."""
    e, width = cfg["n_routed_experts"], cfg.get("router_experts", cfg["n_routed_experts"])
    assignments = tokens * cfg["num_experts_per_tok"]
    return assignments * e / width, e * (1.0 - (1.0 - 1.0 / width) ** assignments)


def held_grouped_launch(tokens: int, cfg: Dict[str, Any], dtype_bytes: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one grouped product of a step that routes
    ``tokens`` valid tokens: the rows to held experts multiply, and an
    expert is read only if a row reached it (a question of 60 tokens
    reaches 27 of the 32 held experts; a chunk of 2048 all of them)."""
    h, i = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows, experts = held_rows_and_experts(tokens, cfg)
    return 2.0 * rows * h * i, float(dtype_bytes) * (experts * h * i + rows * (h + i))
