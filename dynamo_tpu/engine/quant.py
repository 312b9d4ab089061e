"""Weight-only int8 quantization for the serving engine.

Decode at small batch is HBM-bound: every step streams the full weight set,
so halving the weight bytes is (up to the dequant cost) a ~2x decode-
throughput lever.  The reference reaches quantized serving through its
engines (vLLM/TRT-LLM checkpoints); here it is first-party: per-output-
channel symmetric int8 with the scale applied at the point of use --
``x @ (q.astype(bf16) * s)`` -- which XLA fuses into the matmul's operand
read on TPU, so the bf16 weights are never materialized in HBM.

What quantizes: the per-layer matmul weights (attention projections, a
gated short convolution's two projections and MLP/expert weights) and the
untied ``lm_head``.  What stays bf16: the
embedding table (decode gathers B rows per step, not the whole matrix),
norms/biases (tiny), and a tied lm_head (shared with the embedding).

Accuracy: per-(layer, out-channel) scales keep the quantization error well
under bf16's own rounding for typical weight distributions; the parity
tests pin logits cosine > 0.999 against the bf16 model on the tiny config.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]

# per-layer matmul weights safe to quantize (dense + MoE naming); the
# contraction axis is -2 ("in") in every one of them, so the scale lives on
# the output channel
QUANT_KEYS = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    # latent attention (MLA) and shared experts
    "wq_a", "wq_b", "wkv_a", "wkv_b", "ws_gate", "ws_up", "ws_down",
    # the gated short convolution's two projections (lfm2_moe): the operator
    # of most of such a trunk's layers, streamed every step like the rest
    "conv_in", "conv_out",
    # the gated delta rule's two wide projections (qwen3_next), likewise
    "gdn_in", "gdn_out",
)


@jax.tree_util.register_pytree_node_class
@dataclass
class QuantizedTensor:
    """int8 weight + broadcastable per-output-channel scale.

    A pytree node, so it rides ``lax.scan`` over the layer stack (the scan
    slices the leading L axis of both children) and any tree_map/device_put
    the engine applies to params.
    """

    q: jax.Array  # int8, same shape as the original weight
    s: jax.Array  # compute dtype, shape [..., 1, out]

    def tree_flatten(self):
        return (self.q, self.s), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape


def mat(w: Any) -> jax.Array:
    """Weight at the point of use: dequantize a QuantizedTensor (XLA fuses
    the convert+scale into the consuming matmul's read), pass plain arrays
    through."""
    if isinstance(w, QuantizedTensor):
        return w.q.astype(w.s.dtype) * w.s
    return w


def _quantize_slice(w: jax.Array, dtype: Any) -> QuantizedTensor:
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / s), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q=q, s=s.astype(jnp.dtype(dtype)))


def quantize_tensor(w: jax.Array, dtype: Any) -> QuantizedTensor:
    """Symmetric per-output-channel int8 over the contraction axis (-2).

    Stacked weights ([L, ...] or [L, E, ...]) quantize one leading slice at
    a time: the f32 upcast the rounding needs then peaks at ONE layer's
    size, not the whole stack -- a model loaded near HBM capacity (the
    primary reason to quantize) must not 2x its footprint during init.

    Genuinely *partitioned* weights take the whole-tensor path instead:
    every op here is elementwise or an axis reduction, so GSPMD propagates
    the input sharding onto q and s (a per-slice stack would gather
    shards), and the f32 transient is per-device shard-sized.  Replicated
    weights on a multi-device mesh (dp-only meshes, or leaves whose axis
    didn't divide) still chunk per slice -- replication would otherwise
    materialize the full-stack f32 upcast on every device."""
    sharded = (
        hasattr(w, "sharding") and not w.sharding.is_fully_replicated
    )
    if w.ndim >= 3 and not sharded:
        parts = [_quantize_slice(w[i], dtype) for i in range(w.shape[0])]
        return QuantizedTensor(
            q=jnp.stack([p.q for p in parts]),
            s=jnp.stack([p.s for p in parts]),
        )
    return _quantize_slice(w, dtype)


def _quantize_known(tree: Params, dtype: Any) -> Params:
    return {
        k: quantize_tensor(v, dtype) if k in QUANT_KEYS else v
        for k, v in tree.items()
    }


def quantize_params(params: Params, cfg) -> Params:
    """Quantize the streaming-dominant weights of an assembled params tree
    (one-time, on device).  A trunk whose kinds differ in operator keeps
    each kind's operator stacked under its own key (``layers.attn``,
    ``layers.conv`` / ``layers.linear``) and its leading layers singly under
    ``lead``."""
    out = dict(params)
    layers = _quantize_known(params["layers"], cfg.dtype)
    for kind in ("attn", "conv", "linear"):
        if kind in layers:
            layers[kind] = _quantize_known(layers[kind], cfg.dtype)
    out["layers"] = layers
    if "lead" in params:
        out["lead"] = tuple(_quantize_known(lp, cfg.dtype) for lp in params["lead"])
    if "lm_head" in params:
        out["lm_head"] = quantize_tensor(params["lm_head"], cfg.dtype)
    return out
