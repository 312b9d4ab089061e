"""The mellum family's weights from the seed, made on the device, in the type
they are served in (the contract of ``weights.py``: ``build_params(cfg, seed,
each)`` hands the engine the tree its loaders would produce, and the plain
reference draws the same tensors again, a layer and an expert at a time).

Every matrix is normal with the variance ``1/fan_in`` (the embedding:
variance 1; norm weights: uniform in [0.5, 1.5]; the router: ``ROUTER_GAIN``
squared over fan_in, below), keyed by (seed, layer, tensor, expert) and
rounded once to the served dtype.  A layer's tensors do not depend on its
kind (window or full): the kinds differ in mask and rotation only.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from .weights import _matrix, _norm, seed_key  # the draws every family shares

# tensor -> index folded into the key; never renumber
_T = {
    "wq": 0, "wk": 1, "wv": 2, "wo": 3, "input_norm": 4, "post_norm": 5,
    "router": 6, "w_gate": 7, "w_up": 8, "w_down": 9,
    "embed": 10, "final_norm": 11, "lm_head": 12,
}
_TOP = 1 << 20  # "layer" index of the tensors outside the layers
# The router's rows are drawn this many times as large as a projection's
# (PERF.md section 2, the lesson of mistral-small-4-119b): which experts are
# chosen does not depend on the scale, how much each weighs does.  With unit
# logits the eight chosen of 64 weigh 0.2-0.08 and the ninth as much as the
# eighth, so any rounding that moves the last place replaces a twelfth of
# the routed output, and the tail of the error against the float32 reference
# measures that and not the arithmetic.  A trained router is peaked.
ROUTER_GAIN = 4.0
KINDS = {"sliding_attention": "sliding", "full_attention": "full"}
__all__ = ["sizes", "seed_key", "attention_weights", "expert_weights",
           "embed_block", "head_block", "vocab_blocks", "top_weights",
           "build_params"]


def _rope_section(cfg: Dict[str, Any], kind: str) -> Dict[str, Any]:
    rp = cfg.get("rope_parameters") or {}
    sec = rp.get(kind)
    return sec if isinstance(sec, dict) else rp


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes a configuration file states, under short names."""
    h, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    types = cfg.get("layer_types") or ["full_attention"] * L
    rope = {}
    for src, kind in KINDS.items():
        sec = _rope_section(cfg, src)
        rope[kind] = {
            "theta": float(sec.get("rope_theta", cfg.get("rope_theta", 10000.0))),
            "type": sec.get("rope_type") or sec.get("type") or "default",
            "factor": float(sec.get("factor", 1.0)),
            "orig": int(sec.get("original_max_position_embeddings", 0)),
            "beta_fast": float(sec.get("beta_fast", 32)),
            "beta_slow": float(sec.get("beta_slow", 1)),
            "attention_factor": sec.get("attention_factor"),
        }
    return {
        "H": h,
        "L": L,
        "Hq": hq,
        "Hkv": cfg.get("num_key_value_heads", hq),
        "D": cfg.get("head_dim", h // hq),
        "I": cfg["moe_intermediate_size"],
        "E": cfg["num_experts"],
        "K": cfg["num_experts_per_tok"],
        "V": cfg["vocab_size"],
        "eps": float(cfg.get("rms_norm_eps", 1e-6)),
        "window": cfg.get("sliding_window") or 0,
        "kinds": tuple(KINDS[t] for t in types),
        "rope": rope,
        "dtype": {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg.get("torch_dtype", "bfloat16")
        ],
    }


def _key(key, layer, name, expert=0):
    k = jax.random.fold_in(key, layer)
    k = jax.random.fold_in(k, _T[name])
    return jax.random.fold_in(k, expert)


def attention_weights(s: Dict[str, Any], key, layer) -> Dict[str, jax.Array]:
    """One layer's tensors outside the experts."""
    H, D, dt = s["H"], s["D"], s["dtype"]
    return {
        "wq": _matrix(_key(key, layer, "wq"), (H, s["Hq"] * D), dt),
        "wk": _matrix(_key(key, layer, "wk"), (H, s["Hkv"] * D), dt),
        "wv": _matrix(_key(key, layer, "wv"), (H, s["Hkv"] * D), dt),
        "wo": _matrix(_key(key, layer, "wo"), (s["Hq"] * D, H), dt),
        "input_norm": _norm(_key(key, layer, "input_norm"), H, dt),
        "post_norm": _norm(_key(key, layer, "post_norm"), H, dt),
        "router": (
            jax.random.normal(_key(key, layer, "router"), (H, s["E"]), jnp.float32)
            * (ROUTER_GAIN / H ** 0.5)
        ).astype(dt),
    }


def expert_weights(s: Dict[str, Any], key, layer, expert) -> Dict[str, jax.Array]:
    """One expert's SwiGLU of width ``moe_intermediate_size``."""
    H, I, dt = s["H"], s["I"], s["dtype"]
    return {
        "w_gate": _matrix(_key(key, layer, "w_gate", expert), (H, I), dt),
        "w_up": _matrix(_key(key, layer, "w_up", expert), (H, I), dt),
        "w_down": _matrix(_key(key, layer, "w_down", expert), (I, H), dt),
    }


VOCAB_BLOCK = 1024  # the embedding and the head are drawn this many ids at a time


def vocab_blocks(s: Dict[str, Any]) -> int:
    vb = min(VOCAB_BLOCK, s["V"])
    if s["V"] % vb:
        raise ValueError(f"vocab_size {s['V']} is not whole blocks of {vb}")
    return s["V"] // vb


def embed_block(s: Dict[str, Any], key, block) -> jax.Array:
    """Rows ``[block * VOCAB_BLOCK, ...)`` of the embedding, ``[ids, H]``:
    unit variance, the residual stream starts at its own scale.  Drawn a
    block at a time (here and in the reference) because the float32 draw of
    the whole 98304 x 2304 table is 0.9 GB, beside a served model that
    leaves the chip under 3."""
    vb = s["V"] // vocab_blocks(s)
    return jax.random.normal(
        _key(key, _TOP, "embed", block), (vb, s["H"]), jnp.float32
    ).astype(s["dtype"])


def head_block(s: Dict[str, Any], key, block) -> jax.Array:
    """Columns ``[block * VOCAB_BLOCK, ...)`` of the head, ``[H, ids]``."""
    vb = s["V"] // vocab_blocks(s)
    return _matrix(_key(key, _TOP, "lm_head", block), (s["H"], vb), s["dtype"])


def top_weights(s: Dict[str, Any], key) -> Dict[str, jax.Array]:
    H, V, dt = s["H"], s["V"], s["dtype"]
    blocks = jnp.arange(vocab_blocks(s), dtype=jnp.int32)
    return {
        "embed": jax.lax.map(lambda b: embed_block(s, key, b), blocks).reshape(V, H),
        "final_norm": _norm(_key(key, _TOP, "final_norm"), H, dt),
        "lm_head": jax.lax.map(lambda b: head_block(s, key, b), blocks)
        .transpose(1, 0, 2).reshape(H, V),
    }


def build_params(
    cfg: Dict[str, Any], seed: int,
    each: Optional[Callable[[str, jax.Array], Any]] = None,
) -> Dict[str, Any]:
    """The whole parameter tree in one jitted call; layers and experts are
    drawn in a ``lax.map``, so the float32 draw of one matrix is the
    largest temporary.  ``each(name, tensor)``, where given, stands in for
    every tensor as soon as it is drawn."""
    s = sizes(cfg)

    def through(tensors):
        if each is None:
            return tensors
        return {k: each(k, v) for k, v in tensors.items()}

    def one_layer(key, layer):
        lp = through(attention_weights(s, key, layer))
        lp.update(
            jax.lax.map(
                lambda e: through(expert_weights(s, key, layer, e)),
                jnp.arange(s["E"], dtype=jnp.int32),
            )
        )
        return lp

    @jax.jit
    def build(key):
        layers = jax.lax.map(
            lambda l: one_layer(key, l), jnp.arange(s["L"], dtype=jnp.int32)
        )
        out = through(top_weights(s, key))
        out["layers"] = layers
        return out

    return build(seed_key(seed))
