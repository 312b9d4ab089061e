"""The mistral4 family's weights from the seed, made on the device, in the
type they are served in (the contract of ``weights.py``: ``build_params(cfg,
seed, each)`` hands the engine the tree its loaders would produce, and the
plain reference draws the same tensors again, a layer and an expert at a
time).

Every matrix is normal with the variance ``1/fan_in`` (the embedding:
variance 1; norm weights: uniform in [0.5, 1.5]; the router: ``ROUTER_GAIN``
squared over fan_in, below), keyed by (seed, layer, tensor, expert) and
rounded once to the served dtype.  A routed expert is
keyed by its PUBLISHED index (``expert_offset`` + its place here), so the
cuts of one layer held by different chips draw the same experts.  The
embedding and the head are the configuration's slice of the vocabulary,
drawn as tensors of that size.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from .weights import _matrix, _norm, seed_key  # the draws every family shares

# tensor -> index folded into the key; never renumber
_T = {
    "wq_a": 0, "q_a_norm": 1, "wq_b": 2, "wkv_a": 3, "kv_a_norm": 4,
    "wkv_b": 5, "wo": 6, "input_norm": 7, "post_norm": 8, "router": 9,
    "w_gate": 10, "w_up": 11, "w_down": 12,
    "ws_gate": 13, "ws_up": 14, "ws_down": 15,
    "embed": 16, "final_norm": 17, "lm_head": 18,
}
_TOP = 1 << 20  # "layer" index of the tensors outside the layers
# The router's rows are drawn four times as large as a projection's, so its
# logits have a standard deviation near 4 and not 1.  Which experts are
# chosen does not depend on that scale; how much each weighs does.  With
# unit logits the four chosen of 128 weigh 0.33, 0.25, 0.21, 0.20 and the
# fifth would have weighed as much as the fourth, so every time rounding
# moves the fourth place (the two are 0.1 of a logit apart: at 32k tokens in
# bfloat16 that is most positions in some layer) a fifth of the layer's
# routed output is replaced.  That, not the arithmetic's precision, then
# sets the tail of the error against the float32 reference: on the chip the
# 90th percentile read 0.044-0.079 over ten seeds in bfloat16 and
# 0.089-0.108 with int8 weights, while the medians read 0.019 and 0.041
# (PERF.md section 2).  A trained router is peaked, not flat: at a gain of 4
# the chosen weigh about 0.66, 0.20, 0.09, 0.06, a change of fourth place
# moves 6% of the routed output, and the comparison sees the arithmetic.
ROUTER_GAIN = 4.0
__all__ = ["sizes", "seed_key", "attention_weights", "expert_weights",
           "shared_weights", "top_weights", "build_params"]


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes a configuration file states, under short names."""
    rp = cfg.get("rope_parameters") or {}
    held = cfg["n_routed_experts"]
    return {
        "H": cfg["hidden_size"],
        "L": cfg["num_hidden_layers"],
        "Hq": cfg["num_attention_heads"],
        "R": cfg["q_lora_rank"],
        "C": cfg["kv_lora_rank"],
        "Dn": cfg["qk_nope_head_dim"],
        "Dr": cfg["qk_rope_head_dim"],
        "Dv": cfg["v_head_dim"],
        "I": cfg["moe_intermediate_size"],
        "E": held,  # experts held here
        "Er": cfg.get("router_experts", held),  # the router's width
        "E0": cfg.get("expert_offset", 0),  # published index of the first held
        "K": cfg["num_experts_per_tok"],
        "S": cfg.get("n_shared_experts", 0),
        "V": cfg["vocab_size"],
        "eps": float(cfg.get("rms_norm_eps", 1e-6)),
        "theta": float(rp.get("rope_theta", 10000.0)),
        "yarn_factor": float(rp.get("factor", 1.0)),
        "yarn_orig": int(rp.get("original_max_position_embeddings", 0)),
        "beta_fast": float(rp.get("beta_fast", 32)),
        "beta_slow": float(rp.get("beta_slow", 1)),
        "mscale": float(rp.get("mscale", 1)),
        "mscale_all_dim": float(rp.get("mscale_all_dim", 0)),
        "q_beta": float(rp.get("llama_4_scaling_beta", 0.0)),
        "routed_scale": float(cfg.get("routed_scaling_factor", 1.0)),
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
    H, Hq, dt = s["H"], s["Hq"], s["dtype"]

    def m(name, shape):
        return _matrix(_key(key, layer, name), shape, dt)

    def n(name, size):
        return _norm(_key(key, layer, name), size, dt)

    return {
        "wq_a": m("wq_a", (H, s["R"])),
        "q_a_norm": n("q_a_norm", s["R"]),
        "wq_b": m("wq_b", (s["R"], Hq * (s["Dn"] + s["Dr"]))),
        "wkv_a": m("wkv_a", (H, s["C"] + s["Dr"])),
        "kv_a_norm": n("kv_a_norm", s["C"]),
        "wkv_b": m("wkv_b", (s["C"], Hq * (s["Dn"] + s["Dv"]))),
        "wo": m("wo", (Hq * s["Dv"], H)),
        "input_norm": n("input_norm", H),
        "post_norm": n("post_norm", H),
        "router": (
            jax.random.normal(_key(key, layer, "router"), (H, s["Er"]), jnp.float32)
            * (ROUTER_GAIN / H ** 0.5)
        ).astype(dt),
    }


def _swiglu_weights(s, key, layer, names, expert, width):
    H, dt = s["H"], s["dtype"]
    g, u, d = names
    return {
        g: _matrix(_key(key, layer, g, expert), (H, width), dt),
        u: _matrix(_key(key, layer, u, expert), (H, width), dt),
        d: _matrix(_key(key, layer, d, expert), (width, H), dt),
    }


def expert_weights(s: Dict[str, Any], key, layer, published) -> Dict[str, jax.Array]:
    """One routed expert, by its published index."""
    return _swiglu_weights(
        s, key, layer, ("w_gate", "w_up", "w_down"), published, s["I"])


def shared_weights(s: Dict[str, Any], key, layer) -> Dict[str, jax.Array]:
    """The shared experts as one SwiGLU of their joint width."""
    return _swiglu_weights(
        s, key, layer, ("ws_gate", "ws_up", "ws_down"), 0, s["I"] * s["S"])


def top_weights(s: Dict[str, Any], key) -> Dict[str, jax.Array]:
    H, V, dt = s["H"], s["V"], s["dtype"]
    return {
        # unit variance: the residual stream starts at its own scale
        "embed": jax.random.normal(
            _key(key, _TOP, "embed"), (V, H), jnp.float32
        ).astype(dt),
        "final_norm": _norm(_key(key, _TOP, "final_norm"), H, dt),
        "lm_head": _matrix(_key(key, _TOP, "lm_head"), (H, V), dt),
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
                lambda e: through(expert_weights(s, key, layer, s["E0"] + e)),
                jnp.arange(s["E"], dtype=jnp.int32),
            )
        )
        if s["S"]:
            lp.update(through(shared_weights(s, key, layer)))
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
