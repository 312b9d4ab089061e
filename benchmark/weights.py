"""Weights from the seed, made on the device, in the type they are served in.

The benchmark owns the weights: the server hands them to the engine, and the
plain reference (``reference.py``) draws the same tensors again from the
seed, a layer (an expert) at a time, so nothing the program has made reaches
the reference.  Every matrix is normal with the variance ``1/fan_in`` (the
embedding: variance 1; norm weights: uniform in [0.5, 1.5]), keyed by (seed,
layer, tensor, expert), and rounded once to the served dtype.  Normal and
not uniform: what a lower precision costs depends on the tails of the
weights, and a trained model's are no lighter than a normal's.

The layout handed to the engine is the one its loaders produce: per-layer
tensors stacked on a leading layer axis, matrices stored ``[in, out]``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

# tensor -> index folded into the key; never renumber
_T = {
    "wq": 0, "wk": 1, "wv": 2, "wo": 3, "input_norm": 4, "post_norm": 5,
    "router": 6, "w_gate": 7, "w_up": 8, "w_down": 9,
    "embed": 10, "final_norm": 11, "lm_head": 12,
}
_TOP = 1 << 20  # "layer" index of the tensors outside the layers


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes a configuration file states, under short names."""
    h = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return {
        "H": h,
        "I": cfg["intermediate_size"],
        "L": cfg["num_hidden_layers"],
        "Hq": hq,
        "Hkv": cfg.get("num_key_value_heads", hq),
        "D": cfg.get("head_dim", h // hq),
        "E": cfg.get("num_local_experts", 0),
        "K": cfg.get("num_experts_per_tok", 0),
        "V": cfg["vocab_size"],
        "theta": float(cfg.get("rope_theta", 10000.0)),
        "eps": float(cfg.get("rms_norm_eps", 1e-5)),
        "window": cfg.get("sliding_window") or 0,
        "dtype": {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg.get("torch_dtype", "bfloat16")
        ],
    }


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 2**62: --seed may pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def _key(key, layer, name, expert=0):
    k = jax.random.fold_in(key, layer)
    k = jax.random.fold_in(k, _T[name])
    return jax.random.fold_in(k, expert)


def _matrix(key, shape, dtype):
    scale = (1.0 / shape[0]) ** 0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _norm(key, n, dtype):
    return jax.random.uniform(key, (n,), jnp.float32, 0.5, 1.5).astype(dtype)


def attention_weights(s: Dict[str, Any], key, layer) -> Dict[str, jax.Array]:
    """One layer's tensors outside the MLP."""
    H, D, dt = s["H"], s["D"], s["dtype"]
    out = {
        "wq": _matrix(_key(key, layer, "wq"), (H, s["Hq"] * D), dt),
        "wk": _matrix(_key(key, layer, "wk"), (H, s["Hkv"] * D), dt),
        "wv": _matrix(_key(key, layer, "wv"), (H, s["Hkv"] * D), dt),
        "wo": _matrix(_key(key, layer, "wo"), (s["Hq"] * D, H), dt),
        "input_norm": _norm(_key(key, layer, "input_norm"), H, dt),
        "post_norm": _norm(_key(key, layer, "post_norm"), H, dt),
    }
    if s["E"]:
        out["router"] = _matrix(_key(key, layer, "router"), (H, s["E"]), dt)
    return out


def mlp_weights(s: Dict[str, Any], key, layer, expert=0) -> Dict[str, jax.Array]:
    """One dense MLP, or one expert of a sparse one."""
    H, I, dt = s["H"], s["I"], s["dtype"]
    return {
        "w_gate": _matrix(_key(key, layer, "w_gate", expert), (H, I), dt),
        "w_up": _matrix(_key(key, layer, "w_up", expert), (H, I), dt),
        "w_down": _matrix(_key(key, layer, "w_down", expert), (I, H), dt),
    }


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
    """The whole parameter tree in one jitted call.  Layers and experts
    are drawn in a ``lax.map``, so the float32 draw of one matrix is the
    largest temporary.  ``each(name, tensor)``, where given, stands in for
    every tensor as soon as it is drawn (the control's conversion to the
    program's int8 form: a second copy of the model would not fit)."""
    s = sizes(cfg)

    def through(tensors):
        if each is None:
            return tensors
        return {k: each(k, v) for k, v in tensors.items()}

    def one_layer(key, layer):
        lp = through(attention_weights(s, key, layer))
        if s["E"]:
            lp.update(
                jax.lax.map(
                    lambda e: through(mlp_weights(s, key, layer, e)),
                    jnp.arange(s["E"], dtype=jnp.int32),
                )
            )
        else:
            lp.update(through(mlp_weights(s, key, layer)))
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
