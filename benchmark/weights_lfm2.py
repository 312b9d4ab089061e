"""The lfm2_moe family's weights from the seed, made on the device, in the type
they are served in (the contract of ``weights.py``: ``build_params(cfg, seed,
each)`` hands the engine the tree its loaders would produce, and the plain
reference draws the same tensors again, a layer and an expert at a time).

Every matrix is normal with the variance ``1/fan_in``; norm weights are
uniform in [0.5, 1.5]; all keyed by (seed, layer, tensor, expert) and rounded
once to the served dtype.  What this family draws on its own terms, and why
(PERF.md section 2 has the readings that forced each):

- **the convolution operator's projection back into the residual stream**
  (``conv_out``) at variance ``1 / (2 L fan_in)``, ``L`` the layers: the
  residual scaling of GPT-2's initialisation.  The operator is cubic in its
  input (``C * conv(B * X)``); with a branch as large as the stream a
  rounding grows threefold a layer, and the served bfloat16 engine read 0.06
  at the median position against the float32 reference even where routing
  could not change.  Attention's ``wo`` and every ``w_down`` keep ``1/fan_in``:
  scaled down alike they hid the int8 control's rounding behind the stream's;
- **the embedding**, variance 1, plus the constant ``EMBED_SHIFT`` in its first
  ``H / 16`` channels; and the final norm's weight over ``sqrt(H)``, zero in
  those channels: the head is tied to the embedding (``assumed``), so the
  final norm carries the ``1/sqrt(H)`` that an untied head's rows would and
  the logits keep unit scale, and it keeps the constant out of the logits
  (with the constant in every channel each logit was 27 plus its own part,
  bfloat16 rounded them to eighths, and every position read 0.07-0.10).
  **Each channel of that weight takes a sign of its own**: a token's
  embedding stays in the stream, and read back through the same matrix with
  weights of one sign it gives the token's own logit ``|E|^2 / (rms sqrt(H))``,
  about 21 at this width against a deviation of 1 for every other token:
  greedy decoding then answers every prompt with its last token repeated, the
  96 compared positions of a request are one position 96 times (one token's
  routing and one convolution state), and a request read high or low as a
  whole, 0.04-0.23 from seed to seed (PERF.md section 2).  A trained tied
  model predicts the next token, not the one it was given; with signs the
  token's own logit is one among the others and the decoded tokens differ.  The
  shared component, a few channels that every token carries alike as a
  trained model's massive activations are, gives the router below something
  constant to read;
- **the router's rows** at ``ROUTER_GAIN`` (``weights_mellum.ROUTER_GAIN`` is
  the precedent) less ``ROUTER_OFFSET`` over those channels, grown with the
  layer's depth as the constant's share of the normalised stream shrinks, so
  that a token's logits are normal with deviation about 3 around about -9 in
  every layer: one or two
  experts score high, the rest near nothing, and the chosen four weigh about
  0.63, 0.21, 0.10, 0.06 as a trained router's do, every expert with 0.8 to
  1.4 times its share of rows.  A sigmoid router with
  zero-mean logits gives its four chosen a quarter each whatever the gain (the
  scores are renormalised), the fourth and the fifth place are a rounding
  apart at every other token, and a changed place then replaces a quarter of
  a layer's routed output and every later layer's routing with it: the
  engine read 0.33-0.63 at the 90th percentile and int8 weights 0.60-0.68;
- **the selection bias**, normal with deviation ``BIAS_SCALE``: it decides
  between experts whose scores are within a few hundredths, which are the
  low-weight places, and leaves the high scores their places;
- the convolution's taps ``[3, H]``, variance 1/3 (a channel's three taps sum
  to unit variance over independent rows).

The tree is ``dynamo_tpu.engine.model.scan_layers``'s for a trunk with
convolution layers: the layers after the leading dense ones stacked under
``layers`` (norms, router, bias, experts), each kind's operator stacked over
its own layers under ``layers.attn`` / ``layers.conv``, and the leading layers
as a tuple of single layers under ``lead``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from .weights import _matrix, _norm, seed_key  # the draws every family shares

# tensor -> index folded into the key; never renumber
_T = {
    "wq": 0, "wk": 1, "wv": 2, "wo": 3, "q_norm": 4, "k_norm": 5,
    "conv_in": 6, "conv_taps": 7, "conv_out": 8,
    "input_norm": 9, "post_norm": 10, "router": 11, "router_bias": 12,
    "w_gate": 13, "w_up": 14, "w_down": 15,
    "embed": 16, "final_norm": 17,
}
_TOP = 1 << 20  # "layer" index of the tensors outside the layers
ROUTER_GAIN = 4.0
ROUTER_OFFSET = 3.7
BIAS_SCALE = 0.0005
EMBED_SHIFT = 4.0
KINDS = {"conv": "conv", "full_attention": "full"}
VOCAB_BLOCK = 1024  # the embedding is drawn this many ids at a time
__all__ = ["sizes", "seed_key", "operator_weights", "layer_weights",
           "expert_weights", "dense_weights", "embed_block", "vocab_blocks",
           "final_norm", "build_params"]


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes a configuration file states, under short names."""
    h, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    types = cfg.get("layer_types") or ["full_attention"] * L
    if cfg.get("conv_L_cache", 3) != 3 or cfg.get("conv_bias", False):
        raise ValueError("lfm2 weights: a 3-tap filter without bias only")
    if not cfg.get("tie_word_embeddings", True):
        raise ValueError("lfm2 weights: the head is tied to the embedding")
    return {
        "H": h,
        "L": L,
        "Ld": int(cfg.get("num_dense_layers", 0)),
        "Hq": hq,
        "Hkv": cfg.get("num_key_value_heads", hq),
        "D": cfg.get("head_dim") or h // hq,
        "I": cfg["moe_intermediate_size"],
        "Id": cfg["intermediate_size"],
        "E": cfg["num_experts"],
        "K": cfg["num_experts_per_tok"],
        "V": cfg["vocab_size"],
        "eps": float(cfg.get("norm_eps", 1e-5)),
        "theta": float(cfg.get("rope_theta", 1000000.0)),
        "scaling": float(cfg.get("routed_scaling_factor", 1.0)),
        "bias": bool(cfg.get("use_expert_bias", False)),
        "kinds": tuple(KINDS[t] for t in types),
        "shifted": max(h // 16, 1),  # channels that carry the constant
        "dtype": {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg.get("torch_dtype", "bfloat16")
        ],
    }


def _key(key, layer, name, expert=0):
    k = jax.random.fold_in(key, layer)
    k = jax.random.fold_in(k, _T[name])
    return jax.random.fold_in(k, expert)


def _out_matrix(s: Dict[str, Any], key, shape) -> jax.Array:
    """The convolution operator's projection back into the residual stream:
    variance ``1 / (2 L fan_in)``."""
    scale = (1.0 / (2 * s["L"] * shape[0])) ** 0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(s["dtype"])


def operator_weights(s: Dict[str, Any], key, layer, kind: str) -> Dict[str, jax.Array]:
    """One layer's operator: the gated short convolution or the attention."""
    H, D, dt = s["H"], s["D"], s["dtype"]
    if kind == "conv":
        return {
            "conv_in": _matrix(_key(key, layer, "conv_in"), (H, 3 * H), dt),
            "conv_taps": (
                jax.random.normal(_key(key, layer, "conv_taps"), (3, H), jnp.float32)
                / 3.0 ** 0.5
            ).astype(dt),
            "conv_out": _out_matrix(s, _key(key, layer, "conv_out"), (H, H)),
        }
    return {
        "wq": _matrix(_key(key, layer, "wq"), (H, s["Hq"] * D), dt),
        "wk": _matrix(_key(key, layer, "wk"), (H, s["Hkv"] * D), dt),
        "wv": _matrix(_key(key, layer, "wv"), (H, s["Hkv"] * D), dt),
        "wo": _matrix(_key(key, layer, "wo"), (s["Hq"] * D, H), dt),
        "q_norm": _norm(_key(key, layer, "q_norm"), D, dt),
        "k_norm": _norm(_key(key, layer, "k_norm"), D, dt),
    }


def layer_weights(s: Dict[str, Any], key, layer, routed: bool) -> Dict[str, jax.Array]:
    """The two norms of a layer and, where its MLP is routed, the router and
    the selection bias."""
    H, dt = s["H"], s["dtype"]
    out = {
        "input_norm": _norm(_key(key, layer, "input_norm"), H, dt),
        "post_norm": _norm(_key(key, layer, "post_norm"), H, dt),
    }
    if routed:
        # what the stream's constant component adds to a logit is the same
        # for every token: it goes into the offset alone, and out of the
        # random part (made orthogonal to the norm's weight, which that
        # component arrives multiplied by), or a few experts would always win
        shifted = (jnp.arange(H) < s["shifted"]).astype(jnp.float32)
        w = out["post_norm"].astype(jnp.float32) * shifted
        g = jax.random.normal(_key(key, layer, "router"), (H, s["E"]), jnp.float32)
        g = g - w[:, None] * ((w @ g) / (w @ w))
        # the constant's share of the normalised stream shrinks as the
        # layers' outputs add up (about 0.4 for the dense layer, 0.16 an
        # expert layer, beside the embedding's 1 and the constant's 1): the
        # offset grows with it, so that every layer's logits sit as low
        grown = 1.4 + 0.16 * (layer - s["Ld"])
        offset = ROUTER_OFFSET * jnp.sqrt((grown + 1.0) / 2.4)
        out["router"] = (
            g * (ROUTER_GAIN / H ** 0.5)
            - shifted[:, None] * (offset / s["shifted"])
        ).astype(dt)
        if s["bias"]:
            out["router_bias"] = (
                jax.random.normal(_key(key, layer, "router_bias"), (s["E"],), jnp.float32)
                * BIAS_SCALE
            ).astype(dt)
    return out


def expert_weights(s: Dict[str, Any], key, layer, expert) -> Dict[str, jax.Array]:
    """One expert's SwiGLU of width ``moe_intermediate_size``."""
    H, I, dt = s["H"], s["I"], s["dtype"]
    return {
        "w_gate": _matrix(_key(key, layer, "w_gate", expert), (H, I), dt),
        "w_up": _matrix(_key(key, layer, "w_up", expert), (H, I), dt),
        "w_down": _matrix(_key(key, layer, "w_down", expert), (I, H), dt),
    }


def dense_weights(s: Dict[str, Any], key, layer) -> Dict[str, jax.Array]:
    """A leading layer's dense SwiGLU of width ``intermediate_size``."""
    H, I, dt = s["H"], s["Id"], s["dtype"]
    return {
        "w_gate": _matrix(_key(key, layer, "w_gate"), (H, I), dt),
        "w_up": _matrix(_key(key, layer, "w_up"), (H, I), dt),
        "w_down": _matrix(_key(key, layer, "w_down"), (I, H), dt),
    }


def vocab_blocks(s: Dict[str, Any]) -> int:
    vb = min(VOCAB_BLOCK, s["V"])
    if s["V"] % vb:
        raise ValueError(f"vocab_size {s['V']} is not whole blocks of {vb}")
    return s["V"] // vb


def embed_block(s: Dict[str, Any], key, block) -> jax.Array:
    """Rows ``[block * VOCAB_BLOCK, ...)`` of the embedding, ``[ids, H]``,
    which is the head too: unit variance around ``EMBED_SHIFT``."""
    vb = s["V"] // vocab_blocks(s)
    return (
        jax.random.normal(_key(key, _TOP, "embed", block), (vb, s["H"]), jnp.float32)
        + EMBED_SHIFT * (jnp.arange(s["H"]) < s["shifted"])
    ).astype(s["dtype"])


def final_norm(s: Dict[str, Any], key) -> jax.Array:
    """Uniform in [0.5, 1.5] over ``sqrt(H)``, the tied head's scale, with a
    sign drawn a channel; zero in the channels that carry the constant."""
    k = _key(key, _TOP, "final_norm")
    w = jax.random.uniform(k, (s["H"],), jnp.float32, 0.5, 1.5)
    w = w * jax.random.rademacher(jax.random.fold_in(k, 1), (s["H"],), jnp.float32)
    w = w * (jnp.arange(s["H"]) >= s["shifted"])
    return (w / s["H"] ** 0.5).astype(s["dtype"])


def build_params(
    cfg: Dict[str, Any], seed: int,
    each: Optional[Callable[[str, jax.Array], Any]] = None,
) -> Dict[str, Any]:
    """The whole parameter tree in one jitted call; layers and experts are
    drawn in a ``lax.map``, so the float32 draw of one matrix is the largest
    temporary.  ``each(name, tensor)``, where given, stands in for every
    tensor as soon as it is drawn."""
    s = sizes(cfg)
    Ld, kinds = s["Ld"], s["kinds"]
    rest = [l for l in range(Ld, s["L"])]
    of_kind = {k: [l for l in rest if kinds[l] == k] for k in ("full", "conv")}

    def through(tensors):
        if each is None:
            return tensors
        return {k: each(k, v) for k, v in tensors.items()}

    def routed_layer(key, layer):
        lp = through(layer_weights(s, key, layer, True))
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
            lambda l: routed_layer(key, l), jnp.asarray(rest, jnp.int32)
        )
        for name, kind in (("attn", "full"), ("conv", "conv")):
            layers[name] = jax.lax.map(
                lambda l, kind=kind: through(operator_weights(s, key, l, kind)),
                jnp.asarray(of_kind[kind], jnp.int32),
            )
        blocks = jnp.arange(vocab_blocks(s), dtype=jnp.int32)
        out = through({
            "embed": jax.lax.map(
                lambda b: embed_block(s, key, b), blocks
            ).reshape(s["V"], s["H"]),
            "final_norm": final_norm(s, key),
        })
        out["layers"] = layers
        out["lead"] = tuple(
            through({
                **layer_weights(s, key, l, False),
                **operator_weights(s, key, l, kinds[l]),
                **dense_weights(s, key, l),
            })
            for l in range(Ld)
        )
        return out

    return build(seed_key(seed))
