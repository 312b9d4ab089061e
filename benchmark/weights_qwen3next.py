"""The qwen3_next family's weights from the seed, made on the device, in the
type they are served in (the contract of ``weights.py``: ``build_params(cfg,
seed, each)`` hands the engine the tree its loaders would produce, and the
plain reference draws the same tensors again, a layer and an expert at a
time).

Every matrix is normal with the variance ``1/fan_in`` (the embedding:
variance 1), keyed by (seed, layer, tensor, expert) and rounded once to the
served dtype.  What this family draws on its own terms, and why:

- **norm weights centred at zero**: the family's RMSNorm multiplies by ``1 +
  w``, so ``w`` is uniform in [-0.5, 0.5] where the other families draw the
  multiplier in [0.5, 1.5]; the gated norm of a linear layer has a plain
  weight, drawn in [0.5, 1.5];
- **``A_log`` and ``dt_bias``**, so that the value heads' memories are spread
  log-uniformly from ``MEMORY_MIN`` to ``MEMORY_MAX`` tokens (stratified over
  a layer's heads, the place inside a stratum drawn): a head's log-decay a
  token is ``g = -exp(A_log) softplus(a + dt_bias)``, ``a`` about normal(0, 1)
  for a normalised input and a projection of variance ``1/fan_in``, so with
  ``dt_bias`` 1 the softplus is 1.4 on average and ``A_log = -log(1.4 tau)``
  gives a memory of ``tau`` tokens.  The published initialisation (``A``
  uniform in (0, 16), ``dt_bias`` from a time step of 0.001-0.1) is where
  training STARTS; drawn so, nearly every head forgets within a token, the
  state holds nothing a check could miss, and a lost or stale snapshot reads
  the same as a right one.  With memories of 16 to 4096 tokens a restore
  that starts from zeros, or from another sequence's state, is seen hundreds
  of tokens on (``tests/test_qwen3next.py`` holds that);
- **the router's rows** at ``ROUTER_GAIN`` (``weights_mellum.ROUTER_GAIN`` is
  the precedent): logits of deviation 4 over 512 experts, so that the ten
  chosen are peaked as a trained router's are and a change of tenth place
  under rounding moves a few per cent of the routed output;
- **the convolution's taps** ``[4, C]``, variance 1/4 (a channel's four taps
  sum to unit variance over independent rows).

A routed expert is keyed by its PUBLISHED index (``expert_offset`` + its
place here), so the cuts of one layer held by different chips draw the same
experts.  The embedding and the head are the configuration's slice of the
vocabulary, drawn as tensors of that size.

The tree is ``dynamo_tpu.engine.model.scan_layers``'s for a trunk whose
kinds differ in operator: norms, router, experts and the shared expert
stacked over all layers under ``layers``, each kind's operator stacked over
its own layers under ``layers.attn`` / ``layers.linear``, no leading layers.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from .weights import _matrix, _norm, seed_key  # the draws every family shares

# tensor -> index folded into the key; never renumber
_T = {
    "wq": 0, "wk": 1, "wv": 2, "wo": 3, "q_norm": 4, "k_norm": 5,
    "gdn_in": 6, "gdn_ba": 7, "gdn_taps": 8, "gdn_a_log": 9,
    "gdn_dt_bias": 10, "gdn_norm": 11, "gdn_out": 12,
    "input_norm": 13, "post_norm": 14, "router": 15,
    "w_gate": 16, "w_up": 17, "w_down": 18,
    "ws_gate": 19, "ws_up": 20, "ws_down": 21, "ws_router": 22,
    "embed": 23, "final_norm": 24, "lm_head": 25,
}
_TOP = 1 << 20  # "layer" index of the tensors outside the layers
ROUTER_GAIN = 4.0
MEMORY_MIN, MEMORY_MAX = 16.0, 4096.0
MEAN_STEP = 1.4  # softplus(a + 1) on average over a ~ normal(0, 1)
__all__ = ["sizes", "seed_key", "operator_weights", "layer_weights",
           "expert_weights", "top_weights", "engine_order", "build_params"]


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes a configuration file states, under short names."""
    h, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    L, interval = cfg["num_hidden_layers"], cfg.get("full_attention_interval", 4)
    if cfg.get("linear_conv_kernel_dim", 4) != 4:
        raise ValueError("qwen3_next weights: a 4-tap filter only")
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("qwen3_next weights: the head is not tied")
    held = cfg["num_experts"]
    D = cfg.get("head_dim") or h // hq
    return {
        "H": h,
        "L": L,
        "Hq": hq,
        "Hkv": cfg.get("num_key_value_heads", hq),
        "D": D,
        "R": int(D * cfg.get("partial_rotary_factor", 1.0)),
        "Hk": cfg["linear_num_key_heads"],
        "Hv": cfg["linear_num_value_heads"],
        "dk": cfg["linear_key_head_dim"],
        "dv": cfg["linear_value_head_dim"],
        "I": cfg["moe_intermediate_size"],
        "Is": cfg.get("shared_expert_intermediate_size", 0),
        "E": held,
        "router": cfg.get("router_experts", held),
        "offset": cfg.get("expert_offset", 0),
        "K": cfg["num_experts_per_tok"],
        "V": cfg["vocab_size"],
        "eps": float(cfg.get("rms_norm_eps", 1e-6)),
        "theta": float(cfg.get("rope_theta", 10000000.0)),
        "kinds": tuple(
            "full" if (i + 1) % interval == 0 else "linear" for i in range(L)),
        "dtype": {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg.get("torch_dtype", "bfloat16")
        ],
    }


def _key(key, layer, name, expert=0):
    k = jax.random.fold_in(key, layer)
    k = jax.random.fold_in(k, _T[name])
    return jax.random.fold_in(k, expert)


def _columns(heads: int, widths) -> jax.Array:
    """Columns of a published projection that reads a head at a time ``[part
    | part | ..]`` in the order the engine's tree keeps them: every head's
    first part, then every head's second, ... (the engine's
    ``model.delta_columns``, written out again: nothing of the program is
    imported here)."""
    per, out, start = sum(widths), [], 0
    for w in widths:
        out.append(
            (jnp.arange(heads)[:, None] * per + start + jnp.arange(w)[None, :]
             ).reshape(-1))
        start += w
    return jnp.concatenate(out)


def engine_order(s: Dict[str, Any], kind: str, w: Dict[str, jax.Array]):
    """One operator's tensors as the engine's loader hands them on: the
    published ones, with the columns of the projections that interleave
    their parts a head at a time put part by part."""
    w = dict(w)
    if kind == "linear":
        r = s["Hv"] // s["Hk"]
        w["gdn_in"] = w["gdn_in"][:, _columns(
            s["Hk"], (s["dk"], s["dk"], r * s["dv"], r * s["dv"]))]
        w["gdn_ba"] = w["gdn_ba"][:, _columns(s["Hk"], (r, r))]
    else:
        w["wq"] = w["wq"][:, _columns(s["Hq"], (s["D"], s["D"]))]
    return w


def _centred(key, n, dtype):
    """A norm weight of the family's form: ``1 + w`` lies in [0.5, 1.5]."""
    return jax.random.uniform(key, (n,), jnp.float32, -0.5, 0.5).astype(dtype)


def operator_weights(s: Dict[str, Any], key, layer, kind: str) -> Dict[str, jax.Array]:
    """One layer's operator: the gated delta rule or the gated attention."""
    H, D, dt = s["H"], s["D"], s["dtype"]
    if kind == "linear":
        Hk, Hv, dk, dv = s["Hk"], s["Hv"], s["dk"], s["dv"]
        C = 2 * Hk * dk + Hv * dv
        place = jax.random.uniform(_key(key, layer, "gdn_a_log"), (Hv,), jnp.float32)
        tau = MEMORY_MIN * (MEMORY_MAX / MEMORY_MIN) ** (
            (jnp.arange(Hv) + place) / Hv)
        return {
            "gdn_in": _matrix(
                _key(key, layer, "gdn_in"), (H, 2 * Hk * dk + 2 * Hv * dv), dt),
            "gdn_ba": _matrix(_key(key, layer, "gdn_ba"), (H, 2 * Hv), dt),
            "gdn_taps": (
                jax.random.normal(_key(key, layer, "gdn_taps"), (4, C), jnp.float32)
                / 2.0
            ).astype(dt),
            "gdn_a_log": (-jnp.log(MEAN_STEP * tau)).astype(dt),
            "gdn_dt_bias": jnp.ones((Hv,), dt),
            "gdn_norm": _norm(_key(key, layer, "gdn_norm"), dv, dt),
            "gdn_out": _matrix(_key(key, layer, "gdn_out"), (Hv * dv, H), dt),
        }
    return {
        # a head's [query | gate]
        "wq": _matrix(_key(key, layer, "wq"), (H, s["Hq"] * 2 * D), dt),
        "wk": _matrix(_key(key, layer, "wk"), (H, s["Hkv"] * D), dt),
        "wv": _matrix(_key(key, layer, "wv"), (H, s["Hkv"] * D), dt),
        "wo": _matrix(_key(key, layer, "wo"), (s["Hq"] * D, H), dt),
        "q_norm": _centred(_key(key, layer, "q_norm"), D, dt),
        "k_norm": _centred(_key(key, layer, "k_norm"), D, dt),
    }


def layer_weights(s: Dict[str, Any], key, layer) -> Dict[str, jax.Array]:
    """What every layer has outside its operator and its routed experts:
    the two norms, the router over the published width, the shared expert
    and its gate."""
    H, Is, dt = s["H"], s["Is"], s["dtype"]
    out = {
        "input_norm": _centred(_key(key, layer, "input_norm"), H, dt),
        "post_norm": _centred(_key(key, layer, "post_norm"), H, dt),
        "router": (
            jax.random.normal(_key(key, layer, "router"), (H, s["router"]), jnp.float32)
            * (ROUTER_GAIN / H ** 0.5)
        ).astype(dt),
    }
    if Is:
        out.update({
            "ws_gate": _matrix(_key(key, layer, "ws_gate"), (H, Is), dt),
            "ws_up": _matrix(_key(key, layer, "ws_up"), (H, Is), dt),
            "ws_down": _matrix(_key(key, layer, "ws_down"), (Is, H), dt),
            "ws_router": _matrix(_key(key, layer, "ws_router"), (H, 1), dt),
        })
    return out


def expert_weights(s: Dict[str, Any], key, layer, expert) -> Dict[str, jax.Array]:
    """One routed expert, ``expert`` its place among the held ones."""
    H, I, dt = s["H"], s["I"], s["dtype"]
    e = expert + s["offset"]  # keyed by the published index
    return {
        "w_gate": _matrix(_key(key, layer, "w_gate", e), (H, I), dt),
        "w_up": _matrix(_key(key, layer, "w_up", e), (H, I), dt),
        "w_down": _matrix(_key(key, layer, "w_down", e), (I, H), dt),
    }


def top_weights(s: Dict[str, Any], key) -> Dict[str, jax.Array]:
    H, V, dt = s["H"], s["V"], s["dtype"]
    return {
        "embed": jax.random.normal(
            _key(key, _TOP, "embed"), (V, H), jnp.float32).astype(dt),
        "final_norm": _centred(_key(key, _TOP, "final_norm"), H, dt),
        "lm_head": _matrix(_key(key, _TOP, "lm_head"), (H, V), dt),
    }


def build_params(
    cfg: Dict[str, Any], seed: int,
    each: Optional[Callable[[str, jax.Array], Any]] = None,
) -> Dict[str, Any]:
    """The whole parameter tree in one jitted call; layers and experts are
    drawn in a ``lax.map``, so the float32 draw of one matrix is the largest
    temporary.  ``each(name, tensor)``, where given, stands in for every
    tensor as soon as it is drawn."""
    s = sizes(cfg)
    of_kind = {
        k: [l for l in range(s["L"]) if s["kinds"][l] == k]
        for k in ("full", "linear")
    }

    def through(tensors):
        if each is None:
            return tensors
        return {k: each(k, v) for k, v in tensors.items()}

    def layer(key, l):
        lp = through(layer_weights(s, key, l))
        lp.update(
            jax.lax.map(
                lambda e: through(expert_weights(s, key, l, e)),
                jnp.arange(s["E"], dtype=jnp.int32),
            )
        )
        return lp

    @jax.jit
    def build(key):
        layers = jax.lax.map(
            lambda l: layer(key, l), jnp.arange(s["L"], dtype=jnp.int32))
        for name, kind in (("attn", "full"), ("linear", "linear")):
            layers[name] = jax.lax.map(
                lambda l, kind=kind: through(
                    engine_order(s, kind, operator_weights(s, key, l, kind))),
                jnp.asarray(of_kind[kind], jnp.int32),
            )
        out = through(top_weights(s, key))
        out["layers"] = layers
        out["lead"] = ()
        return out

    return build(seed_key(seed))
