"""The plain reference: each configuration's forward pass in ``jax.numpy``,
float32, ``default_matmul_precision("highest")``, with no cache, no kernels,
no batching and no code of the program.

It follows the published description of the Mistral family (pre-norm
decoder, grouped-query attention with rotary embeddings in the rotate-half
convention, SwiGLU MLP; Mistral-7B: causal sliding window in which position
``i`` sees ``j`` when ``i - window < j <= i``; Mixtral: a router over
``num_local_experts`` SwiGLU experts, the ``num_experts_per_tok`` largest
logits softmaxed among themselves).  Its weights come from the seed
(``weights.py``), a layer and an expert at a time, upcast from the served
dtype: no second copy of the model.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

PAD_TO = 512  # sequences are padded to a multiple: few programs to compile
Q_BLOCK = 256  # attention is computed this many query rows at a time


def _f32(tree):
    """Weights upcast to float32."""
    return {k: a.astype(jnp.float32) for k, a in tree.items()}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [T, heads, D]; rotate-half convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def _attention(s, q, k, v):
    """Causal (and windowed) attention; q [T, Hq, D], k/v [T, Hkv, D]."""
    T = q.shape[0]
    g = s["Hq"] // s["Hkv"]
    q = q.reshape(T, s["Hkv"], g, s["D"])
    kpos = jnp.arange(T)

    def block(i):
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        sc = jnp.einsum("qhgd,khd->hgqk", qb, k) / (s["D"] ** 0.5)
        ok = kpos[None, :] <= qpos[:, None]
        if s["window"]:
            ok = ok & (kpos[None, :] > qpos[:, None] - s["window"])
        sc = jnp.where(ok[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    return out.reshape(T, s["Hq"] * s["D"])


def _swiglu(w, x):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def _layer_attention(s, key, layer, x):
    w = _f32(W.attention_weights(s, key, layer))
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, w["input_norm"], s["eps"])
    q = _rope((h @ w["wq"]).reshape(T, s["Hq"], s["D"]), pos, s["theta"])
    k = _rope((h @ w["wk"]).reshape(T, s["Hkv"], s["D"]), pos, s["theta"])
    v = (h @ w["wv"]).reshape(T, s["Hkv"], s["D"])
    x = x + _attention(s, q, k, v) @ w["wo"]
    h2 = _rms(x, w["post_norm"], s["eps"])
    if s["E"]:
        logits = h2 @ w["router"]
        top, idx = jax.lax.top_k(logits, s["K"])
        gate = jnp.zeros_like(logits).at[
            jnp.arange(T)[:, None], idx
        ].set(jax.nn.softmax(top, axis=-1))
        return x, h2, gate
    return x, h2, None


def _layer_mlp(s, key, layer, expert, h2):
    return _swiglu(_f32(W.mlp_weights(s, key, layer, expert)), h2)


def _head(s, key, x, rows, ids):
    w = _f32(W.top_weights(s, key))
    h = _rms(x[rows], w["final_norm"], s["eps"])
    lp = jax.nn.log_softmax(h @ w["lm_head"], axis=-1)
    return jnp.take_along_axis(lp, ids, axis=-1)


def _embed(s, key, tokens):
    return W.top_weights(s, key)["embed"][tokens].astype(jnp.float32)


class Reference:
    """The reference forward pass of one configuration."""

    def __init__(self, cfg: Dict[str, Any]) -> None:
        s = W.sizes(cfg)
        self.s = s
        self._embed = jax.jit(partial(_embed, s))
        self._attn = jax.jit(partial(_layer_attention, s))
        self._mlp = jax.jit(partial(_layer_mlp, s))
        self._head = jax.jit(partial(_head, s))

    def logprobs(
        self, seed: int, tokens: Sequence[int], rows: Sequence[int],
        ids: List[List[int]],
    ) -> np.ndarray:
        """With the weights of ``seed``: log-softmax of the next-token
        logits after ``tokens[: r + 1]`` for each ``r`` in ``rows``, at the
        token ids ``ids[i]``."""
        s, key = self.s, W.seed_key(seed)
        n = len(tokens)
        pad = -(-n // PAD_TO) * PAD_TO
        toks = np.zeros((pad,), np.int32)
        toks[:n] = np.asarray(tokens, np.int32)
        with jax.default_matmul_precision("highest"):
            x = self._embed(key, jnp.asarray(toks))
            for layer in range(s["L"]):
                x, h2, gate = self._attn(key, layer, x)
                if s["E"]:
                    for e in range(s["E"]):
                        x = x + gate[:, e : e + 1] * self._mlp(key, layer, e, h2)
                else:
                    x = x + self._mlp(key, layer, 0, h2)
            out = self._head(
                key, x, jnp.asarray(rows, jnp.int32), jnp.asarray(ids, jnp.int32)
            )
        return np.asarray(out)
