"""The plain reference of the mistral4 family: the language model of
Mistral-Small-4 as its ``config.json`` describes it, in ``jax.numpy``,
float32, ``default_matmul_precision("highest")``, with no cache, no kernels,
no batching and no code of the program.  Its weights come from the seed
(``weights_mistral4.py``), a layer and an expert at a time.

The layer, ``x`` a token's hidden state:

1. ``h = RMSNorm(x)``.  Queries: ``c_q = RMSNorm(h W_qa)``; ``q = c_q
   W_qb``, each head ``[q_nope | q_rope]``.
2. Latent: ``[c_kv | k_r] = h W_kva``; ``c_kv <- RMSNorm(c_kv)``.  Head
   ``i``: ``[k_nope_i | v_i] = c_kv W_kvb,i``; ``k_i = [k_nope_i |
   RoPE(k_r)]``, one ``k_r`` for every head.  Computed here in this
   up-projected form: every head's keys and values are materialised.
3. RoPE on ``q_rope`` and ``k_r``, pairs (2i, 2i+1) rotated
   (``rope_interleave``), YaRN frequencies, cos and sin times
   ``mscale/mscale_all_dim``.  Scores ``scale (q_nope . k_nope + q_rope .
   k_r)``, causal, ``scale = qk_head_dim^-0.5 m^2`` with ``m = 0.1
   mscale_all_dim ln(factor) + 1``; the queries at position ``p`` times ``1
   + beta ln(1 + floor(p / original_max))`` (``llama_4_scaling_beta``).
   ``x <- x + concat_i(softmax(s_i) v_i) W_o``.
4. ``h2 = RMSNorm(x)``; ``g = softmax(h2 W_r)`` over the router's whole
   width; the K largest, renormalised to sum 1, times
   ``routed_scaling_factor``; ``x <- x + sum_k w_k SwiGLU_{e_k}(h2) +
   SwiGLU_shared(h2)``.  Experts this cut does not hold (a deployment's
   other chips) add nothing: the partial sum goes on.  An expert is applied
   to the rows this router sent it, and to no others.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_mistral4 as W

PAD_TO = 512  # sequences are padded to a multiple: few programs to compile
Q_BLOCK = 128  # attention is computed this many query rows at a time
ROW_BUCKET = 512  # an expert's routed rows are padded to a multiple


def _f32(tree):
    return {k: a.astype(jnp.float32) for k, a in tree.items()}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 and mscale else 1.0


def _inv_freq(s):
    """YaRN: frequency i is kept where dimension i turns more than
    ``beta_fast`` times over the original context, divided by ``factor``
    where it turns less than ``beta_slow`` times, blended linearly between."""
    d, theta = s["Dr"], s["theta"]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    if s["yarn_factor"] <= 1.0:
        return inv

    def dim_of(turns):
        return d * math.log(s["yarn_orig"] / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(s["beta_fast"])), 0)
    high = min(math.ceil(dim_of(s["beta_slow"])), d - 1)
    blend = np.clip((np.arange(d // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
    return inv / s["yarn_factor"] * blend + inv * (1.0 - blend)


def _rope(s, x, pos):
    """x [T, heads, Dr]; pairs (2i, 2i+1) rotated by ``pos * inv_freq[i]``."""
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(_inv_freq(s), jnp.float32)
    f = _yarn_mscale(s["yarn_factor"], s["mscale"]) / _yarn_mscale(
        s["yarn_factor"], s["mscale_all_dim"])
    cos, sin = jnp.cos(ang)[:, None, :] * f, jnp.sin(ang)[:, None, :] * f
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


HEADS_AT_ONCE = 4  # heads whose keys and values exist at one time


def _attention(s, q, k, v):
    """Causal attention of a few heads; q, k [T, g, Dn + Dr], v [T, g, Dv]."""
    T = q.shape[0]
    m = _yarn_mscale(s["yarn_factor"], s["mscale_all_dim"])
    scale = (s["Dn"] + s["Dr"]) ** -0.5 * m * m

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        ok = jnp.arange(T)[None, :] <= (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    return out.reshape(T, -1)


def _layer_attention(s, key, layer, x):
    """Steps 1-3, and step 4's routing: the hidden state after attention,
    the normed input of the experts, and the router's choice.  The heads
    are taken ``HEADS_AT_ONCE`` at a time, so that a 32k-token sequence's
    keys and values fit beside the served model; each head's arithmetic is
    the list's."""
    w = _f32(W.attention_weights(s, key, layer))
    T, Hq, C, Dn, Dv = x.shape[0], s["Hq"], s["C"], s["Dn"], s["Dv"]
    g = min(HEADS_AT_ONCE, Hq)
    pos = jnp.arange(T)
    h = _rms(x, w["input_norm"], s["eps"])
    c_q = _rms(h @ w["wq_a"], w["q_a_norm"], s["eps"])
    kv_a = h @ w["wkv_a"]
    c_kv = _rms(kv_a[:, :C], w["kv_a_norm"], s["eps"])
    k_r = _rope(s, kv_a[:, None, C:], pos)  # [T, 1, Dr], one for every head
    q_factor = 1.0
    if s["q_beta"]:
        q_factor = (1.0 + s["q_beta"] * jnp.log1p(
            jnp.floor(pos / s["yarn_orig"]).astype(jnp.float32)))[:, None, None]
    wq_b = w["wq_b"].reshape(-1, Hq // g, g, Dn + s["Dr"])
    wkv_b = w["wkv_b"].reshape(C, Hq // g, g, Dn + Dv)
    wo = w["wo"].reshape(Hq // g, g * Dv, -1)

    def heads(i, x):
        q = jnp.einsum("tr,rhd->thd", c_q, wq_b[:, i])
        q = jnp.concatenate([q[..., :Dn], _rope(s, q[..., Dn:], pos)], axis=-1) * q_factor
        kv = jnp.einsum("tc,chd->thd", c_kv, wkv_b[:, i])
        k = jnp.concatenate(
            [kv[..., :Dn], jnp.broadcast_to(k_r, (T, g, s["Dr"]))], axis=-1)
        return x + _attention(s, q, k, kv[..., Dn:]) @ wo[i]

    x = jax.lax.fori_loop(0, Hq // g, heads, x)
    h2 = _rms(x, w["post_norm"], s["eps"])
    gate = jax.nn.softmax(h2 @ w["router"], axis=-1)  # over the whole width
    top, idx = jax.lax.top_k(gate, s["K"])
    top = top / jnp.sum(top, axis=-1, keepdims=True) * s["routed_scale"]
    return x, h2, top, idx


def _swiglu(w, names, x):
    g, u, d = names
    return (jax.nn.silu(x @ w[g]) * (x @ w[u])) @ w[d]


def _expert_rows(s, key, layer, published, x, h2, rows, weight):
    """One routed expert over the rows routed to it: ``rows`` [n] indexes
    ``h2`` (padded with T, which ``weight`` 0 and the scatter's drop leave
    out)."""
    w = _f32(W.expert_weights(s, key, layer, published))
    y = _swiglu(w, ("w_gate", "w_up", "w_down"), h2[jnp.minimum(rows, h2.shape[0] - 1)])
    return x.at[rows].add(y * weight[:, None], mode="drop")


def _shared(s, key, layer, x, h2):
    w = _f32(W.shared_weights(s, key, layer))
    return x + _swiglu(w, ("ws_gate", "ws_up", "ws_down"), h2)


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
        self._expert = jax.jit(partial(_expert_rows, s), donate_argnums=(3,))
        self._shared = jax.jit(partial(_shared, s))
        self._head = jax.jit(partial(_head, s))

    def hidden(self, seed: int, tokens: Sequence[int]):
        """The hidden states before the final norm, [len(tokens) padded, H]."""
        s, key = self.s, W.seed_key(seed)
        n = len(tokens)
        pad = -(-n // PAD_TO) * PAD_TO
        toks = np.zeros((pad,), np.int32)
        toks[:n] = np.asarray(tokens, np.int32)
        x = self._embed(key, jnp.asarray(toks))
        for layer in range(s["L"]):
            x, h2, top, idx = self._attn(key, layer, x)
            idx, top = np.asarray(idx), np.asarray(top)
            if s["S"]:
                x = self._shared(key, layer, x, h2)
            for e in range(s["E"]):  # the experts this cut holds
                hit = idx == s["E0"] + e  # [T, K]
                rows = np.nonzero(hit.any(axis=1))[0]
                if not len(rows):
                    continue
                weight = (top * hit).sum(axis=1)[rows]
                m = -(-len(rows) // ROW_BUCKET) * ROW_BUCKET
                rows_p = np.full((m,), pad, np.int32)
                rows_p[: len(rows)] = rows
                weight_p = np.zeros((m,), np.float32)
                weight_p[: len(rows)] = weight
                x = self._expert(key, layer, s["E0"] + e, x, h2,
                                 jnp.asarray(rows_p), jnp.asarray(weight_p))
        return x

    def logprobs(
        self, seed: int, tokens: Sequence[int], rows: Sequence[int],
        ids: List[List[int]],
    ) -> np.ndarray:
        """With the weights of ``seed``: log-softmax of the next-token
        logits after ``tokens[: r + 1]`` for each ``r`` in ``rows``, at the
        token ids ``ids[i]``."""
        with jax.default_matmul_precision("highest"):
            x = self.hidden(seed, tokens)
            out = self._head(
                W.seed_key(seed), x, jnp.asarray(rows, jnp.int32),
                jnp.asarray(ids, jnp.int32),
            )
        return np.asarray(out)
