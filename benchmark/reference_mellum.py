"""The plain reference of the mellum family: Mellum2 as its ``config.json``
describes it, in ``jax.numpy``, float32,
``default_matmul_precision("highest")``, with no cache, no kernels, no
batching and no code of the program.  Its weights come from the seed
(``weights_mellum.py``), a layer and an expert at a time.

One layer, ``x`` the hidden states ``[T, H]``, its kind from ``layer_types``:

1. ``h = RMSNorm(x)``; ``q, k, v = h W_q [T, Hq, D], h W_k [T, Hkv, D], h W_v
   [T, Hkv, D]`` (no bias, no query/key norm).
2. RoPE on the whole head of ``q`` and ``k``, rotate-half convention, with
   the parameters of the layer's kind (``rope_parameters``): plain
   frequencies for ``default``; for ``yarn`` frequency ``i`` kept where
   dimension ``i`` turns more than ``beta_fast`` times over the original
   context, divided by ``factor`` where it turns less than ``beta_slow``
   times, blended linearly between (the correction range floored and
   ceiled: ``truncate`` at its default), and cos and sin times
   ``attention_factor`` (so the scores take its square).
3. Scores ``q . k / sqrt(D)``; key ``j`` visible to query ``i`` iff ``j <=
   i`` and, in a sliding layer, ``i - j < sliding_window``: a mask, nothing
   else.  Each KV head serves ``Hq / Hkv`` query heads.  ``x <- x +
   softmax(s) v W_o``.
4. ``h2 = RMSNorm(x)``; ``p = softmax(h2 W_r)`` over all experts, the
   ``num_experts_per_tok`` largest by plain ``top_k``, renormalised to sum
   1 (``norm_topk_prob``); ``x <- x + sum_e p_e SwiGLU_e(h2)``, an expert
   applied to the rows routed to it and to no others.

Then the final norm and the head.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_mellum as W

PAD_TO = 512  # sequences are padded to a multiple: few programs to compile
Q_BLOCK = 128  # attention is computed this many query rows at a time
ROW_BUCKET = 512  # an expert's routed rows are padded to a multiple


def _f32(tree):
    return {k: a.astype(jnp.float32) for k, a in tree.items()}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _inv_freq(d: int, rp: Dict[str, Any]) -> np.ndarray:
    theta = rp["theta"]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    if rp["type"] != "yarn":
        return inv

    def dim_of(turns):
        return d * math.log(rp["orig"] / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(rp["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rp["beta_slow"])), d - 1)
    blend = np.clip((np.arange(d // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
    return inv / rp["factor"] * blend + inv * (1.0 - blend)


def _attention_factor(rp: Dict[str, Any]) -> float:
    if rp["type"] != "yarn":
        return 1.0
    if rp["attention_factor"] is not None:
        return float(rp["attention_factor"])
    return 0.1 * math.log(rp["factor"]) + 1.0 if rp["factor"] > 1 else 1.0


def _rope(x, pos, rp):
    """x [T, heads, D]; rotate-half convention, the whole head rotated."""
    d = x.shape[-1]
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(_inv_freq(d, rp), jnp.float32)
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    f = _attention_factor(rp)
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return x * (jnp.cos(ang) * f) + jnp.concatenate([-x2, x1], axis=-1) * (jnp.sin(ang) * f)


def _attention(s, window, q, k, v):
    """One KV head and the query heads it serves; q [T, g, D], k, v [T, D]."""
    T = q.shape[0]
    kpos = jnp.arange(T)

    def block(i):
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        sc = jnp.einsum("qgd,kd->gqk", qb, k) / (s["D"] ** 0.5)
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok = ok & (qpos[:, None] - kpos[None, :] < window)
        p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", p, v)

    return jax.lax.map(block, jnp.arange(T // Q_BLOCK)).reshape(T, -1)


def _layer_attention(s, kind, key, layer, x):
    """Steps 1-3 and step 4's routing, for a layer of ``kind``: the hidden
    state after attention, the experts' normed input, and the router's
    choice.  The KV heads are taken one at a time, so that a 32k-token
    sequence's scores fit beside the served model."""
    w = _f32(W.attention_weights(s, key, layer))
    T, Hq, Hkv, D = x.shape[0], s["Hq"], s["Hkv"], s["D"]
    g = Hq // Hkv
    rp = s["rope"][kind]
    window = s["window"] if kind == "sliding" else 0
    pos = jnp.arange(T)
    h = _rms(x, w["input_norm"], s["eps"])
    k = _rope((h @ w["wk"]).reshape(T, Hkv, D), pos, rp)
    v = (h @ w["wv"]).reshape(T, Hkv, D)
    wq = w["wq"].reshape(-1, Hkv, g * D)
    wo = w["wo"].reshape(Hkv, g * D, -1)

    def head(i, x):
        q = _rope((h @ wq[:, i]).reshape(T, g, D), pos, rp)
        return x + _attention(s, window, q, k[:, i], v[:, i]) @ wo[i]

    x = jax.lax.fori_loop(0, Hkv, head, x)
    h2 = _rms(x, w["post_norm"], s["eps"])
    gate = jax.nn.softmax(h2 @ w["router"], axis=-1)  # over all experts
    top, idx = jax.lax.top_k(gate, s["K"])
    return x, h2, top / jnp.sum(top, axis=-1, keepdims=True), idx


def _expert_rows(s, key, layer, expert, x, h2, rows, weight):
    """One expert over the rows routed to it: ``rows`` [n] indexes ``h2``
    (padded with T, which ``weight`` 0 and the scatter's drop leave out)."""
    w = _f32(W.expert_weights(s, key, layer, expert))
    hr = h2[jnp.minimum(rows, h2.shape[0] - 1)]
    y = (jax.nn.silu(hr @ w["w_gate"]) * (hr @ w["w_up"])) @ w["w_down"]
    return x.at[rows].add(y * weight[:, None], mode="drop")


def _head(s, key, x, rows, ids):
    """The head a block of the vocabulary at a time (its float32 matrix is
    0.9 GB whole)."""
    norm = W.top_weights(s, key)["final_norm"].astype(jnp.float32)
    h = _rms(x[rows], norm, s["eps"])
    logits = jax.lax.map(
        lambda b: h @ W.head_block(s, key, b).astype(jnp.float32),
        jnp.arange(W.vocab_blocks(s), dtype=jnp.int32),
    )  # [blocks, rows, ids a block]
    lp = jax.nn.log_softmax(logits.transpose(1, 0, 2).reshape(h.shape[0], -1), axis=-1)
    return jnp.take_along_axis(lp, ids, axis=-1)


def _embed(s, key, tokens):
    """Each token's row out of its block of the embedding: a block is drawn,
    its tokens take their rows, and the next block follows."""
    vb = s["V"] // W.vocab_blocks(s)

    def block(x, b):
        rows = W.embed_block(s, key, b).astype(jnp.float32)
        mine = (tokens // vb == b)[:, None]
        return jnp.where(mine, rows[tokens % vb], x), None

    x0 = jnp.zeros((tokens.shape[0], s["H"]), jnp.float32)
    x, _ = jax.lax.scan(block, x0, jnp.arange(W.vocab_blocks(s), dtype=jnp.int32))
    return x


class Reference:
    """The reference forward pass of one configuration."""

    def __init__(self, cfg: Dict[str, Any]) -> None:
        s = W.sizes(cfg)
        self.s = s
        self._embed = jax.jit(partial(_embed, s))
        self._attn = {
            kind: jax.jit(partial(_layer_attention, s, kind))
            for kind in set(s["kinds"])
        }
        self._expert = jax.jit(partial(_expert_rows, s), donate_argnums=(3,))
        self._head = jax.jit(partial(_head, s))

    def hidden(self, seed: int, tokens: Sequence[int]):
        """The hidden states before the final norm, [len(tokens) padded, H]."""
        s, key = self.s, W.seed_key(seed)
        n = len(tokens)
        pad = -(-n // PAD_TO) * PAD_TO
        toks = np.zeros((pad,), np.int32)
        toks[:n] = np.asarray(tokens, np.int32)
        x = self._embed(key, jnp.asarray(toks))
        for layer, kind in enumerate(s["kinds"]):
            x, h2, top, idx = self._attn[kind](key, layer, x)
            idx, top = np.asarray(idx), np.asarray(top)
            for e in range(s["E"]):
                hit = idx == e  # [T, K]
                rows = np.nonzero(hit.any(axis=1))[0]
                if not len(rows):
                    continue
                weight = (top * hit).sum(axis=1)[rows]
                m = -(-len(rows) // ROW_BUCKET) * ROW_BUCKET
                rows_p = np.full((m,), pad, np.int32)
                rows_p[: len(rows)] = rows
                weight_p = np.zeros((m,), np.float32)
                weight_p[: len(rows)] = weight
                x = self._expert(key, layer, e, x, h2,
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

    def logits_at(self, seed: int, tokens: Sequence[int], rows: Sequence[int]):
        """Every next-token log-probability after ``tokens[: r + 1]`` (tests)."""
        V = self.s["V"]
        return self.logprobs(seed, tokens, rows, [list(range(V))] * len(rows))
