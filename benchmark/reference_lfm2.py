"""The plain reference of the lfm2_moe family: LFM2-8B-A1B as its
``config.json`` describes it, in ``jax.numpy``, float32,
``default_matmul_precision("highest")``, with no cache, no state, no kernels,
no batching and no code of the program.  Its weights come from the seed
(``weights_lfm2.py``), a layer and an expert at a time.

One layer, ``x`` the hidden states ``[T, H]``, its operator from
``layer_types``: ``h = x + Op(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``.

- ``Op = conv``: ``[B | C | X] = u W_in`` (no bias, split in that order);
  ``z = B * X``; ``c_t = w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t`` a channel
  (``conv_L_cache`` 3, causal, ``z_t = 0`` before the sequence, the last tap
  on the current token): two shifts of the whole sequence; ``Op = (C * c)
  W_out``.
- ``Op = full_attention``: ``q, k, v = u W_q, u W_k, u W_v`` (no bias);
  RMSNorm over each head's values of ``q`` and of ``k`` (one weight a
  projection, shared by the heads) before RoPE (rotate-half over the whole
  head, ``rope_theta``, no scaling); causal softmax of ``q k / sqrt(D)``,
  each KV head serving ``Hq / Hkv`` query heads; ``W_o``.
- ``FF`` of the first ``num_dense_layers`` layers: SwiGLU of
  ``intermediate_size``.  Of every other: ``s = sigmoid(h2 W_g)`` over all
  experts; chosen: the ``num_experts_per_tok`` largest of ``s + b``
  (``use_expert_bias``); weighted: by ``s`` of the chosen, without ``b``,
  over their sum plus 1e-6 (``norm_topk_prob``), times
  ``routed_scaling_factor``; an expert (SwiGLU of ``moe_intermediate_size``)
  applied to the rows routed to it and to no others.

Then the final norm and the head, which is the embedding (``assumed``).

Departures from the published file, all in the configuration's ``reduced``
and ``assumed``: the cut of ``layer_types``; the tied head; weights drawn
from the seed as ``weights_lfm2.py`` says (the final norm's weight carries
the tied head's ``1/sqrt(H)`` and a sign a channel).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_lfm2 as W

PAD_TO = 512  # sequences are padded to a multiple: few programs to compile
Q_BLOCK = 128  # attention is computed this many query rows at a time
ROW_BUCKET = 512  # an expert's routed rows are padded to a multiple


def _f32(tree):
    return {k: a.astype(jnp.float32) for k, a in tree.items()}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [T, heads, D]; rotate-half convention, the whole head rotated."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def _attention(s, q, k, v):
    """One KV head and the query heads it serves; q [T, g, D], k, v [T, D]."""
    T = q.shape[0]
    kpos = jnp.arange(T)

    def block(i):
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        sc = jnp.einsum("qgd,kd->gqk", qb, k) / (s["D"] ** 0.5)
        ok = kpos[None, :] <= qpos[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", p, v)

    return jax.lax.map(block, jnp.arange(T // Q_BLOCK)).reshape(T, -1)


def _operator(s, kind, key, layer, x, norm):
    """``x + Op(RMSNorm(x))`` for a layer of ``kind``."""
    w = _f32(W.operator_weights(s, key, layer, kind))
    T = x.shape[0]
    u = _rms(x, norm, s["eps"])
    if kind == "conv":
        b, c, xs = jnp.split(u @ w["conv_in"], 3, axis=-1)
        z = b * xs
        zero = jnp.zeros_like(z[:1])
        z1 = jnp.concatenate([zero, z[:-1]], axis=0)  # z_{t-1}
        z2 = jnp.concatenate([zero, zero, z[:-2]], axis=0)  # z_{t-2}
        taps = w["conv_taps"]
        conv = taps[0] * z2 + taps[1] * z1 + taps[2] * z
        return x + (c * conv) @ w["conv_out"]
    Hq, Hkv, D = s["Hq"], s["Hkv"], s["D"]
    g = Hq // Hkv
    pos = jnp.arange(T)
    k = (u @ w["wk"]).reshape(T, Hkv, D)
    k = _rope(_rms(k, w["k_norm"], s["eps"]), pos, s["theta"])
    v = (u @ w["wv"]).reshape(T, Hkv, D)
    wq = w["wq"].reshape(-1, Hkv, g * D)
    wo = w["wo"].reshape(Hkv, g * D, -1)

    def head(i, x):
        q = (u @ wq[:, i]).reshape(T, g, D)
        q = _rope(_rms(q, w["q_norm"], s["eps"]), pos, s["theta"])
        return x + _attention(s, q, k[:, i], v[:, i]) @ wo[i]

    return jax.lax.fori_loop(0, Hkv, head, x)


def _layer_front(s, kind, routed, key, layer, x):
    """The operator, and what the MLP needs: the hidden state after the
    operator, the MLP's normed input, and (a routed layer) the router's
    choice and weights."""
    w = _f32(W.layer_weights(s, key, layer, routed))
    x = _operator(s, kind, key, layer, x, w["input_norm"])
    h2 = _rms(x, w["post_norm"], s["eps"])
    if not routed:
        return x, h2, None, None
    score = jax.nn.sigmoid(h2 @ w["router"])  # every expert on its own
    choice = score + w["router_bias"] if s["bias"] else score
    _, idx = jax.lax.top_k(choice, s["K"])
    top = jnp.take_along_axis(score, idx, axis=-1)  # without the bias
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-6) * s["scaling"]
    return x, h2, top, idx


def _dense(s, key, layer, x, h2):
    w = _f32(W.dense_weights(s, key, layer))
    return x + (jax.nn.silu(h2 @ w["w_gate"]) * (h2 @ w["w_up"])) @ w["w_down"]


def _expert_rows(s, key, layer, expert, x, h2, rows, weight):
    """One expert over the rows routed to it: ``rows`` [n] indexes ``h2``
    (padded with T, which ``weight`` 0 and the scatter's drop leave out)."""
    w = _f32(W.expert_weights(s, key, layer, expert))
    hr = h2[jnp.minimum(rows, h2.shape[0] - 1)]
    y = (jax.nn.silu(hr @ w["w_gate"]) * (hr @ w["w_up"])) @ w["w_down"]
    return x.at[rows].add(y * weight[:, None], mode="drop")


def _head(s, key, x, rows, ids):
    """The tied head a block of the vocabulary at a time."""
    h = _rms(x[rows], W.final_norm(s, key).astype(jnp.float32), s["eps"])
    logits = jax.lax.map(
        lambda b: h @ W.embed_block(s, key, b).astype(jnp.float32).T,
        jnp.arange(W.vocab_blocks(s), dtype=jnp.int32),
    )  # [blocks, rows, ids a block]
    lp = jax.nn.log_softmax(logits.transpose(1, 0, 2).reshape(h.shape[0], -1), axis=-1)
    return jnp.take_along_axis(lp, ids, axis=-1)


def _embed(s, key, tokens):
    """Each token's row out of its block of the embedding."""
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
        self._front = {
            (kind, routed): jax.jit(partial(_layer_front, s, kind, routed))
            for kind in set(s["kinds"]) for routed in (False, True)
        }
        self._dense = jax.jit(partial(_dense, s))
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
            routed = layer >= s["Ld"]
            x, h2, top, idx = self._front[kind, routed](key, layer, x)
            if not routed:
                x = self._dense(key, layer, x, h2)
                continue
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
