"""The plain reference of the qwen3_next family: Qwen3-Next-80B-A3B as its
``config.json`` and the published forward pass describe it, in ``jax.numpy``,
float32, ``default_matmul_precision("highest")``, with no cache, no state
carried between calls, no kernels, no batching and no code of the program.
Its weights come from the seed (``weights_qwen3next.py``), a layer and an
expert at a time.

``n(x; w)`` is RMSNorm with ``rms_norm_eps`` and weight ``1 + w``.  One layer,
``x`` the hidden states ``[T, H]``: ``h = x + Mix(n(x; w_in))``, ``y = h +
MoE(n(h; w_post))``; layer ``i`` is full attention where ``(i + 1) %
full_attention_interval == 0``, else linear.

- *Linear (gated delta rule)*: ``qkvz = u W_qkvz`` read a key head at a time
  as ``[q dk | k dk | v r dv | z r dv]`` (``r`` value heads a key head),
  ``ba = u W_ba`` likewise ``[b r | a r]``.  ``[q | k | v]`` flattened goes
  through a causal depthwise convolution of 4 taps (zeros before the
  sequence, the last tap on the current token), then SiLU.  ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``.  q and k are
  L2-normalised over ``dk`` (eps 1e-6), q scaled by ``dk^-1/2``.  A value
  head's state ``S [dk, dv]``, zero before the sequence, **token by token**
  (a ``lax.scan`` over positions; the served engine computes the same in
  chunks of 64, which is the thing under test):
  ``S <- exp(g_t) S; d = beta_t (v_t - S^T k_t); S <- S + k_t d^T; o_t = S^T
  q_t``.  Then ``(w rms(o_t) silu(z_t)) W_o``, the norm over a head's values
  with a plain weight.
- *Full (gated attention)*: ``u W_q`` a head's ``[query D | gate D]``; ``n``
  over each head of q and of k before RoPE; RoPE rotate-half over the first
  ``partial_rotary_factor D`` columns, ``rope_theta``, no scaling; causal
  softmax attention, scale ``D^-1/2``, each KV head serving ``Hq / Hkv`` query
  heads; ``(attn sigmoid(gate)) W_o``.
- *MoE*: ``p = softmax(h2 W_g)`` over the router's whole published width;
  the ``num_experts_per_tok`` largest, renormalised to sum 1; an expert
  (SwiGLU of ``moe_intermediate_size``) applied to the rows routed to it and
  to no others, **only the experts this configuration holds** (``num_experts``
  of ``router_experts``, from ``expert_offset``): what the absent ones would
  have added is left out and the partial sum goes on, as in the served cut;
  plus ``sigmoid(h2 w_sg) SwiGLU_shared(h2)``.

Then the final ``n`` and the untied head over the configuration's slice of
the vocabulary.  Left out: the multi-token-prediction module (the config has
no key for it and the published forward pass does not run it).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_qwen3next as W

PAD_TO = 512  # sequences are padded to a multiple: few programs to compile
Q_BLOCK = 128  # attention is computed this many query rows at a time
ROW_BUCKET = 512  # an expert's routed rows are padded to a multiple
HEAD_GROUP = 2  # key heads whose recurrence runs in one scan


def _f32(tree):
    return {k: a.astype(jnp.float32) for k, a in tree.items()}


def _rms(x, w, eps):
    """The family's RMSNorm: the weight is stored centred at zero."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, pos, theta, R):
    """x [T, heads, D]; rotate-half over the first ``R`` columns."""
    inv = 1.0 / (theta ** (np.arange(0, R, 2, dtype=np.float64) / R))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    xr, rest = x[..., :R], x[..., R:]
    x1, x2 = xr[..., : R // 2], xr[..., R // 2 :]
    xr = xr * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)
    return jnp.concatenate([xr, rest], axis=-1)


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


def _conv4(taps, x):
    """Causal depthwise convolution of 4 taps over ``x [T, C]``, then SiLU."""
    T = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((3, x.shape[1]), x.dtype), x], axis=0)
    return jax.nn.silu(sum(taps[i] * padded[i : i + T] for i in range(4)))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: q, k ``[T, n, r, dk]`` (a key head's
    rows repeated for its ``r`` value heads), v ``[T, n, r, dv]``, g, beta
    ``[T, n, r]``."""

    def step(S, t):
        qt, kt, vt, gt, bt = t
        S = jnp.exp(gt)[..., None, None] * S
        d = bt[..., None] * (vt - jnp.einsum("nrk,nrkv->nrv", kt, S))
        S = S + kt[..., :, None] * d[..., None, :]
        return S, jnp.einsum("nrk,nrkv->nrv", qt, S)

    S0 = jnp.zeros((*q.shape[1:], v.shape[-1]), jnp.float32)
    return jax.lax.scan(step, S0, (q, k, v, g, beta))[1]


def _linear_operator(s, w, u):
    """``Mix`` of a linear layer over the normed input ``u [T, H]``."""
    T = u.shape[0]
    Hk, Hv, dk, dv = s["Hk"], s["Hv"], s["dk"], s["dv"]
    r, n = Hv // Hk, min(HEAD_GROUP, Hk)
    w_in = w["gdn_in"].reshape(-1, Hk, 2 * dk + 2 * r * dv)
    w_ba = w["gdn_ba"].reshape(-1, Hk, 2 * r)
    # the convolution's channels as the flattened [q | k | v] orders them
    tq = w["gdn_taps"][:, : Hk * dk].reshape(4, Hk, dk)
    tk = w["gdn_taps"][:, Hk * dk : 2 * Hk * dk].reshape(4, Hk, dk)
    tv = w["gdn_taps"][:, 2 * Hk * dk :].reshape(4, Hk, r * dv)
    a_log = w["gdn_a_log"].reshape(Hk, r)
    dt_bias = w["gdn_dt_bias"].reshape(Hk, r)
    w_out = w["gdn_out"].reshape(Hk, r * dv, -1)

    def group(i, acc):
        heads = i * n + jnp.arange(n)
        qkvz = jnp.einsum("th,hnc->tnc", u, w_in[:, heads])
        q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
        ba = jnp.einsum("th,hnc->tnc", u, w_ba[:, heads])
        b, a = ba[..., :r], ba[..., r:]

        def conv(taps, x):  # [4, n, c], [T, n, c]
            c = x.shape[-1]
            return _conv4(taps.reshape(4, n * c), x.reshape(T, n * c)).reshape(T, n, c)

        q = _l2(conv(tq[:, heads], q)) * dk ** -0.5
        k = _l2(conv(tk[:, heads], k))
        v = conv(tv[:, heads], v).reshape(T, n, r, dv)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(a_log[heads]) * jax.nn.softplus(a + dt_bias[heads])
        rep = lambda x: jnp.broadcast_to(x[:, :, None], (T, n, r, dk))  # noqa: E731
        o = _delta_rule(rep(q), rep(k), v, g, beta)  # [T, n, r, dv]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + s["eps"])
        o = o * w["gdn_norm"] * jax.nn.silu(z.reshape(T, n, r, dv))
        return acc + jnp.einsum("tnc,nch->th", o.reshape(T, n, r * dv), w_out[heads])

    return jax.lax.fori_loop(0, Hk // n, group, jnp.zeros_like(u))


def _operator(s, kind, key, layer, x, norm):
    """``x + Mix(n(x))`` for a layer of ``kind``."""
    w = _f32(W.operator_weights(s, key, layer, kind))
    T = x.shape[0]
    u = _rms(x, norm, s["eps"])
    if kind == "linear":
        return x + _linear_operator(s, w, u)
    Hq, Hkv, D = s["Hq"], s["Hkv"], s["D"]
    g = Hq // Hkv
    pos = jnp.arange(T)
    k = (u @ w["wk"]).reshape(T, Hkv, D)
    k = _rope(_rms(k, w["k_norm"], s["eps"]), pos, s["theta"], s["R"])
    v = (u @ w["wv"]).reshape(T, Hkv, D)
    wq = w["wq"].reshape(-1, Hq, 2 * D)
    wo = w["wo"].reshape(Hq, D, -1)

    def head(i, x):  # one query head at a time: 32k rows of 16 do not fit
        qg = u @ wq[:, i]
        q, gate = qg[:, None, :D], qg[:, D:]
        q = _rope(_rms(q, w["q_norm"], s["eps"]), pos, s["theta"], s["R"])
        attn = _attention(s, q, k[:, i // g], v[:, i // g])
        return x + (attn * jax.nn.sigmoid(gate)) @ wo[i]

    return jax.lax.fori_loop(0, Hq, head, x)


def _layer_front(s, kind, key, layer, x):
    """The operator, the shared expert, and what the routed experts need:
    the hidden state so far, the MLP's normed input, the router's choice
    and weights."""
    w = _f32(W.layer_weights(s, key, layer))
    x = _operator(s, kind, key, layer, x, w["input_norm"])
    h2 = _rms(x, w["post_norm"], s["eps"])
    p = jax.nn.softmax(h2 @ w["router"], axis=-1)  # the whole published width
    top, idx = jax.lax.top_k(p, s["K"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    if s["Is"]:
        shared = (jax.nn.silu(h2 @ w["ws_gate"]) * (h2 @ w["ws_up"])) @ w["ws_down"]
        x = x + jax.nn.sigmoid(h2 @ w["ws_router"]) * shared
    return x, h2, top, idx


def _expert_rows(s, key, layer, expert, x, h2, rows, weight):
    """One held expert over the rows routed to it: ``rows`` [n] indexes
    ``h2`` (padded with T, which ``weight`` 0 and the scatter's drop leave
    out)."""
    w = _f32(W.expert_weights(s, key, layer, expert))
    hr = h2[jnp.minimum(rows, h2.shape[0] - 1)]
    y = (jax.nn.silu(hr @ w["w_gate"]) * (hr @ w["w_up"])) @ w["w_down"]
    return x.at[rows].add(y * weight[:, None], mode="drop")


def _head(s, key, x, rows, ids):
    top = _f32(W.top_weights(s, key))
    h = _rms(x[rows], top["final_norm"], s["eps"])
    lp = jax.nn.log_softmax(h @ top["lm_head"], axis=-1)
    return jnp.take_along_axis(lp, ids, axis=-1)


def _embed(s, key, tokens):
    return W.top_weights(s, key)["embed"][tokens].astype(jnp.float32)


class Reference:
    """The reference forward pass of one configuration."""

    def __init__(self, cfg: Dict[str, Any]) -> None:
        s = W.sizes(cfg)
        self.s = s
        self._embed = jax.jit(partial(_embed, s))
        self._front = {
            kind: jax.jit(partial(_layer_front, s, kind)) for kind in set(s["kinds"])
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
            x, h2, top, idx = self._front[kind](key, layer, x)
            idx, top = np.asarray(idx) - s["offset"], np.asarray(top)
            for e in range(s["E"]):  # the held experts alone
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
