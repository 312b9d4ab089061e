"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

Long-context strategy (SURVEY.md 5.7): prompts longer than one device's
memory/compute budget shard their *sequence* dimension across the ``sp``
axis.  Each device holds one contiguous chunk of Q and its local chunk of
K/V; K/V chunks rotate around the ring via ``jax.lax.ppermute`` (one ICI
hop per step) while each device accumulates flash-style online softmax
against every chunk it sees.  After ``sp`` steps every Q chunk has attended
to every K/V chunk; peak memory per device is O(T/sp) and the rotation
overlaps with the attention math of the previous chunk.

This is the TPU-native replacement for the reference's single-GPU long-
context ceiling (its engines cap at what one GPU's KV fits); capability
parity target, not a translation -- the reference has no CP implementation
to copy.

Causal masking uses global positions (device i covers positions
``[i*C, (i+1)*C)``), so chunks strictly in the future contribute nothing --
the plain ring wastes those steps' FLOPs (the classic load imbalance;
striped layouts fix it and can layer on later).  Numerics: f32 running
max/sum/accumulator, matching engine/attention.py and ops/paged_attention.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..engine import attention as att
from ..engine.config import ModelConfig
from ..engine.model import Params, lm_logits, transformer

_NEG_INF = -1e30


def ring_attention_chunk(
    q: jax.Array,  # [B, C, Hq, D] this device's query chunk
    k: jax.Array,  # [B, C, Hkv, D] this device's key chunk
    v: jax.Array,  # [B, C, Hkv, D]
    seq_lens: jax.Array,  # [B] global valid length (replicated)
    axis_name: str,
    axis_size: int,
    window: int = 0,  # sliding-window width; 0 = full attention
) -> jax.Array:
    """Per-shard body (run under shard_map over ``axis_name``).

    Returns the attention output for the local Q chunk [B, C, Hq, D].
    """
    B, C, Hq, D = q.shape
    Hkv = k.shape[2]
    n_rep = Hq // Hkv
    idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))

    qpos = idx * C + jnp.arange(C)  # [C] global positions of local queries
    qf = q.astype(jnp.float32)

    m = jnp.full((B, Hq, C, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, Hq, C, 1), jnp.float32)
    acc = jnp.zeros((B, Hq, C, D), jnp.float32)

    def one_chunk(m, l, acc, k, v, src):
        kpos = src * C + jnp.arange(C)  # [C] global positions of these keys
        kr = att.repeat_kv(k, n_rep).astype(jnp.float32)
        vr = att.repeat_kv(v, n_rep).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kr) * scale  # [B, Hq, C, C]
        causal = kpos[None, :] <= qpos[:, None]
        if window > 0:
            # sliding window over GLOBAL positions: a key further than
            # window-1 behind the query contributes nothing regardless of
            # which shard holds it
            causal = causal & (qpos[:, None] - kpos[None, :] < window)
        mask = causal[None, None] & (
            kpos[None, None, None, :] < seq_lens[:, None, None, None]
        )
        s = jnp.where(mask, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        acc = acc * alpha + jnp.einsum("bhqk,bkhd->bhqd", p, vr)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        return m_new, l, acc

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    for step in range(axis_size):
        src = (idx - step) % axis_size
        m, l, acc = one_chunk(m, l, acc, k, v, src)
        if step != axis_size - 1:  # final rotation would be unused
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)

    safe = jnp.where(l > 0.0, l, 1.0)
    out = (acc / safe).transpose(0, 2, 1, 3)  # [B, C, Hq, D]
    return out.astype(q.dtype)


def make_ring_attention(mesh: Mesh, axis_name: str = "sp", window: int = 0):
    """shard_map'ed causal attention over sequence-sharded [B, T, H, D]
    arrays; composes inside a jit whose other axes GSPMD shards."""
    axis_size = mesh.shape[axis_name]
    spec = P(None, axis_name, None, None)

    fn = jax.shard_map(
        partial(
            ring_attention_chunk, axis_name=axis_name, axis_size=axis_size,
            window=window,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec, P(None)),
        out_specs=spec,
        check_vma=False,
    )

    def ring_attn(q, k, v, seq_lens):
        return fn(q, k, v, seq_lens)

    return ring_attn


@partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "axis_name"),
    donate_argnames=("kv_pages",),
)
def ring_prefill_step(
    params: Params,
    cfg: ModelConfig,
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    tokens: jax.Array,  # [B, T] bucket-padded prompts, T % sp == 0
    seq_lens: jax.Array,  # [B] true prompt lengths
    page_table: jax.Array,  # [B, T // page_size]
    mesh: Mesh,
    axis_name: str = "sp",
) -> Tuple[jax.Array, jax.Array]:
    """Sequence-parallel prefill: engine/step.py prefill_step with the
    sequence dimension sharded over ``sp`` and attention running as a ring.

    Everything else (QKV projections, MLP, KV page writes) is plain GSPMD:
    the per-token ops shard trivially over T, and the page scatter's
    collectives are XLA's problem.  Returns (last-token logits [B, V] f32,
    updated kv_pages)."""
    B, T = tokens.shape
    if T % mesh.shape[axis_name]:
        raise ValueError(
            f"prefill bucket {T} not divisible by sp={mesh.shape[axis_name]}"
        )
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    ring = make_ring_attention(mesh, axis_name, cfg.sliding_window or 0)

    def attn_fn(q, k, v, kv, layer):
        out = ring(q, k, v, seq_lens)
        new_kv = att.write_prefill_kv(kv, k, v, page_table, layer)
        return out, new_kv

    hidden, kv_pages = transformer(params, cfg, tokens, positions, kv_pages, attn_fn)
    last = jnp.clip(seq_lens - 1, 0, T - 1)
    hidden_last = jnp.take_along_axis(hidden, last[:, None, None], axis=1)[:, 0]
    return lm_logits(params, cfg, hidden_last), kv_pages
