"""Pipeline parallelism over the ``pp`` mesh axis (GPipe-style, inference).

Stage-partitions the stacked layer weights ``[L, ...]`` (and the KV pages,
which carry the same leading layer axis) across ``pp`` devices and streams
microbatches through the stages with ``ppermute`` handoffs: at tick ``t``
stage ``s`` runs microbatch ``t - s`` through its ``L/pp`` local layers,
then passes the activations one hop down the ring.  A full forward takes
``M + pp - 1`` ticks for ``M`` microbatches; the (pp-1)-tick bubble
amortizes as M grows.

TPU-native by construction: every stage executes the same SPMD program
under ``shard_map`` (no per-stage Python), handoffs are single ICI hops,
and the local layer loop is the same ``lax.scan`` over
``model.transformer_layer`` the single-device path uses -- the math cannot
diverge.  Bubble ticks compute garbage by design (SPMD cannot skip); their
KV writes are routed to trash page 0 so they cannot corrupt live pages.

Capability parity: the reference delegates PP to its engines (vLLM
--pipeline-parallel-size, SURVEY.md 2.8); here it is first-party.  Prefill
is the PP-relevant phase (compute-bound); decode stays dp/tp-sharded.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..engine import attention as att
from ..engine.config import ModelConfig
from ..engine.model import (
    Params,
    lm_logits,
    rms_norm,
    rope_cos_sin,
    scan_layers,
)


@partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "axis_name", "num_microbatches"),
    donate_argnames=("kv_pages",),
)
def pp_prefill_step(
    params: Params,
    cfg: ModelConfig,
    kv_pages: jax.Array,  # [L, 2, num_pages, page, Hkv, D]
    tokens: jax.Array,  # [B, T] bucket-padded prompts
    seq_lens: jax.Array,  # [B] true prompt lengths
    page_table: jax.Array,  # [B, T // page_size]
    mesh: Mesh,
    axis_name: str = "pp",
    num_microbatches: int = 0,  # 0 = one per stage
) -> Tuple[jax.Array, jax.Array]:
    """Pipeline-parallel prefill; returns (last-token logits [B, V] f32,
    updated kv_pages).  Matches engine/step.py prefill_step numerically."""
    num_stages = mesh.shape[axis_name]
    M = num_microbatches or num_stages
    B, T = tokens.shape
    L = kv_pages.shape[0]
    if L % num_stages:
        raise ValueError(f"{L} layers not divisible by pp={num_stages}")
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    D = cfg.head_dim
    dtype = jnp.dtype(cfg.dtype)

    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    cos, sin = rope_cos_sin(positions, D, cfg.rope_theta, cfg.rope_scaling)  # [B, T, D]
    x = params["embed"][tokens].astype(dtype)  # [B, T, H]

    def split(a):  # [B, ...] -> [M, mb, ...]
        return a.reshape((M, mb) + a.shape[1:])

    x_mb, cos_mb, sin_mb = split(x), split(cos), split(sin)
    pt_mb, lens_mb = split(page_table), split(seq_lens)

    def stage(lp_local, kv_local, x_all, cos_a, sin_a, pt_a, lens_a):
        s = jax.lax.axis_index(axis_name)
        H = x_all.shape[-1]
        state = jnp.zeros((mb, T, H), dtype)
        out = jnp.zeros_like(x_all)
        kv = kv_local
        perm = [(i, i + 1) for i in range(num_stages - 1)]
        for t in range(M + num_stages - 1):
            feed = x_all[jnp.clip(t, 0, M - 1)]
            x_in = jnp.where(s == 0, feed, state)
            mbi = t - s  # microbatch this stage holds at tick t
            valid = (mbi >= 0) & (mbi < M)
            mbi_c = jnp.clip(mbi, 0, M - 1)
            cos_t, sin_t = cos_a[mbi_c], sin_a[mbi_c]
            lens_t = lens_a[mbi_c]
            # bubble ticks write their (garbage) KV to trash page 0
            pt_t = jnp.where(valid, pt_a[mbi_c], 0)

            def attn_fn(q, k, v, kv_buf, layer):
                o = att.prefill_attention(
                    q, k, v, lens_t, cfg.sliding_window or 0
                )
                return o, att.write_prefill_kv(kv_buf, k, v, pt_t, layer)

            x_out, kv, _ = scan_layers(
                lp_local, kv, x_in, cos_t, sin_t, cfg, attn_fn
            )
            oi = t - (num_stages - 1)
            if oi >= 0:
                emit = jnp.where(s == num_stages - 1, x_out, 0)
                out = out.at[oi].set(emit.astype(out.dtype))
            if t != M + num_stages - 2:
                state = jax.lax.ppermute(x_out, axis_name, perm)
        # only the last stage wrote non-zeros; psum replicates the result
        return jax.lax.psum(out, axis_name), kv

    fn = jax.shard_map(
        stage,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(), P(), P(), P(), P()),
        out_specs=(P(), P(axis_name)),
        check_vma=False,  # the stage bodies return stage-local values
    )
    hidden_mb, kv_pages = fn(
        params["layers"], kv_pages, x_mb, cos_mb, sin_mb, pt_mb, lens_mb
    )
    hidden = hidden_mb.reshape(B, T, -1)
    hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
    last = jnp.clip(seq_lens - 1, 0, T - 1)
    hidden_last = jnp.take_along_axis(hidden, last[:, None, None], axis=1)[:, 0]
    return lm_logits(params, cfg, hidden_last), kv_pages
