"""Sharding rules: PartitionSpecs for the model params, KV pages, and batch.

Megatron-style TP layout expressed as GSPMD annotations (XLA inserts the
collectives -- SURVEY.md 5.8 "engine-internal collectives -> XLA over ICI"):

- attention qkv projections column-parallel (heads sharded), output
  projection row-parallel -> one all-reduce per attention block;
- MLP gate/up column-parallel, down row-parallel -> one all-reduce per MLP;
- KV pages sharded over kv_heads so each tp shard attends its own heads
  with zero cross-chip traffic on the decode hot path;
- MoE expert weights sharded over the ``ep`` axis (experts per device
  group), with column/row TP inside each expert.

All specs carry the leading ``num_layers`` axis unsharded (layers are
scanned, not distributed; pipeline parallel splits the scan instead).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.config import ModelConfig
from ..engine.model import Params

# Declared tick-role device-touch site (dynalint DT019): assemble_shards
# is the designed per-shard fetch behind the engine's commit/export sync
# points -- its device_get is the sync those sites already declare.
PACKED_DISPATCH_SITES = ("assemble_shards",)


def param_pspecs(cfg: ModelConfig) -> Dict[str, P]:
    """Pytree-path (``a/b``) -> PartitionSpec for every parameter."""
    specs: Dict[str, P] = {
        "embed": P(None, "tp"),
        "final_norm": P(None),
        "layers/wo": P(None, "tp", None),
        "layers/input_norm": P(None, None),
        "layers/post_norm": P(None, None),
    }
    if cfg.is_mla:
        # latent attention serves data-parallel (ModelConfig.validate_tp
        # refuses tp): its projections replicate, the experts go over ep
        specs["layers/wo"] = P(None, None, None)
        for name in ("wq_a", "wq_b", "wkv_a", "wkv_b"):
            specs[f"layers/{name}"] = P(None, None, None)
        for name in ("q_a_norm", "kv_a_norm"):
            specs[f"layers/{name}"] = P(None, None)
    else:
        specs["layers/wq"] = P(None, None, "tp")
        specs["layers/wk"] = P(None, None, "tp")
        specs["layers/wv"] = P(None, None, "tp")
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, "tp")
    if cfg.attention_bias:
        specs["layers/bq"] = P(None, "tp")
        specs["layers/bk"] = P(None, "tp")
        specs["layers/bv"] = P(None, "tp")
    if cfg.qk_norm:  # [L, D] per-head norms replicate (applied per head)
        specs["layers/q_norm"] = P(None, None)
        specs["layers/k_norm"] = P(None, None)
    if cfg.is_moe:
        # experts over ep; within an expert, classic column/row TP
        specs["layers/router"] = P(None, None, None)
        specs["layers/w_gate"] = P(None, "ep", None, "tp")
        specs["layers/w_up"] = P(None, "ep", None, "tp")
        specs["layers/w_down"] = P(None, "ep", "tp", None)
        if cfg.num_shared_experts:
            specs["layers/ws_gate"] = P(None, None, "tp")
            specs["layers/ws_up"] = P(None, None, "tp")
            specs["layers/ws_down"] = P(None, "tp", None)
    else:
        specs["layers/w_gate"] = P(None, None, "tp")
        specs["layers/w_up"] = P(None, None, "tp")
        specs["layers/w_down"] = P(None, "tp", None)
    return specs


def kv_pspec(cfg: ModelConfig) -> P:
    """KV pages [L, 2, pages, page, Hkv, D]: shard kv heads over tp when
    divisible (GQA models with few kv heads and large tp replicate)."""
    return P(None, None, None, None, "tp", None)


def batch_pspecs() -> Dict[str, P]:
    """Decode batch arrays sharded over dp."""
    return {
        "tokens": P("dp"),
        "seq_lens": P("dp"),
        "page_table": P("dp", None),
        "prompt_tokens": P("dp", None),
    }


def _flatten_with_paths(tree: Any, prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten_with_paths(v, f"{prefix}{k}/"))
    else:
        out[prefix[:-1]] = tree
    return out


def param_shardings(
    cfg: ModelConfig, mesh: Mesh
) -> Dict[str, NamedSharding]:
    """Path -> NamedSharding map (feeds the streaming safetensors loader)."""
    return {
        path: NamedSharding(mesh, spec) for path, spec in param_pspecs(cfg).items()
    }


def shard_params(params: Params, cfg: ModelConfig, mesh: Mesh) -> Params:
    """Device_put an assembled params pytree onto its TP layout.

    Axes that do not divide evenly (e.g. kv heads < tp) fall back to
    replication for that tensor.
    """
    flat = _flatten_with_paths(params)
    specs = param_pspecs(cfg)
    out_flat: Dict[str, jax.Array] = {}
    for path, leaf in flat.items():
        spec = specs.get(path, P())
        spec = _compatible_spec(spec, leaf.shape, mesh)
        out_flat[path] = jax.device_put(leaf, NamedSharding(mesh, spec))
    return _unflatten(out_flat)


def shard_kv(kv: jax.Array, cfg: ModelConfig, mesh: Mesh) -> jax.Array:
    from ..engine.kv_cache import kv_data, place_pool

    spec = _compatible_spec(kv_pspec(cfg), kv_data(kv).shape, mesh)
    return place_pool(kv, NamedSharding(mesh, spec))


def _compatible_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop mesh axes that do not divide the corresponding dim."""
    fixed = []
    for i, axis in enumerate(spec):
        if axis is None:
            fixed.append(None)
            continue
        size = mesh.shape.get(axis, 1)
        if i < len(shape) and shape[i] % size == 0:
            fixed.append(axis)
        else:
            fixed.append(None)
    return P(*fixed)


def _unflatten(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, leaf in flat.items():
        parts = path.split("/")
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return out


# ---------------------------------------------------------------------------
# per-shard export: host-side reassembly of sharded device arrays
# ---------------------------------------------------------------------------


def kv_shard_geometry(arr: jax.Array) -> Optional[Dict[str, int]]:
    """Shard geometry of a (possibly sharded) KV array: ``{"axis": i,
    "parts": n}`` for the first sharded axis, or None when replicated /
    unsharded.  Recorded alongside every KV blob that leaves the device
    (disagg export meta, offload tier records, swap snapshots) so a
    restore site can assert it is scattering into a compatible pool."""
    sharding = getattr(arr, "sharding", None)
    spec = getattr(sharding, "spec", None)
    if sharding is None or spec is None:
        return None
    mesh_shape = getattr(sharding, "mesh", None)
    for axis, names in enumerate(spec):
        if names is None:
            continue
        names = names if isinstance(names, tuple) else (names,)
        parts = 1
        for name in names:
            parts *= int(mesh_shape.shape.get(name, 1))
        if parts > 1:
            return {"axis": axis, "parts": parts}
    return None


def assemble_shards(arr: jax.Array) -> np.ndarray:
    """Materialize a device array on host by gathering each addressable
    shard's slice and reassembling -- ONE device->host transfer per shard,
    no cross-chip collective.

    This is the export half of the per-shard KV contract: a tp-sharded
    pool's pages come to host head-slice by head-slice (each chip moves
    only its own kv heads), and the host concatenation rebuilds the
    full-width blob the wire/offload formats carry.  Replicated or
    single-device arrays take the plain ``device_get``; so does the
    multi-host case (non-addressable shards), where the caller is expected
    to run SPMD-lockstep and use a collective fetch instead."""
    sharding = getattr(arr, "sharding", None)
    if (
        sharding is None
        or getattr(sharding, "is_fully_replicated", True)
        or not getattr(sharding, "is_fully_addressable", False)
    ):
        return np.asarray(jax.device_get(arr))
    out = np.empty(arr.shape, jax.numpy.dtype(arr.dtype))
    seen = set()
    for shard in arr.addressable_shards:
        key = tuple(
            (s.start, s.stop) for s in shard.index if isinstance(s, slice)
        )
        if key in seen:
            continue  # replicated twin of an already-copied slice
        seen.add(key)
        out[shard.index] = np.asarray(shard.data)
    return out


# ---------------------------------------------------------------------------
# sharded serving steps: the engine hot paths re-jitted with explicit
# in/out shardings (GSPMD inserts the collectives; nothing is left to
# propagation, so the KV pool can never be silently replicated)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShardedSteps:
    """Sharding-pinned jit wrappers over the raw engine step functions.

    Built once per engine at startup (``make_sharded_steps``); the engine
    routes every decode-path dispatch through these when it has a mesh.
    Each wrapper declares in/out shardings for the recurrent state --
    params and KV over ``tp`` (kv heads sharded: zero cross-chip traffic
    on the decode hot path), batch/decode-state arrays over ``dp`` -- and
    leaves host-built scratch (row dicts, rng, packed host-bound outputs)
    unconstrained.  Every producer of recurrent decode state is wrapped,
    so the committed shardings form a closed cycle and a placement drift
    surfaces as a loud error at the very next dispatch, not as a silent
    all-gather."""

    mesh: Mesh
    kv_sharding: NamedSharding
    decode_block: Any
    packed_unified_step: Any
    packed_unified_multistep: Any
    verify_and_sample: Any
    update_lanes: Any
    inject_token: Any
    inject_tokens: Any
    zero_count_rows: Any
    bump_counts: Any
    seed_count_rows: Any
    # KV-pool page primitives (disagg delivery, offload onboard, swap
    # snapshots): every producer that reassigns the pool pins its output
    # back onto the pool's sharding, so a host-built blob operand can
    # never drift the placement between dispatches
    scatter_block_pages: Any
    slice_block_pages: Any
    gather_layer_pages: Any
    scatter_layer_pages: Any


def make_sharded_steps(
    mesh: Mesh,
    cfg: ModelConfig,
    params: Params,
    kv_pages: jax.Array,
    max_batch_size: int,
) -> ShardedSteps:
    """Re-jit the serving entry points with explicit in/out shardings.

    Parameter shardings are harvested from the live (already-placed,
    possibly quantized) params pytree and the KV pool, so the declared
    layout is exactly what the loader/quantizer produced -- divisibility
    fallbacks included.  Decode-state arrays shard batch-major over
    ``dp`` (the ``vec``/``mat`` shardings below), filtered through
    :func:`_compatible_spec` at the engine's ``max_batch_size``."""
    from ..engine import step as _step

    param_sh = jax.tree_util.tree_map(lambda x: x.sharding, params)
    # the KV pool may be a pytree (an int8 pool: data + replicated row
    # scales): harvest per-leaf, so the pinned in/out shardings follow
    # whatever layout the pool was actually placed with
    kv_sh = jax.tree_util.tree_map(lambda x: x.sharding, kv_pages)
    B = max_batch_size
    # the engine's whole device-resident decode state (tokens, seq_lens,
    # limit_lens, active, stop_ids, page_table, counts, SamplingParams
    # leaves) is batch-major with unsharded tails, so exactly two
    # shardings cover it: [B] vectors and [B, x] matrices over ``dp``
    # (dropped by _compatible_spec when B does not divide -- resolve_mesh
    # rejects that for the serving path, but an explicit mesh may hit it)
    vec = NamedSharding(mesh, _compatible_spec(P("dp"), (B,), mesh))
    mat = NamedSharding(
        mesh, _compatible_spec(P("dp", None), (B, 1), mesh)
    )
    samp = _step.SamplingParams(*([vec] * 7))  # every leaf is [B]

    decode_block = jax.jit(
        _step._decode_block,
        static_argnames=(
            "cfg", "num_steps", "use_filters", "top_n", "use_penalties"
        ),
        donate_argnames=("kv_pages", "counts"),
        # (params, kv, tokens, seq_lens, limit_lens, active, stop_ids,
        #  page_table, rng, sampling, counts): rng stays unconstrained (the
        # engine threads an uncommitted key), counts may be None
        in_shardings=(
            param_sh, kv_sh, vec, vec, vec, vec, mat, mat, None, samp, None,
        ),
        # (packed, tokens, seq_lens, active, kv, rng, counts): packed is
        # host-bound (device_get at commit) -- forcing it replicated would
        # insert an all-gather on the hot path for nothing
        out_shardings=(None, vec, vec, vec, kv_sh, None, mat),
    )
    packed_unified_step = jax.jit(
        _step._packed_unified_step,
        static_argnames=("cfg", "s_max", "s_spec", "top_n", "use_filters"),
        donate_argnames=("kv_pages", "tokens", "seq_lens", "active"),
        # (params, kv, tokens, seq_lens, limit_lens, active, stop_ids,
        #  page_table, t_tokens, t_lane, t_rel, t_dec, p_start, p_lens,
        #  p_sample, p_activate, dec_cap, seg_off, v_lens, rng, sampling):
        # the packed [Np] token axis interleaves lanes arbitrarily, so it
        # stays unconstrained (GSPMD gathers from the dp-sharded state);
        # the two packed outputs (single-token + folded-verify columns)
        # are host-bound device_get handles, left unconstrained like the
        # other steps' packed outputs
        in_shardings=(
            param_sh, kv_sh, vec, vec, vec, vec, mat, mat,
            None, None, None, None, vec, vec, vec, vec, vec, vec, vec,
            None, samp,
        ),
        out_shardings=(None, None, vec, vec, vec, kv_sh, None),
    )
    packed_unified_multistep = jax.jit(
        _step._packed_unified_multistep,
        static_argnames=(
            "cfg", "s_max", "num_steps", "s_spec", "top_n", "use_filters"
        ),
        donate_argnames=("kv_pages", "tokens", "seq_lens", "active"),
        # identical operand layout to packed_unified_step (the multi-step
        # entry IS that step plus a decode scan over the same state); the
        # widened [B, K, ...] packed output is host-bound like every other
        # packed output and stays unconstrained
        in_shardings=(
            param_sh, kv_sh, vec, vec, vec, vec, mat, mat,
            None, None, None, None, vec, vec, vec, vec, vec, vec, vec,
            None, samp,
        ),
        out_shardings=(None, None, vec, vec, vec, kv_sh, None),
    )
    verify_and_sample = jax.jit(
        _step._verify_and_sample,
        static_argnames=("cfg", "top_n", "use_filters"),
        donate_argnames=("kv_pages",),
        # (params, kv, tokens, base, n_tokens, page_table, rng, sampling)
        in_shardings=(param_sh, kv_sh, mat, vec, vec, mat, None, samp),
        out_shardings=(None, kv_sh),
    )
    update_lanes = jax.jit(
        _step._update_lanes,
        donate_argnames=_step.UPDATE_LANES_DONATED,
        # 13 decode-state arrays + slots + host rows dict (unconstrained)
        in_shardings=(
            vec, vec, vec, vec, mat, mat,
            vec, vec, vec, vec, vec, vec, vec, None, None,
        ),
        out_shardings=(
            vec, vec, vec, vec, mat, mat, vec, vec, vec, vec, vec, vec, vec,
        ),
    )
    inject_token = jax.jit(
        _step._inject_token,
        donate_argnames=("tokens",),
        in_shardings=(vec, None, None),
        out_shardings=vec,
    )
    inject_tokens = jax.jit(
        _step._inject_tokens,
        donate_argnames=("tokens",),
        in_shardings=(vec, None, None),
        out_shardings=vec,
    )
    zero_count_rows = jax.jit(
        _step._zero_count_rows,
        donate_argnames=("counts",),
        in_shardings=(mat, None),
        out_shardings=mat,
    )
    bump_counts = jax.jit(
        _step._bump_counts,
        donate_argnames=("counts",),
        in_shardings=(mat, None, None),
        out_shardings=mat,
    )
    seed_count_rows = jax.jit(
        _step._seed_count_rows,
        donate_argnames=("counts",),
        in_shardings=(mat, None, None, None),
        out_shardings=mat,
    )
    from ..engine import kv_cache as _kv

    # (kv, ids, blob): host-built blobs/ids stay unconstrained; the pool
    # result is pinned so delivery/restore can't drift its placement
    scatter_block_pages = jax.jit(
        _kv._scatter_block_pages,
        donate_argnames=("kv_pages",),
        in_shardings=(kv_sh, None, None),
        out_shardings=kv_sh,
    )
    slice_block_pages = jax.jit(
        _kv._slice_block_pages,
        in_shardings=(kv_sh, None),
        out_shardings=None,  # snapshot: head-sliced like the pool
    )
    gather_layer_pages = jax.jit(
        _kv._gather_layer_pages,
        in_shardings=(kv_sh, None, None),
        out_shardings=None,
    )
    scatter_layer_pages = jax.jit(
        _kv._scatter_layer_pages,
        donate_argnames=("kv_pages",),
        in_shardings=(kv_sh, None, None, None),
        out_shardings=kv_sh,
    )
    return ShardedSteps(
        mesh=mesh,
        kv_sharding=kv_sh,
        decode_block=decode_block,
        packed_unified_step=packed_unified_step,
        packed_unified_multistep=packed_unified_multistep,
        verify_and_sample=verify_and_sample,
        update_lanes=update_lanes,
        inject_token=inject_token,
        inject_tokens=inject_tokens,
        zero_count_rows=zero_count_rows,
        bump_counts=bump_counts,
        seed_count_rows=seed_count_rows,
        scatter_block_pages=scatter_block_pages,
        slice_block_pages=slice_block_pages,
        gather_layer_pages=gather_layer_pages,
        scatter_layer_pages=scatter_layer_pages,
    )


def make_sharded_drafter(mesh: Mesh, params: Params):
    """Re-jit the model drafter's greedy forward with explicit in/out
    shardings for the serving mesh (the make_sharded_steps contract
    applied to the SECOND weight load): draft params stay pinned to the
    tp layout the loader placed them with, the tiny token window and the
    [1, n] proposal are replicated -- a placement drift of the draft
    weights surfaces at the next propose, never as a silent all-gather
    on the target's decode path."""
    from ..spec.model_drafter import _draft_greedy_tokens

    param_sh = jax.tree_util.tree_map(lambda x: x.sharding, params)
    return jax.jit(
        _draft_greedy_tokens,
        static_argnames=("cfg", "n"),
        # (params, tokens, length): window/length/proposal replicated
        in_shardings=(param_sh, None, None),
        out_shardings=None,
    )
