"""Checkpoint loading: HF safetensors -> stacked-layer JAX pytree.

Maps HuggingFace llama/mistral/qwen2/mixtral/gemma/phi3/qwen3 parameter names onto the
stacked ``[num_layers, ...]`` layout of dynamo_tpu.engine.model, transposing
torch ``[out, in]`` linears to ``[in, out]``.

Memory discipline: tensors are read lazily (mmap, on demand) from the open
safetensors shards and each stacked leaf is filled into one preallocated
host buffer, then placed onto its target sharding.  Peak host residency is
bounded by the largest single leaf (one stacked parameter across layers),
not the checkpoint -- a 70B load never materializes all weights on host.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .model import Params


class _ShardIndex:
    """Lazy name->tensor view over a set of safetensors files.

    Tensors are read on demand and never cached here, so the host only ever
    holds what the caller is currently assembling.
    """

    def __init__(self, files: List[str]) -> None:
        from safetensors import safe_open

        self._handles = [safe_open(p, framework="np") for p in files]
        self._where: Dict[str, Any] = {}
        for h in self._handles:
            for name in h.keys():
                self._where[name] = h

    def __contains__(self, name: str) -> bool:
        return name in self._where

    def __getitem__(self, name: str) -> np.ndarray:
        return self._where[name].get_tensor(name)


def load_safetensors_params(
    model_path: str,
    cfg: ModelConfig,
    dtype: Any = None,
    shardings: Optional[Dict[str, Any]] = None,
) -> Params:
    """Load all ``*.safetensors`` files under ``model_path``.

    ``shardings`` optionally maps pytree paths (e.g. ``layers/wq``) to
    ``NamedSharding``; leaves are device_put as they are assembled.
    """
    dtype = jnp.dtype(dtype or cfg.dtype)
    files = sorted(
        os.path.join(model_path, f)
        for f in os.listdir(model_path)
        if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {model_path}")
    return assemble_params(_ShardIndex(files), cfg, dtype, shardings)


def assemble_params(
    raw: Any,
    cfg: ModelConfig,
    dtype: Any,
    shardings: Optional[Dict[str, Any]] = None,
) -> Params:
    """Assemble the stacked pytree from a flat HF name->array mapping
    (a dict, or the lazy ``_ShardIndex``)."""
    if cfg.has_conv:
        raise ValueError(
            "loading a checkpoint of a trunk with convolution layers "
            "(model_type 'lfm2_moe') is not implemented: its tree keeps each "
            "kind's operator apart from the layers' stack (model.scan_layers) "
            "and no tensor names are mapped onto it yet; the benchmark "
            "serves it with weights drawn from a seed (benchmark/weights_lfm2.py)"
        )
    if cfg.has_linear:
        raise ValueError(
            "loading a checkpoint of a trunk with gated delta-rule layers "
            "(model_type 'qwen3_next') is not implemented: its tree keeps each "
            "kind's operator apart from the layers' stack (model.scan_layers), "
            "the projections that interleave their parts a head at a time "
            "part by part (model.delta_columns), and no tensor names are "
            "mapped onto it yet; the benchmark serves it with weights drawn "
            "from a seed (benchmark/weights_qwen3next.py)"
        )
    L = cfg.num_layers

    def get(name: str) -> np.ndarray:
        if name not in raw:
            raise KeyError(f"missing weight {name}")
        return raw[name]

    def linear(name: str) -> np.ndarray:
        return np.ascontiguousarray(get(name).T)  # [out,in] -> [in,out]

    def put(path: str, arr: np.ndarray) -> jax.Array:
        x = jnp.asarray(arr, dtype=dtype)
        if shardings and path in shardings:
            sh = shardings[path]
            # mesh axes that don't divide the dim fall back to replication
            # for that tensor (same rule as sharding.shard_params -- e.g. a
            # vocab or kv-head count the tp degree doesn't divide)
            if hasattr(sh, "spec") and hasattr(sh, "mesh"):
                from ..parallel.sharding import _compatible_spec

                sh = type(sh)(sh.mesh, _compatible_spec(sh.spec, x.shape, sh.mesh))
            x = jax.device_put(x, sh)
        return x

    def stack(path: str, layer_fn: Callable[[int], np.ndarray]) -> jax.Array:
        """Fill one preallocated [L, ...] buffer layer by layer (streaming:
        at most one layer's tensor plus the leaf buffer live on host)."""
        first = layer_fn(0)
        out = np.empty((L,) + first.shape, first.dtype)
        out[0] = first
        del first
        for i in range(1, L):
            out[i] = layer_fn(i)
        return put(path, out)

    pre = "model."
    layers: Dict[str, Any] = {}
    def split_fused(suffix: str, splits) -> None:
        """One fused [sum(rows), H] tensor per layer -> several stacked
        [L, H, rows] leaves.  ONE read per layer fills every slice --
        slicing per projection would re-read/decode the fused tensor once
        per output (phi3 qkv_proj is the largest attention tensor)."""
        w0 = get(f"{pre}layers.0.{suffix}")
        H = w0.shape[1]
        bufs = {k: np.empty((L, H, rows), w0.dtype) for k, rows in splits}
        for i in range(L):
            w = w0 if i == 0 else get(f"{pre}layers.{i}.{suffix}")
            lo = 0
            for k, rows in splits:
                bufs[k][i] = w[lo : lo + rows].T
                lo += rows
        del w0
        for k, _ in splits:
            layers[k] = put(f"layers/{k}", bufs.pop(k))

    fused_qkv = f"{pre}layers.0.self_attn.qkv_proj.weight" in raw
    if cfg.is_mla:
        # mistral4: low-rank queries, one latent projection for keys and
        # values (kv_a_proj_with_mqa's last rows are the shared rotated key)
        for key, suffix in (
            ("wq_a", "q_a_proj"), ("wq_b", "q_b_proj"),
            ("wkv_a", "kv_a_proj_with_mqa"), ("wkv_b", "kv_b_proj"),
            ("wo", "o_proj"),
        ):
            layers[key] = stack(
                f"layers/{key}",
                lambda i, s=suffix: linear(
                    f"{pre}layers.{i}.self_attn.{s}.weight"),
            )
        for key, suffix in (
            ("q_a_norm", "q_a_layernorm"), ("kv_a_norm", "kv_a_layernorm"),
        ):
            layers[key] = stack(
                f"layers/{key}",
                lambda i, s=suffix: get(
                    f"{pre}layers.{i}.self_attn.{s}.weight"),
            )
    elif fused_qkv:
        # phi3: fused qkv_proj rows are [q | k | v] (torch layout [out, in])
        q_rows = cfg.num_heads * cfg.head_dim
        kv_rows = cfg.num_kv_heads * cfg.head_dim
        split_fused(
            "self_attn.qkv_proj.weight",
            [("wq", q_rows), ("wk", kv_rows), ("wv", kv_rows)],
        )
        layers["wo"] = stack(
            "layers/wo",
            lambda i: linear(f"{pre}layers.{i}.self_attn.o_proj.weight"),
        )
    else:
        attn = {
            "wq": "self_attn.q_proj.weight",
            "wk": "self_attn.k_proj.weight",
            "wv": "self_attn.v_proj.weight",
            "wo": "self_attn.o_proj.weight",
        }
        for key, suffix in attn.items():
            layers[key] = stack(
                f"layers/{key}",
                lambda i, s=suffix: linear(f"{pre}layers.{i}.{s}"),
            )
    if cfg.attention_bias:
        for key, suffix in (
            ("bq", "self_attn.q_proj.bias"),
            ("bk", "self_attn.k_proj.bias"),
            ("bv", "self_attn.v_proj.bias"),
        ):
            layers[key] = stack(
                f"layers/{key}",
                lambda i, s=suffix: get(f"{pre}layers.{i}.{s}"),
            )
    if cfg.qk_norm:  # Qwen3: per-head [D] norms applied before RoPE
        for key, suffix in (
            ("q_norm", "self_attn.q_norm.weight"),
            ("k_norm", "self_attn.k_norm.weight"),
        ):
            layers[key] = stack(
                f"layers/{key}",
                lambda i, s=suffix: get(f"{pre}layers.{i}.{s}"),
            )
    layers["input_norm"] = stack(
        "layers/input_norm",
        lambda i: get(f"{pre}layers.{i}.input_layernorm.weight"),
    )
    layers["post_norm"] = stack(
        "layers/post_norm",
        lambda i: get(f"{pre}layers.{i}.post_attention_layernorm.weight"),
    )

    if cfg.is_mla or (
        cfg.is_moe and f"{pre}layers.0.mlp.gate.weight" in raw
    ):
        # mistral4, mellum: mlp.gate is the router over every published
        # expert, the experts sit under mlp.experts.N.{gate,up,down}_proj;
        # this process loads the experts it holds; the shared experts
        # (where there are any) are one SwiGLU
        lo = cfg.local_expert_offset
        layers["router"] = stack(
            "layers/router",
            lambda i: linear(f"{pre}layers.{i}.mlp.gate.weight"),
        )
        for key, name in (
            ("w_gate", "gate_proj"), ("w_up", "up_proj"),
            ("w_down", "down_proj"),
        ):
            layers[key] = stack(
                f"layers/{key}",
                lambda i, n=name: np.stack(
                    [
                        linear(f"{pre}layers.{i}.mlp.experts.{e}.{n}.weight")
                        for e in range(lo, lo + cfg.experts_held)
                    ]
                ),
            )
            if cfg.num_shared_experts:
                layers[key.replace("w_", "ws_")] = stack(
                    f"layers/{key.replace('w_', 'ws_')}",
                    lambda i, n=name: linear(
                        f"{pre}layers.{i}.mlp.shared_experts.{n}.weight"),
                )
    elif cfg.is_moe:
        E = cfg.num_experts
        moe = "block_sparse_moe"
        layers["router"] = stack(
            "layers/router",
            lambda i: linear(f"{pre}layers.{i}.{moe}.gate.weight"),
        )
        # Mixtral: w1 = gate, w3 = up, w2 = down
        for key, w in (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2")):
            layers[key] = stack(
                f"layers/{key}",
                lambda i, w=w: np.stack(
                    [
                        linear(f"{pre}layers.{i}.{moe}.experts.{e}.{w}.weight")
                        for e in range(E)
                    ]
                ),
            )
    elif f"{pre}layers.0.mlp.gate_up_proj.weight" in raw:
        # phi3: fused gate_up_proj rows are [gate | up]
        I = cfg.intermediate_size
        split_fused(
            "mlp.gate_up_proj.weight", [("w_gate", I), ("w_up", I)]
        )
        layers["w_down"] = stack(
            "layers/w_down",
            lambda i: linear(f"{pre}layers.{i}.mlp.down_proj.weight"),
        )
    else:
        for key, name in (
            ("w_gate", "gate_proj"),
            ("w_up", "up_proj"),
            ("w_down", "down_proj"),
        ):
            layers[key] = stack(
                f"layers/{key}",
                lambda i, n=name: linear(f"{pre}layers.{i}.mlp.{n}.weight"),
            )

    params: Params = {
        "embed": put("embed", get(f"{pre}embed_tokens.weight")),
        "layers": layers,
        "final_norm": put("final_norm", get(f"{pre}norm.weight")),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = put("lm_head", linear("lm_head.weight"))
    return params


def param_bytes(params: Params) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
