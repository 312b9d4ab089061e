"""Per-chip memory planning: does ModelConfig x mesh x quantize x KV budget
fit the accelerator's HBM?

The reference reaches deployment sizing empirically (profile_sla sweeps +
the multinode configs in examples/llm/configs/multinode-405b.yaml); here
fit is computed analytically from the exact parameter shapes the engine
allocates (mirrors ``model.init_params``), the sharding rules it applies
(``parallel.sharding.param_pspecs`` -- a tensor whose tp axis does not
divide is replicated, not sharded), and the quantization layout
(``engine.quant``: int8 body + input-dim amax scales).  ``plan_memory``
is the planning primitive; ``max_kv_pages`` inverts it to answer "how
much KV cache can this chip hold after the weights land".

Numbers are bytes-exact for params and KV; activation scratch is a bound,
not an exact figure (XLA's liveness is schedule-dependent), sized from the
dominant live tensors of a prefill dispatch with a safety factor.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

from .config import ModelConfig

# HBM per chip, keyed by ``jax.devices()[0].device_kind`` (Google Cloud TPU
# documentation, "TPU v5e": 16 GiB).  A plan is for a named device: one that
# is not listed here is an error, never a silent default.
HBM_BYTES = {"TPU v5 lite": 16 * 1024**3}
# leave headroom for XLA's runtime buffers, compiled program constants, and
# fragmentation
DEFAULT_RESERVE_FRACTION = 0.06


def hbm_bytes_for(device_kind: str) -> int:
    """HBM of one chip of ``device_kind``; raises for a device the table
    does not know."""
    try:
        return HBM_BYTES[device_kind]
    except KeyError:
        raise ValueError(
            f"no HBM size recorded for device kind {device_kind!r}; known: "
            f"{sorted(HBM_BYTES)}"
        ) from None

_DTYPE_BYTES = {
    "bfloat16": 2, "float16": 2, "float32": 4, "float64": 8, "int8": 1,
}


def _dtype_bytes(dtype: str) -> int:
    try:
        return _DTYPE_BYTES[str(dtype)]
    except KeyError:
        import numpy as np

        return int(np.dtype(dtype).itemsize)


def _param_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    """Exact shapes of every parameter (mirrors model.init_params)."""
    L, H, D = cfg.num_layers, cfg.hidden_size, cfg.head_dim
    Hq, Hkv, I = cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size
    shapes: Dict[str, Tuple[int, ...]] = {
        "embed": (cfg.vocab_size, H),
        "final_norm": (H,),
        "layers/input_norm": (L, H),
        "layers/post_norm": (L, H),
    }
    if cfg.is_mla:
        R, C = cfg.q_lora_rank, cfg.kv_lora_rank
        shapes["layers/wq_a"] = (L, H, R)
        shapes["layers/q_a_norm"] = (L, R)
        shapes["layers/wq_b"] = (L, R, Hq * D)
        shapes["layers/wkv_a"] = (L, H, C + cfg.qk_rope_head_dim)
        shapes["layers/kv_a_norm"] = (L, C)
        shapes["layers/wkv_b"] = (
            L, C, Hq * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        shapes["layers/wo"] = (L, Hq * cfg.v_head_dim, H)
    else:
        shapes["layers/wq"] = (L, H, Hq * D)
        shapes["layers/wk"] = (L, H, Hkv * D)
        shapes["layers/wv"] = (L, H, Hkv * D)
        shapes["layers/wo"] = (L, Hq * D, H)
    if cfg.attention_bias:
        shapes["layers/bq"] = (L, Hq * D)
        shapes["layers/bk"] = (L, Hkv * D)
        shapes["layers/bv"] = (L, Hkv * D)
    if cfg.qk_norm:
        shapes["layers/q_norm"] = (L, D)
        shapes["layers/k_norm"] = (L, D)
    if cfg.is_moe:
        E = cfg.experts_held
        shapes["layers/router"] = (L, H, cfg.num_experts)
        shapes["layers/w_gate"] = (L, E, H, I)
        shapes["layers/w_up"] = (L, E, H, I)
        shapes["layers/w_down"] = (L, E, I, H)
        if cfg.num_shared_experts:
            Is = I * cfg.num_shared_experts
            shapes["layers/ws_gate"] = (L, H, Is)
            shapes["layers/ws_up"] = (L, H, Is)
            shapes["layers/ws_down"] = (L, Is, H)
    else:
        shapes["layers/w_gate"] = (L, H, I)
        shapes["layers/w_up"] = (L, H, I)
        shapes["layers/w_down"] = (L, I, H)
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (H, cfg.vocab_size)
    return shapes


_QUANT_PATHS = frozenset(
    {"layers/wq", "layers/wk", "layers/wv", "layers/wo",
     "layers/w_gate", "layers/w_up", "layers/w_down", "lm_head",
     "layers/wq_a", "layers/wq_b", "layers/wkv_a", "layers/wkv_b",
     "layers/ws_gate", "layers/ws_up", "layers/ws_down"}
)


def _shard_divisor(path: str, shape: Tuple[int, ...], cfg: ModelConfig,
                   tp: int, ep: int) -> int:
    """How many ways the tensor actually splits on the mesh, mirroring
    param_pspecs + _compatible_spec: an axis that does not divide stays
    replicated."""
    from jax.sharding import PartitionSpec  # noqa: F401  (doc parity)

    from ..parallel.sharding import param_pspecs

    spec = param_pspecs(cfg).get(path)
    if spec is None:
        return 1
    div = 1
    for dim, axis in zip(shape, tuple(spec)):
        if axis is None:
            continue
        n = tp if axis == "tp" else ep if axis == "ep" else 1
        if n > 1 and dim % n == 0:
            div *= n
    return div


@dataclass
class MemoryPlan:
    """Per-chip byte budget for one engine instance."""

    param_bytes: int
    kv_bytes: int
    scratch_bytes: int
    reserve_bytes: int
    hbm_bytes: int
    num_pages: int
    bytes_per_page: int  # per chip (kv heads divided by tp when divisible)
    detail: Dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return (self.param_bytes + self.kv_bytes + self.scratch_bytes
                + self.reserve_bytes)

    @property
    def fits(self) -> bool:
        return self.total_bytes <= self.hbm_bytes

    @property
    def headroom_bytes(self) -> int:
        return self.hbm_bytes - self.total_bytes

    def assert_fits(self) -> "MemoryPlan":
        if not self.fits:
            gib = 1024**3
            raise ValueError(
                f"memory plan exceeds HBM: params {self.param_bytes/gib:.2f} "
                f"+ kv {self.kv_bytes/gib:.2f} + scratch "
                f"{self.scratch_bytes/gib:.2f} + reserve "
                f"{self.reserve_bytes/gib:.2f} = {self.total_bytes/gib:.2f} "
                f"GiB > {self.hbm_bytes/gib:.2f} GiB "
                f"(raise tp, quantize, or shrink the page budget)"
            )
        return self


def plan_memory(
    cfg: ModelConfig,
    *,
    tp: int = 1,
    ep: int = 1,
    quantize: Optional[str] = None,
    page_size: int = 16,
    num_pages: int = 512,
    max_batch_size: int = 8,
    prefill_bucket: int = 2048,
    hbm_bytes: int,
    reserve_fraction: float = DEFAULT_RESERVE_FRACTION,
) -> MemoryPlan:
    """Byte-exact params + KV and a bounded scratch estimate, per chip."""
    wbytes = _dtype_bytes(cfg.dtype)
    detail: Dict[str, int] = {}
    pbytes = 0
    for path, shape in _param_shapes(cfg).items():
        n = 1
        for d in shape:
            n *= d
        div = _shard_divisor(path, shape, cfg, tp, ep)
        if quantize == "int8" and path in _QUANT_PATHS:
            # int8 body + amax scales over the input dim (engine.quant:
            # s has the reduced axis at size 1).  The scale's divisor is
            # computed from the SCALE shape: a tensor sharded only on its
            # contracted axis (wo, w_down) keeps its scales replicated
            # (the size-1 dim can't shard), exactly as _compatible_spec
            # resolves it at runtime.
            sshape = shape[:-2] + (1, shape[-1])
            sdiv = _shard_divisor(path, sshape, cfg, tp, ep)
            b = n // div + ((n // shape[-2]) * wbytes) // sdiv
        else:
            b = n * wbytes // div
        detail[path] = b
        pbytes += b

    # KV pages [L, 2, pages, page, Hkv, D]; kv heads shard over tp only
    # when divisible (kv_pspec + _compatible_spec semantics)
    # (a latent cache has no head axis: cfg.kv_geometry, never divided)
    slabs, sides, kv_heads, kv_width = cfg.kv_geometry
    kv_div = tp if tp > 1 and kv_heads % tp == 0 else 1
    bytes_per_page = (
        slabs * sides * page_size * (kv_heads // kv_div) * kv_width * wbytes
    )
    kv_bytes = bytes_per_page * num_pages

    # Scratch bound: the prefill dispatch's dominant live tensors --
    # ~6 hidden-width activation copies (residual, normed, attn out, mlp
    # gate/up/down chain) plus q/k/v at head width, plus full-width logits
    # in f32 at the sampled positions.  The flash kernels keep scores out
    # of HBM; the XLA prefill path's fused softmax chain stays within this
    # bound for the bucket sizes the engine uses.  Batch-major tensors
    # shard over dp; per-chip scratch uses the whole engine batch (worst
    # case dp=1 on this chip).
    B, T, H = max_batch_size, prefill_bucket, cfg.hidden_size
    act = 6 * B * T * H * wbytes
    heads = B * T * (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim * wbytes
    logits = B * cfg.vocab_size * 4 * 2  # f32 logits + softmax workspace
    scratch = (act + heads) // max(tp, 1) + logits

    reserve = int(hbm_bytes * reserve_fraction)
    return MemoryPlan(
        param_bytes=pbytes,
        kv_bytes=kv_bytes,
        scratch_bytes=scratch,
        reserve_bytes=reserve,
        hbm_bytes=hbm_bytes,
        num_pages=num_pages,
        bytes_per_page=bytes_per_page,
        detail=detail,
    )


def max_kv_pages(
    cfg: ModelConfig,
    *,
    tp: int = 1,
    ep: int = 1,
    quantize: Optional[str] = None,
    page_size: int = 16,
    max_batch_size: int = 8,
    prefill_bucket: int = 2048,
    hbm_bytes: int,
    reserve_fraction: float = DEFAULT_RESERVE_FRACTION,
) -> int:
    """Largest page budget that still fits: the KV-cache capacity question
    every deployment asks first ("how many concurrent 8k-token requests
    does a v5e-16 hold at 70B int8?")."""
    base = plan_memory(
        cfg, tp=tp, ep=ep, quantize=quantize, page_size=page_size,
        num_pages=0, max_batch_size=max_batch_size,
        prefill_bucket=prefill_bucket, hbm_bytes=hbm_bytes,
        reserve_fraction=reserve_fraction,
    )
    free = base.hbm_bytes - base.total_bytes
    if free <= 0:
        return 0
    return free // base.bytes_per_page


def llama3_70b_config(dtype: str = "bfloat16") -> ModelConfig:
    """Real Llama-3-70B geometry (HF config.json: 80 layers, 64 q heads,
    8 kv heads, ffn 28672, vocab 128256) -- the north-star model shape
    (BASELINE.md rows 1-4; reference multinode configs serve 70B/405B)."""
    return ModelConfig(
        vocab_size=128256,
        hidden_size=8192,
        intermediate_size=28672,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        max_position=8192,
        dtype=dtype,
    )
