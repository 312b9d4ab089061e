"""Memory planning: byte-exact params/KV accounting vs real allocations,
and the 70B fit table the north star depends on (VERDICT r4 #3).

Reference capability: deployment sizing via profile_sla sweeps and the
multinode configs (examples/llm/configs/multinode-405b.yaml); here fit is
computed analytically and must agree with what the engine allocates.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import memory_plan
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.memory_plan import hbm_bytes_for, llama3_70b_config

# every plan names its device: these are plans for one v5e chip
V5E = hbm_bytes_for("TPU v5 lite")
plan_memory = functools.partial(memory_plan.plan_memory, hbm_bytes=V5E)
max_kv_pages = functools.partial(memory_plan.max_kv_pages, hbm_bytes=V5E)
from dynamo_tpu.engine.model import init_params
from dynamo_tpu.engine.quant import quantize_params
from dynamo_tpu.engine.weights import param_bytes


def test_param_bytes_match_real_allocation_unsharded():
    cfg = ModelConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    plan = plan_memory(cfg, num_pages=0)
    assert plan.param_bytes == param_bytes(params)


def test_param_bytes_match_real_allocation_int8():
    cfg = ModelConfig.tiny()
    params = quantize_params(init_params(cfg, jax.random.PRNGKey(0)), cfg)
    plan = plan_memory(cfg, quantize="int8", num_pages=0)
    assert plan.param_bytes == param_bytes(params)


def test_param_bytes_match_moe_config():
    cfg = ModelConfig.tiny(num_experts=4, num_experts_per_tok=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert plan_memory(cfg, num_pages=0).param_bytes == param_bytes(params)


def test_kv_bytes_match_real_pages():
    cfg = ModelConfig.tiny()
    PAGES, PAGE = 32, 16
    kv = jnp.zeros(
        (cfg.num_layers, 2, PAGES, PAGE, cfg.num_kv_heads, cfg.head_dim),
        jnp.dtype(cfg.dtype),
    )
    plan = plan_memory(cfg, page_size=PAGE, num_pages=PAGES)
    assert plan.kv_bytes == kv.size * kv.dtype.itemsize


def test_tp_divides_only_divisible_axes():
    # kv heads (2) do not divide tp=4 -> KV replicates; q-heads (4) do
    cfg = ModelConfig.tiny()
    p1 = plan_memory(cfg, tp=1, num_pages=64)
    p4 = plan_memory(cfg, tp=4, num_pages=64)
    assert p4.kv_bytes == p1.kv_bytes  # replicated (2 % 4 != 0)
    assert p4.detail["layers/wq"] == p1.detail["layers/wq"] // 4
    p2 = plan_memory(cfg, tp=2, num_pages=64)
    assert p2.kv_bytes == p1.kv_bytes // 2  # kv heads shard 2-way


def test_70b_fit_table():
    """The north-star deployment shape: 70B int8 fits a v5e-16GB at tp=8
    with >= 128k tokens of KV per chip; bf16 at tp=8 does NOT fit."""
    cfg = llama3_70b_config()
    fit = plan_memory(cfg, tp=8, quantize="int8", num_pages=2048)
    assert fit.fits, fit.total_bytes
    # ~70.6B params int8 / 8 chips ~ 8.3 GiB
    assert 8.0 * 1024**3 < fit.param_bytes < 9.0 * 1024**3
    cap = max_kv_pages(cfg, tp=8, quantize="int8", page_size=16)
    assert cap * 16 >= 128_000  # tokens of KV per chip
    unfit = plan_memory(cfg, tp=8, quantize=None, num_pages=2048)
    assert not unfit.fits
    with pytest.raises(ValueError):
        unfit.assert_fits()


def test_max_kv_pages_inverts_plan():
    cfg = ModelConfig.tiny()
    hbm = 64 * 1024**2  # 64 MiB toy budget
    cap = max_kv_pages(cfg, hbm_bytes=hbm, max_batch_size=2,
                       prefill_bucket=128)
    assert cap > 0
    at_cap = plan_memory(cfg, num_pages=cap, hbm_bytes=hbm,
                         max_batch_size=2, prefill_bucket=128)
    over = plan_memory(cfg, num_pages=cap + 1, hbm_bytes=hbm,
                       max_batch_size=2, prefill_bucket=128)
    assert at_cap.fits and not over.fits


def test_hbm_is_keyed_by_device_kind():
    assert V5E == 16 * 1024**3
    with pytest.raises(ValueError, match="no HBM size recorded"):
        hbm_bytes_for("TPU v9 imaginary")
    with pytest.raises(TypeError):  # no silent default device
        memory_plan.plan_memory(ModelConfig.tiny(), num_pages=0)


def test_int8_scale_replication_on_contracted_axis():
    """wo / w_down shard on the contracted axis, whose size-1 scale dim
    cannot shard -> scales replicate while bodies divide (mirrors
    _compatible_spec resolution of the quantized tree)."""
    cfg = ModelConfig.tiny()  # heads divide tp=2
    p1 = plan_memory(cfg, tp=1, quantize="int8", num_pages=0)
    p2 = plan_memory(cfg, tp=2, quantize="int8", num_pages=0)
    L, H, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    wb = 4  # tiny() dtype float32
    # w_down body (L*I*H int8) halves; its scales (L*1*H f32) replicate
    assert p2.detail["layers/w_down"] == L * I * H // 2 + L * H * wb
    # w_gate shards on the output axis: body AND scales halve
    assert p2.detail["layers/w_gate"] == (L * H * I // 2) + (L * I * wb) // 2
    assert p2.param_bytes < p1.param_bytes
