"""The chip's compiler, asked here without a chip (``test_chip_compile.py``'s
manner, a described ``v5e:2x2``): the served steps of a trunk with
convolution layers at LFM2-8B-A1B's published widths, 13 layers, the
attention layers' pool with two 64-wide KV heads a 128-lane row ``[3, 2, P, 16,
4, 128]`` and the state beside it; what the step holds beside its arguments;
and which kernels such a pool takes."""

import re

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import attention as att
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine import step as S
from dynamo_tpu.engine.kv_cache import ConvKV
from dynamo_tpu.engine.sampling import SamplingParams
from tests import test_chip_compile as base
from tests.test_chip_compile import chip, topo  # noqa: F401  (fixtures)

LANES, PAGE = 32, 16


def published():
    return base.published("lfm2-8b-a1b")


def _operands(chip, cfg, eng, Np, table):
    shapes = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    params = jax.tree.map(lambda a: chip(a.shape, a.dtype), shapes)
    Lc, H, P = cfg.kind_layers("conv"), cfg.hidden_size, eng["num_pages"]
    pool = ConvKV(
        chip((cfg.kind_layers("full"), 2, P, PAGE, cfg.pool_kv_heads,
              cfg.pool_head_dim), jnp.bfloat16),
        chip((Lc, 2 * LANES, H), jnp.bfloat16),
        chip((Lc, 2 * P, H), jnp.bfloat16),
    )
    i32 = lambda *d: chip(d, jnp.int32)  # noqa: E731
    b1 = lambda *d: chip(d, jnp.bool_)  # noqa: E731
    f32 = lambda *d: chip(d, jnp.float32)  # noqa: E731
    B = LANES
    sampling = SamplingParams(
        f32(B), f32(B), i32(B), chip((B,), jnp.uint32), f32(B), f32(B), f32(B))
    return (
        params, cfg, pool, i32(B), i32(B), i32(B), b1(B), i32(B, 4),
        i32(B, table), i32(Np), i32(Np), i32(Np), b1(Np), i32(B), i32(B),
        b1(B), b1(B), b1(B), i32(B), i32(B), chip((2,), jnp.uint32), sampling,
    )


@pytest.mark.parametrize(
    "Np,s_max,steps,table", [(2048, 1024, 1, 448), (32, 1, 4, 448)])
def test_lfm2_steps_lower_at_published_widths(chip, monkeypatch, Np, s_max, steps, table):
    """The widest chunk step the configuration mints and one fused block of
    decode steps, at the scheduler's whole page table: the attention layers'
    launches carry their ``_narrow`` names; pool, snapshots and experts are
    held once, and what the step makes beside them is activations.  (With a
    pool of 64-wide rows the same step copied the pool twice, and with the
    snapshots' pair of rows an axis of its own it copied them three times:
    5.5 GB of temporaries beside 12.2 GB of arguments.)"""
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    cfg, eng = published()
    assert cfg.layer_pattern == ("full", "conv", "conv", "conv")
    assert (cfg.kv_head_pack, cfg.kv_geometry) == (2, (3, 2, 4, 128))
    ops = _operands(chip, cfg, eng, Np, table)
    if steps == 1:
        fn = jax.jit(
            lambda *a: S._packed_unified_step(*a, s_max=s_max),
            static_argnums=(1,), donate_argnums=(2,))
    else:
        fn = jax.jit(
            lambda *a: S._packed_unified_multistep(*a, s_max=s_max, num_steps=steps),
            static_argnums=(1,), donate_argnums=(2,))
    compiled = fn.lower(*ops).compile()
    text = compiled.as_text()
    launches = re.findall(r"%(\w*attention\w*?)[.\d]* = ", text)
    want = ["packed_ragged_attention_narrow"]
    if steps > 1:  # the scan's body is a period: one attention layer
        want.append("paged_decode_attention_narrow")
    assert sorted(set(launches)) == sorted(want), launches
    # weights 9.2 GB, pool 1.6 GB and snapshots 1.3 GB are arguments; what
    # the step makes beside them is far under any of the three
    assert compiled.memory_analysis().temp_size_in_bytes < 200 << 20
    assert not re.search(r"bf16\[3,2,16384,16,4,128\]\S* copy\(", text)


def test_lfm2_decode_block_keeps_its_jaxpr(monkeypatch):
    """32 lanes route 128 assignments over a router of 32: the rule that
    hands a step of few rows to the grouped product does not hold, and the
    fused block of four decode steps traces to the program it traced to on
    the parent of PR 51 (counted there with this function): the buffers,
    no count of experts read among its results (tokens, columns, three of
    state, the cache's three parts, the key)."""
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    cfg, eng = published()
    ops = _operands(jax.ShapeDtypeStruct, cfg, eng, 32, 448)
    assert base.decode_block_equations(cfg, (ops[0], *ops[2:])) == (4701, 9)


def test_a_packed_pool_takes_the_work_list_and_a_64_wide_one_the_grid(monkeypatch):
    """Two KV heads a 128-lane row: the pool is an ordinary pair pool of
    four heads of 128, its packed launch walks a work list (any shape fits,
    the page table's width is no axis of an executable) and the fused steps'
    decode launch is the same kernel.  The same heads in rows of 64 would
    take the page-group grid, whose packed operands of the 1024-row chunk
    this cell runs pass what a kernel may hold."""
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    cfg, _eng = published()
    pool = jax.ShapeDtypeStruct((3, 2, 64, PAGE, 4, 128), jnp.bfloat16)
    launch = att.packed_launch(
        pool, cfg.num_heads, cfg.pool_kv_heads, cfg.pool_head_dim, jnp.bfloat16)
    assert launch.walks_work_list and launch.fits(2048, 1024)
    assert att.decode_backend(pool, 32, cfg.pool_head_dim, jnp.bfloat16) == "work_list"
    narrow = jax.ShapeDtypeStruct((3, 2, 64, PAGE, 8, 64), jnp.bfloat16)
    grid = att.packed_launch(narrow, 32, 8, 64, jnp.bfloat16)
    assert not grid.walks_work_list and grid.fits(1024, 512)
    assert not grid.fits(2048, 1024)
