"""The chip's compiler, asked here without a chip (``test_chip_compile.py``'s
manner, a described ``v5e:2x2``): the served steps of a trunk of window and
full layers at Mellum2-12B-A2.5B's published widths, 12 layers, two pools;
and that a trunk of one kind traces to the program it traced to before
``layer_types`` existed."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import attention as att
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine import step as S
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.kv_cache import KindKV
from dynamo_tpu.engine.sampling import SamplingParams
from tests import test_chip_compile as base
from tests.test_chip_compile import (  # noqa: F401  (fixtures)
    assert_one_decode_launch, chip, decode_layer_text, topo,
)

LANES, PAGE, TABLE = 32, 16, 2064


def published():
    return base.published("mellum2-12b-a2.5b")


def _operands(chip, cfg, eng, Np, table):
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: chip(a.shape, a.dtype), shapes)
    pool = KindKV(
        chip((cfg.kind_layers("full"), 2, eng["num_pages"], PAGE,
              cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16),
        chip((cfg.kind_layers("sliding"), 2, eng["num_window_pages"], PAGE,
              cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16),
    )
    i32 = lambda *d: chip(d, jnp.int32)  # noqa: E731
    b1 = lambda *d: chip(d, jnp.bool_)  # noqa: E731
    f32 = lambda *d: chip(d, jnp.float32)  # noqa: E731
    B = LANES
    sampling = SamplingParams(
        f32(B), f32(B), i32(B), chip((B,), jnp.uint32), f32(B), f32(B), f32(B))
    return (
        params, cfg, pool, i32(B), i32(B), i32(B), b1(B), i32(B, 4),
        i32(2, B, table), i32(Np), i32(Np), i32(Np), b1(Np), i32(B), i32(B),
        b1(B), b1(B), b1(B), i32(B), i32(B), chip((2,), jnp.uint32), sampling,
    )


@pytest.mark.parametrize(
    "Np,s_max,steps", [(2048, 1024, 1), (1024, 512, 1), (32, 1, 4)])
def test_mellum_steps_lower_at_published_widths(chip, monkeypatch, Np, s_max, steps):
    """The packed executables of a chunk step (minted as ``(2048, 1024)``;
    since PR 40 a 992-row chunk beside 31 decode rows fills ``(1024,
    512)``, the shape its rows need) and
    one fused decode step of the 12-layer configuration: the window layers'
    launches carry their suffix, the pools are held once (no copy of either,
    nor of a layer's experts), and the temporaries are activations."""
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    cfg, eng = published()
    assert cfg.layer_pattern == ("sliding", "sliding", "sliding", "full")
    ops = _operands(chip, cfg, eng, Np, TABLE)
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
    assert "packed_ragged_attention_window" in text
    assert "packed_ragged_attention." in text or "packed_ragged_attention " in text
    launches = re.findall(r"%(\w*attention\w*?)[.\d]* = ", text)
    if steps > 1:
        # the scan's body is a period, the fused steps' launch of each of
        # its four layers the work list under the decode kernel's name
        assert sorted(launches) == sorted(
            ["packed_ragged_attention_window"] * 3 + ["packed_ragged_attention"]
            + ["paged_decode_attention_window"] * 3 + ["paged_decode_attention"])
    # weights 10.9 GB and pools 2.8 GB are arguments; what the step makes
    # beside them stays far under a layer's experts (0.79 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 700 << 20


@pytest.mark.parametrize("table", [512, TABLE])
@pytest.mark.parametrize("window,suffix", [(0, ""), (1024, "_window")],
                         ids=["full", "window"])
def test_mellum_decode_launch_compiles(chip, monkeypatch, window, suffix, table):
    """The fused steps' decode launch at Mellum2's heads (32 over 4) and 32
    lanes, a full layer's and a window layer's, at a table of 512 pages and
    at the scheduler's whole 2064: one ``paged_decode_attention[_window]``
    a layer, no packed launch beside it, the pool not copied."""
    text = decode_layer_text(
        chip, monkeypatch, lanes=LANES, Hq=32, Hkv=4, window=window,
        table=table, suffix=suffix, pages=4096)
    assert_one_decode_launch(text, "4,2,4096,16,4,128", suffix)


def test_fused_step_does_not_grow_with_the_page_table(monkeypatch):
    """The table's width is no axis of the fused step's program: its jaxpr
    has as many equations at 512 pages as at 2064 (the grid kernel it
    replaces took a block operand a page of its group and a grid step a
    group of the width)."""
    from tests.test_packed_work_list import _eqns

    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    cfg, eng = published()
    spec = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype)  # noqa: E731

    def equations(table):
        ops = _operands(spec, cfg, eng, 32, table)
        jaxpr = jax.make_jaxpr(
            lambda *a: S._packed_unified_multistep(
                a[0], cfg, *a[1:], s_max=1, num_steps=4)
        )(ops[0], *ops[2:])
        return sum(1 for _ in _eqns(jaxpr.jaxpr))

    # ... and as many as on the parent of PR 51 (counted there with this
    # function): 32 lanes route 256 assignments over a router of 64, so the
    # rule that hands a step of few rows to the grouped product does not
    # hold and the block keeps the buffers
    assert equations(512) == equations(TABLE) == 5063


def test_one_kind_packed_step_keeps_its_jaxpr():
    """A model with one kind of layer lowers to the program it lowered to on
    the parent: the packed step of a tiny Mistral-like trunk (a window, 2
    layers) has the equation count it had there, and names no kernel of a
    second kind."""
    from tests.test_packed_work_list import _eqns

    cfg = ModelConfig.tiny(sliding_window=32)
    B, Np, P = 4, 32, 8
    spec = jax.ShapeDtypeStruct
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    pool = spec((cfg.num_layers, 2, 16, 16, cfg.num_kv_heads, cfg.head_dim), jnp.float32)
    i32 = lambda *d: spec(d, jnp.int32)  # noqa: E731
    b1 = lambda *d: spec(d, jnp.bool_)  # noqa: E731
    f32 = lambda *d: spec(d, jnp.float32)  # noqa: E731
    sampling = SamplingParams(
        f32(B), f32(B), i32(B), spec((B,), jnp.uint32), f32(B), f32(B), f32(B))
    jaxpr = jax.make_jaxpr(
        lambda *a: S._packed_unified_step(a[0], cfg, *a[1:], s_max=16)
    )(shapes, pool, i32(B), i32(B), i32(B), b1(B), i32(B, 4), i32(B, P),
      i32(Np), i32(Np), i32(Np), b1(Np), i32(B), i32(B), b1(B), b1(B), b1(B),
      i32(B), i32(B), spec((2,), jnp.uint32), sampling)
    n = sum(1 for _ in _eqns(jaxpr.jaxpr))
    assert n == PARENT_EQUATIONS, n
    assert "attention_window" not in str(jaxpr)


# counted on the parent commit (d94d730) with the function above
PARENT_EQUATIONS = 531
