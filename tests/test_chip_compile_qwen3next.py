"""The chip's compiler, asked here without a chip (``test_chip_compile.py``'s
manner, a described ``v5e:2x2``): the served steps of a trunk with gated
delta-rule layers at Qwen3-Next-80B-A3B's published widths, 12 layers, 128 of
512 experts held: the attention layers' pool ``[3, 2, P, 16, 2, 256]`` with
the lanes' state and the snapshot slots beside it; which kernels heads of 256
take; and that the step copies neither the slots nor the lanes' state."""

import re

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import attention as att
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine import step as S
from dynamo_tpu.engine.kv_cache import DeltaKV
from dynamo_tpu.engine.sampling import SamplingParams
from tests import test_chip_compile as base
from tests.test_chip_compile import chip, topo  # noqa: F401  (fixtures)

LANES, PAGE, TABLE = 16, 16, 2112
# pinned anew by PR 55, for this configuration alone.  A ceiling on the
# compiler's count of the steps' temporaries, MiB by fused steps (the chunk
# step of 2048 rows, the fused block of 4 decode steps), and the equations of
# the chunk step's jaxpr, nested ones counted
TEMP_MIB = {1: 304, 4: 194}
PACKED_STEP_EQUATIONS = 10456


def published():
    return base.published("qwen3-next-80b-a3b")


def _delta_pool(chip, cfg, eng):
    Ll, C, P = cfg.kind_layers("linear"), cfg.linear_conv_width, eng["num_pages"]
    S_ = eng["state_snapshot_slots"]
    mat = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
           cfg.linear_value_head_dim)
    return DeltaKV(
        chip((cfg.kind_layers("full"), 2, P, PAGE, cfg.num_kv_heads,
              cfg.head_dim), jnp.bfloat16),
        chip((Ll, LANES, *mat), jnp.float32),
        chip((Ll, 3 * LANES, C), jnp.bfloat16),
        chip((Ll, S_, *mat), jnp.float32),
        chip((Ll, 3 * S_, C), jnp.bfloat16),
        chip((3, LANES), jnp.int32),
    )


def _operands(shape, cfg, pool, Np, lanes=LANES, table=TABLE):
    """Operands of a packed step of ``Np`` rows over ``pool`` (``shape(dims,
    dtype)`` makes each: the ``chip`` fixture's, or ``jax.ShapeDtypeStruct``
    for a trace alone)."""
    shapes = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    params = jax.tree.map(lambda a: shape(a.shape, a.dtype), shapes)
    i32 = lambda *d: shape(d, jnp.int32)  # noqa: E731
    b1 = lambda *d: shape(d, jnp.bool_)  # noqa: E731
    f32 = lambda *d: shape(d, jnp.float32)  # noqa: E731
    B = lanes
    sampling = SamplingParams(
        f32(B), f32(B), i32(B), shape((B,), jnp.uint32), f32(B), f32(B), f32(B))
    return (
        params, cfg, pool, i32(B), i32(B), i32(B), b1(B), i32(B, 4),
        i32(B, table), i32(Np), i32(Np), i32(Np), b1(Np), i32(B), i32(B),
        b1(B), b1(B), b1(B), i32(B), i32(B), shape((2,), jnp.uint32), sampling,
    )


@pytest.mark.parametrize("Np,s_max,steps", [(2048, 1024, 1), (16, 1, 4)])
def test_qwen3next_steps_lower_at_published_widths(chip, monkeypatch, Np, s_max, steps):
    """The widest chunk step the configuration mints and one fused block of
    decode steps, at the scheduler's whole page table: the attention layers'
    launches carry their ``_wide`` names; weights, pool, lanes and slots are
    held once, no second form of a layer's pages is made in front of an
    attention launch, and no copy of the slots ``[9, 64, 32, 128, 128]`` or
    of the lanes' state ``[9, 16, 32, 128, 128]`` is made beside them."""
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    cfg, eng = published()
    assert cfg.layer_pattern == ("linear", "linear", "linear", "full")
    assert cfg.kv_geometry == (3, 2, 2, 256) and cfg.rope_dim == 64
    assert (cfg.num_experts, cfg.experts_held) == (512, 128)
    ops = _operands(chip, cfg, _delta_pool(chip, cfg, eng), Np)
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
    want = ["packed_ragged_attention_wide"]
    if steps > 1:
        want.append("paged_decode_attention_wide")
    assert sorted(set(launches)) == sorted(want), launches
    # the linear layers' chunks are one launch a layer, found by its name
    # (PR 55), and no loop carries the lanes' states
    assert re.search(r"%gated_delta_chunks[.\d]* = ", text)
    assert not re.search(r"f32\[16,32,128,128\]\S*, f32\[16,32,128,128\]\S*[^\n]* while\(", text)
    assert not re.search(r"f32\[9,64,32,128,128\]\S* copy\(", text)
    assert not re.search(r"f32\[9,16,32,128,128\]\S* copy\(", text)
    assert not re.search(r"bf16\[3,2,16384,16,2,256\]\S* copy\(", text)
    # a page as one matrix [32, 256] is no view of a pool of 2 heads of 256
    # (ragged_attention._pages_are_matrices): the one-row tile reads the
    # pool as it is, and the step makes no second form of it, neither of
    # the whole pool nor of a layer's pages, under whatever name (the
    # compiler called that copy a reshape once)
    assert not re.search(r"bf16\[3,2,16384,32,256\]", text)
    assert not re.search(r"bf16\[1,2,16384,", text)
    mem = compiled.memory_analysis()
    print("TEMP", Np, steps, mem.temp_size_in_bytes / 2**20, "MiB; args",
          mem.argument_size_in_bytes / 2**30, "GiB")
    # 10.8 GB of weights, 1.6 GB of pool, 0.3 GB of lanes and 1.2 GB of
    # slots are arguments; what the step makes beside them has to fit in
    # what is left of 15.75 GiB.  PR 54 took away the attention relayout's
    # temporary (1056 and 1208 MiB -> 525 and 182); with the chunks in a
    # launch (PR 55) the loop's carries, the five padded copies of the rows
    # and the three gathered predecessors are gone too (276.1 and 175.9 MiB
    # read here): TEMP_MIB is a tenth over that
    assert mem.temp_size_in_bytes < TEMP_MIB[steps] << 20


def test_qwen3next_packed_step_traces_to_its_pinned_jaxpr(monkeypatch):
    """The chunk step of 2048 rows as the chip traces it: its equations,
    nested ones counted (pinned for this configuration alone; the other
    families' pins are theirs), one launch ``gated_delta_chunks`` a linear
    layer of the scan's period and no XLA loop of chunks."""
    from tests.test_packed_work_list import _eqns

    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    cfg, eng = published()
    spec = jax.ShapeDtypeStruct
    params, _, *rest = _operands(spec, cfg, _delta_pool(spec, cfg, eng), 2048)
    jaxpr = jax.make_jaxpr(
        lambda *a: S._packed_unified_step(a[0], cfg, *a[1:], s_max=1024)
    )(params, *rest)
    eqns = list(_eqns(jaxpr.jaxpr))
    names = [
        e.params["name"] for e in eqns if e.primitive.name == "pallas_call"]
    assert names.count("gated_delta_chunks") == 3  # the scan body is a period
    assert len(eqns) == PACKED_STEP_EQUATIONS


def packed_step_equations(name, Np, s_max, lanes, table):
    """Equations of ``name``'s packed step's jaxpr at ``(Np, s_max)``, nested
    ones counted, traced as on the chip over its plain pair pool."""
    from tests.test_packed_work_list import _eqns

    cfg, eng = base.published(name)
    spec = jax.ShapeDtypeStruct
    pool = spec((cfg.num_layers, 2, eng["num_pages"], PAGE, cfg.num_kv_heads,
                 cfg.head_dim), jnp.bfloat16)
    params, _, *rest = _operands(spec, cfg, pool, Np, lanes, table)
    jaxpr = jax.make_jaxpr(
        lambda *a: S._packed_unified_step(a[0], cfg, *a[1:], s_max=s_max)
    )(params, *rest)
    return sum(1 for _ in _eqns(jaxpr.jaxpr))


def test_mixtral_packed_step_keeps_its_jaxpr(monkeypatch):
    """Heads of 128: a page as one matrix is a view of the pool, the launch
    is handed what it was handed and the one-row tile keeps its body: the
    chunk step of 1024 rows beside decode rows traces to the program it
    traced to on the parent (counted there with this function)."""
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    assert packed_step_equations("mixtral-8x7b", 1024, 512, 32, 512) == 1888


def test_wide_heads_take_the_work_list(monkeypatch):
    """Two KV heads of 256, eight query heads a KV head: the packed launch
    walks a work list, every minted shape fits, and the fused steps' decode
    launch is the same kernel."""
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    cfg, eng = published()
    pool = jax.ShapeDtypeStruct((3, 2, 64, PAGE, 2, 256), jnp.bfloat16)
    launch = att.packed_launch(
        pool, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, jnp.bfloat16)
    assert launch.walks_work_list
    for np_, s_max in eng["packed_shapes"]:
        assert launch.fits(np_, s_max)
    assert att.decode_backend(pool, 16, 256, jnp.bfloat16) == "work_list"
