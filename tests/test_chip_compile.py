"""The chip's compiler, asked here without a chip: the Pallas kernels of the
served path at the widths they serve (TinyLlama-1.1B and Mixtral-8x7B
heads), compiled for a described ``v5e:2x2`` topology.

Interpret mode cannot see what this sees: a kernel that passed every
interpret-mode test was refused for VMEM from ``s_max`` 256 up, and the int8
pool's scale block for its tiling.  A compile that passes is not a chip run
(``chip_smoke.py`` is); it is what keeps a later PR from minting a shape the
compiler refuses.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU's library, every xdist worker imports
this file, and only the worker that runs it may make the call.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

TINYLLAMA = dict(Hq=32, Hkv=4, D=64)
MIXTRAL = dict(Hq=32, Hkv=8, D=128)
PAGE, PAGES, LAYERS, LANES = 16, 768, 22, 8
# the smallest and the largest (Np, s_max) the default EngineConfig can mint
# (mixed_token_budget 512, 8 lanes), and the shapes the compiler used to
# refuse between them
PACKED_SHAPES = [(1, 1), (512, 128), (256, 256), (512, 512), (1024, 512)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # conftest.py asks for "highest" matmul precision so that CPU parity
    # tests accumulate in f32; a served process runs the backend default,
    # and Mosaic refuses an f32-precision matmul over bf16 operands
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield desc
    jax.config.update("jax_default_matmul_precision", precision)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    return shape


def _pool(chip, w, quant):
    dims = (LAYERS, 2, PAGES, PAGE, w["Hkv"], w["D"])
    if quant:
        return chip(dims, jnp.int8), chip(dims[:4], jnp.float32)
    return chip(dims, jnp.bfloat16), None


def _lanes(chip, P=128):
    vec = chip((LANES,), jnp.int32)
    return chip((LANES, P), jnp.int32), vec


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("widths", [TINYLLAMA, MIXTRAL], ids=["tinyllama", "mixtral"])
@pytest.mark.parametrize("Np,s_max", PACKED_SHAPES)
def test_packed_ragged_attention_compiles(chip, Np, s_max, widths, quant):
    from dynamo_tpu.ops.ragged_attention import (
        packed_ragged_attention,
        packed_shape_fits,
    )

    w = widths
    assert packed_shape_fits(
        Np, s_max, w["Hq"], w["Hkv"], w["D"], PAGE, jnp.bfloat16,
        jnp.int8 if quant else jnp.bfloat16, quant,
    )
    pool, scales = _pool(chip, w, quant)
    table, vec = _lanes(chip)
    q = chip((Np, w["Hq"], w["D"]), jnp.bfloat16)
    kv = chip((Np, w["Hkv"], w["D"]), jnp.bfloat16)

    def call(q, k, v, pool, table, base, off, lens, scales):
        return packed_ragged_attention(
            q, k, v, pool, table, base, off, lens, s_max=s_max, layer=3,
            kv_scales=scales,
        )

    compiled = jax.jit(call).lower(
        q, kv, kv, pool, table, vec, vec, vec, scales
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_packed_bound_refuses_what_the_kernel_cannot_hold():
    """The engine checks this bound before it lets a dispatch mint a shape:
    a mixed budget of 2048 tokens would ask for (4096, 2048)."""
    from dynamo_tpu.ops.ragged_attention import packed_shape_fits

    w = TINYLLAMA
    assert not packed_shape_fits(
        4096, 2048, w["Hq"], w["Hkv"], w["D"], PAGE, jnp.bfloat16,
        jnp.bfloat16, False,
    )


@pytest.mark.parametrize(
    "S,quant", [(1, False), (16, False), (16, True), (128, False)],
    ids=["S1", "S16", "S16-int8", "S128"],
)
def test_ragged_paged_attention_compiles(chip, S, quant):
    from dynamo_tpu.ops.ragged_attention import ragged_paged_attention

    w = TINYLLAMA
    pool, scales = _pool(chip, w, quant)
    table, vec = _lanes(chip)
    q = chip((LANES, S, w["Hq"], w["D"]), jnp.bfloat16)
    kv = chip((LANES, S, w["Hkv"], w["D"]), jnp.bfloat16)

    def call(q, k, v, pool, table, base, lens, scales):
        return ragged_paged_attention(
            q, k, v, pool, table, base, lens, layer=3, kv_scales=scales
        )

    compiled = jax.jit(call).lower(
        q, kv, kv, pool, table, vec, vec, scales
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("widths", [TINYLLAMA, MIXTRAL], ids=["tinyllama", "mixtral"])
def test_paged_decode_attention_v2_compiles(chip, widths):
    from dynamo_tpu.ops.paged_attention import paged_decode_attention_v2

    w = widths
    pool, _ = _pool(chip, w, False)
    table, vec = _lanes(chip, P=16)
    q = chip((LANES, w["Hq"], w["D"]), jnp.bfloat16)

    def call(q, pool, table, lens):
        return paged_decode_attention_v2(q, pool, table, lens, 3, 0, group=8)

    compiled = jax.jit(call).lower(q, pool, table, vec).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("widths", [TINYLLAMA, MIXTRAL], ids=["tinyllama", "mixtral"])
def test_flash_prefill_attention_compiles(chip, widths):
    from dynamo_tpu.ops.flash_prefill import (
        flash_prefill_attention,
        flash_prefix_prefill_attention,
    )

    w, T, Kp = widths, 2048, 512
    q = chip((1, T, w["Hq"], w["D"]), jnp.bfloat16)
    kv = chip((1, T, w["Hkv"], w["D"]), jnp.bfloat16)
    cat = chip((1, Kp + T, w["Hkv"], w["D"]), jnp.bfloat16)
    lens = chip((1,), jnp.int32)
    full = jax.jit(flash_prefill_attention).lower(q, kv, kv, lens).compile()
    assert "tpu_custom_call" in full.as_text()
    suffix = jax.jit(flash_prefix_prefill_attention).lower(
        q, cat, cat, lens, lens
    ).compile()
    assert "tpu_custom_call" in suffix.as_text()


@pytest.mark.parametrize(
    "dispatch,tp,dp",
    [("packed", 4, 1), ("decode", 4, 1), ("flash_prefill", 4, 1),
     ("packed", 1, 2), ("decode", 2, 2)],
    ids=["packed-tp4", "decode-tp4", "flash_prefill-tp4", "packed-dp2",
         "decode-dp2-tp2"],
)
def test_mesh_kernels_compile_per_shard(topo, dispatch, tp, dp, monkeypatch):
    """On a mesh GSPMD cannot partition a Mosaic kernel ("Mosaic kernels
    cannot be automatically partitioned"), whether the mesh shards heads
    (``--tp``) or only lanes (``--dp``): each dispatch compiles only because
    it runs its kernel through ``shard_map`` over the context mesh, and the
    pool stays sharded (no all-gather of a pool-shaped operand)."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.engine import attention as att
    from dynamo_tpu.parallel.mesh import serving_mesh

    w = TINYLLAMA
    mesh = serving_mesh(tp=tp, dp=dp, devices=topo.devices)
    rep = NamedSharding(mesh, P())
    pool_sh = NamedSharding(mesh, P(None, None, None, None, "tp", None))
    dims = (LAYERS, 2, PAGES, PAGE, w["Hkv"], w["D"])

    def arr(shape, dtype, sh=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    def heads(*lead, lanes=None):
        spec = NamedSharding(
            mesh, P(lanes, *([None] * (len(lead) - 1)), "tp", None))
        return (arr((*lead, w["Hq"], w["D"]), jnp.bfloat16, spec),
                arr((*lead, w["Hkv"], w["D"]), jnp.bfloat16, spec))

    pool = arr(dims, jnp.bfloat16, pool_sh)
    # the engine keeps per-lane state over dp (sharding.make_sharded_steps)
    table = arr((LANES, 32), jnp.int32, NamedSharding(mesh, P("dp", None)))
    vec = arr((LANES,), jnp.int32, NamedSharding(mesh, P("dp")))
    layer = jnp.int32(3)
    if dispatch == "packed":
        Np, s_max = 512, 512
        q, kv = heads(Np)
        tok = arr((Np,), jnp.int32)

        def call(q, k, v, pool, table, base, off, lens, lane, rel):
            return att.packed_ragged_attention_dispatch(
                q, k, v, pool, layer, table, base, off, lens, lane, rel, s_max
            )

        operands = (q, kv, kv, pool, table, vec, vec, vec, tok, tok)
    elif dispatch == "decode":
        q, _ = heads(LANES, lanes="dp")

        def call(q, pool, table, lens):
            return att.decode_attention_dispatch(q, pool, table, lens, layer)

        operands = (q, pool, table, vec)
    else:
        q, kv = heads(1, 2048)

        def call(q, k, v, lens):
            return att.prefill_attention_dispatch(q, k, v, lens)

        operands = (q, kv, kv, arr((1,), jnp.int32))
    # the gates ask this host's backend, which is the CPU: say "TPU" here
    for knob in ("DYN_PALLAS_RAGGED", "DYN_PALLAS_DECODE", "DYN_PALLAS_PREFILL"):
        monkeypatch.setenv(knob, "1")
    with jax.set_mesh(mesh):
        text = jax.jit(call).lower(*operands).compile().as_text()
    assert "tpu_custom_call" in text
    gathered = re.findall(r"= \w+\[22,2,768[^\]]*\][^=]*all-gather", text)
    assert not gathered, gathered
