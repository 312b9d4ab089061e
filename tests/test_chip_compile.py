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
import re

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


@pytest.mark.parametrize(
    "widths,quant,window",
    [
        (TINYLLAMA, False, 0), (TINYLLAMA, True, 0),
        (MIXTRAL, False, 0), (MIXTRAL, True, 0),
        # the grid kernel as mistral-7b (Mixtral's head widths, a 4096
        # window) would launch it over an int8 pool
        (MIXTRAL, True, 4096),
    ],
    ids=["tinyllama-bf16", "tinyllama-int8", "mixtral-bf16", "mixtral-int8",
         "mistral-7b-int8"],
)
@pytest.mark.parametrize("Np,s_max", PACKED_SHAPES)
def test_packed_ragged_attention_compiles(chip, Np, s_max, widths, quant,
                                          window):
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
            window=window, kv_scales=scales,
        )

    compiled = jax.jit(call).lower(
        q, kv, kv, pool, table, vec, vec, vec, scales
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the four packed executables the Mistral family's configurations fix
# (benchmark/configs/mixtral-8x7b.json, mistral-7b.json), at their lanes
BENCH_PACKED_SHAPES = [(16, 1), (128, 64), (512, 256), (1024, 512)]


@pytest.mark.parametrize("window", [0, 4096], ids=["mixtral", "mistral-7b"])
@pytest.mark.parametrize("Np,s_max", BENCH_PACKED_SHAPES)
def test_packed_work_list_compiles(chip, Np, s_max, window):
    """The work-list kernel of a dense pool at the widths Mixtral-8x7B and
    Mistral-7B share (32 heads over 8, 128 wide), without a window and
    under Mistral-7B's: queries, pages and rows by DMA, a tile resident."""
    from dynamo_tpu.ops.ragged_attention import (
        _takes_work_list, packed_ragged_attention,
    )

    w = MIXTRAL
    assert _takes_work_list(w["D"], False)
    pool, _ = _pool(chip, w, False)
    table = chip((16, 512), jnp.int32)
    vec = chip((16,), jnp.int32)
    q = chip((Np, w["Hq"], w["D"]), jnp.bfloat16)
    kv = chip((Np, w["Hkv"], w["D"]), jnp.bfloat16)

    def call(q, k, v, pool, table, base, off, lens):
        return packed_ragged_attention(
            q, k, v, pool, table, base, off, lens, s_max=s_max, layer=3,
            window=window,
        )

    text = jax.jit(call).lower(
        q, kv, kv, pool, table, vec, vec, vec
    ).compile().as_text()
    # one kernel, under the name and with the result the roofline's reader
    # finds its launches by
    assert len(re.findall(r"%packed_ragged_attention[.\d]* = ", text)) == 1
    assert f"bf16[{Np},{w['Hq']},{w['D']}]" in text


@pytest.mark.parametrize("window", [0, 4096], ids=["mixtral", "mistral-7b"])
@pytest.mark.parametrize(
    "Np,s_max", BENCH_PACKED_SHAPES + [(2048, 1024)],
    ids=lambda v: str(v))
def test_work_list_kernel_jaxpr_stays_within_its_budget(Np, s_max, window):
    """The set-up budget, where a CPU can guard it: every packed executable
    traces and lowers this kernel once a kind of layer, and PR 31 was
    refused for what its unrolled page copies cost there.  PR 40's kernel
    read 264 equations (295 under a window) and 4 DMA starts at ``(lanes,
    1)``, up to 520 and 5 starts a tile at the shapes with two tiles.  Since
    PR 46 an item also starts what the item after it begins with (its
    queries and its first key block's pages, at any tile's size), waits for
    the output copy of the item before it at any tile's size, and the first
    of a run of live items starts its own; and a packed launch has a third
    tile, the one-row tile with its own short body (a query head a row, no
    head loop, nothing turned heads-major), for the decode rows beside a
    chunk.  ``(lanes, 1)``, that tile alone: 285 equations (288 under a
    window) and 6 starts.  Three tiles: 843 (826) and 32 starts: 6 at the
    head of a run, 8 a tile, one more where a tile reads its span of the
    output back.  The same whatever the packed shape and, the positions'
    divisions being ``lax.div`` now, under a window too."""
    from dynamo_tpu.ops import ragged_attention as ra
    from tests.test_packed_work_list import _count, _eqns

    w = MIXTRAL
    spec = jax.ShapeDtypeStruct
    q = spec((Np, w["Hq"], w["D"]), jnp.bfloat16)
    pool = spec((16, 2, PAGES, PAGE, w["Hkv"], w["D"]), jnp.bfloat16)
    table, vec = spec((16, 528), jnp.int32), spec((16,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: ra._packed_work_list_attention(
            *a, s_max=s_max, layer=3, window=window, interpret=False)
    )(q, pool, table, vec, vec, vec).jaxpr
    tiles = ra._work_list_tiles(s_max, jnp.bfloat16)[1]
    eqns = sum(1 for _ in _eqns(jaxpr))
    starts = _count(jaxpr, "dma_start")
    if s_max == 1:  # the decode launch's tile: one row, nothing read back
        assert starts == 6 and eqns <= 295, (eqns, starts)
    assert starts == {1: 6, 3: 32}[len(tiles)], starts
    assert eqns <= 850, eqns


def test_pair_pool_layers_scatter_and_attend_without_copying_the_pool(chip, monkeypatch):
    """The trunk over a dense pair pool, as the packed step runs it: every
    layer scatters its rows into the pool and the work-list kernel reads
    them from there.  The compiled trunk holds the pool once (1.6 GB here:
    its temporaries are activations) and traces one packed attention
    kernel, not the grid kernel beside it."""
    from dynamo_tpu.engine import attention as att
    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.config import ModelConfig

    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    cfg = ModelConfig(
        vocab_size=256, hidden_size=4096, intermediate_size=14336,
        num_layers=4, num_heads=32, num_kv_heads=8, head_dim=128,
        dtype="bfloat16", sliding_window=4096,
    )
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    stack = jax.tree.map(lambda a: chip(a.shape, a.dtype), shapes["layers"])
    lanes, pages, Np, s_max = 16, 6144, 1024, 512
    pool = chip((4, 2, pages, PAGE, 8, 128), jnp.bfloat16)
    vec = chip((lanes,), jnp.int32)
    rows = chip((Np,), jnp.int32)

    def trunk(stack, pool, x, cos, sin, table, base, off, lens, lane, rel):
        valid = lane < lanes
        pos = base[jnp.clip(lane, 0, lanes - 1)] + rel

        def attend(q, k, v, kv, layer):
            kv = att.write_packed_kv(kv, k[0], v[0], table, lane, pos, valid, layer)
            out = att.packed_ragged_attention_dispatch(
                q[0], k[0], v[0], kv, layer, table, base, off, lens, lane,
                rel, s_max, cfg.sliding_window)
            return out[None], kv

        return M.scan_layers(stack, pool, x, cos, sin, cfg, attend, valid[None])

    compiled = jax.jit(trunk, donate_argnums=(1,)).lower(
        stack, pool, chip((1, Np, 4096), jnp.bfloat16),
        chip((1, Np, 128), jnp.float32), chip((1, Np, 128), jnp.float32),
        chip((lanes, 512), jnp.int32), vec, vec, vec, rows, rows,
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%packed_ragged_attention[.\d]* = ", text)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 29
    # neither as it is nor as the one-row tile reads it, a page one matrix
    made = [l for l in text.splitlines()
            if re.search(r"= bf16\[4,2,6144,(16,8|128),128\]\S* (copy|transpose)\(", l)]
    assert not made, made[:3]


def test_packed_bound_refuses_what_the_kernel_cannot_hold():
    """The engine checks this bound before it lets a dispatch mint a shape:
    a mixed budget of 2048 tokens would ask for (4096, 2048)."""
    from dynamo_tpu.ops.ragged_attention import packed_shape_fits

    w = TINYLLAMA
    assert not packed_shape_fits(
        4096, 2048, w["Hq"], w["Hkv"], w["D"], PAGE, jnp.bfloat16,
        jnp.bfloat16, False,
    )


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


def decode_layer_text(chip, monkeypatch, *, lanes, Hq, Hkv, window, table,
                      suffix="", pages=6144, pool_dtype=jnp.bfloat16):
    """A fused decode step's layer as ``step._decode_once`` runs it over a
    dense pool of 128-wide heads (the new token's row scattered, then the
    dispatch), compiled for the described chip with the pool donated: its
    HLO text."""
    from dynamo_tpu.engine import attention as att

    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    pool = chip((4, 2, pages, PAGE, Hkv, 128), pool_dtype)
    vec = chip((lanes,), jnp.int32)

    def layer(pool, q, k, v, pt, pos):
        pool = att.write_decode_kv(pool, k, v, pt, pos, 3)
        out = att.decode_attention_dispatch(
            q, pool, pt, pos + 1, 3, window, suffix)
        return out, pool

    assert att.decode_backend(pool, Hq, 128, jnp.bfloat16) == "work_list"
    return jax.jit(layer, donate_argnums=(0,)).lower(
        pool, chip((lanes, Hq, 128), jnp.bfloat16),
        chip((lanes, Hkv, 128), jnp.bfloat16),
        chip((lanes, Hkv, 128), jnp.bfloat16),
        chip((lanes, table), jnp.int32), vec,
    ).compile().as_text()


def assert_one_decode_launch(text, pool_dims, suffix=""):
    """One decode launch under the name the ledger's ``breakdown`` and the
    count of forward passes know it by, no packed launch beside it (the
    packed rooflines' readers find theirs by that name), and the pool
    neither copied nor transposed around it."""
    assert len(re.findall(
        rf"%paged_decode_attention{suffix}[.\d]* = ", text)) == 1
    assert "%packed_ragged_attention" not in text
    assert len(re.findall(r"%\w*attention\w*[.\d]* = ", text)) == 1
    # nor made anew as the kernel reads it (PR 46): a page as one matrix
    # ``[page * Hkv, 128]``, which has to be the same bytes
    L, two, pages, page, Hkv, D = pool_dims.split(",")
    flat_dims = f"{L},{two},{pages},{int(page) * int(Hkv)},{D}"
    made = [l for l in text.splitlines()
            if re.search(rf"= \w+\[({pool_dims}|{flat_dims})\]\S* (copy|transpose)\(", l)]
    assert not made, made[:3]
    assert re.search(rf"\[{flat_dims}\]\S* bitcast\(", text)


@pytest.mark.parametrize("table", [512, 2064])
@pytest.mark.parametrize(
    "lanes,window,pool_dtype",
    [(32, 0, jnp.bfloat16), (16, 4096, jnp.bfloat16), (16, 4096, jnp.float32)],
    ids=["mixtral", "mistral-7b", "mistral-7b-f32-pool"],
)
def test_decode_work_list_compiles(chip, monkeypatch, lanes, window,
                                   pool_dtype, table):
    """The fused steps' decode launch over a dense pool at the widths
    Mixtral-8x7B and Mistral-7B share, at their cells' lanes, at a table of
    512 pages and at the scheduler's whole 2064: the work-list kernel at the
    ``(lanes, 1)`` tile with one item a lane.  A float32 pool under a bf16
    model takes it too (the kernel converts a key block in VMEM, as the
    packed launch over that pool does)."""
    text = decode_layer_text(
        chip, monkeypatch, lanes=lanes, Hq=32, Hkv=8, window=window,
        table=table, pool_dtype=pool_dtype)
    assert_one_decode_launch(text, "4,2,6144,16,8,128")
    assert f"bf16[{lanes},32,128]" in text


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


MIXTRAL_MLP = dict(E=8, H=4096, I=14336)


@pytest.mark.parametrize(
    "rows,way",
    [(2048, "up"), (2048, "down"), (1024, "up"), (1024, "down"), (128, "up")],
)
def test_moe_grouped_matmul_compiles(chip, rows, way):
    """The grouped expert product at Mixtral widths, both ways round, at
    the routed rows of the (1024, 512) and (512, 256) packed steps: its
    weight buffers (two blocks spanning all of K) ask for more VMEM than
    the default limit, and the copies it starts itself are sliced on the
    column axis, neither of which interpret mode checks."""
    from dynamo_tpu.ops.grouped_matmul import KERNEL_NAME, _grouped_matmul_pallas

    w = MIXTRAL_MLP
    K, N = (w["H"], w["I"]) if way == "up" else (w["I"], w["H"])
    # the layers' stack and a layer index, as scan_layers hands them over
    compiled = jax.jit(_grouped_matmul_pallas).lower(
        chip((rows, K), jnp.bfloat16), chip((4, w["E"], K, N), jnp.bfloat16),
        chip((w["E"],), jnp.int32), chip((), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and KERNEL_NAME in text


def test_moe_mlp_takes_the_kernel_on_the_chip(chip, monkeypatch):
    """A no-drop expert MLP of a mixed step's 1024 rows, compiled as the
    chip would: three launches of the grouped kernel and no [E, C, .]
    product; a decode step's 32 rows keep the buffers."""
    import re

    from dynamo_tpu.engine import attention as att
    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.ops.grouped_matmul import KERNEL_NAME

    w = MIXTRAL_MLP
    cfg = ModelConfig(
        vocab_size=256, hidden_size=w["H"], intermediate_size=w["I"],
        num_layers=1, num_heads=32, num_kv_heads=8, head_dim=128,
        dtype="bfloat16", num_experts=w["E"], num_experts_per_tok=2,
        moe_capacity_factor=4.0,
    )
    lp = dict(
        router=chip((w["H"], w["E"]), jnp.bfloat16),
        w_gate=chip((w["E"], w["H"], w["I"]), jnp.bfloat16),
        w_up=chip((w["E"], w["H"], w["I"]), jnp.bfloat16),
        w_down=chip((w["E"], w["I"], w["H"]), jnp.bfloat16),
    )
    monkeypatch.setattr(att, "_on_tpu", lambda: True)

    def text(n):
        x = chip((1, n, w["H"]), jnp.bfloat16)
        valid = chip((1, n), jnp.bool_)
        return jax.jit(lambda l, y, v: M._moe_mlp(l, y, cfg, v)).lower(
            lp, x, valid).compile().as_text()

    mixed, decode = text(1024), text(32)
    assert len(re.findall(rf"%{KERNEL_NAME}[.\d]* = ", mixed)) == 3
    assert not re.search(r"bf16\[8,1024,(4096|14336)\]", mixed)
    assert KERNEL_NAME not in decode
    assert re.search(r"bf16\[8,32,14336\]", decode)


def test_scanned_experts_reach_the_kernel_uncopied(chip, monkeypatch):
    """Inside the scan over layers the kernel reads the experts' weights
    out of the layers' stack by index.  Handed the scan's own slice it
    would be fed a copy of all eight matrices before every launch (2.9 ms
    beside a 1.3 ms launch on the chip, PR 27): no operation of the
    compiled trunk may produce one layer's ``[E, ., .]`` weights."""
    import re

    from dynamo_tpu.engine import attention as att
    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.ops.grouped_matmul import KERNEL_NAME

    w, L, n = MIXTRAL_MLP, 2, 1024
    cfg = ModelConfig(
        vocab_size=256, hidden_size=w["H"], intermediate_size=w["I"],
        num_layers=L, num_heads=32, num_kv_heads=8, head_dim=128,
        dtype="bfloat16", num_experts=w["E"], num_experts_per_tok=2,
        moe_capacity_factor=4.0,
    )
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    stack = jax.tree.map(lambda a: chip(a.shape, a.dtype), shapes["layers"])
    monkeypatch.setattr(att, "_on_tpu", lambda: True)

    def trunk(stack, kv, x, cos, sin, valid):
        attend = lambda q, k, v, kv, layer: (q, kv)  # the MLP is the subject
        return M.scan_layers(stack, kv, x, cos, sin, cfg, attend, valid)

    text = jax.jit(trunk).lower(
        stack, chip((L, 2, 8, 16, 8, 128), jnp.bfloat16),
        chip((1, n, w["H"]), jnp.bfloat16), chip((1, n, 128), jnp.float32),
        chip((1, n, 128), jnp.float32), chip((1, n), jnp.bool_),
    ).compile().as_text()
    assert KERNEL_NAME in text
    copies = re.findall(r"= bf16\[(?:1,)?8,(?:4096,14336|14336,4096)\]", text)
    assert not copies, copies


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


# -- latent attention (MLA): mistral-small-4-119b's widths ---------------------

LATENT = dict(Hq=32, C=256, R=64, LAYERS=6, PAGES=32768, LANES=16, TABLE=2064)
# the four packed executables benchmark/configs/mistral-small-4-119b.json fixes
LATENT_SHAPES = [(16, 1), (256, 128), (1024, 512), (4096, 2048)]


def _latent_pool(chip):
    from dynamo_tpu.engine.kv_cache import LatentKV

    w = LATENT
    slab = 2 * (w["C"] + w["R"])  # two layers' rows side by side: 640 = 5 tiles
    return LatentKV(
        chip((w["LAYERS"] // 2, 1, w["PAGES"], PAGE, 1, slab), jnp.bfloat16),
        w["C"],
    )


@pytest.mark.parametrize("Np,s_max", LATENT_SHAPES)
def test_latent_packed_attention_compiles(chip, Np, s_max):
    """Page DMAs out of a 32768-page pool, a 2064-wide page table in scalar
    memory, query tiles of 256 rows x 32 heads: Mosaic refuses a page of a
    320-wide row (not a whole number of 128-lane tiles), which is why two
    layers share a 640-wide slab row (``kv_cache.LatentKV``)."""
    from dynamo_tpu.ops.latent_attention import latent_packed_attention

    w = LATENT
    q = chip((Np, w["Hq"], w["C"] + w["R"]), jnp.bfloat16)
    table = chip((w["LANES"], w["TABLE"]), jnp.int32)
    vec = chip((w["LANES"],), jnp.int32)

    def call(q, pool, table, base, off, lens, layer):
        return latent_packed_attention(q, pool, table, base, off, lens, s_max, layer)

    compiled = jax.jit(call).lower(
        q, _latent_pool(chip), table, vec, vec, vec, chip((), jnp.int32)
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_packed_attention" in text
    # the pool reaches the kernel as it lies: no copy of it, no second pool
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


def test_latent_decode_attention_compiles(chip):
    from dynamo_tpu.ops.latent_attention import latent_decode_attention

    w = LATENT
    q = chip((w["LANES"], w["Hq"], w["C"] + w["R"]), jnp.bfloat16)
    table = chip((w["LANES"], w["TABLE"]), jnp.int32)
    vec = chip((w["LANES"],), jnp.int32)
    compiled = jax.jit(latent_decode_attention).lower(
        q, _latent_pool(chip), table, vec, chip((), jnp.int32)
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("Np,s_max", [(16, 0), *LATENT_SHAPES])
def test_latent_kernel_jaxpr_does_not_grow_with_the_key_block(
    monkeypatch, Np, s_max
):
    """The set-up budget, where a CPU can guard it (the guard PR 31 lacked:
    ``setup_s`` 111 -> 123 s for a kernel whose page copies were unrolled).
    Every packed executable traces and lowers this kernel, so its pages are
    copied in rolled loops over a block's live pages: as many DMA starts and
    (within a few) equations at 1024 keys a block as at 128, few of either.
    ``s_max`` 0 is the fused decode launch."""
    from dynamo_tpu.engine.kv_cache import LatentKV
    from dynamo_tpu.ops import latent_attention as la
    from tests.test_packed_work_list import _count, _eqns

    w = LATENT
    spec = jax.ShapeDtypeStruct
    q = spec((Np, w["Hq"], w["C"] + w["R"]), jnp.bfloat16)
    pool = LatentKV(spec((w["LAYERS"] // 2, 1, w["PAGES"], PAGE, 1,
                          2 * (w["C"] + w["R"])), jnp.bfloat16), w["C"])
    table = spec((w["LANES"], w["TABLE"]), jnp.int32)
    vec = spec((w["LANES"],), jnp.int32)

    def size(keys):
        monkeypatch.setattr(la, "_KEY_BLOCK", keys)
        if s_max:
            jaxpr = jax.make_jaxpr(
                lambda *a: la.latent_packed_attention.__wrapped__(
                    *a, s_max=s_max, layer=3)
            )(q, pool, table, vec, vec, vec)
        else:
            jaxpr = jax.make_jaxpr(
                lambda *a: la.latent_decode_attention.__wrapped__(*a, layer=3)
            )(q, pool, table, vec)
        return (sum(1 for _ in _eqns(jaxpr.jaxpr)),
                _count(jaxpr.jaxpr, "dma_start"))

    at_128, at_512, at_1024 = size(128), size(512), size(1024)
    assert at_128[1] == at_512[1] == at_1024[1]
    # (a 128-key block's row statistics need no repeat along the lanes)
    assert at_1024[0] == at_512[0] <= at_128[0] + 8
    # a tile: the queries' steps, a page's two parts at the item's first
    # fetch and at the next block's, the rows' steps out; two tiles at most.
    # The parent's kernel read 497 equations and 51 starts at 128 keys, 631
    # and 130 at the decode launch's 512
    eqns, starts = at_512
    assert starts <= 12 and eqns <= 900, at_512


def test_latent_layers_scatter_and_attend_without_copying_the_pool(chip, monkeypatch):
    """The trunk over a latent pool, as the packed step runs it: every layer
    scatters its rows into the slab and attends through the kernel.  XLA
    would lay a 320-wide pool out pages-minor and copy all of it before
    every kernel call; with 640-wide slab rows the compiled trunk holds the
    pool once (its temporaries are activations, far under the pool's 2 GB)
    and makes no array of the pool's shape but the pool."""
    import re

    from dynamo_tpu.engine import attention as att
    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.config import ModelConfig

    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    cfg = ModelConfig(
        vocab_size=256, hidden_size=4096, intermediate_size=2048, num_layers=6,
        num_heads=32, num_kv_heads=32, head_dim=128, dtype="bfloat16",
        num_experts=128, num_experts_per_tok=4, num_local_experts=4,
        moe_capacity_factor=32.0, num_shared_experts=1, q_lora_rank=1024,
        kv_lora_rank=256, qk_nope_head_dim=64, qk_rope_head_dim=64,
        v_head_dim=128, rope_interleave=True,
    )
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    stack = jax.tree.map(lambda a: chip(a.shape, a.dtype), shapes["layers"])
    w, Np, s_max = LATENT, 1024, 512
    vec = chip((w["LANES"],), jnp.int32)
    rows = chip((Np,), jnp.int32)

    def trunk(stack, pool, x, cos, sin, table, base, off, lens, lane, rel):
        valid = lane < w["LANES"]
        pos = base[jnp.clip(lane, 0, w["LANES"] - 1)] + rel

        def attend(q, k, v, kv, layer):
            out, kv = att.latent_packed_attention_dispatch(
                q[0], k[0], kv, layer, table, base, off, lens, lane, rel,
                pos, valid, s_max)
            return out[None], kv

        return M.scan_layers(stack, pool, x, cos, sin, cfg, attend, valid[None])

    compiled = jax.jit(trunk, donate_argnums=(1,)).lower(
        stack, _latent_pool(chip), chip((1, Np, 4096), jnp.bfloat16),
        chip((1, Np, 64), jnp.float32), chip((1, Np, 64), jnp.float32),
        chip((w["LANES"], w["TABLE"]), jnp.int32), vec, vec, vec, rows, rows,
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%latent_packed_attention[.\d]* = ", text)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    made = [l for l in text.splitlines()
            if re.search(r"= bf16\[3,(1,)?32768,16,(1,)?640\]\S* (copy|transpose)\(", l)]
    assert not made, made[:3]


# -- the fused decode executables: which layout their expert MLPs take ---------


def published(name):
    """(ModelConfig, engine block) of ``benchmark/configs/<name>.json`` as the
    benchmark serves it: bfloat16, no assignment dropped."""
    import dataclasses
    import json

    from dynamo_tpu.engine.config import ModelConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    mc = ModelConfig.from_hf_config(
        {k: v for k, v in cfg.items() if k not in ("engine", "rehearse")})
    return dataclasses.replace(
        mc, dtype="bfloat16",
        moe_capacity_factor=mc.num_experts / mc.num_experts_per_tok), cfg["engine"]


def _decode_block_operands(shape, cfg, pool, lanes, table):
    """Operands of ``step._packed_unified_multistep`` for a decode-only
    dispatch of ``lanes`` rows, ``cfg`` left out (``shape(dims, dtype)``
    makes each: the ``chip`` fixture's, or ``jax.ShapeDtypeStruct``)."""
    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.sampling import SamplingParams

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
        params, pool, i32(B), i32(B), i32(B), b1(B), i32(B, 4), i32(B, table),
        i32(B), i32(B), i32(B), b1(B), i32(B), i32(B), b1(B), b1(B), b1(B),
        i32(B), i32(B), shape((2,), jnp.uint32), sampling,
    )


def decode_block_equations(cfg, operands, steps=4):
    """Equations of the fused block of ``steps`` decode steps' jaxpr, nested
    ones counted, traced as on the chip (the caller patches ``_on_tpu``)."""
    from dynamo_tpu.engine import step as S
    from tests.test_packed_work_list import _eqns

    jaxpr = jax.make_jaxpr(
        lambda *a: S._packed_unified_multistep(
            a[0], cfg, *a[1:], s_max=1, num_steps=steps)
    )(*operands)
    return sum(1 for _ in _eqns(jaxpr.jaxpr)), len(jaxpr.out_avals)


def test_mixtral_decode_block_keeps_its_jaxpr(monkeypatch):
    """Mixtral's 32 lanes route 64 assignments over a router of 8: the rule
    that hands a step of few rows to the grouped product does not hold, and
    the fused block of four decode steps traces to the program it traced to
    on the parent (counted there with this function): the buffers
    ``[8, 32, .]``, seven results, no count of experts read."""
    from dynamo_tpu.engine import attention as att

    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    cfg, eng = published("mixtral-8x7b")
    spec = jax.ShapeDtypeStruct
    pool = spec((cfg.num_layers, 2, eng["num_pages"], PAGE, cfg.num_kv_heads,
                 cfg.head_dim), jnp.bfloat16)
    ops = _decode_block_operands(spec, cfg, pool, 32, 512)
    assert decode_block_equations(cfg, ops) == (1407, 7)


def test_mistral4_decode_block_reads_the_experts_its_rows_reach(chip, monkeypatch):
    """``mistral-small-4-119b``'s 16 lanes route 64 assignments over a router
    of 128: the ``(16, 1)`` executable and the fused steps behind it take
    the grouped kernel out of the experts' whole stack (three launches a
    layer in each of the two scan bodies), make no ``[32, 16, .]`` buffer
    and no copy of a layer's 32 experts, and return the count of experts
    read beside the seven results."""
    from dynamo_tpu.engine import attention as att
    from dynamo_tpu.engine import step as S
    from dynamo_tpu.ops.grouped_matmul import KERNEL_NAME

    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    cfg, eng = published("mistral-small-4-119b")
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (128, 32, 4)
    lanes = eng["max_batch_size"]
    ops = _decode_block_operands(chip, cfg, _latent_pool(chip), lanes, LATENT["TABLE"])
    fn = jax.jit(
        lambda *a: S._packed_unified_multistep(
            a[0], cfg, *a[1:], s_max=1, num_steps=2),
        donate_argnums=(1,))
    lowered = fn.lower(*ops)
    assert len(lowered.out_info) == 8 and lowered.out_info[-1].shape == (2,)
    text = lowered.compile().as_text()
    assert len(re.findall(rf"%{KERNEL_NAME}[.\d]* = ", text)) == 6
    assert not re.search(r"bf16\[32,16,(4096|2048)\]", text)
    copies = re.findall(r"= bf16\[(?:1,)?32,(?:4096,2048|2048,4096)\]", text)
    assert not copies, copies
