"""Multi-host bootstrap: config parsing + a real 2-process CPU world.

The 2-process test launches two subprocesses that join a jax.distributed
world over localhost (the same path a TPU pod uses), build a global
dp=2 x tp=2 mesh spanning both processes, and run a sharded computation
whose result proves cross-process reduction happened.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

from dynamo_tpu.parallel.multihost import MultiNodeConfig, initialize_multihost


def test_config_from_env(monkeypatch):
    monkeypatch.setenv("DYN_NUM_NODES", "4")
    monkeypatch.setenv("DYN_NODE_RANK", "2")
    monkeypatch.setenv("DYN_LEADER_ADDR", "10.0.0.1:1234")
    cfg = MultiNodeConfig.from_env()
    assert cfg.num_nodes == 4 and cfg.node_rank == 2
    assert cfg.is_multi_node and not cfg.is_leader
    cfg.validate()


def test_config_validation():
    with pytest.raises(ValueError, match="out of range"):
        MultiNodeConfig(num_nodes=2, node_rank=2, leader_addr="x:1").validate()
    with pytest.raises(ValueError, match="leader_addr"):
        MultiNodeConfig(num_nodes=2, node_rank=0).validate()
    MultiNodeConfig().validate()  # single node always fine


def test_single_node_is_noop():
    cfg = initialize_multihost(MultiNodeConfig())
    assert not cfg.is_multi_node


_WORKER = """
import sys
sys.path.insert(0, "@REPO@")
from dynamo_tpu.parallel.multihost import MultiNodeConfig, initialize_multihost

cfg = initialize_multihost(MultiNodeConfig.from_env())
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from dynamo_tpu.parallel.mesh import MeshConfig, build_mesh

assert len(jax.devices()) == 4, jax.devices()  # 2 procs x 2 local
mesh = build_mesh(MeshConfig(dp=2, tp=2))
data = np.arange(32, dtype=np.float32).reshape(4, 8)
arr = jax.make_array_from_callback(
    (4, 8), NamedSharding(mesh, P("dp", None)), lambda idx: data[idx]
)
total = jax.jit(
    lambda x: jnp.sum(x), out_shardings=NamedSharding(mesh, P())
)(arr)
got = float(jax.device_get(total))
assert got == 496.0, got
print("rank %d OK total=%s" % (cfg.node_rank, got), flush=True)
"""


def test_two_process_world_runs_sharded_computation(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    script = tmp_path / "worker.py"
    script.write_text(_WORKER.replace("@REPO@", os.getcwd()))
    procs = []
    for rank in range(2):
        env = {
            k: v
            for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
        }
        env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
            DYN_NUM_NODES="2",
            DYN_NODE_RANK=str(rank),
            DYN_LEADER_ADDR=f"127.0.0.1:{port}",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    try:
        outs = [p.communicate(timeout=180)[0] for p in procs]
    finally:
        for p in procs:
            p.kill()
    if any(_CPU_NO_MULTIPROCESS in out for out in outs):
        pytest.skip(
            "this jax's CPU backend has no multiprocess collectives "
            "(newer jax ships a gloo-backed cross-host CPU path)"
        )
    for rank, out in enumerate(outs):
        assert f"rank {rank} OK total=496.0" in out, f"rank {rank}:\n{out}"


# jax < 0.5-era CPU backends refuse cross-process computations outright;
# the 2-process tests probe for this runtime capability rather than pin a
# version (the TPU driver environment has it, some CI containers do not)
_CPU_NO_MULTIPROCESS = (
    "Multiprocess computations aren't implemented on the CPU backend"
)


_SERVE_WORKER = """
import asyncio, json, sys
sys.path.insert(0, "@REPO@")
from dynamo_tpu.parallel.multihost import MultiNodeConfig, initialize_multihost

cfg_mn = initialize_multihost(MultiNodeConfig.from_env())
import jax

from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
from dynamo_tpu.parallel.mesh import MeshConfig, build_mesh
from dynamo_tpu.protocols.common import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.runtime.engine import Context

assert len(jax.devices()) == 4, jax.devices()  # 2 procs x 2 local
mesh = build_mesh(MeshConfig(dp=2, tp=2))
engine = JaxEngine.random_init(
    ModelConfig.tiny(num_kv_heads=2),
    EngineConfig(max_batch_size=2, max_seq_len=64, page_size=4, num_pages=64,
                 decode_block_size=4, seed=0),
    mesh=mesh,
)

async def main():
    outs = []
    # sequential submission: every process must issue the same collective
    # dispatch sequence (SPMD), so request order cannot be left to the
    # scheduler's arrival timing
    for prompt in json.loads(open("@PROMPTS@").read()):
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=6),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        stream = await engine.generate(Context.new(req))
        toks = []
        async for item in stream:
            d = item.data or {}
            assert not item.is_error(), item.error_message()
            toks.extend(d.get("token_ids") or [])
        outs.append(toks)
    await engine.stop()
    return outs

outs = asyncio.run(main())
expected = json.loads(open("@EXPECTED@").read())
assert outs == expected, (outs, expected)
print("rank %d SERVE OK %s" % (cfg_mn.node_rank, outs), flush=True)
"""


@pytest.mark.slow
def test_two_process_served_engine_matches_single(tmp_path):
    """The v5e-pod serving path: two jax.distributed processes build a
    dp=2 x tp=2 mesh spanning both, and the ENGINE's generate() surface
    serves identical greedy requests collectively -- output must match a
    single-process unsharded engine with the same seed (VERDICT r4 #7).

    Slow lane: the two cold processes re-compile every serving executable
    on one CI core (the 900 s timeout exists for exactly that storm)."""
    import asyncio
    import json

    from dynamo_tpu.engine import EngineConfig, JaxEngine, ModelConfig
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    prompts = [[1, 2, 3, 4, 5], [7, 8, 9]]

    async def reference():
        engine = JaxEngine.random_init(
            ModelConfig.tiny(num_kv_heads=2),
            EngineConfig(max_batch_size=2, max_seq_len=64, page_size=4,
                         num_pages=64, decode_block_size=4, seed=0),
        )
        outs = []
        for p in prompts:
            req = PreprocessedRequest(
                token_ids=p,
                stop_conditions=StopConditions(max_tokens=6),
                sampling_options=SamplingOptions(temperature=0.0),
            )
            stream = await engine.generate(Context.new(req))
            toks = []
            async for item in stream:
                d = item.data or {}
                toks.extend(d.get("token_ids") or [])
            outs.append(toks)
        await engine.stop()
        return outs

    expected = asyncio.run(reference())
    assert all(len(t) == 6 for t in expected)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    (tmp_path / "prompts.json").write_text(json.dumps(prompts))
    (tmp_path / "expected.json").write_text(json.dumps(expected))
    script = tmp_path / "serve_worker.py"
    script.write_text(
        _SERVE_WORKER.replace("@REPO@", os.getcwd())
        .replace("@PROMPTS@", str(tmp_path / "prompts.json"))
        .replace("@EXPECTED@", str(tmp_path / "expected.json"))
    )
    procs = []
    for rank in range(2):
        env = {
            k: v
            for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
        }
        env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
            DYN_NUM_NODES="2",
            DYN_NODE_RANK=str(rank),
            DYN_LEADER_ADDR=f"127.0.0.1:{port}",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    try:
        # generous: a cold XLA-compile storm (2 processes x several fresh
        # executables on one CI core) can take minutes before serving starts
        outs = [p.communicate(timeout=900)[0] for p in procs]
    finally:
        for p in procs:
            p.kill()
    if any(_CPU_NO_MULTIPROCESS in out for out in outs):
        pytest.skip(
            "this jax's CPU backend has no multiprocess collectives "
            "(newer jax ships a gloo-backed cross-host CPU path)"
        )
    for rank, out in enumerate(outs):
        assert f"rank {rank} SERVE OK" in out, f"rank {rank}:\n{out}"
