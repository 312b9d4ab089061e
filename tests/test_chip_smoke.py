"""Guards for the rules the chip run depends on (ISSUE 22 section 3): where
the compile cache lives, that the processes which must leave the chip alone
never initialise a JAX backend, and that ``chip_smoke.py`` rehearses on the
CPU and fails loudly when a phase fails."""

import json
import os
import socket
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore jax's compilation-cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize(
    "env,expect",
    [
        # JAX's own variable names the directory: the engine sets no other
        ({"JAX_COMPILATION_CACHE_DIR": "{tmp}/theirs"}, "unchanged"),
        # nothing set: one fixed directory inside the checkout
        ({}, "checkout"),
        # off stays off, whatever else is set
        ({"DYN_XLA_CACHE_DIR": "off"}, "unchanged"),
        ({"DYN_XLA_CACHE_DIR": "off",
          "JAX_COMPILATION_CACHE_DIR": "{tmp}/theirs"}, "unchanged"),
    ],
    ids=["jax-var", "default", "off", "off-beats-jax-var"],
)
def test_compilation_cache_location(env, expect, tmp_path, monkeypatch,
                                    cache_config):
    from dynamo_tpu.engine import engine as eng

    for name in ("DYN_XLA_CACHE_DIR", "JAX_COMPILATION_CACHE_DIR"):
        monkeypatch.delenv(name, raising=False)
    for name, value in env.items():
        monkeypatch.setenv(name, value.format(tmp=tmp_path))
    monkeypatch.setattr(eng, "XLA_CACHE_DIR", str(tmp_path / "checkout"))
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    eng._enable_compilation_cache()
    got = jax.config.jax_compilation_cache_dir
    if expect == "unchanged":
        assert got == "sentinel"
        assert not (tmp_path / "checkout").exists()
    else:
        assert got == str(tmp_path / "checkout")
        assert (tmp_path / "checkout").is_dir()


def test_default_cache_dir_is_fixed_and_git_ignored():
    from dynamo_tpu.engine.engine import XLA_CACHE_DIR

    assert XLA_CACHE_DIR == os.path.join(ROOT, ".xla_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = f.read().split()
    assert ".xla_cache/" in ignored and ".chip_smoke/" in ignored


_NO_BACKEND = r"""
import asyncio, json, socket, sys, urllib.request
from dynamo_tpu import cli, sdk, supervisor  # the launchers import no backend
from dynamo_tpu.runtime.transports.hub import HubServer

def touched():
    bridge = sys.modules.get("jax._src.xla_bridge")
    return bool(bridge and bridge._backends)

async def main():
    hub = HubServer(host="127.0.0.1", port=0)
    host, port = await hub.start()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        http_port = s.getsockname()[1]
    args = cli.build_parser().parse_args([
        "run", "in=http", "out=dyn", "--hub", f"{host}:{port}",
        "--router-mode", "kv", "--host", "127.0.0.1", "--port", str(http_port),
    ])
    front = asyncio.ensure_future(cli.run_http_frontend(args))
    url = f"http://127.0.0.1:{http_port}/health"
    for _ in range(200):
        await asyncio.sleep(0.05)
        if front.done():
            front.result()
        try:
            status = await asyncio.to_thread(
                lambda: urllib.request.urlopen(url, timeout=2).status)
        except OSError:
            continue
        if status == 200:
            break
    else:
        raise SystemExit("frontend never answered /health")
    print(json.dumps({"backend_initialised": touched()}))
    front.cancel()
    await asyncio.gather(front, return_exceptions=True)
    await hub.stop()

asyncio.run(main())
"""


def test_hub_and_frontend_leave_the_chip_alone():
    """One ``out=jax`` process per chip: the hub and the ``in=http out=dyn``
    frontend (kv router included) must come up without initialising any JAX
    backend, or they would take the chip from the worker."""
    env = dict(os.environ, PYTHONPATH=ROOT)
    env.pop("JAX_PLATFORMS", None)  # nothing may depend on being held off
    proc = subprocess.run(
        [sys.executable, "-c", _NO_BACKEND], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {
        "backend_initialised": False
    }


def _smoke(*argv, timeout):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "DYN_XLA_CACHE_DIR", "DYN_LOG")}
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *argv],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=timeout,
    )


def test_chip_smoke_rehearses_on_the_cpu():
    """The whole control flow at ModelConfig.tiny size, kernels interpreted:
    it passes, and what it prints can never be read as a chip pass."""
    proc = _smoke("--rehearse", timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    phases = {json.loads(ln).get("phase")
              for ln in proc.stdout.splitlines() if ln.startswith("{")}
    assert {"kernels", "serve", "distributed"} <= phases


def test_chip_smoke_fails_when_a_phase_fails():
    """A served child that cannot bind its port dies: the run exits
    non-zero and prints no ``"ok": true`` line."""
    with socket.socket() as busy:
        busy.bind(("127.0.0.1", 0))
        busy.listen(1)
        proc = _smoke("--rehearse", "--phases", "serve",
                      "--port", str(busy.getsockname()[1]), timeout=600)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "chip_smoke FAILED" in proc.stderr


def test_chip_smoke_needs_the_chip_and_the_repository(tmp_path):
    """Without ``--rehearse`` a run that finds no TPU exits non-zero and
    prints no result; so does the script alone, outside the repository."""
    proc = _smoke("--phases", "kernels", timeout=300)  # JAX here sees the CPU
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout and '"passed"' not in proc.stdout
    alone = tmp_path / "chip_smoke.py"
    with open(os.path.join(ROOT, "chip_smoke.py")) as f:
        alone.write_text(f.read())
    proc = subprocess.run(
        [sys.executable, str(alone)], cwd=tmp_path, capture_output=True,
        text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
