"""How ``data/tiny_tpu.xplane.pb`` was recorded (run on the chip, once):

    python tests/bench_harness/make_tiny_trace.py <out-dir>

A few small matrix products inside a ``lax.scan`` (so the trace has a
``while`` that spans its operations), traced with the Python tracer off.
"""
import glob
import os
import shutil
import sys

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    @jax.jit
    def step(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None

        return jax.lax.scan(body, x, None, length=4)[0]

    x = jnp.ones((256, 512), jnp.bfloat16)
    w = jnp.ones((512, 512), jnp.bfloat16) * 0.01
    step(x, w).block_until_ready()
    tmp = os.path.join(out_dir, "tiny_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(3):
        x = step(x, w)
    x.block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(out_dir, "tiny_tpu.xplane.pb"))
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(out_dir, "tiny_tpu.xplane.pb")), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
