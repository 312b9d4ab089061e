"""Test configuration: force a virtual 8-device CPU mesh before JAX loads.

Multi-chip sharding (tp/dp/pp/sp) is validated on virtual CPU devices: the
suite runs where there is no chip.  What only a chip (or its compiler) can
show lives in chip_smoke.py and tests/test_chip_compile.py.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Tier-1 runs single-core CPU, where XLA's default optimization pipeline is
# most of the suite's wall clock (compiling tiny test models over and over).
# Backend optimization level 0 roughly halves the suite; identity tests
# compare like-for-like executables and reference-parity tests stay within
# tolerance (fp32 accumulation is forced separately below).  An explicit
# user/CI setting of the flag wins.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_backend_optimization_level" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_backend_optimization_level=0"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# No persistent XLA cache for the CPU suite: at optimization level 0 a
# warm-cache read is slower than recompiling, and engines built by tests
# must not write a cache into the checkout.  (Where the caller exports
# JAX_COMPILATION_CACHE_DIR, JAX itself still honours it.)
os.environ.setdefault("DYN_XLA_CACHE_DIR", "off")
os.environ.setdefault("DYN_LOG", "warning")

import asyncio  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

# a caller's jax config may have named another platform before this file ran
jax.config.update("jax_platforms", "cpu")

# XLA-CPU's oneDNN path does reduced-precision matmuls by default; parity
# tests against fp64/torch references need full fp32 accumulation.  (On TPU
# the production default -- bf16 on the MXU -- is what we want, so this is
# test-only.)
jax.config.update("jax_default_matmul_precision", "highest")

from dynamo_tpu.tokens.hashing import ensure_native_built  # noqa: E402

ensure_native_built()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: cold-compile storms / soaks excluded from tier-1 "
        "(run with -m slow)",
    )


# what a process may hold of memory mappings is vm.max_map_count, 65,530 by
# default; stay well under it
_MAP_CEILING = 30000


@pytest.fixture(autouse=True)
def _executables_within_the_map_limit():
    """A compiled program is hundreds of memory mappings that its process
    keeps for as long as JAX caches it.  A worker of the whole suite compiles
    thousands; one that crosses the kernel's limit on mappings dies inside
    its next compile (``Fatal Python error: Aborted`` under
    ``backend_compile_and_load``), in whichever test comes next.  So a worker
    that has gathered half the limit gives its programs back: the tests
    after it compile again what they use."""
    yield
    try:
        with open("/proc/self/maps") as f:
            held = sum(1 for _ in f)
    except OSError:  # no /proc: nothing to count, nothing to do
        return
    if held > _MAP_CEILING:
        jax.clear_caches()


@pytest.fixture
def run():
    """Run an async test body on a fresh event loop."""

    def _run(coro):
        return asyncio.run(coro)

    return _run


@pytest.fixture(scope="session")
def model_dir(tmp_path_factory):
    """A mock model directory: real (tiny) tokenizer artifact + config, no
    weights -- the reference's sample-model fixture pattern
    (lib/llm/tests/data/sample-models/mock-llama-3.1-8b-instruct)."""
    import json

    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    d = tmp_path_factory.mktemp("mock-model")
    tok = Tokenizer(models.BPE(unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=512, special_tokens=["<unk>", "<s>", "</s>"]
    )
    corpus = [
        "hello world this is a test of the tokenizer facade",
        "the quick brown fox jumps over the lazy dog",
        "paged attention over a device mesh with sharded kv heads",
        "user assistant system STOP DONE stop done tell me a story",
        "0123456789 abcdefghijklmnopqrstuvwxyz ABCDEFGHIJKLMNOPQRSTUVWXYZ",
        "<|user|> <|assistant|> <|system|> \n !?.,:;'\"()[]{}",
    ]
    tok.train_from_iterator(corpus, trainer)
    tok.save(str(d / "tokenizer.json"))
    (d / "tokenizer_config.json").write_text(
        json.dumps(
            {
                "eos_token": "</s>",
                "bos_token": "<s>",
                "chat_template": (
                    "{% for message in messages %}"
                    "<|{{ message['role'] }}|>\n{{ message['content'] }}\n"
                    "{% endfor %}"
                    "{% if add_generation_prompt %}<|assistant|>\n{% endif %}"
                ),
            }
        )
    )
    (d / "config.json").write_text(
        json.dumps(
            {
                "model_type": "llama",
                "vocab_size": tok.get_vocab_size(),
                "hidden_size": 64,
                "intermediate_size": 128,
                "num_hidden_layers": 2,
                "num_attention_heads": 4,
                "num_key_value_heads": 2,
                "max_position_embeddings": 2048,
            }
        )
    )
    return str(d)
