"""``--rehearse`` of the cell PR 53 adds, on the CPU: the whole command at
tiny size, to its result line.  The served engine (chunked prefill of a long
session, the linear layers' recurrence in chunks of 64 with the state in the
lanes, decode through the fused steps, the longest session again from the
prefix cache, resumed from the snapshot at its last whole block) has to agree
with the plain reference, whose linear layers run one token at a time, to
float32's rounding: 1e-4 is ten times what it reads and a hundredth of what
bfloat16 would.  And the configuration's file keeps the contract
``test_config_names.py`` holds the toy family to."""
import importlib
import inspect
import json
import os
import subprocess
import sys

from benchmark import server, stats, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = "qwen3-next-80b-a3b.longsessions-open"
NAME = "qwen3-next-80b-a3b"


def _rehearse(cell, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell, "--seed", str(seed),
         "--seconds", "4", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.splitlines()
    result = json.loads(lines[-1])
    info = [json.loads(l[5:]) for l in lines if l.startswith("info {")]
    checks = {c["check"]: c for c in (json.loads(l[6:]) for l in lines
                                      if l.startswith("check {"))}
    return result, info, checks


def test_the_new_cell_rehearses_to_its_result_line():
    result, info, checks = _rehearse(NEW, 3600000451)
    assert result["correct"] is False and result["device"]["platform"] == "cpu"
    assert "setup_s" in result["metrics"]
    assert set(result["metrics"]) <= {"tpot_p90_ms", "ttft_p90_ms", "setup_s"}
    assert result["failed"] == 0
    assert checks["failed_requests"]["ok"] and checks["compiles_in_window"]["ok"]
    logits = next(i["logits"] for i in info if "logits" in i)
    assert logits["positions"] >= 192
    assert logits["logprob_err"] < 1e-4, logits
    # the check's requests, then the longest again: resumed from a snapshot
    asked = traffic.load("longsessions-open")["check"]["requests"]
    assert len(logits["prompt_tokens"]) == asked + 1
    assert logits["prompt_tokens"][0] == logits["prompt_tokens"][-1]


def test_the_configuration_names_its_family_and_states_its_cut():
    cfg = server.load_config(NAME, False)
    assert [cfg[k] for k in server.NAMED] == [
        "benchmark.reference_qwen3next", "benchmark.weights_qwen3next",
        "benchmark.costs_qwen3next"]
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    # three whole periods, a quarter of the experts and of the vocabulary
    assert (cfg["num_hidden_layers"], cfg["full_attention_interval"]) == (12, 4)
    assert (cfg["num_experts"], cfg["router_experts"], cfg["expert_offset"]) == (128, 512, 0)
    assert cfg["vocab_size"] * 4 == 151936
    # every published width
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_experts_per_tok"],
            cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"], cfg["partial_rotary_factor"]) == (
        2048, 512, 512, 16, 2, 256, 10, 16, 32, 128, 128, 4, 0.25)
    assert cfg["guarantees"]["kv_cache_dtype"] == cfg["guarantees"]["weights_dtype"] == "bfloat16"
    assert "snapshotted block" in cfg["guarantees"]["prefix_reuse"]
    assert "float32" in cfg["assumed"]["state_dtype"] and "mtp" in cfg["assumed"]
    assert cfg["tolerance"]["logprob_err"] > 0 and "rehearse" not in cfg
    eng = cfg["engine"]
    assert (eng["max_batch_size"], eng["page_size"], eng["num_pages"],
            eng["max_seq_len"], eng["state_snapshot_slots"]) == (16, 16, 16384, 33792, 64)
    assert eng["max_seq_len"] == 32768 + 96 + 512 + 416 and eng["max_seq_len"] % 16 == 0
    assert len(eng["packed_shapes"]) == 4 and eng["packed_shapes"][-1][0] == 2048
    assert eng["mixed_token_budget"] == 2048
    tiny = server.load_config(NAME, True)
    # the rehearsal keeps the configuration's own kinds of layer: two periods
    assert (tiny["num_hidden_layers"], tiny["full_attention_interval"]) == (8, 4)
    assert tiny["weights"] == cfg["weights"] and tiny["router_experts"] == 16


def test_the_families_modules_keep_the_contract():
    """What ``test_config_names.py`` holds the toy family to: a ``Reference``
    with ``logprobs(seed, tokens, rows, ids)`` that imports nothing of the
    program and runs the recurrence, ``build_params(cfg, seed, each)``, and
    the three counts with no JAX."""
    cfg = server.load_config(NAME, False)
    ref = importlib.import_module(cfg["reference"])
    assert list(inspect.signature(ref.Reference.logprobs).parameters) == [
        "self", "seed", "tokens", "rows", "ids"]
    src = inspect.getsource(ref) + inspect.getsource(importlib.import_module(cfg["weights"]))
    src = src.replace("``dynamo_tpu.engine.model.scan_layers``", "")
    assert "dynamo_tpu" not in src and "import dynamo" not in src
    # the linear layers token by token, not in the engine's chunks
    assert "jax.lax.scan(step, S0" in inspect.getsource(ref._delta_rule)
    weights = importlib.import_module(cfg["weights"])
    assert list(inspect.signature(weights.build_params).parameters) == ["cfg", "seed", "each"]
    costs = importlib.import_module(cfg["costs"])
    for name in ("weight_bytes", "kv_bytes_per_token", "forward_passes"):
        assert callable(getattr(costs, name))
    assert "import jax" not in inspect.getsource(costs)


def test_the_engine_the_harness_builds_keeps_state_beside_three_layers_of_pages():
    from dynamo_tpu.engine.config import ModelConfig

    mc = server.model_config(server.load_config(NAME, False))
    assert isinstance(mc, ModelConfig) and mc.has_linear and not mc.has_conv
    assert (mc.kind_layers("full"), mc.kind_layers("linear")) == (3, 9)
    assert mc.kv_geometry == (3, 2, 2, 256) and mc.kv_head_pack == 1
    assert (mc.num_experts, mc.experts_held) == (512, 128)
    assert mc.moe_capacity_factor == 51.2 and mc.dtype == "bfloat16"


def test_the_mix_is_long_sessions_that_come_back_and_runs_under_its_knee():
    spec = traffic.load("longsessions-open")
    assert (spec["block_documents"], spec["asks_per_document"]) == (8, 4)
    assert spec["rate_per_s"] == stats.pitch(spec["knee_per_s"])
    assert spec["check"] == {
        "requests": 2, "decode_tokens": 192, "repeat_for_prefix_hit": True}
    block = traffic.open_block(spec, 7, 0, 37984)
    longest = max(len(r["prompt"]) for r in block)
    # the longest session, its turn and its answer fit a lane
    assert 8192 <= longest and longest + 512 <= 33792
    assert max(max(r["prompt"]) for r in block) < 37984
    docs = {}
    for r in block:
        docs.setdefault(tuple(r["prompt"][:256]), []).append(r)
    assert len(docs) == 8 and all(len(v) == 4 for v in docs.values())
