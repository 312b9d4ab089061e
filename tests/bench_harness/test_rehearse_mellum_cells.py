"""``--rehearse`` of the cell PR 36 adds, on the CPU: the whole command at
tiny size, to its result line.  The served engine
(chunked prefill over a document dozens of windows long, decode through both
pools, the longest document again from the prefix cache) has to agree with
the plain reference to float32's rounding: 1e-4 is fifty times what it reads
(2e-6) and a hundredth of what bfloat16 would.  And the configuration's file
keeps the contract ``test_config_names.py`` holds the toy family to."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import server

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = "mellum2-12b-a2.5b.mixedlen-open"


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
    # ttft_p90_ms did not repeat on the chip and is not judged here (PERF.md section 6)
    result, info, checks = _rehearse(NEW, 3600000201)
    assert result["correct"] is False and result["device"]["platform"] == "cpu"
    # a tail exists only where a request finished inside the short window
    assert "setup_s" in result["metrics"]
    assert set(result["metrics"]) <= {"tpot_p90_ms", "setup_s"}
    assert result["failed"] == 0
    assert checks["failed_requests"]["ok"] and checks["compiles_in_window"]["ok"]
    logits = next(i["logits"] for i in info if "logits" in i)
    assert logits["positions"] >= 96
    assert logits["logprob_err"] < 1e-4, logits
    assert len(logits["prompt_tokens"]) == 3  # the longest again, from the cache
    assert logits["prompt_tokens"][0] == logits["prompt_tokens"][2]
    # dozens of the rehearsal's 32-token windows
    assert logits["prompt_tokens"][0] > 30 * 32


def test_the_configuration_names_its_family_and_states_its_cut():
    cfg = server.load_config("mellum2-12b-a2.5b", False)
    assert [cfg[k] for k in server.NAMED] == [
        "benchmark.reference_mellum", "benchmark.weights_mellum",
        "benchmark.costs_mellum"]
    assert sorted(cfg["reduced"]) == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 12 == len(cfg["layer_types"]) == len(
        cfg["mlp_layer_types"])
    # every published width, and both rope sections
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["num_experts"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["sliding_window"], cfg["vocab_size"]) == (
        2304, 32, 4, 128, 64, 896, 8, 1024, 98304)
    assert set(cfg["rope_parameters"]) == {"full_attention", "sliding_attention"}
    assert cfg["guarantees"]["kv_cache_dtype"] == cfg["guarantees"]["weights_dtype"] == "bfloat16"
    assert cfg["tolerance"]["logprob_err"] > 0 and "rehearse" not in cfg
    eng = cfg["engine"]
    assert (eng["max_batch_size"], eng["mixed_token_budget"], eng["page_size"]) == (32, 1024, 16)
    assert len(eng["packed_shapes"]) == 4
    tiny = server.load_config("mellum2-12b-a2.5b", True)
    # the rehearsal keeps the configuration's own kinds of layer: two periods
    assert tiny["layer_types"] == cfg["layer_types"][:8] and tiny["sliding_window"] == 32
    assert tiny["weights"] == cfg["weights"] and tiny["engine"]["num_window_pages"] == 128


def test_the_engine_the_harness_builds_serves_two_pools():
    from dynamo_tpu.engine.config import ModelConfig

    mc = server.model_config(server.load_config("mellum2-12b-a2.5b", False))
    assert isinstance(mc, ModelConfig) and mc.two_kind
    assert (mc.kind_layers("full"), mc.kind_layers("sliding")) == (3, 9)
    assert mc.moe_capacity_factor == 8.0 and mc.dtype == "bfloat16"
