"""``--rehearse`` of the cell PR 45 adds, on the CPU: the whole command at
tiny size, to its result line.  The served engine (chunked prefill of a
session, decode through the fused steps with the convolution layers' state in
the lanes, the longest session again from the prefix cache, resumed from the
snapshot of the page its hit ends on) has to agree with the plain reference
to float32's rounding: 1e-4 is thirty times what it reads and a hundredth of
what bfloat16 would.  And the configuration's file keeps the contract
``test_config_names.py`` holds the toy family to."""
import importlib
import inspect
import json
import os
import subprocess
import sys

from benchmark import server, stats, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = "lfm2-8b-a1b.sessions-open"


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
    assert logits["positions"] >= 96
    assert logits["logprob_err"] < 1e-4, logits
    # the check's requests, then the longest again, from the cache
    asked = traffic.load("sessions-open")["check"]["requests"]
    assert len(logits["prompt_tokens"]) == asked + 1
    assert logits["prompt_tokens"][0] == logits["prompt_tokens"][-1]


def test_the_configuration_names_its_family_and_states_its_cut():
    cfg = server.load_config("lfm2-8b-a1b", False)
    assert [cfg[k] for k in server.NAMED] == [
        "benchmark.reference_lfm2", "benchmark.weights_lfm2", "benchmark.costs_lfm2"]
    assert sorted(cfg["reduced"]) == ["layer_types", "num_dense_layers", "num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 13 == len(cfg["layer_types"])
    assert cfg["layer_types"] == ["conv"] + ["full_attention", "conv", "conv", "conv"] * 3
    # every published width
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["vocab_size"], cfg["conv_L_cache"]) == (
        2048, 7168, 1792, 32, 8, 32, 4, 65536, 3)
    assert cfg["guarantees"]["kv_cache_dtype"] == cfg["guarantees"]["weights_dtype"] == "bfloat16"
    assert "snapshot" in cfg["guarantees"]["prefix_reuse"]
    assert cfg["tolerance"]["logprob_err"] > 0 and "rehearse" not in cfg
    eng = cfg["engine"]
    assert (eng["max_batch_size"], eng["page_size"], eng["num_pages"],
            eng["max_seq_len"]) == (32, 16, 16384, 7168)
    assert len(eng["packed_shapes"]) == 4 and len(eng["warm_anchor_tokens"]) == 1
    assert eng["mixed_token_budget"] == 1024 and eng["packed_shapes"][-1] == [2048, 1024]
    tiny = server.load_config("lfm2-8b-a1b", True)
    # the rehearsal keeps the configuration's own kinds of layer: a lead, two periods
    assert tiny["layer_types"] == cfg["layer_types"][:9] and tiny["num_dense_layers"] == 1
    assert tiny["weights"] == cfg["weights"] and tiny["use_expert_bias"] is True


def test_the_families_modules_keep_the_contract():
    """What ``test_config_names.py`` holds the toy family to: a ``Reference``
    with ``logprobs(seed, tokens, rows, ids)`` that imports nothing of the
    program, ``build_params(cfg, seed, each)``, and the three counts with no
    JAX."""
    cfg = server.load_config("lfm2-8b-a1b", False)
    ref = importlib.import_module(cfg["reference"])
    assert list(inspect.signature(ref.Reference.logprobs).parameters) == [
        "self", "seed", "tokens", "rows", "ids"]
    src = inspect.getsource(ref) + inspect.getsource(importlib.import_module(cfg["weights"]))
    assert "dynamo_tpu" not in src.replace("``dynamo_tpu.engine.model.scan_layers``", "")
    weights = importlib.import_module(cfg["weights"])
    assert list(inspect.signature(weights.build_params).parameters) == ["cfg", "seed", "each"]
    costs = importlib.import_module(cfg["costs"])
    for name in ("weight_bytes", "kv_bytes_per_token", "forward_passes"):
        assert callable(getattr(costs, name))
    assert "import jax" not in inspect.getsource(costs)


def test_the_engine_the_harness_builds_keeps_state_beside_three_layers_of_pages():
    from dynamo_tpu.engine.config import ModelConfig

    mc = server.model_config(server.load_config("lfm2-8b-a1b", False))
    assert isinstance(mc, ModelConfig) and mc.has_conv and not mc.two_kind
    assert (mc.kind_layers("full"), mc.kind_layers("conv")) == (3, 10)
    # two 64-wide KV heads a 128-lane row of the pool
    assert (mc.kv_head_pack, mc.kv_geometry) == (2, (3, 2, 4, 128))
    assert mc.moe_capacity_factor == 8.0 and mc.dtype == "bfloat16"
    assert (mc.router_score, mc.router_bias, mc.tie_word_embeddings) == ("sigmoid", True, True)


def test_the_mix_is_sessions_that_come_back_and_runs_under_its_knee():
    spec = traffic.load("sessions-open")
    assert (spec["block_documents"], spec["asks_per_document"]) == (16, 4)
    assert spec["rate_per_s"] == stats.pitch(spec["knee_per_s"])
    assert spec["check"]["repeat_for_prefix_hit"] is True
    block = traffic.open_block(spec, 7, 0, 65536)
    longest = max(len(r["prompt"]) for r in block)
    # the longest session, its turn and its answer fit a lane
    assert longest + 512 <= 7168
    # three asks in four find their session's pages: whole blocks of 16 shared
    docs = {}
    for r in block:
        docs.setdefault(tuple(r["prompt"][:256]), []).append(r)
    assert len(docs) == 16 and all(len(v) == 4 for v in docs.values())
