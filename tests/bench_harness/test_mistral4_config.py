"""The ``mistral-small-4-119b`` configuration held to the contract
``test_config_names.py`` holds the toy family to: its file names the modules
of its reference, weights and counts and its rehearsal sizes, the harness
finds each by name, and the counts are the hand counts of its cut.  The
published keys are written out here, so that the file cannot drift from
``config.json`` of ``mistralai/Mistral-Small-4-119B-2603`` unseen."""
import asyncio
import importlib
import importlib.util
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import costs, costs_mistral4, run, server

NAME = "mistral-small-4-119b"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
READERS = os.path.join(ROOT, "benchmark", "layer_metrics")
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 12288,
    "kv_lora_rank": 256, "max_position_embeddings": 1048576, "mlp_bias": False,
    "model_type": "mistral4", "moe_intermediate_size": 2048, "n_group": 1,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4, "num_hidden_layers": 36,
    "num_key_value_heads": 32, "q_lora_rank": 1024, "qk_head_dim": 128,
    "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True,
    "rope_parameters": {
        "beta_fast": 32, "beta_slow": 1, "factor": 128, "llama_4_scaling_beta": 0.1,
        "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 8192,
        "rope_theta": 10000, "rope_type": "yarn", "type": "yarn"},
    "routed_scaling_factor": 1, "sliding_window": None, "tie_word_embeddings": False,
    "topk_group": 1, "v_head_dim": 128, "vocab_size": 131072,
}
CUT = {"num_hidden_layers": 6, "n_routed_experts": 32, "vocab_size": 32768}


@pytest.fixture(scope="module")
def whole():
    return server.load_config(NAME, False)


@pytest.fixture(scope="module")
def tiny():
    return server.load_config(NAME, True)


def _entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return next(c for c in bench["configs"] if c["name"] == NAME)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_published_key_is_in_the_file(whole, key):
    assert whole[key] == CUT.get(key, PUBLISHED[key])


def test_only_depth_experts_held_and_vocabulary_are_cut(whole):
    entry = _entry()
    assert sorted(entry["reduced"]) == sorted(CUT) == sorted(whole["reduced"])
    assert entry["source"] == whole["source"]
    assert entry["source"].endswith("Mistral-Small-4-119B-2603/blob/main/config.json")
    # the file states the published 128 beside the 32 held, and the router's width
    assert whole["router_experts"] == 128 and whole["expert_offset"] == 0
    assert "128 published" in whole["reduced"]["n_routed_experts"]
    for key in entry["reduced"]:  # never a width
        assert not key.endswith(("_dim", "_rank", "_size")) or key == "vocab_size"
    for key in ("head_dim", "router", "softmax_scale", "query_factor", "weights",
                "vision_tower"):
        assert whole["assumed"][key]
    assert whole["guarantees"]["kv_cache_dtype"] == "bfloat16"
    assert whole["guarantees"]["dropped_expert_assignments"] == 0


def test_the_file_names_its_modules_and_they_keep_the_contract(whole):
    assert [whole[k] for k in server.NAMED] == [
        "benchmark.reference_mistral4", "benchmark.weights_mistral4",
        "benchmark.costs_mistral4"]
    ref, weights, model_costs = (importlib.import_module(whole[k]) for k in server.NAMED)
    assert callable(ref.Reference) and callable(weights.build_params)
    for fn in ("weight_bytes", "kv_bytes_per_token", "forward_passes"):
        assert callable(getattr(model_costs, fn))
    with open(ref.__file__) as f:  # the reference imports nothing of the program
        assert "dynamo_tpu" not in f.read()
    with open(model_costs.__file__) as f:  # run.py imports it: no JAX
        assert "import jax" not in f.read()


def test_rehearse_keeps_the_kinds_of_layer(whole, tiny):
    assert "rehearse" not in whole and whole["engine"]["num_pages"] == 32768
    assert whole["engine"]["page_size"] == 16 and whole["engine"]["max_seq_len"] == 33024
    assert tiny["hidden_size"] == 64 and tiny["torch_dtype"] == "float32"
    assert tiny["model_type"] == "mistral4" and tiny["n_shared_experts"] == 1
    assert tiny["router_experts"] > tiny["n_routed_experts"]  # still a share
    assert tiny["rope_interleave"] and tiny["rope_parameters"]["type"] == "yarn"
    assert tiny["weights"] == whole["weights"]
    assert tiny["engine"]["num_pages"] == 1024


def test_the_program_reads_the_file(whole):
    mc = server.model_config(whole)
    assert mc.is_mla and mc.num_layers == 6 and mc.vocab_size == 32768
    assert (mc.num_experts, mc.experts_held, mc.local_expert_offset) == (128, 32, 0)
    assert mc.moe_capacity_factor == 32.0  # = E / K: no assignment can drop
    assert mc.kv_geometry == (3, 1, 1, 640) and mc.kv_values_per_token * 2 == 3840
    assert mc.dtype == "bfloat16"


def test_counts_against_the_hand_counts(whole):
    """Outside the routed experts a layer has 28.05M (attention: 4.19 + 4.19
    + 1.31 + 1.57 + 16.78) + 25.17M (shared) + 0.52M (router); the 32 held
    experts 805.3M; 6 layers and the 134.2M head: 10.58 GB streamed a step,
    with the embedding's slice 10.85 GB held."""
    parts = costs_mistral4.layer_params(whole)
    assert parts["attention"] == (4096 * 1024 + 1024 * 32 * 128 + 4096 * 320
                                  + 256 * 32 * 192 + 32 * 128 * 4096) == 28049408
    assert parts["shared_experts"] == 3 * 4096 * 2048 == 25165824
    assert parts["router"] == 4096 * 128 and parts["held_experts"] == 32 * 25165824
    streamed = 2.0 * (6 * (28049408 + 25165824 + 524288 + 805306368) + 4096 * 32768)
    assert costs_mistral4.weight_bytes(whole) == streamed == 10576986112.0
    assert costs_mistral4.resident_bytes(whole) == streamed + 2 * 4096 * 32768
    assert round(costs_mistral4.resident_bytes(whole) / 1e9, 2) == 10.85
    assert costs_mistral4.kv_bytes_per_token(whole) == 6 * 320 * 2 == 3840
    counts = {"_latent_packed_attention.3": 12, "_latent_decode_attention.4": 42,
              "_moe_grouped_matmul.5": 36, "_fusion.6": 100}
    assert costs_mistral4.forward_passes(counts, whole) == 9.0


def test_the_readers_get_this_familys_counts(whole):
    ctx = run.layer_context(whole, {}, {}, peaks={})
    assert ctx["costs"] is costs and ctx["model_costs"] is costs_mistral4
    ctx["trace"] = {"busy_s": 0.1, "op_counts": {"_latent_decode_attention.1": 30}}
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(READERS, "step.weight_stream_pct.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ctx["peaks"] = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert mod.read(ctx) == pytest.approx(100.0 * 5 * 10576986112.0 / (0.1 * 819e9))


def _bench(control=""):
    return server.Bench(NS(config=NAME, rehearse=True, control=control,
                           workdir="unused", seed=3))


def test_the_server_draws_through_the_named_module(tiny):
    params = _bench().build_params(3)
    layers = params["layers"]
    assert layers["wkv_a"].shape == (2, 64, 16 + 8)
    assert layers["w_gate"].shape == (2, 4, 64, 32)  # the experts held, of the experts' width
    assert layers["router"].shape == (2, 64, 16)  # the router's published width
    assert layers["ws_up"].shape == (2, 64, 32) and params["lm_head"].shape == (64, 256)
    again = importlib.import_module(tiny["weights"]).build_params(tiny, 3)
    assert (layers["wkv_b"] == again["layers"]["wkv_b"]).all()
    assert not (layers["wkv_b"] == _bench().build_params(4)["layers"]["wkv_b"]).all()


def test_the_int8_control_quantizes_the_latent_matrices():
    from dynamo_tpu.engine.quant import QuantizedTensor

    params = _bench("int8_weights").build_params(3)
    for name in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate", "ws_down"):
        assert isinstance(params["layers"][name], QuantizedTensor), name
    assert isinstance(params["lm_head"], QuantizedTensor)
    for name in ("router", "q_a_norm", "kv_a_norm", "input_norm"):
        assert not isinstance(params["layers"][name], QuantizedTensor), name


def test_the_server_finds_the_reference_by_name():
    bench = _bench()
    bench.reply = lambda body: body
    body = {"seed": 3, "tokens": list(range(40)), "rows": [38, 39],
            "ids": [[1, 2, 3], [4, 5, 6]]}
    out = asyncio.run(bench.reference_route(NS(json=lambda: body)))
    assert len(out["logprobs"]) == 2 and len(out["logprobs"][0]) == 3
    assert all(-12 < v < 0 for row in out["logprobs"] for v in row)
