"""A configuration's file names what depends on its architecture: the
modules of its reference, its weights and its counts, and its rehearsal
sizes.  Held to it by a made-up family (``data/toy-family.json``, with
modules of a few lines under ``data/toy_family/``) whose keys the Mistral
family's modules would misread: experts under ``moe_intermediate_size``
beside an unused ``intermediate_size``, and a tensor ``weights._T`` does not
know.  No file of the harness knows the family."""
import asyncio
import importlib.util
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import costs, run, server

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = os.path.join(os.path.dirname(os.path.abspath(costs.__file__)), "layer_metrics")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def toy(monkeypatch):
    """The harness looking for configurations, and their modules, in data/."""
    monkeypatch.setattr(server, "CONFIGS", DATA)
    monkeypatch.syspath_prepend(DATA)
    return server.load_config("toy-family", False)


def _reader(file):
    spec = importlib.util.spec_from_file_location("r", os.path.join(READERS, file))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench(control=""):
    return server.Bench(NS(config="toy-family", rehearse=False, control=control,
                           workdir="unused", seed=3))


@pytest.mark.parametrize("key", server.NAMED)
def test_a_missing_name_fails_by_name(key, tmp_path, monkeypatch):
    with open(os.path.join(DATA, "toy-family.json")) as f:
        cfg = json.load(f)
    del cfg[key]
    (tmp_path / "nameless.json").write_text(json.dumps(cfg))
    monkeypatch.setattr(server, "CONFIGS", str(tmp_path))
    with pytest.raises(SystemExit, match=f"'nameless' names no '{key}' module"):
        server.load_config("nameless", False)


def test_rehearse_merges_the_configurations_own_block(toy):
    assert toy["n_routed_experts"] == 8 and toy["num_hidden_layers"] == 2
    assert "rehearse" not in toy and toy["engine"]["num_pages"] == 64
    tiny = server.load_config("toy-family", True)
    assert tiny["n_routed_experts"] == 4 and tiny["num_hidden_layers"] == 1
    assert tiny["engine"] == {"max_batch_size": 2, "max_seq_len": 64, "page_size": 16,
                              "num_pages": 16}
    # what the block does not name stays: its own kinds of layer, its modules
    assert tiny["moe_intermediate_size"] == 32 and tiny["weights"] == toy["weights"]


def test_a_configuration_without_the_block_cannot_be_rehearsed(toy, tmp_path, monkeypatch):
    (tmp_path / "whole.json").write_text(json.dumps(toy))  # load_config took the block out
    monkeypatch.setattr(server, "CONFIGS", str(tmp_path))
    assert server.load_config("whole", False) == toy
    with pytest.raises(SystemExit, match="cannot be rehearsed"):
        server.load_config("whole", True)


@pytest.mark.parametrize("name,own", (
    ("mixtral-8x7b", {"num_local_experts": 4, "sliding_window": None}),
    ("mistral-7b", {"sliding_window": 128}),
))
def test_the_mistral_family_rehearses_at_the_sizes_it_did(name, own):
    """The values ``server.TINY``, ``TINY_ENGINE`` and ``load_config``'s two
    special cases had before they moved into the configurations' files."""
    tiny = server.load_config(name, True)
    expect = dict(own, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
                  torch_dtype="float32")
    assert {k: tiny.get(k) for k in expect} == expect
    assert tiny["engine"] == {
        "max_batch_size": 4, "max_seq_len": 1024, "page_size": 16, "num_pages": 256,
        "mixed_token_budget": 64, "packed_shapes": [[4, 1], [32, 16], [128, 64]],
        "warm_anchor_tokens": [200]}
    whole = server.load_config(name, False)
    assert whole["hidden_size"] == 4096 and whole["engine"]["num_pages"] == 6144
    assert [whole[k] for k in server.NAMED] == [
        "benchmark.reference", "benchmark.weights", "benchmark.costs"]


def test_the_server_draws_through_the_named_module(toy):
    params = _bench().build_params(3)
    # a tensor the Mistral family's table does not know, and experts of the
    # width the family states, not of the unused dense width
    assert sorted(params) == ["q_norm", "router", "w_gate", "wq"]
    assert params["w_gate"].shape == (8, 64, 32) and params["q_norm"].shape == (16,)
    again = importlib.import_module("toy_family.weights").build_params(toy, 3)
    assert (params["w_gate"] == again["w_gate"]).all()
    assert not (params["wq"] == _bench().build_params(4)["wq"]).all()


def test_the_server_finds_the_reference_by_name(toy):
    bench = _bench()
    bench.reply = lambda body: body
    body = {"seed": 3, "tokens": [5, 6, 7], "rows": [1, 2], "ids": [[1, 2, 3], [4, 5, 6]]}
    out = asyncio.run(bench.reference_route(NS(json=lambda: body)))
    assert out["logprobs"] == [[pytest.approx(-4.852, abs=1e-3)] * 3] * 2  # -log(128)


def test_the_readers_get_the_configurations_own_counts(toy):
    ctx = run.layer_context(toy, {}, {}, peaks=PEAK)
    assert ctx["costs"] is costs and ctx["model_costs"].__name__ == "toy_family.costs"
    # 2 layers x (attention 2 x 64 x 16 x 6 + 8 experts x (3 x 64 x 32 + 64)) + head 64 x 128
    own = 2.0 * (2 * (12288 + 8 * 6208) + 8192)
    assert ctx["model_costs"].weight_bytes(toy) == own == 264192.0
    # the Mistral family's count takes the unused dense width for one MLP
    assert costs.weight_bytes(toy) == 2.0 * (2 * (12288 + 3 * 64 * 512) + 8192)
    assert ctx["model_costs"].kv_bytes_per_token(toy) == 2 * 2 * 2 * 16 * 2
    # two attention kernels a layer: 8 events are 2 passes of 2 layers
    ctx["trace"] = {"busy_s": 1e-6, "op_counts": {"_selector_attention.1": 4,
                                                    "_attention.2": 4, "_fusion.3": 9}}
    assert ctx["model_costs"].forward_passes(ctx["trace"]["op_counts"], toy) == 2.0
    assert _reader("step.weight_stream_pct.py").read(ctx) == pytest.approx(
        100.0 * 2 * own / (1e-6 * 819e9))


@pytest.mark.parametrize("name,nbytes", (("mixtral-8x7b", 11.87e9), ("mistral-7b", 7.24e9)))
def test_weight_stream_reads_as_it_did_for_the_mistral_family(name, nbytes):
    """The parent's arithmetic, by hand: weight bytes x attention events /
    layers / (busy seconds x peak bytes a second)."""
    cfg = server.load_config(name, False)
    counts = {"_packed_ragged_attention.10___bf16_1024_32_128_": 3 * cfg["num_hidden_layers"],
              "_paged_decode_attention_v2.11___bf16_32_32_128_": 5 * cfg["num_hidden_layers"],
              "_fusion.505___bf16_8_32_14336_": 77}
    ctx = run.layer_context(cfg, {}, {}, peaks=PEAK, trace={"busy_s": 0.25, "op_counts": counts})
    assert ctx["model_costs"] is costs
    assert costs.forward_passes(counts, cfg) == 8.0
    got = _reader("step.weight_stream_pct.py").read(ctx)
    assert got == 100.0 * costs.weight_bytes(cfg) * 8.0 / (0.25 * 819e9)
    assert got == pytest.approx(100.0 * nbytes * 8 / (0.25 * 819e9), rel=0.01)
    assert _reader("step.weight_stream_pct.py").read(
        dict(ctx, trace={"busy_s": 0.25, "op_counts": {"_fusion.1": 3}})) is None
