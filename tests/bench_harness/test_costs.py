"""Operations, bytes and roofline bounds against hand-worked shapes."""
import json
import os

import pytest

from benchmark import costs

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg(name):
    with open(os.path.join(costs.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_matmul_counts():
    flops, nbytes = costs.matmul(32, 4096, 14336)
    assert flops == 2 * 32 * 4096 * 14336
    assert nbytes == 2 * (32 * 4096 + 4096 * 14336 + 32 * 14336)


@pytest.mark.parametrize("c,bound", ((32, "memory"), (1024, "compute")))
def test_expert_matmul_bound_by_batch(c, bound):
    flops, nbytes = costs.expert_matmul(8, c, 4096, 14336)
    assert flops == 8 * 2 * c * 4096 * 14336
    t, which = costs.roofline_seconds(flops, nbytes, V5E)
    assert which == bound
    if bound == "memory":  # 8 x 117 MB of weights at 819 GB/s: 1.15 ms
        assert t == pytest.approx(8 * 4096 * 14336 * 2 / 819e9, rel=0.02)
    else:  # 962 GFLOP at 197 TFLOP/s: 4.9 ms
        assert t == pytest.approx(8 * 2 * 1024 * 4096 * 14336 / 197e12)


def test_weight_bytes_of_the_two_cuts():
    # mixtral, 4 layers: 4 x (2.818 GB experts + 84 MB attention + router) + 262 MB head
    assert costs.weight_bytes(_cfg("mixtral-8x7b")) == pytest.approx(11.87e9, rel=0.01)
    # mistral, 16 layers of 436 MB + 262 MB head
    assert costs.weight_bytes(_cfg("mistral-7b")) == pytest.approx(7.24e9, rel=0.01)


def test_kv_bytes_per_token():
    assert costs.kv_bytes_per_token(_cfg("mixtral-8x7b")) == 16384
    assert costs.kv_bytes_per_token(_cfg("mistral-7b")) == 65536


def test_unknown_device_kind_is_an_error():
    assert costs.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        costs.peaks("TPU v9")
