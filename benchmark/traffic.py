"""Traffic generator: one general generator, driven by a traffic file.

A traffic file (``benchmark/traffic/<name>.json``) holds the parameters of
the length, gap and sharing distributions and the block size.  Requests are
built in blocks.  Every block holds one request at the midpoint of each of
``n`` equal-probability strata of every distribution, so every block has the
same multiset of lengths and gaps and the same total work.  The order within
a block is drawn from the mix itself, so every seed replays one schedule;
``--seed`` draws the token ids (and, in the server, the weights).  It never
changes a length, a gap, a sharing pattern, an order or a count.

Pure Python and numpy: the load generator's process never imports JAX.
"""

from __future__ import annotations

import json
import math
import os
import random
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FIRST_TOKEN_ID = 3  # ids below are the tokenizer's special tokens


def load(name: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


# -- quantiles ---------------------------------------------------------------


def _gamma_cdf(x: float, k: float) -> float:
    """Regularised lower incomplete gamma P(k, x), by its series."""
    if x <= 0:
        return 0.0
    term = total = 1.0 / k
    n = 1
    while abs(term) > 1e-15 * abs(total) and n < 10000:
        term *= x / (k + n)
        total += term
        n += 1
    return total * math.exp(-x + k * math.log(x) - math.lgamma(k))


def _gamma_quantile(p: float, k: float) -> float:
    lo, hi = 0.0, 1.0
    while _gamma_cdf(hi, k) < p:
        hi *= 2.0
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if _gamma_cdf(mid, k) < p:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def quantile(spec: Dict[str, Any], p: float) -> float:
    """The ``p``-quantile of the distribution a traffic file describes,
    clipped to its ``min``/``max``."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(p))
    elif dist == "uniform":
        x = spec["min"] + p * (spec["max"] - spec["min"])
    elif dist == "gamma":  # unit mean; the caller scales
        x = _gamma_quantile(p, spec["shape"]) / spec["shape"]
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "min" in spec:
        x = max(x, spec["min"])
    if "max" in spec:
        x = min(x, spec["max"])
    return x


def strata(spec: Dict[str, Any], n: int) -> List[float]:
    """One value at the midpoint of each of ``n`` equal-probability strata."""
    return [quantile(spec, (i + 0.5) / n) for i in range(n)]


def _ints(values: List[float]) -> List[int]:
    return [int(round(v)) for v in values]


# -- order: from the mix; token ids: from the seed ------------------------------

ORDER = "mix"  # what keys every shuffle: the same for every seed


def _rng(seed: Any, block: int, salt: int) -> random.Random:
    return random.Random(f"{seed}/{block}/{salt}")


def _shuffled(values: List[Any], seed: int, block: int, salt: int) -> List[Any]:
    out = list(values)
    _rng(seed, block, salt).shuffle(out)
    return out


def _token_ids(seed: int, block: int, salt: int, n: int, vocab: int) -> List[int]:
    rs = np.random.RandomState(_rng(seed, block, salt).getrandbits(32))
    return rs.randint(FIRST_TOKEN_ID, vocab, size=(n,)).tolist()


# -- closed loop ---------------------------------------------------------------


def closed_block(
    spec: Dict[str, Any], seed: int, block: int, vocab: int
) -> List[Dict[str, Any]]:
    """Block ``block`` of a closed-loop mix: one request per joint stratum
    of (prompt, output) length, in the mix's own order."""
    n = spec["block"]
    prompts = _ints(strata(spec["prompt"], n))
    outputs = _ints(strata(spec["output"], n))
    stride = spec.get("pair_stride", 1)
    if math.gcd(stride, n) != 1:
        raise ValueError("pair_stride must be coprime to the block size")
    pairs = [(prompts[i], outputs[(i * stride + stride // 2) % n]) for i in range(n)]
    reqs = []
    for j, (p, o) in enumerate(_shuffled(pairs, ORDER, block, 0)):
        reqs.append(
            {
                "id": f"b{block}.{j}",
                "block": block,
                "prompt": _token_ids(seed, block, 1000 + j, p, vocab),
                "max_tokens": o,
            }
        )
    return reqs


# -- open loop -----------------------------------------------------------------


def open_block(
    spec: Dict[str, Any], seed: int, block: int, vocab: int
) -> List[Dict[str, Any]]:
    """Block ``block`` of an open-loop mix of documents asked several times.
    ``due`` is relative to the block's start; the block lasts exactly
    ``block_documents * asks / rate`` seconds."""
    d = spec["block_documents"]
    asks = spec["asks_per_document"]
    doc_gap = asks / spec["rate_per_s"]  # mean seconds between documents
    gaps = strata(spec["gap"], d)
    scale = d * doc_gap / sum(gaps)  # midpoints cut the tail: renormalise
    gaps = _shuffled([g * scale for g in gaps], ORDER, block, 1)

    # which question, answer and delay go with which document
    def fixed(values, salt):
        return _shuffled(values, ORDER, 0, salt)

    doc_lens = _ints(strata(spec["document"], d))
    delays = fixed(strata(spec["ask_delay_s"], d * (asks - 1)), 3)
    questions = fixed(_ints(strata(spec["question"], d * asks)), 4)
    outputs = fixed(_ints(strata(spec["output"], d * asks)), 5)
    order = _shuffled(list(range(d)), ORDER, block, 2)
    reqs = []
    t = 0.0
    for slot, i in enumerate(order):
        t += gaps[slot]
        doc_tokens = _token_ids(seed, block, 2000 + i, doc_lens[i], vocab)
        due = t
        for a in range(asks):
            if a:
                due += delays[i * (asks - 1) + a - 1]
            k = i * asks + a
            q = _token_ids(seed, block, 3000 + k, questions[k], vocab)
            reqs.append(
                {
                    "id": f"b{block}.d{i}.a{a}",
                    "block": block,
                    "doc_tokens": doc_lens[i],
                    "due": due,
                    "prompt": doc_tokens + q,
                    "max_tokens": outputs[k],
                }
            )
    return reqs


def open_schedule(
    spec: Dict[str, Any], seed: int, vocab: int, horizon_s: float
) -> List[Dict[str, Any]]:
    """Every request due before ``horizon_s``, in order of its due time."""
    d = spec["block_documents"]
    block_s = d * spec["asks_per_document"] / spec["rate_per_s"]
    reqs: List[Dict[str, Any]] = []
    block = 0
    while block * block_s < horizon_s:
        for r in open_block(spec, seed, block, vocab):
            r["due"] += block * block_s
            reqs.append(r)
        block += 1
    reqs.sort(key=lambda r: r["due"])
    # the first seconds lack the follow-up asks of documents before time 0:
    # the warm phase is there to cover that ramp
    return [r for r in reqs if r["due"] < horizon_s]


def mean_gap_s(spec: Dict[str, Any]) -> float:
    return 1.0 / spec["rate_per_s"]
