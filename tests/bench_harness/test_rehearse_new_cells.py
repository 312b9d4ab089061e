"""``--rehearse`` of the two cells PR 30 adds, on the CPU: the whole command
at tiny size, to its result line.  In the latent cell the served engine
(chunked prefill, decode through the latent cache, the longest document
again from the prefix cache) has to agree with the plain reference to
float32's rounding: 1e-4 is fifty times what it reads (1.4e-6) and a
hundredth of what bfloat16 would."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rehearse(cell, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell, "--seed", str(seed),
         "--seconds", "4", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.splitlines()
    result = json.loads(lines[-1])
    info = [json.loads(l[5:]) for l in lines if l.startswith("info {")]
    checks = {c["check"]: c for c in (json.loads(l[6:]) for l in lines
                                      if l.startswith("check {"))}
    return result, info, checks


@pytest.mark.parametrize("cell,metrics", [
    ("mistral-small-4-119b.longdoc-open", {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}),
    ("mixtral-8x7b.chat-open", {"tpot_p90_ms", "setup_s"}),
])
def test_a_new_cell_rehearses_to_its_result_line(cell, metrics):
    result, info, checks = _rehearse(cell, 3000000201)
    assert result["correct"] is False and result["device"]["platform"] == "cpu"
    # a tail exists only where a request finished inside the short window
    assert "setup_s" in result["metrics"] and set(result["metrics"]) <= metrics
    assert result["failed"] == 0
    assert checks["failed_requests"]["ok"]
    logits = next(i["logits"] for i in info if "logits" in i)
    assert logits["positions"] >= 96
    assert logits["logprob_err"] < 1e-4, logits
    # an untraced run prints the window's counters beside its result (what
    # tells two runs of one tree apart): the cell's own, and numbers only
    counters = next(i["window_counters"] for i in info if "window_counters" in i)
    assert "step.compiles_in_window" in counters
    assert all(isinstance(v, float) for v in counters.values())
    if cell.startswith("mistral-small-4"):
        assert len(logits["prompt_tokens"]) == 3  # the longest again, from the cache
        assert logits["prompt_tokens"][0] == logits["prompt_tokens"][2]
