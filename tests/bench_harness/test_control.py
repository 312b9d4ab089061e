"""The comparison that decides ``correct`` has to fail its control: the
program serving through its own int8 path.  Here at the rehearsal size on
the CPU (float32 weights, so the sound run agrees to rounding); on the chip
at the cell's own size the readings are in PERF.md."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rehearse(cell, *extra):
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell, "--seed", "5",
         "--seconds", "2", "--trace", "0", "--rehearse", *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    logits = next(json.loads(l[5:])["logits"] for l in lines if l.startswith('info {"logits"'))
    checks = {c["check"]: c for c in (json.loads(l[6:]) for l in lines if l.startswith("check "))}
    return logits, checks, json.loads(lines[-1])


@pytest.mark.slow
@pytest.mark.parametrize("cell,control,stated", (
    ("mixtral-8x7b.batch-closed", "int8_weights", "weights_dtype"),
    ("mistral-7b.docqa-open", "int8_weights", "weights_dtype"),
    ("mistral-7b.docqa-open", "int8_kv", "kv_cache_dtype"),
))
def test_control_is_far_from_the_reference(cell, control, stated):
    sound, _checks, line = _rehearse(cell)
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert sound["logprob_err"] < 1e-4
    broken, checks, _ = _rehearse(cell, "--control", control)
    assert broken["logprob_err"] > 30 * sound["logprob_err"]
    # and the type the engine says it serves in is no longer the stated one
    assert checks[stated]["ok"] is False and "int8" in str(checks[stated]["value"])
