"""What the harness promises of an open cell's rate: it is at most 0.75 of a
knee that the traffic file states, a cell that judges ``ttft_p90_ms`` has a
hundred requests due in its window (or says how many it has), and the knee
is a function of a sweep's printed lines, not of who reads them."""
import json
import os
import re

import pytest

from benchmark import stats, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
OPEN_MIXES = sorted(
    name[:-len(".json")] for name in os.listdir(os.path.join(ROOT, "benchmark", "traffic"))
    if name.endswith(".json") and traffic.load(name[:-len(".json")])["loop"] == "open")
TTFT_CELLS = next(m for m in BENCH["end_to_end"] if m["name"] == "ttft_p90_ms")["workloads"]
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def _due_in_window(spec):
    warm = float(spec["warm"]["seconds"])
    sched = traffic.open_schedule(spec, 1, 1000, warm + BENCH["run_seconds"])
    return sum(1 for r in sched if r["due"] >= warm)


@pytest.mark.parametrize("mix", OPEN_MIXES)
def test_an_open_mix_runs_at_no_more_than_three_quarters_of_its_knee(mix):
    spec = traffic.load(mix)
    assert spec["rate_per_s"] <= 0.75 * spec["knee_per_s"] + 1e-9
    # and no lower than the rule puts it, or at 0.6 under the exception
    assert spec["rate_per_s"] in (stats.pitch(spec["knee_per_s"]),
                                  stats.pitch(spec["knee_per_s"], 0.6))


@pytest.mark.parametrize("cell", TTFT_CELLS)
def test_a_judged_tail_has_a_hundred_requests_or_says_how_many(cell):
    spec = traffic.load(CELLS[cell]["traffic"])
    assert spec["loop"] == "open"
    due = _due_in_window(spec)
    if spec["rate_per_s"] * BENCH["run_seconds"] < 100 or due < 100:
        stated = re.search(r"(\d+) due", CELLS[cell]["why"])
        assert stated and int(stated.group(1)) == due
    # every seed replays one schedule: the count is the same for all
    other = sum(1 for r in traffic.open_schedule(spec, 3_000_000_001, 1000, 60.0)
                if r["due"] >= 10.0)
    assert other == due


@pytest.mark.parametrize("cell", sorted(
    w["name"] for w in BENCH["workloads"] if traffic.load(w["traffic"])["loop"] == "open"))
def test_a_cells_why_states_the_rate_it_runs_at(cell):
    spec = traffic.load(CELLS[cell]["traffic"])
    assert f"{spec['rate_per_s']:g} req/s" in CELLS[cell]["why"]


def _line(rate, out_tok_s, first, second, no_first_token=0, failed=0):
    return {"rate_per_s": rate, "out_tok_s": out_tok_s, "failed": failed,
            "no_first_token": no_first_token, "ttft_p50_first_half_ms": first,
            "ttft_p50_second_half_ms": second}


# PR 36's sweep of mixedlen-open as PERF.md section 5 recorded it: out_tok_s
# at every rate, the halves' medians at 4 and 5, 91 requests without a first
# token at 8.  The halves at 2 and 3 were not written down (both held: set
# equal here); at 6 and 8 the p90 stands for a second half that grew.
PR36 = [
    _line(2, 160.0, 1000.0, 1000.0), _line(3, 243.3, 1000.0, 1000.0),
    _line(4, 321.0, 1020.0, 1066.0), _line(5, 354.1, 3346.0, 4542.0),
    _line(6, 339.0, 5000.0, 18945.0), _line(8, 323.4, 9000.0, 80034.0, no_first_token=91),
]


# PR 44's sweeps on PR 42's tree (PERF.md section 4 has every line whole):
# rate, out_tok_s, the halves' medians, requests left without a first token
PR44 = {
    "docqa-open": (5.0, [
        _line(4, 322.8, 164.7, 177.6), _line(5, 397.0, 286.9, 540.3),
        _line(6, 388.8, 2351.6, 8999.2), _line(7, 335.8, 7808.3, 24067.6, no_first_token=16),
        _line(8, 311.7, 14362.4, 34635.1, no_first_token=96),
        _line(10, 295.4, 25246.6, 49031.2, no_first_token=221)]),
    # lightly loaded, either half may wait three times as long as the other
    "longdoc-open": (3.5, [
        _line(1.5, 124.2, 68.9, 248.7), _line(2, 151.7, 493.6, 106.8),
        _line(2.5, 196.8, 682.8, 634.8), _line(3, 233.6, 1073.7, 1061.7),
        _line(3.5, 260.3, 2310.8, 2471.9), _line(4, 271.3, 3922.4, 6954.1)]),
    "mixedlen-open": (4.0, [
        _line(4, 320.0, 313.8, 524.4), _line(5, 378.8, 884.6, 2233.1),
        _line(6, 409.8, 3220.8, 7011.3), _line(7, 403.8, 4807.9, 15693.6),
        _line(8, 368.1, 8855.3, 25886.4, no_first_token=22),
        _line(10, 353.8, 18637.4, 38794.7, no_first_token=157)]),
}


@pytest.mark.parametrize("mix", sorted(PR44))
def test_a_traffic_file_states_the_knee_its_sweep_gave(mix):
    knee, lines = PR44[mix]
    assert stats.knee(lines) == knee == traffic.load(mix)["knee_per_s"]


def test_the_knee_of_pr_36s_recorded_sweep_is_4():
    assert stats.knee(PR36) == 4
    assert stats.knee(reversed(PR36)) == 4  # lines in any order
    assert stats.pitch(4.0) == 3.0


@pytest.mark.parametrize("lines, want", [
    # tokens per second stop rising before the halves part: the knee is
    # where they last rose
    ([_line(1, 100.0, 50.0, 50.0), _line(2, 200.0, 50.0, 55.0),
      _line(3, 199.0, 60.0, 62.0)], 2),
    # a rate that failed a request does not hold, whatever its halves say,
    # and nothing above it counts
    ([_line(1, 100.0, 50.0, 50.0), _line(2, 200.0, 50.0, 50.0, failed=1),
      _line(3, 300.0, 50.0, 50.0)], 1),
    # a request left without a first token
    ([_line(1, 100.0, 50.0, 50.0), _line(2, 200.0, 50.0, 50.0, no_first_token=2)], 1),
    # the lowest rate swept did not hold: no knee, sweep lower
    ([_line(4, 100.0, 50.0, 900.0), _line(5, 120.0, 50.0, 1900.0)], None),
    # a half that finished nothing reads None and does not hold
    ([_line(1, 100.0, 50.0, 50.0), _line(2, 150.0, 50.0, None)], 1),
    # the slack: a second half a quarter longer still holds, more does not
    ([_line(1, 100.0, 4000.0, 5000.0), _line(2, 200.0, 4000.0, 5001.0)], 1),
    # the floor: half a second between short waits is the schedule's doing
    ([_line(1, 100.0, 100.0, 600.0), _line(2, 200.0, 100.0, 601.0)], 1),
], ids=["tokens_stop_rising", "failed_request", "no_first_token", "lowest_did_not_hold",
        "empty_half", "slack", "floor"])
def test_the_knee_rule(lines, want):
    assert stats.knee(lines) == want


@pytest.mark.parametrize("knee, share, want", [
    (6.0, 0.75, 4.5), (3.0, 0.75, 2.25), (7.0, 0.75, 5.25), (9.0, 0.75, 6.75),
    (2.5, 0.75, 1.85), (5.5, 0.75, 4.1), (3.5, 0.6, 2.1), (1.2, 0.75, 0.9),
])
def test_a_cell_is_pitched_at_a_share_of_its_knee_rounded_down(knee, share, want):
    assert stats.pitch(knee, share) == want
