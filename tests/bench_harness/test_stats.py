"""Percentiles, window edges and tails on hand-made event lists."""
import pytest

from benchmark import stats


def test_percentile_interpolates():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([10, 20], 90) == pytest.approx(19)
    assert stats.percentile([7], 90) == 7
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_tokens_count_by_arrival_inside_the_window():
    events = [(0.9, 5), (1.0, 3), (1.5, 8), (2.0, 4), (2.5, 1)]
    # [1.0, 2.0): the chunk at the start edge counts, the one at the end does not
    assert stats.tokens_in_window(events, 1.0, 2.0) == 11


def test_tpot_divides_by_tokens_less_one():
    assert stats.tpot_ms([(1.0, 1), (1.1, 1), (1.3, 1)]) == pytest.approx(150.0)
    # a block of 8 fused tokens arrives as one chunk
    assert stats.tpot_ms([(1.0, 1), (1.8, 8)]) == pytest.approx(100.0)
    assert stats.tpot_ms([(1.0, 1)]) is None


def _req(due, chunks, finished, ok=True):
    return {"due": due, "sent": due, "chunks": chunks, "finished": finished, "ok": ok}


def test_end_to_end_on_a_hand_made_run():
    results = [
        # finished before the window: its tokens and tpot do not count
        _req(0.0, [(0.5, 1), (0.9, 1)], 0.9),
        # straddles the start: only tokens inside count; finished inside
        _req(0.5, [(0.9, 1), (1.1, 1), (1.3, 1)], 1.3),
        # inside
        _req(1.0, [(1.2, 1), (1.4, 4), (1.6, 4)], 1.6),
        # due inside, cancelled at the end of the run after two tokens
        _req(2.5, [(2.8, 1), (2.9, 1)], None, ok=None),
        # due inside, failed: counts as the largest time to first token
        _req(2.0, [], None, ok=False),
    ]
    out = stats.end_to_end(results, 1.0, 3.0, exit_time=3.5)
    assert out["out_tok_s"] == pytest.approx((2 + 9 + 2) / 2.0)
    # tpot: request 2 -> 200 ms, request 3 -> 50 ms; p90 by interpolation
    assert out["tpot_p90_ms"] == pytest.approx(50 + 0.9 * 150)
    # ttft of requests due in the window: 200, 300, and the failed one = 2500
    assert out["ttft_p90_ms"] == pytest.approx(stats.percentile([200, 300, 2500], 90))


def test_counters_delta_over_a_window():
    before = 'a_total{kind="x"} 3\na_total{kind="y"} 1\n# HELP b\nb_sum 0.5\nb_count 2\n'
    after = 'a_total{kind="x"} 10\na_total{kind="y"} 4\nb_sum 2.5\nb_count 6\nc_total 9\n'
    c = stats.Counters(before, after)
    assert c.delta("a_total") == 10
    assert c.delta("a_total", kind="x") == 7
    assert c.delta("b_sum") / c.delta("b_count") == pytest.approx(0.5)
    assert c.delta("c_total") == 9 and c.has("c_total") and not c.has("zzz")


# -- the number `correct` holds against the tolerance ------------------------------


def test_position_error_is_the_rms_over_a_position_s_tokens():
    got = [[-1.0, -2.0], [-0.5, -3.0]]
    ref = [[-1.0, -2.0], [-0.8, -3.4]]
    errs = stats.position_errors(got, ref)
    assert errs[0] == 0.0
    assert errs[1] == pytest.approx(((0.3 ** 2 + 0.4 ** 2) / 2) ** 0.5)


@pytest.mark.parametrize("flipped,want", ((0, 0.0109), (5, 0.0109), (15, 0.9)))
def test_compared_error_keeps_the_tail(flipped, want):
    # 100 positions at 0.010-0.0109; `flipped` of them read 0.9 (a router
    # that chose another expert): up to a tenth of the positions do not move
    # the 90th percentile, more than a tenth do
    errs = [0.010 + 0.00001 * i for i in range(100)]
    for i in range(flipped):
        errs[i * 6] = 0.9
    assert stats.compared_error(errs) == pytest.approx(want, rel=0.02)


def test_compared_error_moves_with_every_position():
    # a lower precision moves all positions, and the number with them
    errs = [0.010 + 0.00001 * i for i in range(100)]
    assert stats.compared_error([2 * e for e in errs]) == pytest.approx(
        2 * stats.compared_error(errs))
