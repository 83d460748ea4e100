import math

import pytest

from benchmark import stats


def _req(due, times, counted=True, failed=False, submit=None):
    return {"due": due, "submit": due if submit is None else submit,
            "token_times": times, "counted": counted, "failed": failed}


def test_percentile_by_hand():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 100) == 5.0
    assert stats.percentile(xs, 90) == pytest.approx(4.6)
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_ttft_counts_from_due_and_a_failure_is_a_miss():
    reqs = [_req(10.0, [10.5, 10.6], submit=10.2),
            _req(11.0, [11.25]),
            _req(12.0, [], failed=True),          # never answered
            _req(13.0, [13.1, 13.2], failed=True),  # answered, then failed
            _req(1.0, [1.5], counted=False)]       # warm-up: not counted
    got = stats.ttfts(reqs)
    assert got[:2] == [0.5, 0.25]
    assert got[2:] == [math.inf, math.inf]
    assert len(got) == 4
    # two of four are misses: the 90th percentile is a miss, and prints
    # as a number beyond every other
    assert stats.finite(stats.percentile(got, 90)) == stats.MISS_S
    assert stats.percentile(got, 25) == pytest.approx(0.4375)
    assert stats.lateness(reqs) == pytest.approx([0.2, 0, 0, 0])


def test_token_gaps_pool_over_requests_inside_the_window():
    reqs = [_req(0.0, [1.0, 1.5, 2.5, 4.0]),
            _req(0.0, [2.0, 2.0, 2.25]),          # two tokens in one look
            _req(0.0, [9.0, 9.5], counted=False)]
    # window (1.2, 3.0]: gaps ending at 1.5, 2.5, 2.0, 2.25
    assert sorted(stats.token_gaps(reqs, 1.2, 3.0)) \
        == [0.0, 0.25, 0.5, 1.0]
    assert stats.tokens_in_window(reqs, 1.2, 3.0) == 5
    # warm-up requests' tokens count too: a rate is over all the work
    assert stats.tokens_in_window(reqs, 8.0, 10.0) == 2


def test_thirds_and_admissions_by_hand():
    # window (0, 9]: thirds (0, 3], (3, 6], (6, 9]
    reqs = [_req(0.0, [1.0, 2.0, 3.0, 7.0]),     # 3 tokens, 0, 1
            _req(0.0, [2.0, 5.0, 7.0, 8.0]),     # 1, 1, 2
            _req(0.0, [9.5], counted=False)]     # outside
    assert stats.rate_by_thirds(reqs, 0.0, 9.0) \
        == pytest.approx([4 / 3, 1 / 3, 3 / 3])
    # over the whole window the thirds average to the window's rate
    assert sum(stats.rate_by_thirds(reqs, 0.0, 9.0)) / 3 \
        == pytest.approx(stats.tokens_in_window(reqs, 0.0, 9.0) / 9.0)
    # admissions show at 1.0, 2.0 and 9.5. Six gaps end in the window;
    # only (1, 2] of the first request holds another's admission (at
    # 2.0): a request's own admission opens its first gap, it is not
    # inside it
    assert stats.admission_times(reqs) == [1.0, 2.0, 9.5]
    assert stats.gaps_with_admission_share(reqs, 0.0, 9.0) \
        == pytest.approx(100.0 / 6)
    assert stats.gaps_with_admission_share(reqs, 20.0, 30.0) is None
    steps = [{"t": 1.0, "dur_s": 0.4}, {"t": 2.0, "dur_s": 0.6},
             {"t": 3.0, "dur_s": 0.1}, {"t": 5.0, "dur_s": 0.3}]
    assert stats.step_durations(steps, reqs) == {
        "admission_steps": 2, "admission_mean_s": pytest.approx(0.5),
        "decode_only_steps": 2, "decode_only_mean_s": pytest.approx(0.2)}
    assert stats.step_durations([], reqs)["decode_only_mean_s"] is None
