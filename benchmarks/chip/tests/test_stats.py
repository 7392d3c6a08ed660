import numpy as np
import pytest

import stats


@pytest.mark.parametrize("q", [0, 5, 50, 95, 100])
def test_percentile_matches_numpy_linear(q):
    xs = list(np.random.default_rng(3).exponential(size=37))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 95) is None


def test_window_is_half_open():
    assert stats.in_window(1.0, 1.0, 2.0)
    assert not stats.in_window(2.0, 1.0, 2.0)
    assert stats.count_in_window([0.5, 1.0, 1.5, 2.0], 1.0, 2.0) == 2


def test_gaps_count_by_their_end():
    times = [0.0, 0.9, 1.2, 2.5, 3.1]
    # gaps end at 0.9, 1.2, 2.5, 3.1; those ending in [1, 3) are 0.3, 1.3
    assert stats.gaps_ending_in(times, 1.0, 3.0) == pytest.approx([0.3, 1.3])


class _Req:
    def __init__(self, due, first, times=(), admitted=None):
        self.due, self.first, self.times = due, first, list(times)
        self.admitted = admitted


class _Ctx:
    def __init__(self, window, requests):
        self.window = self.span = window
        self.requests = requests


def _reader(name):
    import cost
    return cost.load_module(cost.HERE / "metrics" / f"{name}.py").read


def test_ttft_counts_requests_drained_after_the_window():
    # due in [0, 10): three requests, one served only after the window
    reqs = [_Req(1.0, 1.5), _Req(2.0, 2.2), _Req(9.0, 14.0),
            _Req(11.0, 11.1)]              # due after the window: left out
    got = _reader("ttft_p95_ms")(_Ctx((0.0, 10.0), reqs))
    assert got == pytest.approx(np.percentile([500, 200, 5000], 95))


def test_output_tokens_and_gaps_in_the_window():
    reqs = [_Req(0, 0.5, [0.5, 1.0, 1.5, 2.5]), _Req(0, 1.8, [1.8, 2.9])]
    ctx = _Ctx((1.0, 2.0), reqs)
    assert _reader("output_tok_s")(ctx) == pytest.approx(3 / 1.0)
    # gaps ending in [1, 2): 0.5 (->1.0) and 0.5 (->1.5)
    assert _reader("itl_p95_ms")(ctx) == pytest.approx(500.0)


def test_queue_wait_median_over_requests_due_in_span():
    reqs = [_Req(1.0, 2.0, admitted=1.25), _Req(2.0, 3.0, admitted=2.75),
            _Req(3.0, 4.0, admitted=3.5), _Req(12.0, 13.0, admitted=12.1)]
    assert _reader("sched.queue_wait_p50_ms")(_Ctx((0.0, 10.0), reqs)) == \
        pytest.approx(500.0)
