"""Window arithmetic: censored TTFT, tokens of unfinished requests, gaps."""
import pytest

from bench.lib import window

OPEN, CLOSE = 10.0, 20.0
REQS = [
    # due before the window: not in TTFT, its tokens inside count
    {"due": 9.0, "submit": 9.0, "token_s": [9.5, 10.5, 11.0],
     "finished": True},
    # due inside, served
    {"due": 12.0, "submit": 12.25, "token_s": [12.5, 13.0, 13.75],
     "finished": True},
    # due inside, unfinished at the close: tokens up to the close count
    {"due": 15.0, "submit": 15.5, "token_s": [16.0, 19.0, 21.0],
     "finished": False},
    # due inside, never got a first token: censored at the close
    {"due": 18.0, "submit": 18.0, "token_s": [], "finished": False},
    # due inside, never submitted
    {"due": 19.5, "submit": None, "token_s": [], "finished": False},
]


def test_censored_ttft():
    assert window.ttfts(REQS, OPEN, CLOSE) == [0.5, 1.0, 2.0, 0.5]


def test_tokens_of_unfinished_requests_count():
    assert window.tokens_in_window(REQS, OPEN, CLOSE) == 2 + 3 + 2


def test_gaps_inside_the_window():
    assert sorted(window.itl_gaps(REQS, OPEN, CLOSE)) == pytest.approx(
        [0.5, 0.5, 0.75, 3.0])


def test_generator_lag():
    assert window.submit_lags(REQS, OPEN, CLOSE) == [0.25, 0.5, 0.0]


def test_percentile():
    assert window.percentile([], 95) is None
    assert window.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 50) == 3.0
    assert window.percentile(list(range(101)), 95) == pytest.approx(95.0)
