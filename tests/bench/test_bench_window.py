"""Window arithmetic: a rate over whole calls."""
import pytest

from bench import window
from bench.window import Call


def test_rate_is_all_work_over_first_start_to_last_end():
    calls = [Call(10.0, 16.0, 6), Call(16.5, 22.0, 6), Call(22.0, 30.0, 8)]
    assert window.rate(calls) == pytest.approx(20 / 20.0)


def test_rate_of_one_call_that_outlasts_the_window():
    assert window.rate([Call(0.0, 4.0, 100)]) == pytest.approx(25.0)


def test_rate_counts_the_gaps_between_calls():
    """Host time between calls is the caller's, and in the window."""
    calls = [Call(0.0, 1.0, 10), Call(3.0, 4.0, 10)]
    assert window.rate(calls) == pytest.approx(5.0)


def test_rate_without_calls_is_an_error():
    with pytest.raises(ValueError):
        window.rate([])
