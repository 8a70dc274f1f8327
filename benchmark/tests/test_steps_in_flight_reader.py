"""``steps_in_flight.serve``: the mean of a count the engine observes at
every issue, handed over like the timeline's intervals; a program without
it (the parent of PR 42) reads None and does not raise."""

import pytest

from benchmark import run as bench_run


def _read(phases):
    return bench_run.read_metric("steps_in_flight.serve",
                                 {"counters": {"phases": phases}})


def test_the_mean_of_the_window():
    # 90 issues that found one step ahead of them and 10 that found two
    ph = {"steps_in_flight": {"total_s": 90 * 2 + 10 * 3.0, "count": 100},
          "dispatch": {"total_s": 0.05, "count": 100}}
    assert _read(ph) == pytest.approx(2.1)


@pytest.mark.parametrize("phases", [
    None, {}, {"dispatch": {"total_s": 0.05, "count": 100}},
    {"steps_in_flight": {"total_s": 0.0, "count": 0}}])
def test_a_program_without_the_count_reads_none(phases):
    assert _read(phases) is None
