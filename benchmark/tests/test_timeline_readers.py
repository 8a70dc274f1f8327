"""The seven readers of the engine's device-queue timeline: turns,
starvation and the split waits, as the serve driver hands them over —
every ``PhaseTimes`` name differenced across the window. Hand-made
contexts; a program without the phases (the parent) reads None in every
one, and a share over the loaded turns does not move with the drain."""

import pytest

from benchmark import run as bench_run

READERS = ("chunk_turn_ms.serve", "admit_stall_ms.serve",
           "admit_stall_share_pct.serve", "device_starved_pct.serve",
           "first_token_queued_ms.serve", "first_token_ride_ms.serve",
           "slot_vacant_ms.serve")


def _read(name, phases):
    return bench_run.read_metric(name, {"counters": {"phases": phases}})


def _row(total_s, count):
    return {"total_s": total_s, "count": count}


def _window(drain_turns=0):
    """40 clean turns of 70 ms and 10 turns of 95 ms that hold an
    admission while requests wait (30 of the clean ones close with a
    request waiting), 0.1 s starved; then ``drain_turns`` more clean
    turns with nobody waiting."""
    clean = 40 + drain_turns
    return {"turn": _row(0.070 * clean + 0.95, clean + 10),
            "turn_clean": _row(0.070 * clean, clean),
            "turn_admit": _row(0.95, 10),
            "turn_loaded": _row(0.070 * 30 + 0.95, 40),
            "starved": _row(0.1, 12),
            "first_token": _row(1.2, 8),
            "first_token_queued": _row(0.4, 8),
            "first_token_ride": _row(0.8, 8),
            "slot_vacant": _row(0.05, 10),
            "dispatch": _row(0.05, clean + 10)}


def test_turns_by_hand():
    ph = _window()
    assert _read("chunk_turn_ms.serve", ph) == pytest.approx(70.0)
    assert _read("admit_stall_ms.serve", ph) == pytest.approx(25.0)
    # 10 x 25 ms of 3.05 s loaded
    assert _read("admit_stall_share_pct.serve", ph) == \
        pytest.approx(100 * 0.25 / 3.05)
    assert _read("device_starved_pct.serve", ph) == \
        pytest.approx(100 * 0.1 / 3.05)


def test_waits_by_hand_and_the_split_sums_to_the_whole():
    ph = _window()
    queued = _read("first_token_queued_ms.serve", ph)
    ride = _read("first_token_ride_ms.serve", ph)
    assert queued == pytest.approx(50.0) and ride == pytest.approx(100.0)
    assert queued + ride == pytest.approx(
        _read("admit_to_first_token_ms.serve", ph))
    assert _read("slot_vacant_ms.serve", ph) == pytest.approx(5.0)


@pytest.mark.parametrize("name", ["admit_stall_share_pct.serve",
                                  "device_starved_pct.serve",
                                  "admit_stall_ms.serve",
                                  "chunk_turn_ms.serve"])
def test_the_drain_moves_no_share(name):
    """A longer drain adds clean turns that are not loaded: of the same
    length, they move neither the shares' sums nor the clean mean."""
    assert _read(name, _window(drain_turns=400)) == \
        pytest.approx(_read(name, _window()))


def test_no_starved_enqueue_reads_zero_not_none():
    ph = _window()
    del ph["starved"]
    assert _read("device_starved_pct.serve", ph) == 0.0


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("phases", [
    None, {},                                           # no snapshot rows
    {"dispatch": {"total_s": 0.1, "count": 10},         # the parent's keys
     "first_token": {"total_s": 1.0, "count": 5},
     "queue_wait": {"total_s": 0.6, "count": 4}},
    {n: {"total_s": 0.0, "count": 0} for n in _window()}])   # idle window
def test_a_program_without_the_phases_reads_none(name, phases):
    assert _read(name, phases) is None


def test_admissions_without_a_clean_turn_read_none():
    """Every turn held an admission (an opening burst): there is no clean
    turn to take off, and the stall is not reckoned from nothing."""
    ph = _window()
    del ph["turn_clean"]
    assert _read("admit_stall_ms.serve", ph) is None
    assert _read("admit_stall_share_pct.serve", ph) is None
    assert _read("chunk_turn_ms.serve", ph) is None
