"""Window arithmetic on a fake clock."""
import pytest

import window


class FakeClock:
    """Advances only when told; ``stall_s`` passes, once, just before its
    ``stall_at``-th reading (the window reads it three times a round:
    dispatch, return, completion)."""

    def __init__(self, stall_at=None, stall_s=0.0):
        self.t = 100.0
        self.reads = 0
        self.stall_at, self.stall_s = stall_at, stall_s

    def __call__(self):
        if self.reads == self.stall_at:
            self.t += self.stall_s
        self.reads += 1
        return self.t


def drive(seconds, round_s, stall_before=None, stall_s=0.0, dispatch_s=0.01):
    """A window of rounds of ``round_s``; with ``stall_before`` = i, a host
    pause of ``stall_s`` between round i - 1's completion and round i's
    dispatch."""
    stall_at = None if stall_before is None else 3 * stall_before
    clock = FakeClock(stall_at, stall_s)

    def round_fn(i, state):
        clock.t += dispatch_s
        return state + 1

    def wait(state):
        clock.t += round_s - dispatch_s

    return window.run_window(round_fn, wait, 0, seconds, steps_per_round=2,
                             clock=clock)


def test_rate_is_all_steps_over_the_whole_window():
    state, w = drive(10.0, 0.5)
    assert state == w.rounds == 20
    assert w.seconds == pytest.approx(10.0)
    assert w.steps_per_s() == pytest.approx(4.0)
    assert window.percentile(w.round_s(), 90) == pytest.approx(0.5)


def test_one_stall_lowers_the_rate_by_its_share_and_not_the_p90():
    _, clean = drive(10.0, 0.5)
    _, stalled = drive(10.0, 0.5, stall_before=4, stall_s=0.5)
    # the stall takes the place of one round: 19 rounds in 10 s
    assert stalled.rounds == 19
    assert stalled.seconds == pytest.approx(10.0)
    share = 0.5 / stalled.seconds
    assert stalled.steps_per_s() == pytest.approx(
        clean.steps_per_s() * (1 - share))
    assert window.percentile(stalled.round_s(), 90) == pytest.approx(
        window.percentile(clean.round_s(), 90))
    assert max(stalled.between_s()) == pytest.approx(0.5)


def test_a_round_in_flight_at_the_end_counts_with_its_steps_and_time():
    _, w = drive(10.2, 0.5)     # the 21st round ends at 10.5 s
    assert w.rounds == 21
    assert w.seconds == pytest.approx(10.5)
    assert w.steps_per_s() == pytest.approx(42 / 10.5)


def test_dispatch_and_between_times():
    _, w = drive(2.0, 0.5, dispatch_s=0.02)
    assert w.dispatch_s() == pytest.approx([0.02] * w.rounds)
    assert w.between_s() == pytest.approx([0.0] * (w.rounds - 1))


def test_percentile_interpolates_like_numpy():
    np = pytest.importorskip("numpy")
    xs = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.0]
    for q in (0, 10, 50, 90, 100):
        assert window.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
