"""The measured window: a closed loop of one round at a time, and the
arithmetic that turns its timestamps into end-to-end metrics.

Each round is dispatched, then waited for (``block_until_ready``); the
next is dispatched only after. The window opens at the first dispatch and
closes at the completion of the first round that ends at or after
``seconds``: a round in flight when the nominal time runs out is counted
whole, with its steps and its time. So the rate is every step completed
over every second of the window, host pauses between rounds included.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable


@dataclasses.dataclass
class Window:
    """Timestamps (seconds, one clock) of every round of a window."""
    dispatched: list = dataclasses.field(default_factory=list)
    returned: list = dataclasses.field(default_factory=list)
    completed: list = dataclasses.field(default_factory=list)
    steps_per_round: int = 1

    @property
    def rounds(self) -> int:
        return len(self.completed)

    @property
    def seconds(self) -> float:
        """First dispatch to the completion of the last round."""
        return self.completed[-1] - self.dispatched[0]

    def steps_per_s(self) -> float:
        return self.rounds * self.steps_per_round / self.seconds

    def round_s(self) -> list:
        """Each round's wall time: dispatch to completion."""
        return [c - d for d, c in zip(self.dispatched, self.completed)]

    def dispatch_s(self) -> list:
        """Host time each dispatch took to return."""
        return [r - d for d, r in zip(self.dispatched, self.returned)]

    def between_s(self) -> list:
        """Host time from one round's completion to the next dispatch."""
        return [d - c for c, d in zip(self.completed, self.dispatched[1:])]


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default method)."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def run_window(round_fn: Callable, wait: Callable, state, seconds: float,
               steps_per_round: int, clock=time.perf_counter):
    """Drive ``state = round_fn(i, state)`` then ``wait(state)`` until a
    round completes at or after ``seconds`` from the first dispatch.
    Returns (state, Window)."""
    w = Window(steps_per_round=steps_per_round)
    i = 0
    while True:
        t = clock()
        w.dispatched.append(t)
        state = round_fn(i, state)
        w.returned.append(clock())
        wait(state)
        w.completed.append(clock())
        i += 1
        if w.completed[-1] - w.dispatched[0] >= seconds:
            return state, w
