"""What a run hands the metric readers (``bench/metrics/<name>.py``)."""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional


@dataclass
class RunData:
    model: dict  # the configuration file
    mix: dict  # the traffic file
    peaks: dict  # bench/peaks.json row of this device
    closed: bool
    t0: float  # window start, host perf_counter
    t1: float  # window end
    setup_s: float
    flights: list  # client.Flight, every request of the run
    steps: list  # client.StepLog of the window's steps
    stats0: dict  # EngineStats counters at the window's start
    stats1: dict  # ... and at its end
    trace: Optional[object] = None  # devtrace.Reduced (traced runs)
    notes: dict = field(default_factory=dict)  # readers add plain facts here

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def delta(self, counter: str) -> float:
        return self.stats1[counter] - self.stats0[counter]

    def due_in_window(self) -> list:
        """Open loop: requests due inside the window; closed: submitted in it."""
        return [f for f in self.flights if self.t0 <= f.due_t < self.t1]


def percentile(values: list[float], p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least p% of the
    sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ranked = sorted(values)
    return ranked[max(math.ceil(p / 100.0 * len(ranked)) - 1, 0)]


def samples_beyond(n: int, p: float) -> int:
    """How many samples of n lie beyond the p-th percentile's rank."""
    return n - math.ceil(p / 100.0 * n)
