"""The measured window: whole iterations, and the rate over all of them.

An iteration is one call of the timed path over one iteration's sweep
points. The window starts when set-up ends and closes when the first
iteration that ends at or after `seconds` has its results on the host;
every iteration it holds is whole. The rate is all the live work of
those iterations over all of that time.

Live work is each returned cell's `n_ops`: the trace ops of real cells.
Pad ops and the pad cells a fleet replays to fill its cell axis are not
in it (the runner returns no result for a pad cell).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Sequence


@dataclasses.dataclass
class Iteration:
    index: int
    t0: float
    t1: float
    points: Sequence
    results: Dict
    timings: List[dict]

    @property
    def live_ops(self) -> int:
        return sum(int(self.results[p]["n_ops"]) for p in self.points
                   if p in self.results)

    @property
    def missing(self) -> int:
        return sum(1 for p in self.points if p not in self.results)


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    iterations: List[Iteration]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def live_ops(self) -> int:
        return sum(it.live_ops for it in self.iterations)

    @property
    def rate(self) -> float:
        return self.live_ops / self.seconds

    @property
    def attempted(self) -> int:
        return sum(len(it.points) for it in self.iterations)

    @property
    def missing(self) -> int:
        return sum(it.missing for it in self.iterations)


def run(iterate: Callable[[int, list], tuple],
        points_of: Callable[[int], list],
        seconds: float, clock: Callable[[], float] = time.perf_counter
        ) -> Window:
    """Run iterations 0, 1, ... until one ends at or after `seconds`.

    `iterate(i, points)` runs the timed path and returns (results,
    timings) with the results on the host."""
    t0 = clock()
    its: List[Iteration] = []
    while True:
        i = len(its)
        pts = points_of(i)
        ts = clock()
        results, timings = iterate(i, pts)
        te = clock()
        its.append(Iteration(i, ts, te, pts, results, timings))
        if te - t0 >= seconds:
            return Window(t0, te, its)
