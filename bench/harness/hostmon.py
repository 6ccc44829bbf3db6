"""Did the load generator itself stall during a window?

The generator, the router and the engine share the cores of a one-chip
machine with whatever else that host runs.  When the generator's event loop
is not scheduled for a while, requests go out late and first tokens are read
late, and a request's clock starts when it was *due*: the stall reads as a
slow server (PERF.md, PR 33: 0.3 to 1.9 s, in four runs of seven on one
machine and none of seven on the next).  A task on that loop wakes every
50 ms and keeps how late it woke; ``run.py`` reports the latest wake-ups of
the window (``"say": "generator"``, ``run.json: generator_lag_ms``) so that a
stalled run can be told from a slow server.  Nothing acts on it: the window
stands as measured.
"""

from __future__ import annotations

import asyncio
import time
from typing import List, Tuple

PERIOD_S = 0.05


class LoopLag:
    def __init__(self) -> None:
        self.lags: List[Tuple[float, float]] = []   # (monotonic, seconds late)

    async def run(self) -> None:
        """Until cancelled."""
        due = time.monotonic()
        while True:
            await asyncio.sleep(max(0.0, due - time.monotonic()))
            now = time.monotonic()
            self.lags.append((now, now - due))
            due = max(due + PERIOD_S, now)

    def worst(self, t0: float, t1: float) -> List[List[float]]:
        """The five latest wake-ups inside [t0, t1), in time order:
        [seconds into the window, milliseconds late]."""
        inside = sorted((x for x in self.lags if t0 <= x[0] < t1),
                        key=lambda x: -x[1])[:5]
        return [[round(t - t0, 2), round(1e3 * late, 1)]
                for t, late in sorted(inside)]
