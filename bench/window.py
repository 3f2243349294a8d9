"""The measured window: calls back to back, whole calls only.

The window opens when the first call starts and closes at the end of the
first call that finishes past ``seconds``, so the work and the time of a
rate are both taken over whole calls.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, List


@dataclasses.dataclass
class CallRecord:
    index: int
    start: float
    end: float
    out: Any


@dataclasses.dataclass
class Window:
    calls: List[CallRecord]

    @property
    def seconds(self) -> float:
        return self.calls[-1].end - self.calls[0].start


def run_window(call: Callable[[int], Any], seconds: float,
               clock: Callable[[], float] = time.perf_counter) -> Window:
    """Run ``call(i)`` for i = 0, 1, … until the window is ``seconds`` long.

    ``call`` returns only once its result is on the host, so each record's
    end is the moment its answer is complete."""
    calls: List[CallRecord] = []
    t0 = clock()
    while True:
        i = len(calls)
        start = clock()
        out = call(i)
        end = clock()
        calls.append(CallRecord(i, start, end, out))
        if end - t0 >= seconds:
            return Window(calls)
