"""Window arithmetic: a rate over whole calls, and what a window yields.

A closed loop starts calls until the window's seconds have passed and
lets the last one run to its end; its rate is the work of every call
started, over the time from the first call's start to the last call's
end.  No statistic is a median of chunks.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence


@dataclasses.dataclass
class Call:
    """One closed-loop iteration: host-clock start and end, and work."""
    start: float
    end: float
    flops: int


@dataclasses.dataclass
class Outcome:
    """What a loop's window yields: its counts, its length on the host
    clock, the end-to-end metrics it measured (all but ``setup_s``), the
    answers its loop compares once the window has closed, and anything
    further the per-layer readers read (``extra``)."""
    attempted: int
    failed: int
    window_s: float
    metrics: Dict[str, float]
    answers: object
    extra: dict = dataclasses.field(default_factory=dict)


def rate(calls: Sequence[Call]) -> float:
    """Work per second over the whole window (0 calls: an error)."""
    if not calls:
        raise ValueError("no call in the window")
    span = max(c.end for c in calls) - min(c.start for c in calls)
    return sum(c.flops for c in calls) / span
