"""What the per-layer readers read of the library's own tracer
(``repro.telemetry``): the self time of its spans, as a share of the
window, and its counters.

A profiler session turns the library's tracer on for as long as it
records, so in a traced run (``--trace 1``) its totals are the window's
alone: set-up runs before the profiler starts.  Those totals are the
process's, and nothing here resets them, so the readings hold for one
run per process, as ``bench/run.py`` makes them.  A library without the
tracer, or a run in which it recorded no span, gives None.
"""
from __future__ import annotations

from typing import Optional


def snapshot() -> Optional[dict]:
    try:
        from repro import telemetry
    except ImportError:
        return None
    snap = telemetry.snapshot()
    return snap if snap["spans"] else None


def self_share(rec, *names: str) -> Optional[float]:
    """Share (%) of the window in the self time of the named spans."""
    snap = snapshot()
    if snap is None:
        return None
    spans = snap["spans"]
    return 100.0 * sum(spans[n]["self_s"] for n in names
                       if n in spans) / rec.window_s


def per_call(rec, counter: str) -> Optional[float]:
    """A counter's total over the API calls of the window."""
    snap = snapshot()
    if snap is None or rec.spans is None or rec.spans.calls == 0:
        return None
    return snap["counters"].get(counter, 0) / rec.spans.calls
