"""The benchmark's own spans and counters around the library's layers.

``Spans`` times each API call (``call``) and each step group the runtime
hands its backend (``TimedBackend.run_group``) on the host clock, counts
the bytes of the tiles each group is handed, and, while the profiler
runs, writes both as ``jax.profiler.TraceAnnotation`` spans (``bench.*``)
onto the device trace's clock.  ``CompileCounter`` counts the executables
JAX builds or loads while it is armed.
"""
from __future__ import annotations

import contextlib
import threading
import time

WINDOW_SPAN = "bench.window"
CALL_SPAN = "bench.call"
GROUP_SPAN = "bench.run_group"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Spans:
    """Host-clock totals of the spans, safe to feed from several lanes."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self._lock = threading.Lock()
        self.call_s = 0.0
        self.group_s = 0.0
        self.calls = 0
        self.groups = 0
        self.staged_bytes = 0

    def reset(self) -> None:
        """Zero the totals (set-up's calls are not the window's)."""
        with self._lock:
            self.call_s = self.group_s = 0.0
            self.calls = self.groups = self.staged_bytes = 0

    def _span(self, name: str, **kw):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name, **kw)

    @contextlib.contextmanager
    def call(self, routine: str):
        t0 = time.perf_counter()
        with self._span(f"{CALL_SPAN}.{routine}"):
            yield
        dt = time.perf_counter() - t0
        with self._lock:
            self.call_s += dt
            self.calls += 1

    @contextlib.contextmanager
    def window(self):
        with self._span(WINDOW_SPAN):
            yield

    def add_group(self, seconds: float, nbytes: int) -> None:
        with self._lock:
            self.group_s += seconds
            self.groups += 1
            self.staged_bytes += nbytes


class TimedBackend:
    """Stands in for a runtime's backend and times its ``run_group``."""

    def __init__(self, inner, spans: Spans):
        self.inner = inner
        self.name = inner.name
        self.spans = spans

    def run_group(self, key, a_tiles, b_tiles):
        nbytes = sum(t.nbytes for t in a_tiles) + sum(t.nbytes for t in b_tiles)
        t0 = time.perf_counter()
        with self.spans._span(GROUP_SPAN, op=key.op, m=key.m, k=key.k,
                              n=key.n, steps=key.steps):
            out = self.inner.run_group(key, a_tiles, b_tiles)
        self.spans.add_group(time.perf_counter() - t0, nbytes)
        return out


def instrument(ctx, spans: Spans) -> None:
    """Route a context's step groups through the timing wrapper."""
    rt = ctx.runtime
    if not isinstance(rt.backend, TimedBackend):
        rt.backend = TimedBackend(rt.backend, spans)


class CompileCounter:
    """Counts JAX executable builds (compiled or loaded from the
    persistent cache) between ``arm()`` and ``disarm()``."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self._armed = False
        self._monitoring = jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self._armed and event == COMPILE_EVENT:
            self.count += 1

    def arm(self) -> None:
        self.count = 0
        self._armed = True

    def disarm(self) -> None:
        self._armed = False

    def close(self) -> None:
        self._armed = False
        self._monitoring.unregister_event_duration_listener(self._on_event)
