"""In-program tracer of the BLAS call path: named spans with their self
time, and byte counters, on the JAX profiler's clock.

Off by default.  ``enable()`` turns it on; so does a JAX profiler session
that records host events (``jax.profiler.start_trace`` or
``jax.profiler.trace``), for as long as it records, so a profile of any
program that calls BLASX carries the library's spans beside the device's
operations.  Off, ``span`` returns one shared no-op context and ``count``
returns, each after one check whether a profiler records: no clock
read, no allocation, no lock.

On, each span reads ``time.perf_counter`` at entry and exit and sits on a
per-thread stack, so its self time (its duration less the time its child
spans cover) is summed per name; ``count`` sums counters.  ``snapshot()``
returns both as a plain dict.  While a profiler records, each span also
opens a ``jax.profiler.TraceAnnotation`` of the same name that carries
the id of the API call it serves (``call=``), so the spans of one call
join across worker threads; without a profiler there is nothing to
annotate.  A garbage collection that interrupts a span is recorded as
its child ``blasx.gc``.  The collection hook is removed by ``disable()``,
or, after a profiler session that ``enable()`` did not open, by the
first span that finds the tracer off.

The readings are observations only: none reaches the scheduler, a
ledger, the sim time model or a result.
"""
from __future__ import annotations

import contextlib
import contextvars
import gc
import itertools
import sys
import threading
import time
from typing import Dict

CALL_SPAN = "blasx.call"
GC_SPAN = "blasx.gc"
# the API call a span serves; worker threads run in a copy of the
# caller's context, so they see it too
_CALL: contextvars.ContextVar = contextvars.ContextVar("blasx_call",
                                                       default=None)
# what ``span`` returns while the tracer is off: one shared object
NOOP = contextlib.nullcontext()


class _Span:
    __slots__ = ("tracer", "name", "meta", "annotate", "child", "t0",
                 "note", "token")

    def __init__(self, tracer: "Tracer", name: str, meta: dict,
                 annotate: bool):
        self.tracer, self.name, self.meta = tracer, name, meta
        self.annotate = annotate
        self.child = 0.0
        self.note = self.token = None

    def __enter__(self):
        if self.name == CALL_SPAN and _CALL.get() is None:
            self.token = _CALL.set(next(self.tracer._ids))
        if self.annotate:
            import jax

            cid = _CALL.get()
            meta = self.meta if cid is None else dict(self.meta, call=cid)
            self.note = jax.profiler.TraceAnnotation(self.name, **meta)
            self.note.__enter__()
        self.tracer._stack().append(self)
        self.t0 = self.tracer.clock()
        return self

    def __exit__(self, *exc):
        self.tracer._close(self, self.tracer.clock())
        if self.note is not None:
            self.note.__exit__(*exc)
        if self.token is not None:
            _CALL.reset(self.token)
        return False


class Tracer:
    """Self-time totals per span name and counters, fed from any thread."""

    _GUARDED_BY = {"_lock": ("_totals", "_counters")}

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        # reentrant: a collection can start while this thread holds it,
        # and the gc hook then records under it
        self._lock = threading.RLock()
        self._totals: Dict[str, list] = {}
        self._counters: Dict[str, int] = {}
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._on = False
        self._probe = None      # TraceAnnotation.is_enabled, once jax is in
        self._gc_hooked = False

    # ------------------------------------------------------------ switch
    def enable(self) -> None:
        self._on = True
        self._hook_gc()

    def disable(self) -> None:
        self._on = False
        self._unhook_gc()

    def _profiling(self) -> bool:
        """Whether a JAX profiler session records host events now."""
        if self._probe is None:
            jax = sys.modules.get("jax")
            if jax is None:
                return False
            try:
                self._probe = jax.profiler.TraceAnnotation.is_enabled
            except AttributeError:   # jax half imported
                return False
        return self._probe()

    def recording(self) -> bool:
        return self._on or self._profiling()

    # -------------------------------------------------------------- record
    def span(self, name: str, **meta):
        profiling = self._profiling()
        if not (self._on or profiling):
            if self._gc_hooked:         # a profiler session has ended
                self._unhook_gc()
            return NOOP
        self._hook_gc()
        return _Span(self, name, meta, profiling)

    def count(self, name: str, n: int) -> None:
        if not self.recording():
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + int(n)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _close(self, s: _Span, t1: float) -> None:
        stack = self._stack()
        stack.remove(s)
        dur = t1 - s.t0
        if stack:
            stack[-1].child += dur
        with self._lock:
            tot = self._totals.setdefault(s.name, [0.0, 0])
            tot[0] += dur - s.child
            tot[1] += 1

    def _hook_gc(self) -> None:
        if not self._gc_hooked:
            with self._lock:
                if not self._gc_hooked:
                    gc.callbacks.append(self._on_gc)
                    self._gc_hooked = True

    def _unhook_gc(self) -> None:
        with self._lock:
            if self._gc_hooked:
                gc.callbacks.remove(self._on_gc)
                self._gc_hooked = False

    def _on_gc(self, phase: str, info: dict) -> None:
        """A collection that interrupts a span becomes its child."""
        stack = getattr(self._local, "stack", None)
        if not stack:
            return
        if phase == "start":
            _Span(self, GC_SPAN, {}, stack[-1].annotate).__enter__()
        elif stack[-1].name == GC_SPAN:
            stack[-1].__exit__(None, None, None)

    # ---------------------------------------------------------------- read
    def reset(self) -> None:
        with self._lock:
            self._totals.clear()
            self._counters.clear()

    def snapshot(self) -> dict:
        """``{"spans": {name: {"self_s", "count"}}, "counters": {...}}``."""
        with self._lock:
            return {"spans": {k: {"self_s": v[0], "count": v[1]}
                              for k, v in self._totals.items()},
                    "counters": dict(self._counters)}


# the process's one tracer: the profiler it writes into is process-wide too
_TRACER = Tracer()
enable = _TRACER.enable
disable = _TRACER.disable
recording = _TRACER.recording
span = _TRACER.span
count = _TRACER.count
reset = _TRACER.reset
snapshot = _TRACER.snapshot
