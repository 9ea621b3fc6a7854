"""The in-program tracer (``repro.telemetry``): self time, per-thread
stacks, the off state, garbage collections, the spans of the BLAS call
path, and that tracing changes nothing the library computes."""
import gc
import itertools
import json
import threading
import time

import numpy as np
import pytest

from repro import telemetry
from repro.api import BlasxContext
from repro.api import context as ctxmod
from repro.core.runtime import RuntimeConfig
from repro.telemetry import NOOP, Tracer

# the spans of the BLAS call path (a GEMM, a SYRK and a side-R TRSM on
# the jax backend open every one of them)
PATH_SPANS = {"blasx.call", "blasx.prep", "blasx.plan", "blasx.run",
              "blasx.gather", "blasx.dispatch", "blasx.group",
              "blasx.stage", "blasx.h2d", "blasx.kernel", "blasx.d2h",
              "blasx.finalize", "blasx.model", "blasx.gc"}


@pytest.fixture
def tracer():
    """The process's tracer, zeroed, and off again afterwards."""
    telemetry.reset()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


class FakeClock:
    """Reads advance only when the test says so."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_self_time_of_nested_spans():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    tr.enable()
    try:
        with tr.span("outer"):
            clock.t += 1.0
            with tr.span("inner"):
                clock.t += 2.0
                with tr.span("leaf"):
                    clock.t += 4.0
            clock.t += 8.0
            with tr.span("inner"):
                clock.t += 16.0
    finally:
        tr.disable()
    spans = tr.snapshot()["spans"]
    assert spans["outer"] == {"self_s": 9.0, "count": 1}
    assert spans["inner"] == {"self_s": 18.0, "count": 2}
    assert spans["leaf"] == {"self_s": 4.0, "count": 1}
    assert sum(s["self_s"] for s in spans.values()) == 31.0


def test_each_thread_keeps_its_own_stack():
    """A span another thread opens meanwhile is no child of this one."""
    tr = Tracer()
    tr.enable()
    opened, done = threading.Event(), threading.Event()
    held = {}

    def hold():
        t0 = time.perf_counter()
        with tr.span("held"):
            opened.set()
            assert done.wait(10)
        held["s"] = time.perf_counter() - t0

    th = threading.Thread(target=hold)
    try:
        th.start()
        assert opened.wait(10)
        with tr.span("other"):
            time.sleep(0.05)
        done.set()
        th.join(10)
    finally:
        tr.disable()
    assert not th.is_alive()
    spans = tr.snapshot()["spans"]
    assert spans["other"]["self_s"] >= 0.05
    # all of the held span's time is its own: "other" ran on another stack
    assert spans["held"]["self_s"] == pytest.approx(held["s"], rel=0.05)
    assert spans["held"]["self_s"] > spans["other"]["self_s"]


def test_off_is_the_shared_noop_and_builds_no_annotation(tracer,
                                                        monkeypatch):
    import jax

    built = []

    class Spy:
        def __init__(self, *a, **kw):
            built.append(a)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    assert not tracer.recording()
    assert tracer.span("blasx.call", routine="gemm") is NOOP
    tracer.count("h2d_bytes", 10)
    ctx = BlasxContext(RuntimeConfig(n_devices=1, backend="jax"), tile=32,
                       dtype="float32")
    a = np.ones((64, 64), np.float32)
    ctx.gemm(a, a)
    assert built == []
    assert tracer.snapshot() == {"spans": {}, "counters": {}}


def test_a_collection_inside_a_span_is_its_child():
    tr = Tracer()
    tr.enable()
    try:
        with tr.span("outer"):
            gc.collect()
        gc.collect()                        # interrupts no span
    finally:
        tr.disable()
    spans = tr.snapshot()["spans"]
    assert spans["blasx.gc"]["count"] == 1
    assert spans["blasx.gc"]["self_s"] > 0
    assert tr._on_gc not in gc.callbacks


def _operands(seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((128, 128)).astype(np.float32)
    b = rng.standard_normal((128, 128)).astype(np.float32)
    lo = np.tril(rng.standard_normal((32, 32))).astype(np.float32) \
        + 8 * np.eye(32, dtype=np.float32)
    x = rng.standard_normal((128, 32)).astype(np.float32)
    return a, b, lo, x


def _calls(ctx, a, b, lo, x):
    return [ctx.gemm(a, b).array(),
            ctx.syrk(a, C=b, alpha=-1.0, beta=1.0, uplo="L").array(),
            ctx.trsm(lo, x, side="R", uplo="L", transa="T").array()]


def test_the_call_path_opens_every_span_and_self_times_add_up(tracer):
    ctx = BlasxContext(RuntimeConfig(n_devices=1, backend="jax"), tile=32,
                       dtype="float32")
    ops = _operands()
    _calls(ctx, *ops)                       # compile outside the count
    threshold = gc.get_threshold()
    tracer.enable()
    gc.set_threshold(50)                    # collections inside the calls
    try:
        t0 = time.perf_counter()
        # a collection between two calls is a child of this span
        with tracer.span("test.calls"):
            _calls(ctx, *ops)
        wall = time.perf_counter() - t0
    finally:
        gc.set_threshold(*threshold)
        tracer.disable()
    snap = tracer.snapshot()
    spans = dict(snap["spans"])
    outer = spans.pop("test.calls")
    assert set(spans) == PATH_SPANS
    # one each: the side-R trsm runs as one call
    assert spans["blasx.call"]["count"] == 3
    total = sum(s["self_s"] for s in snap["spans"].values())
    assert total == pytest.approx(wall, rel=0.01)
    # the library's spans cover its calls: little is left between them
    assert outer["self_s"] < 0.01 * wall
    # syrk: 10 lower tiles of a 4x4 grid, one 4-step item each, 4 tiles
    # of 32x32 float32 per operand; gemm: 16 items of 4 steps
    assert snap["counters"] == {"h2d_bytes": (16 + 10) * 2 * 4 * 32 * 32 * 4}


def test_host_transposes_are_counted(tracer):
    """``host_transpose_bytes``: a side-R trsm transposes nothing; a
    side-R trmm and symm count each operand they transpose and the
    transposed result."""
    rng = np.random.default_rng(5)
    A = rng.standard_normal((48, 48)) / 48 + np.eye(48)
    B = rng.standard_normal((40, 48)).astype(np.float32)
    C = rng.standard_normal((40, 48))
    ctx = BlasxContext(RuntimeConfig(n_devices=1), tile=32)
    tracer.enable()
    ctx.trsm(A, B, side="R", uplo="L", transa="T")
    assert "host_transpose_bytes" not in tracer.snapshot()["counters"]
    out = ctx.trmm(A, B, side="R")
    assert out.array().dtype == np.float32
    assert tracer.snapshot()["counters"]["host_transpose_bytes"] == \
        B.nbytes + out.array().nbytes
    tracer.reset()
    out = ctx.symm(A, B, C, beta=0.5, side="R")
    assert tracer.snapshot()["counters"]["host_transpose_bytes"] == \
        B.nbytes + C.nbytes + out.array().nbytes
    ctx.close()


def _profiling(monkeypatch):
    """Makes the tracer see a profiler session: its spans record and
    open annotations (which, with no session, go nowhere)."""
    monkeypatch.setattr(telemetry._TRACER, "_probe", lambda: True)


def _replay(backend, on):
    ctxmod._MATRIX_IDS = itertools.count()  # the trace names tiles by id
    if on:
        telemetry.enable()
    try:
        ctx = BlasxContext(RuntimeConfig(n_devices=2, backend=backend,
                                         mode="sim"), tile=32,
                           dtype="float32")
        out = _calls(ctx, *_operands(3))
        return json.dumps(ctx.trace(), sort_keys=True), list(ctx.calls), out
    finally:
        telemetry.disable()
        telemetry.reset()


@pytest.mark.parametrize("backend,profiled", [("numpy", False),
                                              ("jax", False),
                                              ("jax", True)])
def test_sim_replay_is_identical_with_the_tracer_on(backend, profiled,
                                                    monkeypatch):
    # each replay restarts the id stream; the test restores it
    monkeypatch.setattr(ctxmod, "_MATRIX_IDS", ctxmod._MATRIX_IDS)
    off = _replay(backend, False)
    if profiled:
        _profiling(monkeypatch)
    on = _replay(backend, True)
    assert on[0] == off[0]
    assert on[1] == off[1]
    for x, y in zip(on[2], off[2]):
        np.testing.assert_array_equal(x, y)


def test_worker_threads_carry_the_call_id(tracer, monkeypatch):
    """In threads mode the spans of one call, on whichever thread, name
    the same call; the next call gets another id."""
    import jax

    notes = []

    class Spy:
        def __init__(self, name, **meta):
            notes.append((threading.get_ident(), name, meta.get("call")))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    ctx = BlasxContext(RuntimeConfig(n_devices=2, mode="threads"), tile=32,
                       dtype="float32")
    a = np.ones((128, 128), np.float32)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    _profiling(monkeypatch)
    ctx.gemm(a, a)
    ctx.gemm(a, a)
    monkeypatch.undo()
    me = threading.get_ident()
    calls = [c for _, n, c in notes if n == "blasx.call"]
    assert len(calls) == 2 and calls[0] != calls[1]
    workers = [(n, c) for t, n, c in notes if t != me]
    assert {n for n, _ in workers} >= {"blasx.gather", "blasx.group",
                                       "blasx.finalize"}
    assert {c for _, c in workers} == set(calls)
    assert all(c is not None for _, _, c in notes)


def test_a_profiler_session_turns_the_tracer_on(tracer, tmp_path):
    import jax

    ctx = BlasxContext(RuntimeConfig(n_devices=1, backend="jax"), tile=32,
                       dtype="float32")
    a = np.ones((64, 64), np.float32)
    ctx.gemm(a, a)
    assert tracer.snapshot()["spans"] == {}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    hook = telemetry._TRACER._on_gc
    try:
        assert tracer.recording()
        ctx.gemm(a, a)
        assert hook in gc.callbacks
    finally:
        jax.profiler.stop_trace()
    assert not tracer.recording()
    spans = tracer.snapshot()["spans"]
    # the first span after the session, off, takes the collection hook out
    ctx.gemm(a, a)
    assert hook not in gc.callbacks
    assert tracer.snapshot()["spans"] == spans
    assert spans["blasx.call"]["count"] == 1
    assert spans["blasx.kernel"]["count"] == 1   # 4 tasks, one group
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = {e.name for p in jax.profiler.ProfileData.from_file(
                 str(path)).planes for line in p.lines for e in line.events}
    assert {"blasx.call", "blasx.stage", "blasx.h2d", "blasx.kernel",
            "blasx.d2h", "blasx.finalize"} <= names
