"""The reader of the library's ``host_transpose_bytes`` counter
(``bench/metrics/host_transpose_bytes_per_call.l3.py``): the count per
API call of the window, 0 in a cell that transposes nothing, and silence
without a recording."""
import sys
import time

import numpy as np
import pytest

from bench.cell import loop_class, metric_reader
from bench.run import Record
from bench.spans import Spans

NAME = "host_transpose_bytes_per_call.l3"


@pytest.fixture
def telemetry():
    from repro import telemetry

    telemetry.reset()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


def _record(calls):
    rec = Record(window_s=10.0, spans=Spans(annotate=False), groups=0,
                 compiles=0)
    rec.spans.calls = calls
    return rec


def test_the_count_per_api_call(telemetry):
    from repro.api import BlasxContext
    from repro.core.runtime import RuntimeConfig

    B = np.ones((40, 24), np.float32)
    ctx = BlasxContext(RuntimeConfig(n_devices=1), tile=16)
    telemetry.enable()
    out = ctx.trmm(np.eye(24), B, side="R")
    ctx.trsm(np.eye(24), B, side="R")
    telemetry.disable()
    ctx.close()
    assert metric_reader(NAME)(_record(2)) == \
        (B.nbytes + out.array().nbytes) / 2


def test_zero_in_a_cholesky_update_window(telemetry, small_cell):
    """The cell's side-R trsm, at a test size, transposes nothing."""
    cell = small_cell("n8192.chol_update")
    spans = Spans(annotate=False)
    loop = loop_class(cell)(cell, 2 ** 31 + 9, spans)
    try:
        loop.warm()
        spans.reset()
        telemetry.enable()
        t0 = time.perf_counter()
        loop.iterate(0)
        rec = Record(window_s=time.perf_counter() - t0, spans=spans,
                     groups=0, compiles=0)
        telemetry.disable()
    finally:
        loop.close()
    assert rec.spans.calls == 2
    assert metric_reader(NAME)(rec) == 0.0


def test_silent_without_a_recording(telemetry):
    assert metric_reader(NAME)(_record(3)) is None


def test_silent_where_the_library_has_no_tracer(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert metric_reader(NAME)(_record(3)) is None
