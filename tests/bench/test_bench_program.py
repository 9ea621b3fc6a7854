"""The per-layer readers of the library's own tracer (``bench/program.py``
and the ``bench/metrics`` files that use it), on windows recorded here on
the CPU: what each reads, that the staged bytes agree with the
benchmark's own count, and silence where the library has no tracer."""
import sys

import numpy as np
import pytest

from bench import program
from bench.cell import metric_reader
from bench.run import Record
from bench.spans import Spans

SHARES = {
    "prep_share.l3": ("blasx.prep",),
    "schedule_share.l3": ("blasx.call", "blasx.plan", "blasx.run",
                          "blasx.dispatch", "blasx.group", "blasx.model"),
    "gather_share.l3": ("blasx.gather",),
    "epilogue_share.l3": ("blasx.finalize",),
    "gc_share.l3": ("blasx.gc",),
    "stage_share.l3": ("blasx.stage",),
    "h2d_share.l3": ("blasx.h2d",),
    "d2h_share.l3": ("blasx.d2h",),
}
NEW = sorted(SHARES) + ["h2d_bytes_per_call.l3"]


@pytest.fixture
def telemetry():
    from repro import telemetry

    telemetry.reset()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


def _window(telemetry, small_cell, name, calls=2):
    """``calls`` iterations of a cell's loop at a test size, the tracer
    and the benchmark's spans on; the Record the readers get."""
    import time

    from bench.cell import loop_class

    cell = small_cell(name)
    spans = Spans(annotate=False)
    loop = loop_class(cell)(cell, 5, spans)
    try:
        loop.warm()
        spans.reset()
        telemetry.enable()
        t0 = time.perf_counter()
        for i in range(calls):
            loop.iterate(i % len(loop.sets))
        window_s = time.perf_counter() - t0
        telemetry.disable()
    finally:
        loop.close()
    return Record(window_s=window_s, spans=spans, groups=0, compiles=0)


@pytest.mark.parametrize("name", ["n8192.gemm", "n8192.chol_update",
                                  "n8192.gemm_fresh"])
def test_readers_on_a_recorded_window(telemetry, small_cell, name):
    rec = _window(telemetry, small_cell, name)
    snap = telemetry.snapshot()
    got = {m: metric_reader(m)(rec) for m in NEW}
    for m, names in SHARES.items():
        want = 100.0 * sum(snap["spans"].get(n, {"self_s": 0.0})["self_s"]
                           for n in names) / rec.window_s
        assert got[m] == pytest.approx(want)
        assert 0.0 <= got[m] < 100.0
    # the library hands the device exactly the tiles the benchmark's
    # wrapper saw handed to run_group
    assert rec.spans.staged_bytes > 0
    assert got["h2d_bytes_per_call.l3"] == rec.spans.staged_bytes \
        / rec.spans.calls
    # with kernel time, the shares cover the calls' time
    kernel = 100.0 * snap["spans"]["blasx.kernel"]["self_s"] / rec.window_s
    assert sum(got[m] for m in SHARES) + kernel == pytest.approx(
        100.0 * rec.spans.call_s / rec.window_s, abs=2.0)


def test_staged_bytes_of_a_8192_gemm_at_test_size(telemetry, small_cell):
    """n = 512, tile 128: 4 groups of 4 items, 4 steps, two 64 KiB tiles
    a step; the 8192 cell's 16 x 4 x 8 x 8 MiB is the same count."""
    rec = _window(telemetry, small_cell, "n8192.gemm", calls=1)
    assert metric_reader("h2d_bytes_per_call.l3")(rec) == \
        4 * 4 * 4 * 2 * 128 * 128 * 4


@pytest.mark.parametrize("name", NEW)
def test_silent_without_a_recording(telemetry, name):
    rec = Record(window_s=10.0, spans=Spans(annotate=False), groups=0,
                 compiles=0)
    rec.spans.calls = 3
    assert metric_reader(name)(rec) is None


@pytest.mark.parametrize("name", NEW)
def test_silent_where_the_library_has_no_tracer(monkeypatch, name):
    """The parent of the tracer: ``import repro.telemetry`` fails."""
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    rec = Record(window_s=10.0, spans=Spans(annotate=False), groups=0,
                 compiles=0)
    rec.spans.calls = 3
    assert program.snapshot() is None
    assert metric_reader(name)(rec) is None


def test_a_share_reads_the_named_spans_only(telemetry):
    telemetry.enable()
    with telemetry.span("blasx.call"):
        with telemetry.span("blasx.stage"):
            np.ones(1000).sum()
    telemetry.disable()
    snap = telemetry.snapshot()
    rec = Record(window_s=snap["spans"]["blasx.stage"]["self_s"] * 4,
                 spans=None, groups=0, compiles=0)
    assert metric_reader("stage_share.l3")(rec) == pytest.approx(25.0)
    assert metric_reader("h2d_share.l3")(rec) == 0.0
    assert metric_reader("h2d_bytes_per_call.l3")(rec) is None


@pytest.mark.parametrize("trace", [False, True])
def test_the_harness_as_it_stands_reads_them_in_a_traced_run(
        telemetry, small_cell, tmp_path, trace):
    """``run_cell`` turns on no tracer of the library itself: the
    profiler of ``--trace 1`` does, for the window; ``--trace 0`` leaves
    it off and the result line as it was."""
    import time

    from bench.cell import load_peaks
    from bench.run import run_cell

    cell = small_cell("n8192.chol_update")
    res = run_cell(cell, 2 ** 31 + 77, 0.5, trace, load_peaks("TPU v5 lite"),
                   time.perf_counter(), tmp_path / "trace")
    assert res["correct"]
    if trace:
        assert set(NEW) <= set(res["metrics"])
        assert res["metrics"]["h2d_bytes_per_call.l3"]["value"] > 0
    else:
        assert set(res["metrics"]) == {"tflops", "setup_s"}
        assert telemetry.snapshot() == {"spans": {}, "counters": {}}


def test_library_spans_in_a_trace_change_no_existing_reading():
    """The committed trace with a ``blasx.*`` span laid under every
    ``bench.*`` one: ``bench/trace.py`` reads the same numbers."""
    import dataclasses
    import json
    from pathlib import Path

    from bench import trace
    from bench.trace import Event

    doc = json.loads((Path(__file__).parent / "data"
                      / "chol_update_trace.json").read_text())
    ev = [Event(*row) for row in doc["events"]]
    ours = [dataclasses.replace(e, name="blasx.stage", start=e.start + 1,
                                end=e.end - 1)
            for e in ev if e.name.startswith("bench.call")]
    assert ours
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert trace.summarize(ev + ours, peaks) == trace.summarize(ev, peaks)
