"""The trace reduction: busy union, idle share, kernel time, roofline
share and breakdown, on hand-made events and on a trace recorded on a
TPU v5e."""
import json
from pathlib import Path

import pytest

from bench import trace
from bench.trace import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
MATMUL = ("%vmap_jit_matmul__.2 = f32[4,1024,1024]{2,1,0:T(8,128)} "
          "custom-call(f32[4,1024,8192]{2,1,0:T(8,128)} %bitcast.3, "
          "f32[4,8192,1024]{2,1,0:T(8,128)} %bitcast.1), "
          "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
          "{f32[4,1024,8192]{2,1,0}, f32[4,8192,1024]{2,1,0}}")
COPY = ("%copy.1 = f32[4,8,128,8,1024]{4,3,1,2,0:T(8,128)} "
        "copy(f32[4,8,128,8,1024]{4,3,2,1,0:T(8,128)} %bitcast.2)")
DOT = ("%dot.7 = f32[2,64,32]{2,1,0} dot(f32[2,64,128]{2,1,0} %a, "
       "bf16[2,128,32]{2,1,0} %b), lhs_batch_dims={0}, "
       "lhs_contracting_dims={2}, rhs_batch_dims={0}, "
       "rhs_contracting_dims={1}")
FIXTURE = Path(__file__).parent / "data" / "chol_update_trace.json"


def op(name, a, b):
    return Event(DEV, "XLA Ops", name, a, b)


def span(name, a, b, line="python"):
    return Event(HOST, line, name, a, b)


def test_merge_unions_and_clips():
    got = trace.merge([(5, 8), (0, 3), (2, 4), (9, 12), (20, 30)], 1, 11)
    assert got == [(1, 4), (5, 8), (9, 11)]


def test_busy_and_idle_share():
    ev = [span("bench.window", 0, 100), op(COPY, 10, 30), op(COPY, 20, 40),
          op(MATMUL, 90, 120), span("bench.run_group", 5, 45)]
    s = trace.summarize(ev, {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(40e-9)   # 10..40 and 90..100
    assert s.idle_share == pytest.approx(60.0)


def test_idle_share_is_silent_without_a_device_plane():
    s = trace.summarize([span("bench.window", 0, 100)],
                        {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    assert s.idle_share is None and s.roofline is None


def test_matmul_cost_from_the_pallas_kernel_text():
    flops, nbytes = trace.matmul_cost(MATMUL)
    assert flops == 2 * 4 * 1024 * 1024 * 8192
    assert nbytes == 4 * (4 * 1024 * 1024 + 2 * 4 * 1024 * 8192)


def test_matmul_cost_of_an_xla_dot_mixed_dtypes():
    flops, nbytes = trace.matmul_cost(DOT)
    assert flops == 2 * 2 * 64 * 32 * 128
    assert nbytes == 4 * 2 * 64 * 32 + 4 * 2 * 64 * 128 + 2 * 2 * 128 * 32


@pytest.mark.parametrize("name", [COPY, "jit_run(123)", "%fusion.3 = f32[8]{0} "
                                  "fusion(f32[8]{0} %p), kind=kLoop"])
def test_other_operations_are_no_matmul(name):
    assert trace.matmul_cost(name) is None


def test_roofline_share_and_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flops, nbytes = trace.matmul_cost(MATMUL)
    least = max(flops / 197e12, nbytes / 819e9)
    ev = [op(MATMUL, 0, 2_000_000), op(MATMUL, 3_000_000, 5_000_000),
          op(COPY, 2_000_000, 3_000_000)]
    r = trace.roofline(ev, 0, 1e9, peaks["bf16_flops_per_s"],
                       peaks["hbm_bytes_per_s"])
    assert r["kernel_s"] == pytest.approx(4e-3)   # the copy is no kernel
    assert r["share"] == pytest.approx(100 * 2 * least / 4e-3)
    assert r["bound"] == "compute"


def test_roofline_counts_kernels_that_start_in_the_window_only():
    ev = [op(MATMUL, -5, 10), op(MATMUL, 10, 20)]
    r = trace.roofline(ev, 0, 100, 1e12, 1e9)
    assert r["kernel_s"] == pytest.approx(10e-9)
    assert trace.roofline([op(COPY, 0, 5)], 0, 100, 1e12, 1e9) is None


def test_breakdown_names_gaps_by_the_host_span():
    ev = [span("bench.window", 0, 100), span("bench.call.gemm", 0, 100),
          span("bench.run_group", 10, 40), op(MATMUL, 35, 40),
          op(COPY, 60, 65), span("bench.run_group", 50, 70, line="lane1")]
    b = trace.breakdown(ev, 0, 100)
    assert b["device_ops"][0][0] == "vmap_jit_matmul__ custom-call f32[4,1024,1024]"
    assert b["device_ops"][0][1] == pytest.approx(5e-9)
    gaps = dict((n, s) for n, s in b["idle_gaps"])
    assert b["idle_gaps"][0][1] == pytest.approx(35e-9)   # 0..35
    assert gaps["bench.run_group"] == pytest.approx(35e-9)
    assert gaps["bench.call.gemm"] == pytest.approx(35e-9)  # 65..100
    # 40..60: the main thread is in its call, lane1 in a run_group
    assert gaps["bench.call.gemm+bench.run_group"] == pytest.approx(20e-9)


def test_recorded_trace_of_a_cholesky_update():
    doc = json.loads(FIXTURE.read_text())
    ev = [Event(*row) for row in doc["events"]]
    s = trace.summarize(ev, {"bf16_flops_per_s": 197e12,
                             "hbm_bytes_per_s": 819e9})
    kernels = [e for e in ev if trace.matmul_cost(e.name)]
    assert len(kernels) == 7                       # the syrk's 7 groups
    assert s.roofline["kernel_s"] == pytest.approx(
        sum(e.end - e.start for e in kernels) * 1e-9)
    # 4 x 1024^3 per group moves more bytes per flop than the bf16 peak
    # feeds: the HBM bound applies
    assert s.roofline["bound"] == "memory"
    assert 0 < s.roofline["share"] < 100
    assert s.busy_s == pytest.approx(s.roofline["kernel_s"])
    assert 99.0 < s.idle_share < 100.0
    assert s.breakdown["idle_gaps"][0][0] in ("bench.call.chol",
                                              "bench.call", "bench.run_group")
