"""One runtime device per chip: ``BlasxRuntime`` binds device ``d`` to
``jax.local_devices()[d]`` (``d mod count`` on fewer chips), runs each of
its step groups there, and counts the k-steps of every chip; the
benchmark's four-chip cell and the readers of those counters."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro import telemetry
from repro.api import BlasxContext
from repro.core.runtime import RuntimeConfig, bind_chips

REPO = Path(__file__).resolve().parent.parent
CELL = "pod.ring_gemm"
# a bfloat16 GEMM's only rounding past the exact bf16 x bf16 products is
# that of its output to bfloat16: at most 2**-8 of the largest entry.
# The float32 accumulation over k = 512 adds well under 1e-4 of it.
BF16_OUT_TOL = 2.0 ** -8 + 1e-4

# Four forced host devices (jax fixes the count at start-up, hence a
# subprocess).  A backend that holds each group a little lets all four
# workers start before the queue drains, so every chip gets work.
_FOUR_DEVICES = textwrap.dedent("""
    import json, threading, time
    import jax, ml_dtypes, numpy as np
    from repro import telemetry
    from repro.api import BlasxContext
    from repro.core.runtime import RuntimeConfig

    class Held:
        def __init__(self, inner):
            self.inner, self.name = inner, inner.name

        def run_group(self, key, a, b):
            time.sleep(0.05)
            return self.inner.run_group(key, a, b)

    puts = []
    put = jax.device_put

    def spy(x, device=None, *args, **kw):
        out = put(x, device, *args, **kw)
        puts.append((threading.get_ident(), sorted(
            {d.id for leaf in jax.tree.leaves(out) for d in leaf.devices()})))
        return out

    jax.device_put = spy
    rng = np.random.default_rng(0)
    a, b = (rng.standard_normal((512, 512), dtype=np.float32)
            for _ in range(2))
    four = RuntimeConfig(n_devices=4, mode="threads", backend="jax")
    ctx = BlasxContext(four, tile=128, dtype="bfloat16")
    ctx.runtime.backend = Held(ctx.runtime.backend)
    telemetry.enable()
    c = ctx.gemm(a.astype(ml_dtypes.bfloat16),
                 b.astype(ml_dtypes.bfloat16)).array()
    telemetry.disable()
    bf16_puts = list(puts)
    ref = (a.astype(ml_dtypes.bfloat16).astype(np.float64)
           @ b.astype(ml_dtypes.bfloat16).astype(np.float64))
    f32 = BlasxContext(four, tile=128, dtype="float32").gemm(a, b).array()
    one = BlasxContext(RuntimeConfig(n_devices=1, mode="sim", backend="jax"),
                       tile=128, dtype="float32").gemm(a, b).array()
    print("RESULT " + json.dumps({
        "chips": [s["chip"] for s in ctx.stats()["devices"].values()],
        "counters": telemetry.snapshot()["counters"],
        "puts": bf16_puts,
        "err": float(np.abs(c.astype(np.float64) - ref).max()
                     / np.abs(ref).max()),
        "f32_bit_identical": bool(np.array_equal(f32, one))}))
""")


def _run_four_devices(script):
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", "")).strip()
    env["PYTHONPATH"] = os.pathsep.join([str(REPO / "src"), str(REPO)])
    proc = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.fixture(scope="module")
def four_devices():
    return _run_four_devices(_FOUR_DEVICES)


def test_each_device_runs_its_groups_on_its_own_chip(four_devices):
    assert four_devices["chips"] == [0, 1, 2, 3]
    steps = {k: v for k, v in four_devices["counters"].items()
             if k.startswith("chip_steps.")}
    assert sorted(steps) == [f"chip_steps.{i}" for i in range(4)]
    assert all(v > 0 for v in steps.values())
    # 16 tasks of 4 k-steps
    assert sum(steps.values()) == 64
    # a worker thread is one runtime device: all its groups went to one
    # chip, and the four workers to four chips
    by_thread = {}
    for thread, devices in four_devices["puts"]:
        assert len(devices) == 1
        by_thread.setdefault(thread, set()).update(devices)
    assert all(len(d) == 1 for d in by_thread.values())
    assert sorted(d.pop() for d in by_thread.values()) == [0, 1, 2, 3]


def test_four_chips_match_the_float64_reference_in_bf16(four_devices):
    assert four_devices["err"] <= BF16_OUT_TOL


def test_four_chips_are_bit_identical_to_one_device_at_f32(four_devices):
    assert four_devices["f32_bit_identical"]


# Every Level-3 routine on four placed runtime devices (threads mode, one
# forced host device each) and on one device, at float32 on ragged
# shapes: 160 x 224 outputs, k = 200, tile 64.
_ROUTINES = textwrap.dedent("""
    import json, time
    import numpy as np
    from repro.api import BlasxContext
    from repro.core import (ref_gemm, ref_symm, ref_syr2k, ref_syrk,
                            ref_trmm, ref_trsm)
    from repro.core.runtime import RuntimeConfig

    class Held:
        def __init__(self, inner):
            self.inner, self.name = inner, inner.name

        def run_group(self, key, a, b):
            time.sleep(0.01)
            return self.inner.run_group(key, a, b)

    rng = np.random.default_rng(11)
    M, N, K = 160, 224, 200

    def mat(r, c):
        return rng.standard_normal((r, c)).astype(np.float32)

    def tri(n):  # well conditioned in either triangle
        return (rng.standard_normal((n, n)) / n
                + 2.0 * np.eye(n)).astype(np.float32)

    c_sym = mat(M, M)
    c_sym = (c_sym + c_sym.T) / 2
    VARIANTS = {
        "gemm_nn": ("gemm", (mat(M, K), mat(K, N)), {}),
        "gemm_tt": ("gemm", (mat(K, M), mat(N, K)),
                    dict(transa="T", transb="T")),
        "gemm_beta_c": ("gemm", (mat(M, K), mat(K, N), mat(M, N)),
                        dict(alpha=0.5, beta=-1.5)),
        "syrk_lower_n": ("syrk", (mat(M, K),), dict(uplo="L")),
        "syrk_upper_t": ("syrk", (mat(K, M),), dict(uplo="U", trans="T")),
        "syrk_chol_update": ("syrk", (mat(M, K), c_sym),
                             dict(alpha=-1.0, beta=1.0, uplo="L")),
        "syr2k_lower": ("syr2k", (mat(M, K), mat(M, K)), dict(uplo="L")),
        "symm_left": ("symm", (mat(M, M), mat(M, N)),
                      dict(side="L", uplo="U")),
        "symm_right": ("symm", (mat(N, N), mat(M, N)),
                       dict(side="R", uplo="L")),
        "trmm_left_lower": ("trmm", (mat(M, M), mat(M, N)),
                            dict(side="L", uplo="L")),
        "trmm_right_upper": ("trmm", (mat(N, N), mat(M, N)),
                             dict(side="R", uplo="U")),
        "trsm_left_lower": ("trsm", (tri(M), mat(M, N)),
                            dict(side="L", uplo="L")),
        "trsm_left_upper_t": ("trsm", (tri(M), mat(M, N)),
                              dict(side="L", uplo="U", transa="T")),
        "trsm_right_lower_t": ("trsm", (tri(N), mat(M, N)),
                               dict(side="R", uplo="L", transa="T")),
        "trsm_right_upper": ("trsm", (tri(N), mat(M, N)),
                             dict(side="R", uplo="U")),
    }
    REFS = {"gemm": ref_gemm, "syrk": ref_syrk, "syr2k": ref_syr2k,
            "symm": ref_symm, "trmm": ref_trmm, "trsm": ref_trsm}
    FOUR = RuntimeConfig(n_devices=4, mode="threads", backend="jax")
    ONE = RuntimeConfig(n_devices=1, mode="sim", backend="jax")

    def call(cfg, routine, args, kw, tile=64):
        with BlasxContext(cfg, tile=tile, dtype="float32") as ctx:
            if cfg.n_devices > 1:
                ctx.runtime.backend = Held(ctx.runtime.backend)
            out = getattr(ctx, routine)(*args, **kw).array()
            return out, ctx.last_call

    result = {}
    for name, (routine, args, kw) in VARIANTS.items():
        four, rec = call(FOUR, routine, args, kw)
        one, _ = call(ONE, routine, args, kw)
        ref = REFS[routine](*(a.astype(np.float64) for a in args), **kw)
        result[name] = {
            "err": float(np.abs(four.astype(np.float64) - ref).max()
                         / np.abs(ref).max()),
            "bit_identical": bool(np.array_equal(four, one)),
            "dtype": str(four.dtype), "d2d_bytes": rec.d2d_bytes}
    # many tasks sharing each operand tile: peers serve one another
    a, b = mat(256, 256), mat(256, 256)
    four, rec = call(FOUR, "gemm", (a, b), {}, tile=32)
    one, _ = call(ONE, "gemm", (a, b), {}, tile=32)
    result["l2_serve"] = {"d2d_bytes": rec.d2d_bytes,
                          "bit_identical": bool(np.array_equal(four, one))}
    print("RESULT " + json.dumps(result))
""")
ROUTINE_VARIANTS = [
    "gemm_nn", "gemm_tt", "gemm_beta_c",
    "syrk_lower_n", "syrk_upper_t", "syrk_chol_update", "syr2k_lower",
    "symm_left", "symm_right", "trmm_left_lower", "trmm_right_upper",
    "trsm_left_lower", "trsm_left_upper_t", "trsm_right_lower_t",
    "trsm_right_upper",
]
# float32 against the float64 reference, relative to the largest entry:
# k = 200 (or a 224-long TRSM chain) of float32 roundings stays near
# 3e-7 on these inputs; 1e-5 leaves room without hiding a wrong tile
F32_TOL = 1e-5


@pytest.fixture(scope="module")
def routines_on_four_devices():
    return _run_four_devices(_ROUTINES)


@pytest.mark.parametrize("variant", ROUTINE_VARIANTS)
def test_four_devices_match_the_float64_reference(routines_on_four_devices,
                                                  variant):
    got = routines_on_four_devices[variant]
    assert got["dtype"] == "float32"
    assert got["err"] <= F32_TOL, got


@pytest.mark.parametrize("variant", ROUTINE_VARIANTS)
def test_four_devices_are_bit_identical_to_one_device(
        routines_on_four_devices, variant):
    assert routines_on_four_devices[variant]["bit_identical"]


def test_an_l2_serve_between_devices_gives_the_same_bits(
        routines_on_four_devices):
    got = routines_on_four_devices["l2_serve"]
    assert got["d2d_bytes"] > 0
    assert got["bit_identical"]


def test_more_devices_than_chips_wrap_round_them():
    import jax

    count = len(jax.local_devices())
    n = count + 2
    assert bind_chips(n, "jax") == [d % count for d in range(n)]
    assert bind_chips(n, "numpy") == [None] * n
    rng = np.random.default_rng(3)
    a, b = (rng.standard_normal((256, 256), dtype=np.float32)
            for _ in range(2))
    ctx = BlasxContext(RuntimeConfig(n_devices=n, mode="threads",
                                     backend="jax"), tile=64, dtype="float32")
    telemetry.reset()
    telemetry.enable()
    try:
        got = ctx.gemm(a, b).array()
    finally:
        telemetry.disable()
    counters = telemetry.snapshot()["counters"]
    telemetry.reset()
    one = BlasxContext(RuntimeConfig(n_devices=1, backend="jax"), tile=64,
                       dtype="float32").gemm(a, b).array()
    np.testing.assert_array_equal(got, one)
    assert [s["chip"] for s in ctx.stats()["devices"].values()] == \
        [d % count for d in range(n)]
    steps = {k: v for k, v in counters.items() if k.startswith("chip_steps.")}
    assert sorted(steps) == sorted(f"chip_steps.{c}" for c in range(count))
    assert sum(steps.values()) == 16 * 4


def test_one_device_places_nothing_and_counts_no_chip():
    ctx = BlasxContext(RuntimeConfig(n_devices=1, backend="jax"), tile=32,
                       dtype="float32")
    a = np.ones((64, 64), np.float32)
    telemetry.reset()
    telemetry.enable()
    try:
        ctx.gemm(a, a)
    finally:
        telemetry.disable()
    assert not any(k.startswith("chip_steps.")
                   for k in telemetry.snapshot()["counters"])
    telemetry.reset()
    assert ctx.stats()["devices"]["device0"]["chip"] == 0


# ----------------------------------------------------------- the benchmark
def _bench():
    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
    spec = importlib.util.spec_from_file_location(
        "bench_tests_conftest", REPO / "tests" / "bench" / "conftest.py")
    conftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(conftest)
    return conftest.shrink


def _fp8_inputs(a, b):
    """The control: the reference with its inputs rounded to float8
    (e4m3), the precision below the cell's bfloat16."""
    import ml_dtypes

    def r(x):
        return x.astype(ml_dtypes.float8_e4m3fn).astype(np.float64)

    return r(a) @ r(b)


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 12345])
def test_the_four_chip_cell_meets_its_limit_and_fp8_does_not(seed):
    import jax  # noqa: F401  (registers bfloat16 with numpy)

    from bench import check
    from bench.cell import find_cell, loop_class

    cell = _bench()(find_cell(CELL))
    assert cell.chips == 4 and cell.config["runtime"]["n_devices"] == 4
    loop = loop_class(cell)(cell, seed)
    try:
        out = loop.window(0.0)
    finally:
        loop.close()
    correct, checks = check.verdict(loop.numbers(out), cell.limits)
    assert correct, checks
    control = loop.control_numbers(out, _fp8_inputs)
    assert not check.verdict(control, cell.limits)[0], control


def _reading(monkeypatch, name, counters, wait_s=None):
    from bench.cell import metric_reader
    from bench.run import Record

    spans = {"blasx.call": {"self_s": 1.0, "count": 1}}
    if wait_s is not None:
        spans["blasx.wait"] = {"self_s": wait_s, "count": 3}
    monkeypatch.setattr(telemetry, "snapshot",
                        lambda: {"spans": spans, "counters": counters})
    rec = Record(window_s=10.0, spans=None, groups=0, compiles=0)
    return metric_reader(name)(rec)


def test_chip_balance_reads_the_fewest_steps_over_the_most(monkeypatch):
    _bench()
    steps = {"chip_steps.0": 40, "chip_steps.1": 30, "chip_steps.2": 40,
             "chip_steps.3": 20, "h2d_bytes": 7}
    assert _reading(monkeypatch, "chip_balance.l3", steps) == 50.0
    # a bound chip that ran nothing
    assert _reading(monkeypatch, "chip_balance.l3",
                    dict(steps, **{"chip_steps.3": 0})) == 0.0
    # a library that binds no chip
    assert _reading(monkeypatch, "chip_balance.l3", {"h2d_bytes": 7}) is None


def test_worker_wait_share_is_over_the_window_of_every_chip(monkeypatch):
    _bench()
    four = {f"chip_steps.{i}": 10 for i in range(4)}
    assert _reading(monkeypatch, "worker_wait_share.l3", four,
                    wait_s=8.0) == pytest.approx(20.0)
    assert _reading(monkeypatch, "worker_wait_share.l3",
                    {"chip_steps.0": 10}, wait_s=8.0) == pytest.approx(80.0)
    # no worker parked
    assert _reading(monkeypatch, "worker_wait_share.l3", four) == 0.0
    assert _reading(monkeypatch, "worker_wait_share.l3", {},
                    wait_s=8.0) is None


def test_a_placed_chip_compiles_the_smaller_groups_with_its_first():
    """A threads-mode call ends with groups of fewer items: on a placed
    chip those compile with the first group of the shape, not later."""
    import dataclasses

    import jax.monitoring

    from repro.backends.base import StepGroupKey, on_chip
    from repro.backends.jax_backend import JaxBackend

    compiles = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    # shapes no other test uses: the compile caches are the process's
    key = StepGroupKey("gemm", False, False, "full", "full", 24, 40, 8,
                       "float32", steps=2)

    def group(key, items):
        n = items * key.steps
        return ([np.ones((key.m, key.k), np.float32)] * n,
                [np.ones((key.k, key.n), np.float32)] * n)

    backend = JaxBackend()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with on_chip(0):
            backend.run_group(key, *group(key, 3))
            first = len(compiles)
            for items in (1, 2, 3):
                out = backend.run_group(key, *group(key, items))
                assert len(out.products) == items
        assert first > 0 and len(compiles) == first
        # placed nowhere: each smaller group compiles when it comes
        other = dataclasses.replace(key, m=20, k=44, n=12)
        backend.run_group(other, *group(other, 3))
        before = len(compiles)
        backend.run_group(other, *group(other, 2))
        assert len(compiles) > before
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def test_a_threads_mode_call_compiles_its_tail_groups_with_its_first():
    """16 tasks over four threads-mode workers, placed on one chip here:
    the call ends with groups of 1 to 3 tasks, whose on-device stacking
    was compiled with the chip's first group of the shape, so a second
    call compiles nothing."""
    import jax.monitoring

    from repro.backends.base import StepGroupKey, on_chip

    compiles, items = [], []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    # a tile no other test uses: the compile caches are the process's
    ctx = BlasxContext(RuntimeConfig(n_devices=4, mode="threads",
                                     backend="jax"), tile=36,
                       dtype="float32")
    inner = ctx.runtime.backend

    class Sizes:
        name = inner.name

        def run_group(self, key, a, b):
            items.append(len(a) // key.steps)
            return inner.run_group(key, a, b)

    ctx.runtime.backend = Sizes()
    rng = np.random.default_rng(3)
    a, b = (rng.standard_normal((144, 144)).astype(np.float32)
            for _ in range(2))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        ctx.gemm(a, b)
        assert compiles
        first = len(compiles)
        items.clear()
        out = ctx.gemm(a, b).array()
        assert len(compiles) == first
        # every smaller group, whether or not a call has run one yet
        key = StepGroupKey("gemm", False, False, "full", "full", 36, 36,
                           36, "float32", steps=4)
        with on_chip(0):
            for g in (1, 2, 3):
                tiles = [np.ones((36, 36), np.float32)] * (4 * g)
                assert len(inner.run_group(key, tiles, tiles).products) == g
        assert len(compiles) == first
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        ctx.close()
    assert sum(items) == 16 and min(items) < 4
    np.testing.assert_allclose(out, a @ b, rtol=1e-4, atol=1e-4)
