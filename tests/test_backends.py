"""Execution-backend suite: NumPy/JAX/Pallas parity across all six L3
routines x transpose/uplo/side variants, batched-dispatch launch
accounting, backend selection through every API layer, and how a group
goes to the device: its tiles as they are, stacked there, with the
bytes still copied on the host counted (``stage_copy_bytes``).

Parity runs the full pipeline (taskize -> schedule -> batched backend
dispatch -> epilogue) on 2 simulated devices with ragged edge tiles,
so group formation covers task-contraction AND per-step fallback
paths.  float32 inputs: the jax engine computes in float32 on default
CPU jax (see repro.backends.jax_backend), so float32 keeps the
comparison apples-to-apples.

The heaviest Pallas cases (interpret mode on CPU) are marked slow;
one case per routine stays in the fast lane.
"""
import dataclasses
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import telemetry
from repro.backends import available_backends, create_backend
from repro.backends.base import StepGroupKey
from repro.backends.jax_backend import JaxBackend
from repro.backends.numpy_backend import NumpyBackend
from repro.backends.pallas_backend import PallasBackend
from repro.core import blas3
from repro.core.runtime import BlasxRuntime, RuntimeConfig

M, N, K, TILE = 48, 40, 56, 16   # 40/56 leave ragged edge tiles
TOL = dict(rtol=2e-3, atol=2e-3)


def cfg(backend, **kw):
    kw.setdefault("n_devices", 2)
    kw.setdefault("mode", "sim")
    return RuntimeConfig(backend=backend, **kw)


def _f32(rng, *shape):
    return rng.standard_normal(shape).astype(np.float32)


def _run_case(case, backend):
    """Returns (got, want) for one routine/variant under one backend."""
    rng = np.random.default_rng(11)
    r = dict(case)
    routine = r.pop("routine")
    config = cfg(backend)
    if routine == "gemm":
        ta, tb = r["transa"], r["transb"]
        A = _f32(rng, *((M, K) if ta == "N" else (K, M)))
        B = _f32(rng, *((K, N) if tb == "N" else (N, K)))
        C = _f32(rng, M, N) if r.get("beta") else None
        got = blas3.gemm(A, B, C, tile=TILE, config=config, **r)
        want = blas3.ref_gemm(A, B, C, **r)
    elif routine == "syrk":
        tr = r["trans"]
        A = _f32(rng, *((M, K) if tr == "N" else (K, M)))
        C = _f32(rng, M, M) if r.get("beta") else None
        got = blas3.syrk(A, C, tile=TILE, config=config, **r)
        want = blas3.ref_syrk(A, C, **r)
    elif routine == "syr2k":
        tr = r["trans"]
        shape = (M, K) if tr == "N" else (K, M)
        A, B = _f32(rng, *shape), _f32(rng, *shape)
        C = _f32(rng, M, M) if r.get("beta") else None
        got = blas3.syr2k(A, B, C, tile=TILE, config=config, **r)
        want = blas3.ref_syr2k(A, B, C, **r)
    elif routine == "symm":
        side = r["side"]
        d = M if side == "L" else N
        A = _f32(rng, d, d)
        B = _f32(rng, M, N)
        C = _f32(rng, M, N) if r.get("beta") else None
        got = blas3.symm(A, B, C, tile=TILE, config=config, **r)
        want = blas3.ref_symm(A, B, C, **r)
    elif routine in ("trmm", "trsm"):
        side = r["side"]
        d = M if side == "L" else N
        A = _f32(rng, d, d)
        if routine == "trsm":  # keep the solve well-conditioned in f32
            A = A + d * np.eye(d, dtype=np.float32)
        B = _f32(rng, M, N)
        fn = blas3.trmm if routine == "trmm" else blas3.trsm
        ref = blas3.ref_trmm if routine == "trmm" else blas3.ref_trsm
        got = fn(A, B, tile=TILE, config=config, **r)
        want = ref(A, B, **r)
    else:  # pragma: no cover
        raise ValueError(routine)
    return got, want


CASES = [
    dict(routine="gemm", transa="N", transb="N"),
    dict(routine="gemm", transa="N", transb="T", beta=0.5),
    dict(routine="gemm", transa="T", transb="N", alpha=-0.5),
    dict(routine="gemm", transa="T", transb="T"),
    dict(routine="syrk", uplo="U", trans="N"),
    dict(routine="syrk", uplo="U", trans="T", beta=0.3),
    dict(routine="syrk", uplo="L", trans="N", alpha=0.7),
    dict(routine="syrk", uplo="L", trans="T"),
    dict(routine="syr2k", uplo="U", trans="N"),
    dict(routine="syr2k", uplo="U", trans="T"),
    dict(routine="syr2k", uplo="L", trans="N", beta=1.5),
    dict(routine="syr2k", uplo="L", trans="T"),
    dict(routine="symm", side="L", uplo="U"),
    dict(routine="symm", side="L", uplo="L", beta=0.5),
    dict(routine="symm", side="R", uplo="U"),
    dict(routine="symm", side="R", uplo="L"),
    dict(routine="trmm", side="L", uplo="U", transa="N"),
    dict(routine="trmm", side="L", uplo="L", transa="T", diag="U"),
    dict(routine="trmm", side="R", uplo="U", transa="T"),
    dict(routine="trmm", side="R", uplo="L", transa="N"),
    dict(routine="trsm", side="L", uplo="U", transa="N"),
    dict(routine="trsm", side="L", uplo="L", transa="T", diag="U"),
    dict(routine="trsm", side="R", uplo="U", transa="T"),
    dict(routine="trsm", side="R", uplo="L", transa="N"),
]


def _case_id(case):
    return "-".join(str(v) for v in case.values())


def _parity_params():
    params = []
    for backend in ("numpy", "jax", "pallas"):
        smoke_done = set()
        for case in CASES:
            marks = []
            if backend == "pallas":
                # interpret mode is slow on CPU: one fast case per
                # routine, the rest ride the slow lane
                if case["routine"] in smoke_done:
                    marks.append(pytest.mark.slow)
                smoke_done.add(case["routine"])
            params.append(pytest.param(
                backend, case, marks=marks,
                id=f"{backend}-{_case_id(case)}"))
    return params


@pytest.mark.parametrize("backend,case", _parity_params())
def test_backend_parity(backend, case):
    got, want = _run_case(case, backend)
    np.testing.assert_allclose(got, want, **TOL)


# ===================================================== launch accounting
def test_batched_dispatch_fewer_launches_than_tasks():
    """The acceptance property: batched backends issue strictly fewer
    kernel launches than scheduled tile tasks (and far fewer than
    k-steps); the per-step numpy baseline pays one launch per step."""
    rng = np.random.default_rng(0)
    A = rng.standard_normal((256, 256)).astype(np.float32)
    B = rng.standard_normal((256, 256)).astype(np.float32)
    per_backend = {}
    for backend in ("numpy", "jax"):
        rt = BlasxRuntime(cfg(backend, n_devices=1))
        out = blas3.gemm(A, B, tile=32, runtime=rt)
        np.testing.assert_allclose(out, A @ B, **TOL)
        per_backend[backend] = rt.launch_stats()
    jx, npy = per_backend["jax"], per_backend["numpy"]
    assert jx["tasks"] == 64 and jx["steps"] == 512
    assert jx["kernel_launches"] < jx["tasks"] < jx["steps"]
    assert jx["launches_saved"] == jx["steps"] - jx["kernel_launches"]
    # numpy = seed behavior: a launch per step, nothing saved
    assert npy["kernel_launches"] == npy["steps"] == 512
    assert npy["launches_saved"] == 0


def test_ledger_attributes_engines_pallas_fallback():
    """PallasBackend routes full-fill groups to the pallas engine and
    sym-fill diagonal steps to the jax fallback; the ledger splits the
    flops accordingly and accounts every dispatched step."""
    rng = np.random.default_rng(1)
    A = rng.standard_normal((96, 96)).astype(np.float32)
    B = rng.standard_normal((96, 64)).astype(np.float32)
    rt = BlasxRuntime(cfg("pallas", n_devices=1))
    out = blas3.symm(A, B, tile=32, runtime=rt)
    np.testing.assert_allclose(out, blas3.ref_symm(A, B), **TOL)
    ls = rt.launch_stats()
    assert ls["engine_flops"].get("pallas", 0) > 0   # full-fill rows
    assert ls["engine_flops"].get("jax", 0) > 0      # sym-fill diagonal
    total = sum(d.ledger.flops for d in rt.devices)
    assert sum(ls["engine_flops"].values()) == total
    assert ls["steps"] == 18   # 3x2 output tiles x 3 k-steps each


def test_launch_stats_reset():
    rng = np.random.default_rng(2)
    A = rng.standard_normal((64, 64))
    rt = BlasxRuntime(cfg("jax", n_devices=1))
    blas3.gemm(A, A, tile=32, runtime=rt)
    assert rt.launch_stats()["kernel_launches"] > 0
    rt.reset_stats()
    ls = rt.launch_stats()
    assert ls["kernel_launches"] == 0 and ls["steps"] == 0
    assert ls["engine_flops"] == {}


def test_threads_mode_jax_parity():
    """Batched dispatch composes with the faithful threaded engine."""
    rng = np.random.default_rng(3)
    A = rng.standard_normal((96, 80)).astype(np.float32)
    B = rng.standard_normal((80, 96)).astype(np.float32)
    out = blas3.gemm(A, B, tile=32,
                     config=cfg("jax", n_devices=2, mode="threads"))
    np.testing.assert_allclose(out, A @ B, **TOL)


# ==================================================== selection threading
def test_backend_selection_through_api_layers():
    from repro.api import BlasxContext, cblas

    rng = np.random.default_rng(4)
    A = rng.standard_normal((48, 32))
    B = rng.standard_normal((32, 40))
    # context kwarg
    with BlasxContext(backend="jax", tile=16) as ctx:
        out = ctx.gemm(A, B)
        st = ctx.stats()
        assert st["backend"] == "jax"
        assert st["launch"]["kernel_launches"] < st["launch"]["tasks"]
        np.testing.assert_allclose(out.array(), A @ B, **TOL)
    # legacy wrapper kwarg
    np.testing.assert_allclose(blas3.gemm(A, B, tile=16, backend="jax"),
                               A @ B, **TOL)
    # cblas kwarg (float64 in-place contract, f32 engine compute)
    C = np.zeros((48, 40))
    cblas.cblas_dgemm(cblas.CblasRowMajor, cblas.CblasNoTrans,
                      cblas.CblasNoTrans, 48, 40, 32, 1.0, A, 32,
                      B, 40, 0.0, C, 40, backend="jax")
    np.testing.assert_allclose(C, A @ B, **TOL)


def test_backend_mismatch_and_unknown_rejected():
    from repro.api import BlasxContext

    rt = BlasxRuntime(cfg("numpy"))
    with pytest.raises(ValueError, match="backend"):
        BlasxContext(runtime=rt, backend="jax")
    with pytest.raises(ValueError, match="unknown backend"):
        RuntimeConfig(backend="cuda")
    with pytest.raises(ValueError, match="unknown backend"):
        create_backend("nope")
    assert set(available_backends()) == {"numpy", "jax", "pallas"}


def test_legacy_kernel_alias():
    assert RuntimeConfig(kernel="jax").backend == "jax"
    assert RuntimeConfig(backend="pallas").kernel == "pallas"
    # explicit backend wins over the legacy spelling
    assert RuntimeConfig(kernel="numpy", backend="jax").kernel == "jax"


def test_execute_false_skips_dispatch():
    """Metadata-only runs schedule and account but never launch."""
    from repro.core.blas3 import shadow_run

    rt = BlasxRuntime(cfg("jax", n_devices=2, execute=False))
    shadow_run("gemm", 2048, tile=256, runtime=rt)
    ls = rt.launch_stats()
    assert ls["tasks"] > 0
    assert ls["kernel_launches"] == 0 and ls["steps"] == 0


# ============================== a group's tiles go to the device as they are
STAGED = ["float32", "bfloat16", "b_turned", "float64", "views",
          "ragged_fallback"]


def _tiles(rng, shape, n, dtype=np.float32):
    return [rng.standard_normal(shape).astype(dtype) for _ in range(n)]


def _staged_case(name):
    """One step group as ``run_group`` receives it: (key, A tiles, B
    tiles, bytes the host still has to copy or cast to send it).  Two
    items of three k-steps, except the ragged fallback's three single
    steps."""
    import ml_dtypes

    rng = np.random.default_rng(7)
    key = StepGroupKey("gemm", False, False, "full", "full", 32, 24, 16,
                       "float32", steps=3)
    n = 2 * key.steps
    a, b = _tiles(rng, (32, 24), n), _tiles(rng, (24, 16), n)
    if name == "float32":
        return key, a, b, 0
    if name == "bfloat16":
        cast = [t.astype(ml_dtypes.bfloat16) for t in a + b]
        return (dataclasses.replace(key, dtype="bfloat16"), cast[:n],
                cast[n:], 0)
    if name == "b_turned":
        # a SYRK's B side: each tile the ``.T`` view of a cached tile
        turned = [t.T for t in _tiles(rng, (16, 24), n)]
        return dataclasses.replace(key, op="syrk", transb=True), a, turned, 0
    if name == "float64":
        # a float64 group on the 32-bit engine: every tile cast
        wide = [t.astype(np.float64) for t in a + b]
        return (dataclasses.replace(key, dtype="float64"), wide[:n],
                wide[n:], sum(t.nbytes for t in a + b))
    if name == "views":
        # A tiles read straight out of a wider matrix: neither contiguous
        # nor the transpose of a contiguous array
        wide = rng.standard_normal((32 * n, 40)).astype(np.float32)
        views = [wide[32 * i:32 * (i + 1), 8:32] for i in range(n)]
        return key, views, b, sum(t.nbytes for t in a)
    assert name == "ragged_fallback"
    # single steps of a TRMM's triangular diagonal tile at a ragged edge
    # (24 of a 32 tile): the Pallas backend hands these to the JAX path
    key = StepGroupKey("trmm", False, False, "tri_l", "full", 24, 24, 8,
                       "float32", steps=1)
    return key, [np.tril(t) for t in _tiles(rng, (24, 24), 3)], \
        _tiles(rng, (24, 8), 3), 0


@pytest.fixture
def tracer():
    telemetry.reset()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


@pytest.mark.parametrize("name", STAGED)
@pytest.mark.parametrize("backend", [JaxBackend, PallasBackend],
                         ids=["jax", "pallas"])
def test_a_group_goes_to_the_device_as_its_tiles(tracer, backend, name):
    """Products as the numpy engine's; ``h2d_bytes`` exactly the tiles'
    bytes in the engine dtype; ``stage_copy_bytes`` only what could not
    go as it is."""
    import jax

    assert not jax.config.jax_enable_x64     # the 32-bit engine
    key, a, b, copied = _staged_case(name)
    tracer.enable()
    got = backend().run_group(key, a, b)
    tracer.disable()
    want = NumpyBackend().run_group(
        key, [t.astype(np.float64) for t in a],
        [t.astype(np.float64) for t in b]).products
    assert len(got.products) == len(want) == len(a) // key.steps
    # bfloat16 output: one rounding of each entry; float32 otherwise
    tol = 2.0 ** -8 if key.dtype == "bfloat16" else 1e-5
    for g, w in zip(got.products, want):
        assert g.dtype == np.dtype(key.dtype)
        np.testing.assert_allclose(g.astype(np.float64), w, rtol=tol,
                                   atol=tol * np.abs(w).max())
    engine_bytes = 2 if key.dtype == "bfloat16" else 4
    counters = tracer.snapshot()["counters"]
    assert counters["h2d_bytes"] == sum(t.size for t in a + b) * engine_bytes
    assert counters["stage_copy_bytes"] == copied


def test_no_host_buffer_holds_the_whole_group(monkeypatch):
    """The tiles go to the device one array each: no ``(G*steps, m, k)``
    staging buffer is made on the host."""
    from repro.backends import jax_backend

    assert not hasattr(jax_backend, "stack_items")
    key, a, b, _ = _staged_case("float32")
    made = []
    for fn in ("empty", "stack", "concatenate"):
        real = getattr(np, fn)

        def spy(*args, _real=real, **kw):
            out = _real(*args, **kw)
            made.append(out.shape)
            return out

        monkeypatch.setattr(np, fn, spy)
    JaxBackend().run_group(key, a, b)
    monkeypatch.undo()
    group = {(len(a), key.m, key.k), (len(b), key.k, key.n),
             (len(a) // key.steps, key.steps, key.m, key.k),
             (len(b) // key.steps, key.steps, key.k, key.n)}
    assert not group & set(made)


# the benchmark's reader of ``stage_copy_bytes``
STAGE_COPY = "stage_copy_bytes_per_call.l3"


def _bench():
    """The benchmark package and the shrink of its cells to a test size."""
    root = Path(__file__).resolve().parent.parent
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    spec = importlib.util.spec_from_file_location(
        "bench_tests_conftest", root / "tests" / "bench" / "conftest.py")
    conftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(conftest)
    return conftest.shrink


def _record(calls):
    from bench.run import Record
    from bench.spans import Spans

    rec = Record(window_s=10.0, spans=Spans(annotate=False), groups=0,
                 compiles=0)
    rec.spans.calls = calls
    return rec


@pytest.mark.parametrize("cell", ["n8192.gemm", "n8192.chol_update",
                                  "n8192.gemm_fresh", "pod.ring_gemm"])
def test_no_cell_copies_a_tile_on_the_host(tracer, cell):
    """Each cell at a test size, one call of its loop: every tile goes as
    it is, the Cholesky update's SYRK B side as the tiles it turns."""
    import jax  # noqa: F401  (registers bfloat16 with numpy)

    from bench.cell import find_cell, loop_class, metric_reader

    c = _bench()(find_cell(cell))
    loop = loop_class(c)(c, 2 ** 31 + 5)
    try:
        loop.warm()
        tracer.reset()
        tracer.enable()
        loop.iterate(0)
        tracer.disable()
    finally:
        loop.close()
    counters = tracer.snapshot()["counters"]
    assert counters["h2d_bytes"] > 0
    assert metric_reader(STAGE_COPY)(_record(1)) == 0.0


def test_the_reader_counts_per_api_call_and_is_silent_without_a_count(
        tracer):
    _bench()
    from bench.cell import metric_reader

    read = metric_reader(STAGE_COPY)
    # nothing recorded yet
    assert read(_record(3)) is None
    key, a, b, copied = _staged_case("views")
    tracer.enable()
    with tracer.span("blasx.call"):
        JaxBackend().run_group(key, a, b)
    tracer.disable()
    assert read(_record(2)) == copied / 2
    # a library that records spans but keeps no such counter
    tracer.reset()
    tracer.enable()
    with tracer.span("blasx.call"):
        tracer.count("h2d_bytes", 8)
    tracer.disable()
    assert read(_record(1)) is None
