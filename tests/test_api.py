"""Two-layer API tests: the persistent BlasxContext handle layer (warm
tile caches, per-call ledgers, futures, batching), the CBLAS legacy
layer, and the three-surface equivalence required by the redesign —
every L3 routine must produce oracle-identical results through the
legacy blas3 functions, BlasxContext methods, and cblas_* wrappers."""
import concurrent.futures
import threading
import time

import numpy as np
import pytest

from repro.api import (BackpressureError, BlasxContext, CblasColMajor,
                       CblasLower, CblasNonUnit, CblasNoTrans, CblasRight,
                       CblasRowMajor, CblasTrans, CblasUnit, CblasUpper,
                       MatrixHandle, SerialExecutor, cblas_dgemm,
                       cblas_dsymm, cblas_dsyr2k, cblas_dsyrk, cblas_dtrmm,
                       cblas_dtrsm)
from repro.core import (blas3, ref_gemm, ref_symm, ref_syr2k, ref_syrk,
                        ref_trmm, ref_trsm)
from repro.core.runtime import RuntimeConfig

RNG = np.random.default_rng(11)
TOL = dict(rtol=1e-10, atol=1e-10)


def _ctx(**kw):
    kw.setdefault("n_devices", 2)
    kw.setdefault("mode", "sim")
    kw.setdefault("cache_bytes", 64 << 20)
    return BlasxContext(RuntimeConfig(**kw), tile=48)


def _spd(n):
    """Well-conditioned triangular-solve operand."""
    return RNG.standard_normal((n, n)) / n + np.eye(n)


# ===================================================== three-surface parity
# Each case: (routine, kwargs, operand builder, oracle); beta != 0
# accumulation everywhere a beta exists, side='R' for symm/trmm/trsm.
def _case_gemm():
    A = RNG.standard_normal((110, 70))
    B = RNG.standard_normal((70, 90))
    C = RNG.standard_normal((110, 90))
    kw = dict(alpha=1.3, beta=-0.7)
    return (A, B, C), kw, ref_gemm(A, B, C, **kw)


def _case_syrk():
    A = RNG.standard_normal((96, 60))
    C = RNG.standard_normal((96, 96))
    kw = dict(alpha=0.8, beta=1.4, uplo="L")
    return (A, C), kw, ref_syrk(A, C, **kw)


def _case_syr2k():
    A = RNG.standard_normal((88, 50))
    B = RNG.standard_normal((88, 50))
    C = RNG.standard_normal((88, 88))
    kw = dict(alpha=0.5, beta=0.9, uplo="U")
    return (A, B, C), kw, ref_syr2k(A, B, C, **kw)


def _case_symm():
    B = RNG.standard_normal((72, 100))
    A = RNG.standard_normal((100, 100))      # side='R': A is n x n
    C = RNG.standard_normal((72, 100))
    kw = dict(alpha=1.1, beta=0.6, side="R", uplo="L")
    return (A, B, C), kw, ref_symm(A, B, C, **kw)


def _case_trmm():
    A = RNG.standard_normal((84, 84))
    B = RNG.standard_normal((96, 84))        # side='R'
    kw = dict(alpha=0.9, side="R", uplo="U", transa="T", diag="U")
    return (A, B), kw, ref_trmm(A, B, **kw)


def _case_trsm():
    A = _spd(80)
    B = RNG.standard_normal((64, 80))        # side='R'
    kw = dict(alpha=1.2, side="R", uplo="L", transa="N", diag="N")
    return (A, B), kw, ref_trsm(A, B, **kw)


CASES = {
    "gemm": _case_gemm, "syrk": _case_syrk, "syr2k": _case_syr2k,
    "symm": _case_symm, "trmm": _case_trmm, "trsm": _case_trsm,
}


@pytest.mark.parametrize("routine", sorted(CASES))
def test_legacy_surface_matches_oracle(routine):
    ops, kw, want = CASES[routine]()
    out = getattr(blas3, routine)(*ops, tile=48, **kw)
    np.testing.assert_allclose(out, want, **TOL)


@pytest.mark.parametrize("routine", sorted(CASES))
def test_context_surface_matches_oracle(routine):
    ops, kw, want = CASES[routine]()
    with _ctx() as ctx:
        out = getattr(ctx, routine)(*ops, **kw)
        assert isinstance(out, MatrixHandle)
        np.testing.assert_allclose(out.array(), want, **TOL)


def test_cblas_surface_matches_oracle_all_six():
    with _ctx() as ctx:
        (A, B, C), kw, want = _case_gemm()
        Cb = np.array(C)
        m, n, k = 110, 90, 70
        cblas_dgemm(CblasRowMajor, CblasNoTrans, CblasNoTrans, m, n, k,
                    kw["alpha"], A, k, B, n, kw["beta"], Cb, n, ctx=ctx)
        np.testing.assert_allclose(Cb, want, **TOL)

        (A, C), kw, want = _case_syrk()
        Cb = np.array(C)
        cblas_dsyrk(CblasRowMajor, CblasLower, CblasNoTrans, 96, 60,
                    kw["alpha"], A, 60, kw["beta"], Cb, 96, ctx=ctx)
        np.testing.assert_allclose(Cb, want, **TOL)

        (A, B, C), kw, want = _case_syr2k()
        Cb = np.array(C)
        cblas_dsyr2k(CblasRowMajor, CblasUpper, CblasNoTrans, 88, 50,
                     kw["alpha"], A, 50, B, 50, kw["beta"], Cb, 88, ctx=ctx)
        np.testing.assert_allclose(Cb, want, **TOL)

        (A, B, C), kw, want = _case_symm()
        Cb = np.array(C)
        cblas_dsymm(CblasRowMajor, CblasRight, CblasLower, 72, 100,
                    kw["alpha"], A, 100, B, 100, kw["beta"], Cb, 100,
                    ctx=ctx)
        np.testing.assert_allclose(Cb, want, **TOL)

        (A, B), kw, want = _case_trmm()
        Bb = np.array(B)
        cblas_dtrmm(CblasRowMajor, CblasRight, CblasUpper, CblasTrans,
                    CblasUnit, 96, 84, kw["alpha"], A, 84, Bb, 84, ctx=ctx)
        np.testing.assert_allclose(Bb, want, **TOL)

        (A, B), kw, want = _case_trsm()
        Bb = np.array(B)
        cblas_dtrsm(CblasRowMajor, CblasRight, CblasLower, CblasNoTrans,
                    CblasNonUnit, 64, 80, kw["alpha"], A, 80, Bb, 80,
                    ctx=ctx)
        np.testing.assert_allclose(Bb, want, rtol=1e-8, atol=1e-8)


# ------------------------------------------------- §III-C transpose paths
@pytest.mark.parametrize("side", ["L", "R"])
@pytest.mark.parametrize("uplo", ["U", "L"])
def test_context_symm_sides_with_accumulation(side, uplo):
    m, n = 60, 84
    B = RNG.standard_normal((m, n))
    dim = m if side == "L" else n
    A = RNG.standard_normal((dim, dim))
    C = RNG.standard_normal((m, n))
    with _ctx() as ctx:
        out = ctx.symm(A, B, C, alpha=0.7, beta=1.9, side=side, uplo=uplo)
    np.testing.assert_allclose(
        out.array(), ref_symm(A, B, C, alpha=0.7, beta=1.9, side=side,
                              uplo=uplo), **TOL)


@pytest.mark.parametrize("side", ["L", "R"])
@pytest.mark.parametrize("transa", ["N", "T"])
def test_context_trmm_trsm_sides(side, transa):
    m, n = 72, 56
    B = RNG.standard_normal((m, n))
    dim = m if side == "L" else n
    A = _spd(dim)
    with _ctx() as ctx:
        out_m = ctx.trmm(A, B, alpha=1.3, side=side, transa=transa)
        out_s = ctx.trsm(A, B, alpha=1.3, side=side, transa=transa)
    np.testing.assert_allclose(
        out_m.array(), ref_trmm(A, B, alpha=1.3, side=side, transa=transa),
        **TOL)
    np.testing.assert_allclose(
        out_s.array(), ref_trsm(A, B, alpha=1.3, side=side, transa=transa),
        rtol=1e-8, atol=1e-8)


# ------------------------------------------------ side-R TRSM, run natively
# B's (m, n) at tile 16: A one tile (a blocked Cholesky's panel solve,
# B of several tile rows); A of 3x3 tiles (k-chains along X's rows); m
# and n both ragged
SIDE_R_SHAPES = {"one_tile": (40, 16), "chains": (32, 48),
                 "ragged": (27, 40)}
F32_TOL = dict(rtol=2e-3, atol=2e-3)   # the precision suite's f32 limit


def _side_r_operands(shape):
    m, n = SIDE_R_SHAPES[shape]
    # small off-diagonal, dominant diagonal: well conditioned for diag U too
    A = RNG.standard_normal((n, n)) / n + np.eye(n)
    return A, RNG.standard_normal((m, n))


@pytest.mark.parametrize("backend,hold", [("numpy", "raw"),
                                          ("numpy", "handles"),
                                          ("jax", "raw"),
                                          ("pallas", "handles")])
@pytest.mark.parametrize("shape", sorted(SIDE_R_SHAPES))
@pytest.mark.parametrize("alpha", [1.0, -0.5])
@pytest.mark.parametrize("diag", ["N", "U"])
@pytest.mark.parametrize("transa", ["N", "T"])
@pytest.mark.parametrize("uplo", ["U", "L"])
def test_trsm_side_r_native(uplo, transa, diag, alpha, shape, backend, hold):
    A, B = _side_r_operands(shape)
    kw = dict(alpha=alpha, uplo=uplo, diag=diag)
    want = ref_trsm(A, B, side="R", transa=transa, **kw)
    with BlasxContext(RuntimeConfig(n_devices=1, mode="sim",
                                    backend=backend), tile=16) as ctx:
        ops = (A, B) if hold == "raw" else (ctx.tile(A), ctx.tile(B))
        out = ctx.trsm(*ops, side="R", transa=transa, **kw)
        assert ctx.n_calls == 1
        assert out.shape == B.shape
        np.testing.assert_allclose(
            out.array(), want,
            **(dict(rtol=1e-8, atol=1e-8) if backend == "numpy"
               else F32_TOL))
        if backend == "numpy" and shape == "one_tile":
            # the former route: a left-side solve of the transposed copy
            flip = "T" if transa == "N" else "N"
            old = ctx.trsm(A, np.ascontiguousarray(B.T), side="L",
                           transa=flip, **kw).array().T
            np.testing.assert_array_equal(out.array(), old)


@pytest.mark.parametrize("mode", ["threads", "sim"])
def test_trsm_side_r_native_on_two_devices(mode):
    """Two devices (threads, or sim, whose schedule spreads X's row
    chains over both) agree with sim on one."""
    A, B = _side_r_operands("ragged")
    B = np.vstack([B, RNG.standard_normal((70, B.shape[1]))])
    kw = dict(alpha=-0.5, side="R", uplo="U", transa="T")
    with BlasxContext(RuntimeConfig(n_devices=1, mode="sim"),
                      tile=16) as one:
        want = one.trsm(A, B, **kw).array()
    with BlasxContext(RuntimeConfig(n_devices=2, mode=mode),
                      tile=16) as two:
        got = two.trsm(A, B, **kw).array()
        if mode == "sim":   # a threads worker may take every task
            assert all(d.ledger.tasks for d in two.runtime.devices)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(got, ref_trsm(A, B, **kw), rtol=1e-8,
                               atol=1e-8)


@pytest.mark.parametrize("routine", ["gemm", "syrk", "syr2k", "symm"])
def test_beta_accumulation_matches_oracle(routine):
    """beta != 0 reads C through the ledgered bypass path — verify the
    accumulation term end to end for every beta-bearing routine."""
    n, k = 64, 40
    A = RNG.standard_normal((n, k))
    B = RNG.standard_normal((n, k))
    Bs = RNG.standard_normal((n, n))
    C = RNG.standard_normal((n, n))
    with _ctx() as ctx:
        if routine == "gemm":
            out = ctx.gemm(A, B, C, alpha=1.1, beta=2.3, transb="T")
            want = ref_gemm(A, B, C, alpha=1.1, beta=2.3, transb="T")
        elif routine == "syrk":
            out = ctx.syrk(A, C, alpha=1.1, beta=2.3)
            want = ref_syrk(A, C, alpha=1.1, beta=2.3)
        elif routine == "syr2k":
            out = ctx.syr2k(A, B, C, alpha=1.1, beta=2.3)
            want = ref_syr2k(A, B, C, alpha=1.1, beta=2.3)
        else:
            out = ctx.symm(Bs, A, np.zeros((n, k)) + C[:, :k], alpha=1.1,
                           beta=2.3)
            want = ref_symm(Bs, A, C[:, :k], alpha=1.1, beta=2.3)
    np.testing.assert_allclose(out.array(), want, **TOL)


# ==================================================== warm-cache contract
def test_chained_calls_reuse_cached_tiles():
    """The redesign's core claim: a second call on the same handles
    moves strictly fewer H2D bytes than the first (acceptance
    criterion: chained < 2 cold calls)."""
    A = RNG.standard_normal((512, 512))
    B = RNG.standard_normal((512, 512))
    with _ctx(n_devices=1, cache_bytes=256 << 20) as ctx:
        Ah, Bh = ctx.tile(A), ctx.tile(B)
        ctx.gemm(Ah, Bh)
        cold = ctx.last_call
        ctx.gemm(Ah, Bh)
        warm = ctx.last_call
        assert warm.h2d_bytes < cold.h2d_bytes
        assert warm.h2d_bytes == 0          # single device: all L1 hits
        assert warm.l1_hits > 0 and warm.l1_misses == 0
        # chained total strictly beats two cold calls
        assert cold.h2d_bytes + warm.h2d_bytes < 2 * cold.h2d_bytes


def test_chained_beats_per_call_api_multi_device():
    """Same comparison across the per-call legacy API — the handle
    path must win on input traffic even with multiple devices."""
    A = RNG.standard_normal((768, 768))
    B = RNG.standard_normal((768, 768))

    def cold_bytes():
        ctx = _ctx(n_devices=3)
        ctx.gemm(A, B, tile=128)
        return ctx.last_call.h2d_bytes

    two_cold = cold_bytes() + cold_bytes()
    with _ctx(n_devices=3) as ctx:
        Ah, Bh = ctx.tile(A, 128), ctx.tile(B, 128)
        r1 = ctx.gemm(Ah, Bh)
        r2 = ctx.gemm(Ah, Bh)
        chained = ctx.calls[-2].h2d_bytes + ctx.calls[-1].h2d_bytes
        np.testing.assert_allclose(r2.array(), A @ B, **TOL)
    assert chained < two_cold


def test_output_handle_feeds_next_call():
    """C := A@B then D := C@B without re-tiling C (Cholesky-sweep
    shape); numerics stay oracle-exact."""
    n = 256
    A = RNG.standard_normal((n, n))
    B = RNG.standard_normal((n, n))
    with _ctx() as ctx:
        Ch = ctx.gemm(ctx.tile(A), ctx.tile(B))
        Dh = ctx.gemm(Ch, ctx.tile(B))
        np.testing.assert_allclose(Dh.array(), (A @ B) @ B, **TOL)


def test_mixed_routine_chain_matches_oracles():
    """syrk -> trsm -> gemm sweep through one context (warm caches all
    along); each stage checked against its oracle."""
    n = 192
    A = RNG.standard_normal((n, 96))
    L = _spd(n)
    with _ctx() as ctx:
        Ah = ctx.tile(A)
        S = ctx.syrk(Ah, alpha=1.0, uplo="U")
        np.testing.assert_allclose(S.array(), ref_syrk(A, alpha=1.0,
                                                       uplo="U"), **TOL)
        X = ctx.trsm(ctx.tile(L), Ah, uplo="L")
        np.testing.assert_allclose(X.array(), ref_trsm(L, A, uplo="L"),
                                   rtol=1e-8, atol=1e-8)
        G = ctx.gemm(X, Ah, transb="T")
        np.testing.assert_allclose(
            G.array(), ref_trsm(L, A, uplo="L") @ A.T, rtol=1e-8, atol=1e-8)


def test_handle_invalidate_after_mutation():
    A = RNG.standard_normal((128, 128))
    B = RNG.standard_normal((128, 128))
    with _ctx(n_devices=1) as ctx:
        Ah, Bh = ctx.tile(A), ctx.tile(B)
        ctx.gemm(Ah, Bh)
        A2 = 2.0 * A                       # handles alias the caller array,
        Ah.array()[:] = A2                 # so snapshot the new value first
        dropped = Ah.invalidate()
        assert dropped > 0
        out = ctx.gemm(Ah, Bh)
        np.testing.assert_allclose(out.array(), A2 @ B, **TOL)


def test_cross_context_handles_rejected():
    with _ctx() as c1, _ctx() as c2:
        h = c1.tile(RNG.standard_normal((32, 32)))
        with pytest.raises(ValueError):
            c2.gemm(h, h)


# ============================================== stats / ledgers / lifecycle
def test_per_call_records_and_cumulative_stats():
    A = RNG.standard_normal((256, 256))
    with _ctx() as ctx:
        Ah = ctx.tile(A)
        ctx.gemm(Ah, Ah)
        ctx.syrk(Ah)
        assert [c.routine for c in ctx.calls] == ["gemm", "syrk"]
        assert all(c.tasks > 0 for c in ctx.calls)
        st = ctx.stats()
        assert st["calls"] == 2
        assert st["comm_bytes"]["h2d"] == sum(c.h2d_bytes for c in ctx.calls)
        assert st["comm_bytes"]["d2h"] == sum(c.d2h_bytes for c in ctx.calls)
        ctx.reset_stats()                  # counters drop, caches stay
        assert ctx.stats()["calls"] == 0
        assert ctx.stats()["comm_bytes"]["h2d"] == 0
        ctx.gemm(Ah, Ah)
        assert ctx.last_call.h2d_bytes == 0   # still warm after reset_stats
        dev0 = ctx.runtime.devices[0].alru
        assert dev0.lifetime_misses > dev0.misses  # lifetime survives reset


def test_context_close_and_reset():
    A = RNG.standard_normal((128, 128))
    ctx = _ctx()
    Ah = ctx.tile(A)
    ctx.gemm(Ah, Ah)
    ctx.reset()                            # cold restart keeps ctx usable
    ctx.gemm(Ah, Ah)
    assert ctx.last_call.h2d_bytes > 0     # caches were dropped
    ctx.close()
    assert ctx.closed
    with pytest.raises(RuntimeError):
        ctx.gemm(Ah, Ah)
    ctx.close()                            # idempotent


# ================================================================== async
def test_submit_returns_future_with_result():
    A = RNG.standard_normal((192, 192))
    B = RNG.standard_normal((192, 192))
    with _ctx() as ctx:
        f1 = ctx.submit("gemm", A, B, alpha=0.5)
        f2 = ctx.submit("syrk", A)
        out1, out2 = f1.result(timeout=60), f2.result(timeout=60)
        assert f1.done() and f2.done()
        assert f1.exception() is None
        np.testing.assert_allclose(out1.array(), 0.5 * A @ B, **TOL)
        np.testing.assert_allclose(out2.array(), ref_syrk(A), **TOL)


def test_submit_propagates_errors_and_validates_names():
    with _ctx() as ctx:
        f = ctx.submit("gemm", np.zeros((3, 4)), np.zeros((5, 6)))
        with pytest.raises(ValueError):
            f.result(timeout=60)
        assert isinstance(f.exception(), ValueError)
        with pytest.raises(ValueError):
            ctx.submit("not_a_routine")


def test_submitted_chain_overlaps_in_order():
    A = RNG.standard_normal((160, 160))
    with _ctx() as ctx:
        Ah = ctx.tile(A)
        futs = [ctx.submit("gemm", Ah, Ah) for _ in range(4)]
        outs = [f.result(timeout=60) for f in futs]
        for o in outs:
            np.testing.assert_allclose(o.array(), A @ A, **TOL)
        # later submissions ran warm
        assert ctx.calls[-1].h2d_bytes < ctx.calls[0].h2d_bytes


def test_serial_executor_backpressure_bound():
    """Fails before the max_pending bound existed: the executor
    accepted unbounded work and never raised."""
    ex = SerialExecutor(max_pending=1)
    gate = threading.Event()
    running = threading.Event()
    try:
        f1 = ex.submit(lambda: (running.set(), gate.wait(30)) and 1 or 1)
        assert running.wait(30)
        with pytest.raises(BackpressureError, match="max_pending"):
            ex.submit(lambda: 2)
        assert ex.pending == 1
        gate.set()
        assert f1.result(timeout=30) == 1
        # slot freed on completion: submitting works again
        assert ex.submit(lambda: 3).result(timeout=30) == 3
    finally:
        gate.set()
        ex.shutdown()


def test_serial_executor_blocking_submit_waits_for_slot():
    ex = SerialExecutor(max_pending=1)
    gate = threading.Event()
    try:
        f1 = ex.submit(lambda: gate.wait(30))
        threading.Timer(0.05, gate.set).start()
        f2 = ex.submit(lambda: 42, block=True, block_timeout=30)
        assert f2.result(timeout=30) == 42
        assert f1.result(timeout=30)
    finally:
        gate.set()
        ex.shutdown()


def test_serial_executor_blocking_submit_times_out():
    ex = SerialExecutor(max_pending=1)
    gate = threading.Event()
    try:
        ex.submit(lambda: gate.wait(30))
        with pytest.raises(BackpressureError, match="timed out"):
            ex.submit(lambda: 2, block=True, block_timeout=0.05)
    finally:
        gate.set()
        ex.shutdown()


def test_serial_executor_unbounded_stays_legacy():
    ex = SerialExecutor()                   # max_pending=None
    gate = threading.Event()
    try:
        futs = [ex.submit(lambda: gate.wait(30)) for _ in range(20)]
        gate.set()
        assert all(f.result(timeout=30) for f in futs)
    finally:
        gate.set()
        ex.shutdown()


def test_blasfuture_cancel_semantics():
    """A queued submission cancels; result()/exception() then raise
    CancelledError; a running submission refuses to cancel."""
    ex = SerialExecutor()
    gate = threading.Event()
    running = threading.Event()
    try:
        f1 = ex.submit(lambda: (running.set(), gate.wait(30)) and "ran")
        assert running.wait(30)
        f2 = ex.submit(lambda: "never")
        assert not f1.cancel()              # already running
        assert f2.cancel()                  # still queued
        assert f2.cancelled() and f2.done()
        assert "cancelled" in repr(f2)
        with pytest.raises(concurrent.futures.CancelledError):
            f2.result(timeout=1)
        with pytest.raises(concurrent.futures.CancelledError):
            f2.exception(timeout=1)
        gate.set()
        assert f1.result(timeout=30) == "ran"
        assert not f1.cancelled()
    finally:
        gate.set()
        ex.shutdown()


def test_cancelled_submission_frees_backpressure_slot():
    ex = SerialExecutor(max_pending=2)
    gate = threading.Event()
    try:
        ex.submit(lambda: gate.wait(30))
        doomed = ex.submit(lambda: None)
        with pytest.raises(BackpressureError):
            ex.submit(lambda: None)
        assert doomed.cancel()
        f = ex.submit(lambda: "fits")       # cancel freed the slot
        gate.set()
        assert f.result(timeout=30) == "fits"
    finally:
        gate.set()
        ex.shutdown()


def test_ctx_submit_close_race_is_clean():
    """submit during close raises cleanly, in-flight work completes,
    and the executor does not leak."""
    gate = threading.Event()
    running = threading.Event()
    ctx = _ctx()
    f = ctx.submit(lambda: (running.set(), gate.wait(30)) and "done")
    assert running.wait(30)
    closer = threading.Thread(target=ctx.close)
    closer.start()
    deadline = time.monotonic() + 30
    while not ctx.closed and time.monotonic() < deadline:
        time.sleep(0.001)
    assert ctx.closed
    with pytest.raises(RuntimeError):       # close flagged before drain
        ctx.submit("gemm", np.eye(8), np.eye(8))
    gate.set()
    closer.join(timeout=30)
    assert not closer.is_alive()
    assert f.result(timeout=30) == "done"   # in-flight work completed
    assert ctx._executor is None            # no executor leak


def test_ctx_submit_fifo_under_concurrent_submitters():
    """The single-lane executor preserves each submitter's relative
    order even when many threads race on submit."""
    order = []
    with _ctx() as ctx:
        def submitter(tid):
            for i in range(8):
                ctx.submit(lambda t=tid, k=i: order.append((t, k)))

        threads = [threading.Thread(target=submitter, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        ctx.submit(lambda: None).result(timeout=30)  # drain barrier
    assert len(order) == 32
    for tid in range(4):
        ks = [k for t, k in order if t == tid]
        assert ks == sorted(ks)             # per-thread FIFO preserved


# ================================================================ batched
def test_gemm_batched_shared_weight_handle():
    W = RNG.standard_normal((128, 96))
    xs = [RNG.standard_normal((64, 128)) for _ in range(5)]
    with _ctx(n_devices=1) as ctx:
        Wh = ctx.tile(W)
        outs = ctx.gemm_batched(xs, [Wh] * len(xs))
        for x, o in zip(xs, outs):
            np.testing.assert_allclose(o.array(), x @ W, **TOL)
        # W transferred once, then served from the warm cache
        w_bytes = W.nbytes
        total_h2d = sum(c.h2d_bytes for c in ctx.calls)
        cold_would_be = sum(x.nbytes for x in xs) + len(xs) * w_bytes
        assert total_h2d <= cold_would_be - (len(xs) - 1) * w_bytes


def test_gemm_batched_submittable_async():
    """Regression: submitting the batch itself must not deadlock the
    single-worker executor (the batch loops synchronously inside)."""
    A = RNG.standard_normal((64, 64))
    with _ctx() as ctx:
        f = ctx.submit("gemm_batched", [A, A], [A, A])
        outs = f.result(timeout=60)
        assert f.done()
        for o in outs:
            np.testing.assert_allclose(o.array(), A @ A, **TOL)


def test_gemm_strided_batched_broadcasts_weights():
    x = RNG.standard_normal((3, 48, 64))
    W = RNG.standard_normal((64, 32))
    C = RNG.standard_normal((3, 48, 32))
    with _ctx() as ctx:
        out = ctx.gemm_strided_batched(x, W, C, alpha=1.5, beta=0.5)
    assert out.shape == (3, 48, 32)
    for i in range(3):
        np.testing.assert_allclose(
            out[i], 1.5 * x[i] @ W + 0.5 * C[i], **TOL)


def test_gemm_batched_validates_lengths():
    with _ctx() as ctx:
        with pytest.raises(ValueError):
            ctx.gemm_batched([np.eye(8)], [np.eye(8), np.eye(8)])


# ================================================================= cblas
def test_cblas_flat_buffers_row_and_col_major():
    m, n, k = 30, 24, 18
    A = RNG.standard_normal((m, k))
    B = RNG.standard_normal((k, n))
    C = RNG.standard_normal((m, n))
    want = ref_gemm(A, B, C, alpha=1.2, beta=0.8)
    with _ctx() as ctx:
        # row-major flat with padded leading dimensions
        lda, ldb, ldc = k + 3, n + 2, n + 5
        Af = np.zeros(m * lda)
        Af.reshape(m, lda)[:, :k] = A
        Bf = np.zeros(k * ldb)
        Bf.reshape(k, ldb)[:, :n] = B
        Cf = np.zeros(m * ldc)
        Cf.reshape(m, ldc)[:, :n] = C
        cblas_dgemm(CblasRowMajor, CblasNoTrans, CblasNoTrans, m, n, k,
                    1.2, Af, lda, Bf, ldb, 0.8, Cf, ldc, ctx=ctx)
        np.testing.assert_allclose(Cf.reshape(m, ldc)[:, :n], want, **TOL)

        # column-major flat (Fortran layout)
        lda, ldb, ldc = m + 1, k + 4, m + 2
        Af = np.zeros(lda * k)
        Af.reshape(k, lda).T[:m, :] = A
        Bf = np.zeros(ldb * n)
        Bf.reshape(n, ldb).T[:k, :] = B
        Cf = np.zeros(ldc * n)
        Cf.reshape(n, ldc).T[:m, :] = C
        cblas_dgemm(CblasColMajor, CblasNoTrans, CblasNoTrans, m, n, k,
                    1.2, Af, lda, Bf, ldb, 0.8, Cf, ldc, ctx=ctx)
        np.testing.assert_allclose(Cf.reshape(n, ldc).T[:m, :], want, **TOL)


def test_cblas_transposed_inputs():
    m, n, k = 26, 22, 34
    A = RNG.standard_normal((k, m))       # op(A) = A^T
    B = RNG.standard_normal((n, k))       # op(B) = B^T
    C = np.zeros((m, n))
    with _ctx() as ctx:
        cblas_dgemm(CblasRowMajor, CblasTrans, CblasTrans, m, n, k,
                    1.0, A, m, B, k, 0.0, C, n, ctx=ctx)
    np.testing.assert_allclose(C, A.T @ B.T, **TOL)


def test_cblas_syrk_preserves_opposite_triangle_beta_zero():
    n, k = 40, 16
    A = RNG.standard_normal((n, k))
    C = RNG.standard_normal((n, n))
    orig = C.copy()
    with _ctx() as ctx:
        cblas_dsyrk(CblasRowMajor, CblasUpper, CblasNoTrans, n, k,
                    1.0, A, k, 0.0, C, n, ctx=ctx)
    low = np.tril_indices(n, -1)
    np.testing.assert_array_equal(C[low], orig[low])
    np.testing.assert_allclose(np.triu(C), np.triu(A @ A.T), **TOL)


def test_cblas_rejects_bad_buffers():
    with _ctx() as ctx:
        C = np.zeros((4, 4), dtype=np.float32)
        with pytest.raises(TypeError):
            cblas_dgemm(CblasRowMajor, CblasNoTrans, CblasNoTrans, 4, 4, 4,
                        1.0, np.eye(4), 4, np.eye(4), 4, 0.0, C, 4, ctx=ctx)
        with pytest.raises(ValueError):   # ld smaller than n cols
            cblas_dgemm(CblasRowMajor, CblasNoTrans, CblasNoTrans, 4, 4, 4,
                        1.0, np.zeros(16), 2, np.eye(4), 4, 0.0,
                        np.zeros((4, 4)), 4, ctx=ctx)
        with pytest.raises(ValueError):   # bogus trans flag
            cblas_dgemm(CblasRowMajor, 999, CblasNoTrans, 4, 4, 4,
                        1.0, np.eye(4), 4, np.eye(4), 4, 0.0,
                        np.zeros((4, 4)), 4, ctx=ctx)


def test_cblas_rejects_list_output_buffer():
    """A list passes np.asarray but the update would land in a detached
    copy — must be rejected loudly, not silently dropped."""
    with _ctx() as ctx:
        with pytest.raises(TypeError):
            cblas_dgemm(CblasRowMajor, CblasNoTrans, CblasNoTrans, 2, 2, 2,
                        1.0, np.eye(2), 2, np.eye(2), 2, 0.0,
                        [0.0] * 4, 2, ctx=ctx)


def test_legacy_output_dtype_preserved():
    """Backward-compat contract: output dtype follows C (or B for trmm)
    exactly as the pre-context implementation did."""
    A = RNG.standard_normal((40, 40))
    B32 = RNG.standard_normal((40, 40)).astype(np.float32)
    C32 = RNG.standard_normal((40, 40)).astype(np.float32)
    assert blas3.gemm(A, B32, C32, beta=1.0, tile=16).dtype == np.float32
    assert blas3.trmm(A, B32, tile=16).dtype == np.float32
    assert blas3.syrk(B32, C32, beta=0.5, tile=16).dtype == np.float32


def test_side_r_leaves_no_intermediate_tiles():
    """The §III-C reduction's intermediate left-side output must not
    squat on cache capacity in a long-lived context."""
    A = _spd(48)
    B = RNG.standard_normal((32, 48))
    with _ctx(n_devices=1) as ctx:
        res = ctx.trsm(A, B, side="R")
        live = {k.matrix_id for d in ctx.runtime.devices[0:1]
                for k in d.alru.keys()}
        # nothing cached except (possibly) tiles of operands that still
        # have a reachable handle — the intermediate result id is gone
        assert res.matrix_id not in live  # transposed copy never ran
        assert len(live) == 0             # ephemerals + intermediate dropped


def test_tile_mismatch_rejected_in_all_two_operand_routines():
    with _ctx() as ctx:
        a64 = ctx.tile(RNG.standard_normal((64, 64)), 64)
        b32 = ctx.tile(RNG.standard_normal((64, 64)), 32)
        for call in (lambda: ctx.gemm(a64, b32),
                     lambda: ctx.syr2k(a64, b32),
                     lambda: ctx.symm(a64, b32),
                     lambda: ctx.trmm(a64, b32),
                     lambda: ctx.trsm(a64, b32)):
            with pytest.raises(ValueError, match="tile mismatch"):
                call()


def test_adopted_runtime_survives_context_close():
    from repro.core.runtime import BlasxRuntime
    rt = BlasxRuntime(RuntimeConfig(n_devices=2, mode="sim",
                                    cache_bytes=32 << 20))
    A = RNG.standard_normal((128, 128))
    with BlasxContext(runtime=rt, tile=32) as ctx:
        ctx.gemm(ctx.tile(A), ctx.tile(A))
    assert rt.total_comm_bytes()["h2d"] > 0   # ledgers not wiped on close


# ===================================================== legacy equivalence
def test_legacy_default_context_is_module_cached():
    from repro.api import default_context
    a = default_context()
    assert default_context() is a
    A = RNG.standard_normal((64, 64))
    out = blas3.gemm(A, A, tile=32)
    np.testing.assert_allclose(out, A @ A, **TOL)
    assert default_context().runtime.runs > 0
