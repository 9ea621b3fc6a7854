"""Multi-precision suite: dtype threading through every API layer
(legacy blas3 / BlasxContext / cblas_s*), per-dtype byte accounting in
the ALRU/heap/ledger, f32-accumulation engines on jax/pallas for the
half precisions, and the backend gating rules — plus regression tests
for the cache/threads-mode bugfix sweep that rode along:

  * ALRU over-eviction guard + on_evict-after-heap.free ordering,
  * cblas ``_view`` honoring (or rejecting) padded 2-D leading dims,
  * threads-mode condition-variable wakeup + RS drain on worker crash.
"""
import threading
import time

import numpy as np
import pytest

from repro.api import (BlasxContext, CblasColMajor, CblasNonUnit,
                       CblasNoTrans, CblasRight, CblasRowMajor, CblasUpper,
                       cblas_dgemm, cblas_sgemm, cblas_ssymm, cblas_ssyr2k,
                       cblas_ssyrk, cblas_strmm, cblas_strsm)
from repro.core import blas3
from repro.core.alru import Alru
from repro.core.dtypes import (canonical_dtype, promote_dtypes,
                               validate_backend_dtype)
from repro.core.heap import BlasxHeap
from repro.core.runtime import BlasxRuntime, RuntimeConfig
from repro.core.tiling import TileKey

RNG = np.random.default_rng(23)
F32_TOL = dict(rtol=2e-3, atol=2e-3)

M, N, K, TILE = 48, 40, 56, 16    # ragged edges, same shapes as parity


def _cfg(backend="numpy", **kw):
    kw.setdefault("n_devices", 2)
    kw.setdefault("mode", "sim")
    return RuntimeConfig(backend=backend, **kw)


def _f64(*shape):
    return RNG.standard_normal(shape)


# ========================================================= dtype registry
def test_canonical_dtype_spellings():
    assert canonical_dtype("float32") == np.float32
    assert canonical_dtype(np.float64) == np.float64
    assert canonical_dtype(np.dtype("float16")) == np.float16
    assert canonical_dtype("bfloat16").name == "bfloat16"
    with pytest.raises(ValueError, match="unsupported dtype"):
        canonical_dtype("int32")
    with pytest.raises(ValueError, match="unsupported dtype"):
        canonical_dtype("complex128")


def test_backend_dtype_matrix():
    for be in ("numpy", "jax", "pallas"):
        validate_backend_dtype("float64", be)
        validate_backend_dtype("float32", be)
    for half in ("float16", "bfloat16"):
        validate_backend_dtype(half, "jax")
        validate_backend_dtype(half, "pallas")
        with pytest.raises(ValueError, match="not supported"):
            validate_backend_dtype(half, "numpy")


def test_promote_dtypes_handles_bfloat16():
    bf = canonical_dtype("bfloat16")
    assert promote_dtypes(bf, bf) == bf       # fast path, no numpy table
    assert promote_dtypes(np.float32, np.float64) == np.float64


# ============================================= dtype through the surfaces
@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_f32_gemm_matches_f32_oracle_all_backends(backend):
    A, B, C = _f64(M, K), _f64(K, N), _f64(M, N)
    got = blas3.gemm(A, B, C, alpha=1.3, beta=-0.7, tile=TILE,
                     dtype=np.float32, config=_cfg(backend))
    assert got.dtype == np.float32
    want = blas3.ref_gemm(A.astype(np.float32), B.astype(np.float32),
                          C.astype(np.float32), alpha=1.3, beta=-0.7)
    np.testing.assert_allclose(got, want, **F32_TOL)


@pytest.mark.parametrize("routine", ["syrk", "syr2k", "symm", "trmm", "trsm"])
def test_f32_dtype_through_legacy_wrappers(routine):
    A = _f64(M, K)
    S = _f64(M, M) / M + np.eye(M)            # well-conditioned for trsm
    B = _f64(M, N)
    if routine == "syrk":
        got = blas3.syrk(A, tile=TILE, dtype="float32")
        want = blas3.ref_syrk(A.astype(np.float32))
    elif routine == "syr2k":
        B2 = _f64(M, K)
        got = blas3.syr2k(A, B2, tile=TILE, dtype="float32")
        want = blas3.ref_syr2k(A.astype(np.float32), B2.astype(np.float32))
    elif routine == "symm":
        got = blas3.symm(S, B, tile=TILE, dtype="float32")
        want = blas3.ref_symm(S.astype(np.float32), B.astype(np.float32))
    elif routine == "trmm":
        got = blas3.trmm(S, B, tile=TILE, dtype="float32")
        want = blas3.ref_trmm(S.astype(np.float32), B.astype(np.float32))
    else:
        got = blas3.trsm(S, B, tile=TILE, dtype="float32")
        want = blas3.ref_trsm(S.astype(np.float32), B.astype(np.float32))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def test_context_default_dtype_casts_and_propagates():
    A, B = _f64(M, K), _f64(K, N)
    with BlasxContext(_cfg(), tile=TILE, dtype=np.float32) as ctx:
        Ah = ctx.tile(A)
        assert Ah.dtype == np.float32
        out = ctx.gemm(Ah, B)                 # raw B cast on coercion
        assert out.dtype == np.float32
        # per-call override beats the context default
        out64 = ctx.gemm(A, B, dtype=np.float64)
        assert out64.dtype == np.float64
    np.testing.assert_allclose(
        out.array(), A.astype(np.float32) @ B.astype(np.float32), **F32_TOL)


def test_per_call_dtype_override_beats_context_default_on_inputs():
    """Regression: raw-array coercion used to re-tile through the
    context default, recasting a per-call dtype= override — wrong
    numerics in one direction (inputs quantized through a narrower
    default) and wrong byte accounting in the other."""
    A, B = _f64(64, 64), _f64(64, 64)
    with BlasxContext(_cfg(n_devices=1), tile=32, dtype=np.float64) as ctx:
        out = ctx.gemm(A, B, dtype=np.float32)
        assert out.dtype == np.float32
        # inputs moved at f32, not silently at the f64 context default
        assert ctx.last_call.h2d_bytes == (A.size + B.size) * 4
    with BlasxContext(_cfg("jax", n_devices=1), tile=32,
                      dtype="bfloat16") as ctx:
        E = np.eye(8) * 1.001
        out = ctx.gemm(E, np.eye(8), tile=8, dtype=np.float64)
        assert out.dtype == np.float64
        # a bf16 default must not quantize the f64-requested inputs:
        # bf16(1.001) == 1.0 exactly (error 1e-3); the f32-computing
        # CPU jax engine keeps it to ~1e-8
        assert abs(out.array()[0, 0] - 1.001) < 1e-4


def test_side_r_keeps_per_call_dtype_over_context_default():
    """Regression: the side='R' transpose epilogue used to re-tile the
    result through ctx.tile(), re-applying the context default dtype
    and silently recasting a per-call dtype= override."""
    n, m = 40, 32
    S = _f64(n, n) / n + np.eye(n)
    B = _f64(m, n)
    with BlasxContext(_cfg(), tile=16, dtype=np.float64) as ctx:
        out = ctx.symm(S, B, side="R", dtype=np.float32)
        assert out.dtype == np.float32
        np.testing.assert_allclose(
            out.array(),
            blas3.ref_symm(S.astype(np.float32), B.astype(np.float32),
                           side="R"), **F32_TOL)
        sol = ctx.trsm(S, B, side="R", dtype=np.float32)
        assert sol.dtype == np.float32


def test_context_rejects_handle_dtype_mismatch():
    with BlasxContext(_cfg(), tile=TILE) as ctx:
        Ah = ctx.tile(_f64(32, 32))           # float64 handle
        with pytest.raises(ValueError, match="re-tile"):
            ctx.gemm(Ah, Ah, dtype=np.float32)


def test_override_tiled_handle_usable_without_repeating_dtype():
    """Regression: a handle tiled with a per-call dtype override in a
    context with a default dtype was rejected by every subsequent
    dtype-less call (the default was enforced against the handle).
    Only an explicit per-call dtype= is strict; the context default
    still governs raw arrays and the output."""
    A = _f64(32, 32)
    with BlasxContext(_cfg(n_devices=1), tile=16,
                      dtype=np.float64) as ctx:
        h = ctx.tile(A, dtype=np.float32)     # documented override
        assert h.dtype == np.float32
        out = ctx.gemm(h, h)                  # must not raise
        assert out.dtype == np.float64        # output follows the default
        np.testing.assert_allclose(
            out.array(),
            A.astype(np.float32) @ A.astype(np.float32), **F32_TOL)
        assert ctx.tile(h) is h               # re-adoption also fine


def test_half_precision_rejected_on_numpy_backend():
    with pytest.raises(ValueError, match="not supported"):
        BlasxContext(_cfg("numpy"), dtype="float16")
    with pytest.raises(ValueError, match="not supported"):
        blas3.gemm(_f64(8, 8), _f64(8, 8), tile=8, dtype="bfloat16")
    # registration is validated too, not just the routine call
    with BlasxContext(_cfg("numpy"), tile=8) as ctx:
        with pytest.raises(ValueError, match="not supported"):
            ctx.tile(_f64(8, 8), dtype="float16")


def test_half_precision_input_rejected_even_when_promotion_widens():
    """Regression: a bf16 operand mixed with a wider one used to slip
    past the numpy-backend gate (the promoted output is f64) and crawl
    through ml_dtypes scalar paths."""
    bf = canonical_dtype("bfloat16")
    A16 = _f64(16, 16).astype(bf)
    B64 = _f64(16, 16)
    with BlasxContext(_cfg("numpy"), tile=8) as ctx:
        with pytest.raises(ValueError, match="not supported"):
            ctx.gemm(A16, B64)
        with pytest.raises(ValueError, match="not supported"):
            ctx.trmm(A16, B64)


def test_mixed_half_precisions_get_clear_error():
    """bfloat16 x float16 has no common numpy dtype; the promotion
    helper must surface a clear ValueError, not DTypePromotionError."""
    bf = canonical_dtype("bfloat16")
    A16 = _f64(16, 16).astype(bf)
    B16 = _f64(16, 16).astype(np.float16)
    with pytest.raises(ValueError, match="no common precision"):
        promote_dtypes(bf, np.float16)
    with BlasxContext(_cfg("jax"), tile=8) as ctx:
        with pytest.raises(ValueError, match="no common precision"):
            ctx.gemm(A16, B16)


def test_side_r_rejects_handle_dtype_mismatch_like_side_l():
    """Regression: side='R' degraded handles to raw arrays before the
    dtype-mismatch guard ran, silently recasting where side='L'
    raises."""
    n, m = 32, 24
    with BlasxContext(_cfg(), tile=16) as ctx:
        Ah = ctx.tile(_f64(n, n))             # float64 handle
        B = _f64(m, n)
        with pytest.raises(ValueError, match="re-tile"):
            ctx.symm(Ah, B, side="R", dtype=np.float32)
        with pytest.raises(ValueError, match="re-tile"):
            ctx.trsm(Ah, B, side="R", dtype=np.float32)


def test_c_seed_handle_casts_freely_on_both_sides():
    """C only seeds the output (it never becomes a cached-tile
    operand), so a dtype-mismatched C handle is cast — identically —
    on side='L' and side='R'."""
    n, m = 32, 24
    with BlasxContext(_cfg(), tile=16) as ctx:
        S32 = _f64(n, n).astype(np.float32)
        B32 = _f64(m, n).astype(np.float32)
        Ch = ctx.tile(_f64(m, n))             # float64 seed handle
        for side, A_, B_ in (("L", _f64(m, m).astype(np.float32), B32),
                             ("R", S32, B32)):
            out = ctx.symm(A_, B_, Ch, beta=0.5, side=side,
                           dtype=np.float32)
            assert out.dtype == np.float32
            want = blas3.ref_symm(A_, B_, Ch.array().astype(np.float32),
                                  beta=0.5, side=side)
            np.testing.assert_allclose(out.array(), want, **F32_TOL)


def test_half_precision_c_rejected_on_numpy_backend():
    """Regression: a bf16 C seed slipped past the gate — with
    force=False the output keeps C's dtype, so C's dtype is the real
    output dtype and must pass the backend check."""
    bf = canonical_dtype("bfloat16")
    A = _f64(16, 16).astype(np.float32)
    C16 = _f64(16, 16).astype(bf)
    with BlasxContext(_cfg("numpy"), tile=8) as ctx:
        with pytest.raises(ValueError, match="not supported"):
            ctx.gemm(A, A, C16, beta=1.0)
    # but the same C is fine where bf16 is supported
    with BlasxContext(_cfg("jax"), tile=8) as ctx:
        out = ctx.gemm(A, A, C16, beta=1.0)
        assert out.dtype.name == "bfloat16"


@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
def test_half_precision_gemm_on_jax_backend(dtype):
    A, B = _f64(M, K), _f64(K, N)
    got = blas3.gemm(A, B, tile=TILE, dtype=dtype, config=_cfg("jax"))
    assert got.dtype == canonical_dtype(dtype)
    # f32 accumulation: error is dominated by the input rounding, so a
    # half-precision-tolerance compare against the f64 oracle passes
    np.testing.assert_allclose(got.astype(np.float64), A @ B,
                               rtol=0.06, atol=0.3)


def test_half_precision_gemm_on_pallas_backend():
    n = 32
    A, B = _f64(n, n), _f64(n, n)
    got = blas3.gemm(A, B, tile=16, dtype="bfloat16", config=_cfg("pallas"))
    assert got.dtype.name == "bfloat16"
    np.testing.assert_allclose(got.astype(np.float64), A @ B,
                               rtol=0.06, atol=0.3)


def test_step_groups_key_on_dtype():
    """Mixed-precision session: f32 and f64 calls through one runtime
    must never share a dispatch group (the compile caches key on dtype
    via StepGroupKey)."""
    rt = BlasxRuntime(_cfg("jax", n_devices=1))
    A = _f64(64, 64)
    blas3.gemm(A, A, tile=32, runtime=rt, dtype=np.float32)
    blas3.gemm(A, A, tile=32, runtime=rt, dtype=np.float64)
    ls = rt.launch_stats()
    assert ls["groups"] >= 2                  # one per dtype at minimum


# ===================================== precision-aware byte accounting
def test_tile_nbytes_track_storage_dtype():
    """The ALRU/heap/ledger accounting and the comm model are storage-
    dtype aware: the same workload in f32 moves and caches exactly half
    the bytes of f64 (bf16 a quarter)."""
    A = _f64(256, 256)

    def run(dtype, backend="numpy"):
        ctx = BlasxContext(_cfg(backend, n_devices=1), tile=64)
        try:
            ctx.gemm(A, A, dtype=dtype)
            rec = ctx.last_call
            heap_used = ctx.runtime.devices[0].heap.used
            return rec.h2d_bytes, rec.d2h_bytes, heap_used
        finally:
            ctx.close()

    h64, w64, u64 = run(np.float64)
    h32, w32, u32 = run(np.float32)
    assert h64 == 2 * h32 and w64 == 2 * w32 and u64 == 2 * u32
    h16, w16, u16 = run("bfloat16", backend="jax")
    assert h64 == 4 * h16 and w64 == 4 * w16 and u64 == 4 * u16


def test_shadow_run_models_precision():
    rt64 = BlasxRuntime(_cfg(execute=False))
    blas3.shadow_run("gemm", 2048, tile=256, runtime=rt64)
    rt32 = BlasxRuntime(_cfg(execute=False))
    blas3.shadow_run("gemm", 2048, tile=256, runtime=rt32, dtype="float32")
    assert rt64.total_comm_bytes()["h2d"] == \
        2 * rt32.total_comm_bytes()["h2d"]
    # half the bytes -> half the modeled transfer time -> faster clock
    assert rt32.makespan() < rt64.makespan()


# ================================================== cblas single precision
def test_cblas_sgemm_matches_f32_oracle_all_backends():
    m, n, k = 48, 40, 32
    A = _f64(m, k).astype(np.float32)
    B = _f64(k, n).astype(np.float32)
    for backend in ("numpy", "jax", "pallas"):
        C = _f64(m, n).astype(np.float32)
        want = blas3.ref_gemm(A, B, C, alpha=1.2, beta=0.8)
        cblas_sgemm(CblasRowMajor, CblasNoTrans, CblasNoTrans, m, n, k,
                    1.2, A, k, B, n, 0.8, C, n, backend=backend)
        np.testing.assert_allclose(C, want, **F32_TOL)


def test_cblas_single_precision_surface_all_six():
    n, k, m = 40, 24, 32
    A = _f64(n, k).astype(np.float32)
    B = _f64(n, k).astype(np.float32)
    S = (_f64(n, n) / n + np.eye(n)).astype(np.float32)
    X = _f64(m, n).astype(np.float32)
    with BlasxContext(_cfg(), tile=16) as ctx:
        C = np.zeros((n, n), np.float32)
        cblas_ssyrk(CblasRowMajor, CblasUpper, CblasNoTrans, n, k, 1.0,
                    A, k, 0.0, C, n, ctx=ctx)
        np.testing.assert_allclose(np.triu(C), np.triu(A @ A.T), **F32_TOL)

        C = np.zeros((n, n), np.float32)
        cblas_ssyr2k(CblasRowMajor, CblasUpper, CblasNoTrans, n, k, 0.5,
                     A, k, B, k, 0.0, C, n, ctx=ctx)
        np.testing.assert_allclose(
            np.triu(C), np.triu(0.5 * (A @ B.T + B @ A.T)), **F32_TOL)

        C = np.zeros((m, n), np.float32)
        cblas_ssymm(CblasRowMajor, CblasRight, CblasUpper, m, n, 1.0,
                    S, n, X, n, 0.0, C, n, ctx=ctx)
        want = blas3.ref_symm(S, X, side="R", uplo="U")
        np.testing.assert_allclose(C, want, **F32_TOL)

        Bb = X.copy()
        cblas_strmm(CblasRowMajor, CblasRight, CblasUpper, CblasNoTrans,
                    CblasNonUnit, m, n, 0.9, S, n, Bb, n, ctx=ctx)
        np.testing.assert_allclose(
            Bb, blas3.ref_trmm(S, X, alpha=0.9, side="R"), **F32_TOL)

        Bb = X.copy()
        cblas_strsm(CblasRowMajor, CblasRight, CblasUpper, CblasNoTrans,
                    CblasNonUnit, m, n, 1.1, S, n, Bb, n, ctx=ctx)
        np.testing.assert_allclose(
            Bb, blas3.ref_trsm(S, X, alpha=1.1, side="R"),
            rtol=5e-3, atol=5e-3)

        # every tile the f32 surface cached is 4 bytes/element
        assert all(c.h2d_bytes % 4 == 0 for c in ctx.calls)


def test_cblas_sgemm_rejects_f64_output_buffer():
    with pytest.raises(TypeError, match="float32"):
        cblas_sgemm(CblasRowMajor, CblasNoTrans, CblasNoTrans, 4, 4, 4,
                    1.0, np.eye(4, dtype=np.float32), 4,
                    np.eye(4, dtype=np.float32), 4, 0.0,
                    np.zeros((4, 4)), 4)
    with pytest.raises(TypeError, match="float64"):
        cblas_dgemm(CblasRowMajor, CblasNoTrans, CblasNoTrans, 4, 4, 4,
                    1.0, np.eye(4), 4, np.eye(4), 4, 0.0,
                    np.zeros((4, 4), np.float32), 4)


# ================================= bugfix: _view padded 2-D leading dims
@pytest.mark.parametrize("dtype,fn", [(np.float64, cblas_dgemm),
                                      (np.float32, cblas_sgemm)])
def test_cblas_padded_ld_row_major_round_trip(dtype, fn):
    """2-D operands that are strided views into padded storage: ld is
    honored (the pre-fix code silently returned dense semantics)."""
    m, n, k = 20, 14, 12
    lda, ldb, ldc = k + 5, n + 3, n + 7
    A = _f64(m, k).astype(dtype)
    B = _f64(k, n).astype(dtype)
    C = _f64(m, n).astype(dtype)
    want = blas3.ref_gemm(A, B, C, alpha=1.1, beta=0.4)
    Abuf = np.zeros((m, lda), dtype)
    Abuf[:, :k] = A
    Bbuf = np.zeros((k, ldb), dtype)
    Bbuf[:, :n] = B
    Cbuf = np.zeros((m, ldc), dtype)
    Cbuf[:, :n] = C
    fn(CblasRowMajor, CblasNoTrans, CblasNoTrans, m, n, k, 1.1,
       Abuf[:, :k], lda, Bbuf[:, :n], ldb, 0.4, Cbuf[:, :n], ldc)
    np.testing.assert_allclose(Cbuf[:, :n], want,
                               **(F32_TOL if dtype == np.float32
                                  else dict(rtol=1e-10, atol=1e-10)))
    # the padding columns were never touched
    assert not Cbuf[:, n:].any()


@pytest.mark.parametrize("dtype,fn", [(np.float64, cblas_dgemm),
                                      (np.float32, cblas_sgemm)])
def test_cblas_padded_ld_col_major_round_trip(dtype, fn):
    m, n, k = 18, 16, 10
    lda, ldb, ldc = m + 4, k + 2, m + 6
    A = _f64(m, k).astype(dtype)
    B = _f64(k, n).astype(dtype)
    want = blas3.ref_gemm(A, B)
    # column-major padded storage: F-ordered buffers, logical view on top
    Abuf = np.zeros((lda, k), dtype, order="F")
    Abuf[:m, :] = A
    Bbuf = np.zeros((ldb, n), dtype, order="F")
    Bbuf[:k, :] = B
    Cbuf = np.zeros((ldc, n), dtype, order="F")
    fn(CblasColMajor, CblasNoTrans, CblasNoTrans, m, n, k, 1.0,
       Abuf[:m, :], lda, Bbuf[:k, :], ldb, 0.0, Cbuf[:m, :], ldc)
    np.testing.assert_allclose(Cbuf[:m, :], want,
                               **(F32_TOL if dtype == np.float32
                                  else dict(rtol=1e-10, atol=1e-10)))
    assert not Cbuf[m:, :].any()


def test_cblas_padded_ld_input_of_other_dtype_is_cast_not_rejected():
    """The documented contract: read-only inputs of other dtypes are
    cast AND a padded ld is honored — the layout check must run on the
    caller's buffer, not on the cast's dense copy."""
    m, n, k = 10, 8, 6
    lda = k + 4
    A = _f64(m, k)                            # float64 into cblas_sgemm
    B = _f64(k, n).astype(np.float32)
    Abuf = np.zeros((m, lda))
    Abuf[:, :k] = A
    C = np.zeros((m, n), np.float32)
    cblas_sgemm(CblasRowMajor, CblasNoTrans, CblasNoTrans, m, n, k, 1.0,
                Abuf[:, :k], lda, B, n, 0.0, C, n)
    np.testing.assert_allclose(C, A.astype(np.float32) @ B, **F32_TOL)


def test_cblas_single_row_accepts_any_ld():
    """With one row (row major) the leading stride is never exercised,
    so a larger-than-dense ld is legal C usage on a dense buffer."""
    k, n = 4, 5
    A = _f64(1, k)
    B = _f64(k, n)
    C = np.zeros((1, n))
    cblas_dgemm(CblasRowMajor, CblasNoTrans, CblasNoTrans, 1, n, k,
                1.0, A, k + 4, B, n, 0.0, C, n + 7)
    np.testing.assert_allclose(C, A @ B, rtol=1e-10, atol=1e-10)


def test_cblas_dense_buffer_with_padded_ld_raises():
    """Regression: a dense 2-D array with ld > dense leading dimension
    used to be silently accepted with dense semantics."""
    m, n, k = 8, 6, 4
    A = np.zeros((m, k))
    B = np.zeros((k, n))
    C = np.zeros((m, n))
    with pytest.raises(ValueError, match="memory layout"):
        cblas_dgemm(CblasRowMajor, CblasNoTrans, CblasNoTrans, m, n, k,
                    1.0, A, k + 3, B, n, 0.0, C, n)
    with pytest.raises(ValueError, match="memory layout"):
        cblas_dgemm(CblasColMajor, CblasNoTrans, CblasNoTrans, m, n, k,
                    1.0, np.asfortranarray(A), m, np.asfortranarray(B), k,
                    0.0, np.asfortranarray(C), m + 2)


# ============================ bugfix: ALRU over-eviction + evict ordering
def test_alru_unattainable_translate_evicts_nothing():
    """Regression: a request that can never fit (pinned blocks fence
    the heap) used to wipe every zero-reader block before failing."""
    heap = BlasxHeap(300)
    a = Alru(0, heap)
    evicted = []
    a.on_evict = lambda dev, key: evicted.append(key)
    k1, k2, k3 = (TileKey("A", 0, i) for i in range(3))
    a.translate(k1, 100)
    a.release(k1)
    a.translate(k2, 100)                      # pinned: reader stays 1
    a.translate(k3, 100)
    a.release(k3)
    assert a.translate(TileKey("A", 0, 9), 250) is None
    assert evicted == []                      # no over-eviction
    assert k1 in a and k2 in a and k3 in a
    a.check_invariants()
    heap.check_invariants()
    # an attainable request still succeeds by evicting only what it
    # needs (one 100-byte run opens by evicting the LRU block alone)
    assert a.translate(TileKey("A", 0, 10), 100) is not None
    assert k2 in a                            # pinned block untouched
    assert len(evicted) == 1                  # exactly one victim


def test_alru_on_evict_fires_after_heap_free():
    """Regression: on_evict used to fire before heap.free, so the
    directory observed an evicted tile whose bytes were still
    allocated."""
    heap = BlasxHeap(200)
    a = Alru(0, heap)
    used_at_evict = []
    a.on_evict = lambda dev, key: used_at_evict.append(heap.used)
    for j in range(2):
        k = TileKey("A", 0, j)
        a.translate(k, 100)
        a.release(k)
    a.translate(TileKey("A", 0, 7), 150)      # evicts both 100-byte blocks
    # at each callback the victim's bytes were already freed:
    # first eviction leaves <=100 used, second leaves 0
    assert used_at_evict == [100, 0]


def test_heap_largest_attainable_run():
    h = BlasxHeap(100)
    a = h.malloc(30)
    b = h.malloc(30)
    h.malloc(30)
    assert h.largest_free_run() == 10
    # freeing a (offset 0) alone yields its 30-byte run; freeing b too
    # bridges a+b; the tail free 10 only joins via the occupied third
    assert h.largest_attainable_run({a}) == 30
    assert h.largest_attainable_run({a, b}) == 60
    h.free(b)
    assert h.largest_free_run() == 30
    assert h.largest_attainable_run({a}) == 60


# =========================== bugfix: threads-mode wakeup + crash recovery
def test_threads_crash_leaves_no_stranded_tasks():
    """Regression: a crashed worker used to strand its RS-resident
    (incl. stolen) tasks; survivors now drain every RS back to the
    global queue and the injected error surfaces as raised."""
    A = RNG.standard_normal((256, 256))
    rt = BlasxRuntime(RuntimeConfig(n_devices=2, mode="threads",
                                    cache_bytes=32 << 20))
    orig = rt._execute_batch
    crashed = threading.Event()

    def boom(d, batch):
        if d.id == 1:
            crashed.set()
            raise RuntimeError("injected-crash")
        # the healthy device runs nothing before the crash, so the crash
        # lands with work still queued however the threads are timed
        crashed.wait(timeout=10)
        return orig(d, batch)

    rt._execute_batch = boom
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="injected-crash"):
        blas3.gemm(A, A, tile=32, runtime=rt)
    assert time.perf_counter() - t0 < 30      # survivors exit promptly
    assert all(len(d.rs) == 0 for d in rt.devices)
    assert rt._queue.has_ready()              # drained tasks were requeued
    # full accounting: every task is either completed or dequeueable
    # again — the crashed worker's in-flight batch included
    executed = sum(d.ledger.tasks for d in rt.devices)
    assert executed + len(rt._queue) == 64    # 8x8 tiles at tile=32


def test_failed_batch_releases_acquired_readers():
    """Regression: a batch failing after gather (backend error) left
    its acquired tiles pinned (reader > 0) for the whole session —
    blocking eviction and making handle.invalidate() raise."""
    A = RNG.standard_normal((128, 128))
    rt = BlasxRuntime(RuntimeConfig(n_devices=2, mode="threads",
                                    cache_bytes=32 << 20))
    orig = rt._dispatch_steps
    crashed = threading.Event()

    def boom(d, recs):
        if d.id == 1:
            crashed.set()
            raise RuntimeError("dispatch-crash")   # after gather
        # the healthy device dispatches nothing before the crash, so it
        # cannot drain the queue before the crashing one gets a batch
        crashed.wait(timeout=10)
        return orig(d, recs)

    rt._dispatch_steps = boom
    with pytest.raises(RuntimeError, match="dispatch-crash"):
        blas3.gemm(A, A, tile=32, runtime=rt)
    for d in rt.devices:
        for k in d.alru.keys():
            assert d.alru.peek(k).reader == 0, (d.id, k)


def test_threads_workers_never_sleep_poll(monkeypatch):
    """Regression: starved workers used to busy-wait with
    time.sleep(0.0005); they now park on a condition variable."""
    import repro.core.runtime as rtmod

    calls = []
    real_sleep = time.sleep

    class TimeProxy:
        perf_counter = staticmethod(time.perf_counter)

        @staticmethod
        def sleep(s):
            calls.append(s)
            return real_sleep(s)

    monkeypatch.setattr(rtmod, "time", TimeProxy)
    A = RNG.standard_normal((192, 192))
    # more devices than work at the tail -> pre-fix this spins sleep()
    out = blas3.gemm(A, A, tile=64,
                     config=RuntimeConfig(n_devices=4, mode="threads"))
    np.testing.assert_allclose(out, A @ A, rtol=1e-10, atol=1e-10)
    assert calls == []


def test_threads_condition_variable_wakes_on_completion():
    """A worker parked on the CV (deps pending) is woken by a peer's
    completion, not by a poll timeout: TRSM's intra-column chains
    complete in threads mode well before any timeout-paced schedule
    could."""
    n = 128
    A = RNG.standard_normal((n, n)) / n + np.eye(n)
    B = RNG.standard_normal((n, n))
    out = blas3.trsm(A, B, tile=32,
                     config=RuntimeConfig(n_devices=3, mode="threads"))
    np.testing.assert_allclose(out, blas3.ref_trsm(A, B),
                               rtol=1e-8, atol=1e-8)
