"""Correctness of the tiled L3 BLAS routines against pure-numpy oracles,
across policies, modes, tile sizes, transposes, uplo/side/diag."""
import numpy as np
import pytest

from repro.core import (gemm, ref_gemm, ref_symm, ref_syr2k, ref_syrk,
                        ref_trmm, ref_trsm, symm, syr2k, syrk, trmm, trsm)
from repro.core.runtime import RuntimeConfig

RNG = np.random.default_rng(42)
TOL = dict(rtol=1e-10, atol=1e-10)


def cfg(**kw):
    kw.setdefault("n_devices", 2)
    kw.setdefault("mode", "sim")
    kw.setdefault("cache_bytes", 32 << 20)
    return RuntimeConfig(**kw)


# ------------------------------------------------------------------- GEMM
@pytest.mark.parametrize("transa", ["N", "T"])
@pytest.mark.parametrize("transb", ["N", "T"])
def test_gemm_transposes(transa, transb):
    m, k, n = 130, 70, 95
    A = RNG.standard_normal((m, k) if transa == "N" else (k, m))
    B = RNG.standard_normal((k, n) if transb == "N" else (n, k))
    C = RNG.standard_normal((m, n))
    out = gemm(A, B, C, alpha=1.3, beta=-0.4, transa=transa, transb=transb,
               tile=48, config=cfg())
    ref = ref_gemm(A, B, C, alpha=1.3, beta=-0.4, transa=transa, transb=transb)
    np.testing.assert_allclose(out, ref, **TOL)


@pytest.mark.parametrize("tile", [17, 64, 128, 300])
def test_gemm_tile_sizes(tile):
    A = RNG.standard_normal((257, 129))
    B = RNG.standard_normal((129, 200))
    out = gemm(A, B, tile=tile, config=cfg())
    np.testing.assert_allclose(out, A @ B, **TOL)


@pytest.mark.parametrize("policy",
                         ["blasx", "parsec", "cublasxt", "static",
                          "supermatrix"])
def test_gemm_all_policies(policy):
    A = RNG.standard_normal((200, 150))
    B = RNG.standard_normal((150, 180))
    C = RNG.standard_normal((200, 180))
    out = gemm(A, B, C, alpha=0.9, beta=1.7, tile=64,
               config=cfg(n_devices=3, policy=policy))
    np.testing.assert_allclose(out, ref_gemm(A, B, C, alpha=0.9, beta=1.7),
                               **TOL)


def test_gemm_threads_mode():
    A = RNG.standard_normal((256, 256))
    B = RNG.standard_normal((256, 256))
    out = gemm(A, B, tile=64, config=cfg(n_devices=4, mode="threads"))
    np.testing.assert_allclose(out, A @ B, **TOL)


def test_gemm_beta_zero_no_c():
    A = RNG.standard_normal((64, 32))
    B = RNG.standard_normal((32, 48))
    out = gemm(A, B, tile=32)
    np.testing.assert_allclose(out, A @ B, **TOL)


def test_gemm_single_tile():
    A = RNG.standard_normal((30, 20))
    B = RNG.standard_normal((20, 25))
    out = gemm(A, B, tile=512)
    np.testing.assert_allclose(out, A @ B, **TOL)


def test_gemm_shape_errors():
    with pytest.raises(ValueError):
        gemm(np.zeros((3, 4)), np.zeros((5, 6)))
    with pytest.raises(ValueError):
        gemm(np.zeros((3, 4)), np.zeros((4, 6)), beta=1.0)  # needs C


# ------------------------------------------------------------- SYRK/SYR2K
@pytest.mark.parametrize("uplo", ["U", "L"])
@pytest.mark.parametrize("trans", ["N", "T"])
def test_syrk(uplo, trans):
    n, k = 150, 90
    A = RNG.standard_normal((n, k) if trans == "N" else (k, n))
    C = RNG.standard_normal((n, n))
    out = syrk(A, C, alpha=0.7, beta=1.2, uplo=uplo, trans=trans, tile=64,
               config=cfg())
    ref = ref_syrk(A, C, alpha=0.7, beta=1.2, uplo=uplo, trans=trans)
    np.testing.assert_allclose(out, ref, **TOL)


@pytest.mark.parametrize("uplo", ["U", "L"])
@pytest.mark.parametrize("trans", ["N", "T"])
def test_syr2k(uplo, trans):
    n, k = 140, 80
    A = RNG.standard_normal((n, k) if trans == "N" else (k, n))
    B = RNG.standard_normal((n, k) if trans == "N" else (k, n))
    C = RNG.standard_normal((n, n))
    out = syr2k(A, B, C, alpha=0.6, beta=0.8, uplo=uplo, trans=trans,
                tile=48, config=cfg())
    ref = ref_syr2k(A, B, C, alpha=0.6, beta=0.8, uplo=uplo, trans=trans)
    np.testing.assert_allclose(out, ref, **TOL)


def test_syrk_preserves_other_triangle():
    n, k = 100, 50
    A = RNG.standard_normal((n, k))
    C = RNG.standard_normal((n, n))
    out = syrk(A, C, alpha=1.0, beta=0.0, uplo="U", tile=32)
    # strictly-lower triangle must be untouched original C
    low = np.tril_indices(n, -1)
    np.testing.assert_array_equal(out[low], C[low])


# ------------------------------------------------------------------- SYMM
@pytest.mark.parametrize("side", ["L", "R"])
@pytest.mark.parametrize("uplo", ["U", "L"])
def test_symm(side, uplo):
    m, n = 120, 90
    B = RNG.standard_normal((m, n))
    dim = m if side == "L" else n
    A = RNG.standard_normal((dim, dim))
    C = RNG.standard_normal((m, n))
    out = symm(A, B, C, alpha=1.4, beta=-0.2, side=side, uplo=uplo, tile=40,
               config=cfg())
    ref = ref_symm(A, B, C, alpha=1.4, beta=-0.2, side=side, uplo=uplo)
    np.testing.assert_allclose(out, ref, **TOL)


# ------------------------------------------------------------------- TRMM
@pytest.mark.parametrize("side", ["L", "R"])
@pytest.mark.parametrize("uplo", ["U", "L"])
@pytest.mark.parametrize("transa", ["N", "T"])
@pytest.mark.parametrize("diag", ["N", "U"])
def test_trmm(side, uplo, transa, diag):
    m, n = 110, 70
    B = RNG.standard_normal((m, n))
    dim = m if side == "L" else n
    A = RNG.standard_normal((dim, dim))
    out = trmm(A, B, alpha=0.9, side=side, uplo=uplo, transa=transa,
               diag=diag, tile=48, config=cfg())
    ref = ref_trmm(A, B, alpha=0.9, side=side, uplo=uplo, transa=transa,
                   diag=diag)
    np.testing.assert_allclose(out, ref, **TOL)


# ------------------------------------------------------------------- TRSM
@pytest.mark.parametrize("side", ["L", "R"])
@pytest.mark.parametrize("uplo", ["U", "L"])
@pytest.mark.parametrize("transa", ["N", "T"])
@pytest.mark.parametrize("diag", ["N", "U"])
def test_trsm(side, uplo, transa, diag):
    m, n = 100, 60
    B = RNG.standard_normal((m, n))
    dim = m if side == "L" else n
    # well conditioned for BOTH diag modes: small off-diagonal (unit-
    # triangular solves grow with prod(1+|a_ij|)), dominant diagonal
    A = RNG.standard_normal((dim, dim)) / dim + np.eye(dim)
    out = trsm(A, B, alpha=1.1, side=side, uplo=uplo, transa=transa,
               diag=diag, tile=32, config=cfg())
    ref = ref_trsm(A, B, alpha=1.1, side=side, uplo=uplo, transa=transa,
                   diag=diag)
    np.testing.assert_allclose(out, ref, rtol=1e-8, atol=1e-8)


def _trsm_left_tasks_as_before(ga, gb, gc, uplo, transa, diag, alpha):
    """The side-L TRSM taskizer as it stood before side R ran natively."""
    from repro.core.task import (FILL_TRI_L, FILL_TRI_LU, FILL_TRI_U,
                                 FILL_TRI_UU, Finalize, Step, TaskBuilder,
                                 TileRef)

    def op_a(i, k, fill="full"):
        if transa == "N":
            return TileRef(ga.key(i, k), fill=fill)
        return TileRef(ga.key(k, i), trans=True, fill=fill)

    b = TaskBuilder({g.matrix_id: g for g in (ga, gb, gc)})
    z = gc.n_tile_rows - 1
    eff_upper = (uplo == "U") == (transa == "N")
    tri_fill = {("U", "N"): FILL_TRI_U, ("U", "U"): FILL_TRI_UU,
                ("L", "N"): FILL_TRI_L, ("L", "U"): FILL_TRI_LU}[uplo, diag]
    order = range(z, -1, -1) if eff_upper else range(0, z + 1)
    tid = {}
    for j in range(gc.n_tile_cols):
        for i in order:
            ks = range(i + 1, z + 1) if eff_upper else range(0, i)
            steps = tuple(Step(op_a(i, k), TileRef(gc.key(k, j))) for k in ks)
            fin = Finalize(kind="trsm", diag_ref=op_a(i, i, fill=tri_fill),
                           rhs_ref=TileRef(gb.key(i, j)),
                           lower=not eff_upper, unit_diag=(diag == "U"))
            t = b.add(routine="trsm", out=gc.key(i, j), i=i, j=j,
                      steps=steps, alpha=alpha, beta=0.0, finalize=fin,
                      deps=tuple(tid[(k, j)] for k in ks))
            tid[(i, j)] = t.task_id
    return b.tasks


@pytest.mark.parametrize("uplo", ["U", "L"])
@pytest.mark.parametrize("transa", ["N", "T"])
@pytest.mark.parametrize("diag", ["N", "U"])
def test_trsm_side_l_task_list_unchanged(uplo, transa, diag):
    """Side R's own taskization leaves side L's steps, refs, deps and
    finalize exactly as they were (ragged 3x2 tile grid of B)."""
    from repro.core.task import taskize_trsm
    from repro.core.tiling import TileGrid

    ga, gb, gc = (TileGrid("A", 40, 40, 16), TileGrid("B", 40, 27, 16),
                  TileGrid("C", 40, 27, 16))
    want = _trsm_left_tasks_as_before(ga, gb, gc, uplo, transa, diag, 0.7)
    assert len(want) == 6 and any(t.deps for t in want)
    assert taskize_trsm(ga, gb, gc, uplo, transa, diag, 0.7) == want
    assert taskize_trsm(ga, gb, gc, uplo, transa, diag, 0.7,
                        side="L") == want


@pytest.mark.parametrize("uplo", ["U", "L"])
@pytest.mark.parametrize("transa", ["N", "T"])
def test_trsm_side_r_tasks_mirror_side_l(uplo, transa):
    """Side R on B (27x40) is side L on B^T mirrored: as many tasks,
    steps, dependencies and flops; its chains run along X's rows."""
    from repro.core.task import taskize_trsm, total_flops
    from repro.core.tiling import TileGrid

    ga = TileGrid("A", 40, 40, 16)
    right = taskize_trsm(ga, TileGrid("B", 27, 40, 16),
                         TileGrid("C", 27, 40, 16), uplo, transa, "N", 1.0,
                         side="R")
    left = taskize_trsm(ga, TileGrid("B", 40, 27, 16),
                        TileGrid("C", 40, 27, 16), uplo,
                        "T" if transa == "N" else "N", "N", 1.0)
    assert total_flops(right) == total_flops(left)
    for f in (lambda t: len(t.steps), lambda t: len(t.deps)):
        assert sorted(map(f, right)) == sorted(map(f, left))
    by_id = {t.task_id: t for t in right}
    assert all(by_id[d].i == t.i for t in right for d in t.deps)
    assert all(t.finalize.side == "R" for t in right)


def test_trsm_residual():
    """A @ X == alpha * B (solve property, independent of the oracle)."""
    m, n = 96, 40
    A = RNG.standard_normal((m, m)) + m * np.eye(m)
    B = RNG.standard_normal((m, n))
    X = trsm(A, B, alpha=2.0, uplo="U", tile=32,
             config=cfg(n_devices=3))
    np.testing.assert_allclose(np.triu(A) @ X, 2.0 * B, rtol=1e-8, atol=1e-8)


@pytest.mark.parametrize("policy", ["blasx", "static", "cublasxt"])
def test_trsm_dependency_chain_across_policies(policy):
    m, n = 128, 64
    A = RNG.standard_normal((m, m)) + m * np.eye(m)
    B = RNG.standard_normal((m, n))
    out = trsm(A, B, uplo="L", tile=32,
               config=cfg(n_devices=3, policy=policy))
    np.testing.assert_allclose(out, ref_trsm(A, B, uplo="L"),
                               rtol=1e-8, atol=1e-8)


# ------------------------------------------------------------ JAX kernel
def test_gemm_jax_tile_kernel():
    A = RNG.standard_normal((96, 64)).astype(np.float32)
    B = RNG.standard_normal((64, 80)).astype(np.float32)
    out = gemm(A, B, tile=32, config=cfg(kernel="jax"))
    np.testing.assert_allclose(out, A @ B, rtol=1e-4, atol=1e-4)
