"""The routines' standard flop counts and their plain references."""
import numpy as np
import pytest
import scipy.linalg

from bench.cell import routine


@pytest.mark.parametrize("name,shapes,kw,want", [
    ("gemm", [(8192, 8192), (8192, 8192)], {}, 2 * 8192 ** 3),
    ("gemm", [(300, 200), (200, 100)], {}, 2 * 300 * 200 * 100),
    ("gemm", [(200, 300), (100, 200)], {"transa": "T", "transb": "T"},
     2 * 300 * 200 * 100),
    ("syrk", [(7168, 1024)], {"uplo": "L"}, 7168 ** 2 * 1024),
    ("syrk", [(1024, 7168)], {"trans": "T"}, 7168 ** 2 * 1024),
    ("trsm", [(1024, 1024), (7168, 1024)], {"side": "R"}, 7168 * 1024 ** 2),
    ("trsm", [(300, 300), (300, 40)], {"side": "L"}, 300 ** 2 * 40),
])
def test_flops(name, shapes, kw, want):
    assert routine(name).flops(shapes, kw) == want


def test_cholesky_update_is_6e10_flops():
    trsm = routine("trsm").flops([(1024, 1024), (7168, 1024)], {"side": "R"})
    syrk = routine("syrk").flops([(7168, 1024)], {"uplo": "L"})
    assert trsm + syrk == pytest.approx(6.0e10, rel=0.01)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def test_gemm_reference_and_rows(rng):
    a, b, c = (rng.standard_normal((6, 6)) for _ in range(3))
    kw = {"C": c, "alpha": 2.0, "beta": -1.0, "transb": "T"}
    full = routine("gemm").reference([a, b], kw)
    np.testing.assert_allclose(full, 2.0 * a @ b.T - c)
    rows = np.array([1, 4])
    np.testing.assert_allclose(routine("gemm").reference([a, b], kw, rows),
                               full[rows])


@pytest.mark.parametrize("uplo", ["L", "U"])
def test_syrk_reference_keeps_the_other_triangle(rng, uplo):
    a = rng.standard_normal((5, 3))
    c = rng.standard_normal((5, 5))
    kw = {"C": c, "alpha": -1.0, "beta": 1.0, "uplo": uplo}
    got = routine("syrk").reference([a], kw)
    tri = np.tril if uplo == "L" else np.triu
    other = np.triu(c, 1) if uplo == "L" else np.tril(c, -1)
    np.testing.assert_allclose(got, tri(c - a @ a.T) + other)
    rows = np.array([0, 3])
    np.testing.assert_allclose(routine("syrk").reference([a], kw, rows),
                               got[rows])


@pytest.mark.parametrize("side,transa", [("R", "T"), ("R", "N"),
                                         ("L", "N"), ("L", "T")])
def test_trsm_reference_solves(rng, side, transa):
    a = np.tril(rng.standard_normal((4, 4))) + 4 * np.eye(4)
    b = rng.standard_normal((6, 4) if side == "R" else (4, 6))
    kw = {"side": side, "uplo": "L", "transa": transa, "alpha": 0.5}
    x = routine("trsm").reference([a, b], kw)
    op = a.T if transa == "T" else a
    lhs = x @ op if side == "R" else op @ x
    np.testing.assert_allclose(lhs, 0.5 * b, atol=1e-12)
    rows = np.array([1, 2])
    np.testing.assert_allclose(routine("trsm").reference([a, b], kw, rows),
                               x[rows])


def test_trsm_side_r_matches_scipy(rng):
    a = np.tril(rng.standard_normal((5, 5))) + 5 * np.eye(5)
    b = rng.standard_normal((7, 5))
    x = routine("trsm").reference(
        [a, b], {"side": "R", "uplo": "L", "transa": "T"})
    want = scipy.linalg.solve_triangular(a, b.T, lower=True).T
    np.testing.assert_allclose(x, want)
