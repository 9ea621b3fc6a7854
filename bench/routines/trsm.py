"""TRSM: solve op(tri(A)) @ X = alpha * B (side L) or
X @ op(tri(A)) = alpha * B (side R) for X."""
from __future__ import annotations

import numpy as np
import scipy.linalg


def _side(kw) -> str:
    return kw.get("side", "L").upper()[0]


def out_shape(shapes, kw):
    return tuple(shapes[1])


def flops(shapes, kw) -> int:
    m, n = shapes[1]
    return m * n * n if _side(kw) == "R" else m * m * n


def reference(args, kw, rows=None, mm=np.matmul):
    """``mm`` is unused: the solve has no product to lower."""
    a, b = args[0], args[1]
    alpha = kw.get("alpha", 1.0)
    lower = kw.get("uplo", "U").upper()[0] == "L"
    transposed = kw.get("transa", "N").upper()[0] != "N"
    unit = kw.get("diag", "N").upper()[0] == "U"
    if _side(kw) == "R":
        # X op(A) = alpha B  <=>  op(A)^T X^T = alpha B^T, row by row
        rhs = b if rows is None else b[rows]
        x_t = scipy.linalg.solve_triangular(
            a, (alpha * rhs).T, lower=lower, trans="N" if transposed else "T",
            unit_diagonal=unit)
        return x_t.T
    x = scipy.linalg.solve_triangular(
        a, alpha * b, lower=lower, trans="T" if transposed else "N",
        unit_diagonal=unit)
    return x if rows is None else x[rows]
