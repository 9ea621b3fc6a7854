"""GEMM: C = alpha * op(A) @ op(B) + beta * C."""
from __future__ import annotations

import numpy as np


def _op(x, trans: str):
    return x if trans.upper()[0] == "N" else x.T


def _dims(shapes, kw):
    a, b = shapes[0], shapes[1]
    m, k = a if kw.get("transa", "N").upper()[0] == "N" else a[::-1]
    n = b[1] if kw.get("transb", "N").upper()[0] == "N" else b[0]
    return m, k, n


def out_shape(shapes, kw):
    m, _, n = _dims(shapes, kw)
    return (m, n)


def flops(shapes, kw) -> int:
    m, k, n = _dims(shapes, kw)
    return 2 * m * k * n


def reference(args, kw, rows=None, mm=np.matmul):
    a = _op(args[0], kw.get("transa", "N"))
    b = _op(args[1], kw.get("transb", "N"))
    if rows is not None:
        a = a[rows]
    out = kw.get("alpha", 1.0) * mm(a, b)
    beta = kw.get("beta", 0.0)
    if beta:
        c = kw["C"] if rows is None else kw["C"][rows]
        out = out + beta * c
    return out
