"""SYRK: C = alpha * op(A) @ op(A)^T + beta * C on the ``uplo`` triangle;
the other triangle keeps C (zeros without C)."""
from __future__ import annotations

import numpy as np


def _op(x, trans: str):
    return x if trans.upper()[0] == "N" else x.T


def _dims(shapes, kw):
    n, k = shapes[0] if kw.get("trans", "N").upper()[0] == "N" \
        else shapes[0][::-1]
    return n, k


def out_shape(shapes, kw):
    n, _ = _dims(shapes, kw)
    return (n, n)


def flops(shapes, kw) -> int:
    n, k = _dims(shapes, kw)
    return n * n * k


def reference(args, kw, rows=None, mm=np.matmul):
    a = _op(args[0], kw.get("trans", "N"))
    n = a.shape[0]
    r = np.arange(n) if rows is None else np.asarray(rows)
    prod = kw.get("alpha", 1.0) * mm(a[r], a.T)
    c = kw.get("C")
    kept = np.zeros_like(prod) if c is None else c[r].astype(prod.dtype)
    beta = kw.get("beta", 0.0)
    new = prod + beta * kept if beta else prod
    cols = np.arange(n)[None, :]
    if kw.get("uplo", "U").upper()[0] == "L":
        tri = cols <= r[:, None]
    else:
        tri = cols >= r[:, None]
    return np.where(tri, new, kept)
