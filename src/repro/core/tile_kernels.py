"""Tile materialization + per-tile solver kernels for the runtime.

Fill modifiers realize triangular/symmetric *storage* semantics: stored
tiles are always dense, only the ``uplo`` triangle is meaningful, so we
mask/symmetrize on load (before the §III-C transpose trick).

Step *execution* moved to the pluggable backends in
``repro.backends`` (numpy | jax | pallas, batched per step group).
The TRSM finalize solver stays here — it runs per task on the host
either way.
"""
from __future__ import annotations

import numpy as np
import scipy.linalg

from .task import (FILL_FULL, FILL_SYM_L, FILL_SYM_U, FILL_TRI_L,
                   FILL_TRI_LU, FILL_TRI_U, FILL_TRI_UU, TileRef)


def apply_fill(tile: np.ndarray, fill: str) -> np.ndarray:
    if fill == FILL_FULL:
        return tile
    if fill == FILL_SYM_U:
        u = np.triu(tile)
        return u + np.triu(tile, 1).T
    if fill == FILL_SYM_L:
        lo = np.tril(tile)
        return lo + np.tril(tile, -1).T
    if fill == FILL_TRI_U:
        return np.triu(tile)
    if fill == FILL_TRI_L:
        return np.tril(tile)
    if fill == FILL_TRI_UU:
        t = np.triu(tile, 1)
        return t + np.eye(tile.shape[0], tile.shape[1], dtype=tile.dtype)
    if fill == FILL_TRI_LU:
        t = np.tril(tile, -1)
        return t + np.eye(tile.shape[0], tile.shape[1], dtype=tile.dtype)
    raise ValueError(f"unknown fill {fill}")


def materialize(tile: np.ndarray, ref: TileRef) -> np.ndarray:
    out = apply_fill(tile, ref.fill)
    if ref.trans:
        out = out.T
    return out


# ------------------------------------------------------------ TRSM solver
def solve_triangular(a: np.ndarray, b: np.ndarray, lower: bool,
                     unit_diag: bool) -> np.ndarray:
    """Tile-level triangular solve for the TRSM finalize step."""
    return scipy.linalg.solve_triangular(
        a, b, lower=lower, unit_diagonal=unit_diag, check_finite=False)


def solve_triangular_right(a: np.ndarray, r: np.ndarray, lower: bool,
                           unit_diag: bool) -> np.ndarray:
    """Side-R TRSM finalize: X with ``X @ a = r``, solved as
    ``a^T X^T = r^T`` by the solver's own ``trans`` flag.  ``r.T`` is
    Fortran-ordered, so LAPACK solves in ``r``'s buffer (``r`` is
    overwritten) with no re-layout, and the result's ``.T`` is C-ordered
    again for the write-back."""
    return scipy.linalg.solve_triangular(
        a, r.T, trans="T", lower=lower, unit_diagonal=unit_diag,
        overwrite_b=True, check_finite=False).T
