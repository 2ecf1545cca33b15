"""The exact-product reference of the GEMM cells: double-double products.

``dd_matmul`` is the vectorized double-double (Dekker two-product, TwoSum)
matrix product, effective precision about 2^-106.  ``dd_dots`` applies the
same arithmetic to a set of independent dot products, which is what the
benchmark compares: entries of the product drawn from the seed.  Plain
numpy; nothing here comes from the program under test.
"""
from __future__ import annotations

import numpy as np

_SPLITTER = 134217729.0  # 2^27 + 1, Dekker split constant for f64


def _two_prod(a: np.ndarray, b: np.ndarray):
    """a*b = p + e exactly (Dekker two-product, no FMA needed)."""
    p = a * b
    a1 = a * _SPLITTER
    ah = a1 - (a1 - a)
    al = a - ah
    b1 = b * _SPLITTER
    bh = b1 - (b1 - b)
    bl = b - bh
    e = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return p, e


def _two_sum(a: np.ndarray, b: np.ndarray):
    s = a + b
    bb = s - a
    e = (a - (s - bb)) + (b - bb)
    return s, e


def dd_matmul(a: np.ndarray, b: np.ndarray, block: int | None = None):
    """Double-double A @ B: (hi, lo) with hi + lo accurate to ~2^-106.
    Every ``block`` gives bit-identical output (the TwoSum accumulation
    walks the contraction in column order)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    m, n = a.shape
    n2, p = b.shape
    if n != n2:
        raise ValueError(f"bad shapes {a.shape} @ {b.shape}")
    if block is None:
        block = max(1, min(64, (1 << 14) // max(m * p, 1)))
    hi = np.zeros((m, p))
    lo = np.zeros((m, p))
    for j0 in range(0, n, block):
        j1 = min(j0 + block, n)
        prod, perr = _two_prod(a[:, j0:j1, None], b[None, j0:j1, :])
        for i in range(j1 - j0):
            hi, e = _two_sum(hi, prod[:, i, :])
            lo += e + perr[:, i, :]
    return _two_sum(hi, lo)


def dd_dots(rows: np.ndarray, cols: np.ndarray, block: int = 256):
    """Double-double dot products of paired vectors: ``rows[i] . cols[i]``
    for rows, cols of shape (N, n).  Returns (hi, lo), each (N,); the same
    arithmetic and order as :func:`dd_matmul`."""
    rows = np.asarray(rows, np.float64)
    cols = np.asarray(cols, np.float64)
    if rows.shape != cols.shape:
        raise ValueError(f"bad shapes {rows.shape} . {cols.shape}")
    N, n = rows.shape
    hi = np.zeros(N)
    lo = np.zeros(N)
    for j0 in range(0, n, block):
        j1 = min(j0 + block, n)
        prod, perr = _two_prod(rows[:, j0:j1], cols[:, j0:j1])
        for i in range(j1 - j0):
            hi, e = _two_sum(hi, prod[:, i])
            lo += e + perr[:, i]
    return _two_sum(hi, lo)
