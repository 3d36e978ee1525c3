"""Plain float64 numpy references; nothing here imports the program.

The 2-D transforms are checked through lines and projections, each one
blocked pass over the input, so that a 16384^2 spectrum (4 GiB in
complex128) is never formed on the host:

* lines: rows ``Y[r, :]`` and columns ``Y[:, c]`` of ``Y = fft2(x)``;
* projections: ``u^T Y v`` for random complex vectors ``u, v``, which is
  ``fft(u)^T x fft(v)`` because the DFT matrix is symmetric. A
  projection sees every element of ``Y``: changing one element by its
  own size moves it by about ``1/N`` of ``||Y||_F``.
"""

from __future__ import annotations

import numpy as np

BLOCK_ROWS = 512


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.complex128)
    want = np.asarray(want, np.complex128)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def dft_rows(ks, n: int) -> np.ndarray:
    """Rows ``ks`` of the float64 DFT matrix of size ``n``."""
    return np.exp(-2j * np.pi * np.outer(ks, np.arange(n)) / n)


def complex_normal(rng: np.random.Generator, shape) -> np.ndarray:
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)) / np.sqrt(2)


def fft2_reference(x: np.ndarray, rows, cols, us, vs) -> dict:
    """float64 rows, columns and projections of ``Y = fft2(x)``, and
    ``||Y||_F = sqrt(N0 N1) ||x||_F`` (Parseval), from one pass over x:
    per block of rows, one product from the left (the rows' DFT weights
    and the ``fft(u)``) and one from the right (the columns' DFT
    weights)."""
    n0, n1 = x.shape
    b = np.stack([np.fft.fft(v) for v in vs])  # (K, n1)
    left = np.concatenate([dft_rows(rows, n0), [np.fft.fft(u) for u in us]])
    right = dft_rows(cols, n1).T  # (n1, C)
    acc = np.zeros((len(left), n1), np.complex128)
    u_cols = np.empty((n0, len(cols)), np.complex128)
    sq = 0.0
    for s in range(0, n0, BLOCK_ROWS):
        xb = x[s : s + BLOCK_ROWS].astype(np.complex128)
        sq += np.vdot(xb, xb).real
        acc += left[:, s : s + BLOCK_ROWS] @ xb
        u_cols[s : s + BLOCK_ROWS] = xb @ right
    ux = acc[len(rows) :]  # (K, n1): fft(u)^T x
    return {
        "rows": np.fft.fft(acc[: len(rows)], axis=1),
        "cols": np.fft.fft(u_cols, axis=0).T,
        "proj": np.einsum("kj,kj->k", ux, b),
        "norm": float(np.sqrt(n0 * n1 * sq)),
    }


def transposed_lines(y: np.ndarray, rows, cols) -> tuple:
    """Rows and columns of ``Y`` read from ``y = Y^T`` (the slab layout)."""
    return np.asarray(y[:, rows]).T, np.asarray(y[cols, :])


def transposed_projections(y: np.ndarray, us, vs) -> np.ndarray:
    """``u^T Y v`` in float64 from ``y = Y^T``: ``v^T y u``."""
    u = np.stack(us)  # (K, n0)
    v = np.stack(vs)  # (K, n1)
    out = np.zeros(len(us), np.complex128)
    for s in range(0, y.shape[0], BLOCK_ROWS):
        yb = y[s : s + BLOCK_ROWS].astype(np.complex128)
        out += np.einsum("kj,kj->k", v[:, s : s + BLOCK_ROWS], (yb @ u.T).T)
    return out
