"""NAS Parallel Benchmarks FT in whole-array float64 numpy, written from
NPB's description (NPB 3.x ``FT/ft.f``; RNR-94-007) and independent of
the program.

For an initial field ``u[z, y, x]``: ``u_hat = fftn(u)``; then for
``t = 1..niter``: ``u_hat <- u_hat * exp(-4 alpha pi^2 |k|^2)`` with ``k``
the signed mode numbers (cumulative), ``u2 = ifftn(u_hat)`` (normalised),
and ``C_t = sum_{j=1..1024} u2[5j mod nz, 3j mod ny, j mod nx]``.
"""

import numpy as np


def twiddle(shape, alpha: float) -> np.ndarray:
    """``exp(-4 alpha pi^2 (kz^2 + ky^2 + kx^2))`` in the natural layout."""
    k2 = 0.0
    for axis, n in enumerate(shape):
        k = np.fft.fftfreq(n) * n  # 0, 1, .., n/2 - 1, -n/2, .., -1
        k2 = k2 + (k * k).reshape([n if a == axis else 1 for a in range(len(shape))])
    return np.exp(-4.0 * alpha * np.pi**2 * k2)


def checksum(u2: np.ndarray, count: int = 1024) -> complex:
    nz, ny, nx = u2.shape
    return complex(sum(u2[(5 * j) % nz, (3 * j) % ny, j % nx] for j in range(1, count + 1)))


def npb_ft(u: np.ndarray, niter: int, alpha: float = 1e-6):
    """(the ``niter`` checksums, the last ``u2``) of NPB FT from ``u``."""
    u_hat = np.fft.fftn(np.asarray(u, np.complex128))
    tau = twiddle(u.shape, alpha)
    sums = []
    for _ in range(niter):
        u_hat = u_hat * tau
        u2 = np.fft.ifftn(u_hat)
        sums.append(checksum(u2))
    return np.array(sums), u2
