"""Plain float64 numpy reference of NAS Parallel Benchmarks FT; nothing
here imports the program.

NPB FT from an initial field ``u[z, y, x]`` (NPB 3.x ``FT/ft.f``;
RNR-94-007): ``u_hat = fftn(u)``; for ``t = 1..niter``, the cumulative
evolve ``u_hat <- u_hat * tau`` with ``tau[k] = exp(-4 alpha pi^2 |k|^2)``
(``k`` the signed mode numbers), ``u2_t = ifftn(u_hat)`` and the checksum
``C_t = sum_{j=1..1024} u2_t[5j mod nz, 3j mod ny, j mod nx]``.

At 512^3 no ``u2_t`` is formed. From one float64 ``fftn``:

* every ``C_t`` from one binned pass: ``C_t = sum_k u_hat[k] tau[k]^t
  W(m(k)) / N``, where the points' phases ``k . x_j`` are ``j m(k) / L``
  (``L`` the least common multiple of the sides) and ``W(m) = sum_j
  exp(2 pi i j m / L)``. Grouped by ``s = |k|^2``, on which ``tau``
  depends, the pass is one ``bincount`` per plane;
* the scale each ``C_t`` is compared at: ``sqrt(1024 / N) ||u2_t||_F``,
  the norm of 1024 values of ``u2_t`` at its mean square, from the
  same pass by Parseval (``||u2_t||_F^2 = sum_k |u_hat tau^t|^2 / N``);
* projections ``sum_x a_z[z] a_y[y] a_x[x] u2_t[x]`` by separable
  vectors: ``tau^t`` is separable too, so each is one contraction of
  ``u_hat`` with three vectors.

:func:`whole_array` computes the same from every ``u2_t``, for the tests.
"""

from __future__ import annotations

import math
import os

import numpy as np

try:
    import scipy.fft as _scipy_fft
except ImportError:  # numpy's single-threaded FFT
    _scipy_fft = None

COUNT = 1024
STRIDES = (5, 3, 1)
#: planes of ``u2`` converted to complex128 at a time
BLOCK = 64


def fftn(u: np.ndarray) -> np.ndarray:
    """float64 ``fftn`` of ``u``, on every core where scipy is here."""
    x = np.array(u, np.complex128)  # a copy, which the transform may overwrite
    if _scipy_fft is not None:
        return _scipy_fft.fftn(x, workers=os.cpu_count(), overwrite_x=True)
    return np.fft.fftn(x)


def modes(n: int) -> np.ndarray:
    """The signed mode numbers ``0, 1, .., n/2 - 1, -n/2, .., -1``."""
    return np.rint(np.fft.fftfreq(n) * n).astype(np.int64)


def decay(alpha: float) -> float:
    """``log tau`` per unit of ``|k|^2``."""
    return -4.0 * alpha * np.pi**2


def _point_weights(shape) -> tuple:
    """(W over m in [0, L), m_d(k_d) per axis) of the checksum's points."""
    lcm = math.lcm(*shape)
    j = np.arange(1, COUNT + 1)
    m = np.arange(lcm)
    w = np.exp(2j * np.pi * (np.outer(m, j) % lcm) / lcm).sum(axis=1)
    per_axis = [(s * np.arange(n) * (lcm // n)) % lcm for s, n in zip(STRIDES, shape)]
    return w, per_axis, lcm


def binned(u_hat: np.ndarray) -> tuple:
    """(G, E) over ``s = |k|^2``: ``G[s] = sum_{|k|^2 = s} u_hat W(m(k))``
    and ``E[s] = sum_{|k|^2 = s} |u_hat|^2``, one plane at a time."""
    nz, ny, nx = u_hat.shape
    w, (mz, my, mx), lcm = _point_weights(u_hat.shape)
    sz, sy, sx = (modes(n) ** 2 for n in u_hat.shape)
    s_yx = sy[:, None] + sx[None, :]
    m_yx = my[:, None] + mx[None, :]
    size = int(sz.max() + sy.max() + sx.max()) + 1
    g_re, g_im, e = np.zeros(size), np.zeros(size), np.zeros(size)
    for z in range(nz):
        plane = u_hat[z]
        s = (s_yx + sz[z]).ravel()
        v = (plane * w[(m_yx + mz[z]) % lcm]).ravel()
        g_re += np.bincount(s, v.real, size)
        g_im += np.bincount(s, v.imag, size)
        e += np.bincount(s, (plane.real**2 + plane.imag**2).ravel(), size)
    return g_re + 1j * g_im, e


def checksums(u_hat: np.ndarray, niter: int, alpha: float) -> tuple:
    """(``C_t``, the scale of ``C_t``, ``||u2_t||_F``) for ``t = 1..niter``."""
    g, e = binned(u_hat)
    n = u_hat.size
    s = np.arange(g.size)
    t = np.arange(1, niter + 1)[:, None]
    sums = np.exp(decay(alpha) * s * t) @ g / n
    norm = np.sqrt(np.exp(2 * decay(alpha) * s * t) @ e / n)
    return sums, np.sqrt(COUNT / n) * norm, norm


def projections_of_spectrum(u_hat: np.ndarray, t: int, alpha: float, vecs) -> np.ndarray:
    """``sum_x a_z[z] a_y[y] a_x[x] u2_t[z, y, x]`` for each row of
    ``vecs = (a_z, a_y, a_x)`` (each ``(P, n)``), from the spectrum:
    ``u2_t = ifftn(u_hat tau^t)`` and ``tau^t`` is separable, so each
    vector meets its axis as ``n ifft(a) tau_axis^t``."""
    bz, by, bx = (
        n * np.fft.ifft(a, axis=1) * np.exp(decay(alpha) * t * modes(n) ** 2)
        for a, n in zip(vecs, u_hat.shape)
    )
    nz, ny, nx = u_hat.shape
    part = (u_hat.reshape(-1, nx) @ bx.T).reshape(nz, ny, -1)
    return np.einsum("zyp,py,pz->p", part, by, bz) / u_hat.size


def projections(u: np.ndarray, vecs) -> np.ndarray:
    """``sum_x a_z[z] a_y[y] a_x[x] u[z, y, x]`` in float64, a block of
    planes at a time."""
    az, ay, ax = vecs
    out = np.zeros(len(az), np.complex128)
    for z0 in range(0, u.shape[0], BLOCK):
        block = np.asarray(u[z0 : z0 + BLOCK], np.complex128)
        part = (block.reshape(-1, u.shape[2]) @ ax.T).reshape(block.shape[0], u.shape[1], -1)
        out += np.einsum("zyp,py,pz->p", part, ay, az[:, z0 : z0 + BLOCK])
    return out


def reference(u: np.ndarray, niter: int, alpha: float, t_last: int, vecs) -> dict:
    """Everything the check compares, from one ``fftn`` of ``u``: the
    ``niter`` checksums and their scales, and the projections and norm
    of ``u2`` at iteration ``t_last``."""
    u_hat = fftn(u)
    sums, scale, norm = checksums(u_hat, niter, alpha)
    proj = projections_of_spectrum(u_hat, t_last, alpha, vecs)
    return {"checksums": sums, "scale": scale, "proj": proj, "norm": norm[t_last - 1]}


def whole_array(u: np.ndarray, niter: int, alpha: float, t_last: int, vecs) -> dict:
    """:func:`reference` from every ``u2_t`` (small grids only)."""
    u_hat = np.fft.fftn(np.asarray(u, np.complex128))
    k2 = sum(np.meshgrid(*(modes(n) ** 2 for n in u.shape), indexing="ij"))
    tau = np.exp(decay(alpha) * k2)
    j = np.arange(1, COUNT + 1)
    points = tuple((s * j) % n for s, n in zip(STRIDES, u.shape))
    out = {"checksums": [], "scale": []}
    for t in range(1, niter + 1):
        u_hat = u_hat * tau
        u2 = np.fft.ifftn(u_hat)
        out["checksums"].append(u2[points].sum())
        out["scale"].append(np.sqrt(COUNT / u2.size) * np.linalg.norm(u2))
        if t == t_last:
            out["proj"] = np.einsum("zyx,pz,py,px->p", u2, *vecs)
            out["norm"] = np.linalg.norm(u2)
    return {k: np.asarray(v) for k, v in out.items()}
