"""Local (on-chip) FFT in MXU-friendly matmul form.

TPU adaptation of the paper's per-node FFTW stage: TPUs have no scalar
FFT codelets -- the efficient formulation is the Cooley-Tukey / Bailey
four-step factorization expressed as DFT-*matrix* matmuls, which map
directly onto the 128x128 MXU systolic array.

For a length-``n`` transform with ``n = n1 * n2``::

    A           = x.reshape(n1, n2)                    # j = j1*n2 + j2
    B[k1, j2]   = sum_j1 W_n1[k1, j1] * A[j1, j2]      # DFT over j1  (matmul)
    C[k1, j2]   = B[k1, j2] * exp(-2*pi*i*k1*j2 / n)   # twiddle
    D[k1, k2]   = sum_j2 C[k1, j2] * W_n2[k2, j2]      # DFT over j2  (matmul)
    X[k1+n1*k2] = D[k1, k2]                            # transposed read-out

The recursion bottoms out at a direct DFT matmul of size <= ``max_dft``.
``jnp.fft`` is kept as the oracle path (it is also what the ``xla_auto``
distributed reference uses, mirroring the paper's FFTW3 baseline).

All twiddle/DFT tables are computed host-side in float64 (numpy) and cast
to complex64, which keeps the matmul-FFT error ~1e-5 relative even for
n = 2^14 (validated in tests/test_fft_local.py).
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs

LocalImpl = Literal["jnp", "matmul", "pallas"]

#: Largest direct DFT-matrix applied as a single matmul. 512 keeps the
#: operand (512x512 c64 = 2 MiB as 4 real f32 matmuls of 1 MiB) well within
#: one VMEM-resident tile set while giving the MXU K-dims >= 128.
MAX_DFT = 512


@functools.lru_cache(maxsize=64)
def _dft_matrix_np(n: int, dtype: str = "complex64") -> np.ndarray:
    """DFT matrix W[k, j] = exp(-2*pi*i*k*j/n), computed in float64 and
    cast to ``dtype`` (the fused exchange stages keep complex128 tables
    so c128 transforms stay at double precision)."""
    k = np.arange(n, dtype=np.float64)
    return np.exp(-2j * np.pi * np.outer(k, k) / n).astype(dtype)


@functools.lru_cache(maxsize=64)
def _twiddle_np(n1: int, n2: int, dtype: str = "complex64") -> np.ndarray:
    """Four-step twiddle T[k1, j2] = exp(-2*pi*i*k1*j2/(n1*n2)), float64."""
    k1 = np.arange(n1, dtype=np.float64)
    j2 = np.arange(n2, dtype=np.float64)
    return np.exp(-2j * np.pi * np.outer(k1, j2) / (n1 * n2)).astype(dtype)


def dft_matrix(n: int, dtype="complex64") -> jax.Array:
    return jnp.asarray(_dft_matrix_np(n, np.dtype(dtype).name))


def twiddle(n1: int, n2: int, dtype="complex64") -> jax.Array:
    return jnp.asarray(_twiddle_np(n1, n2, np.dtype(dtype).name))


def split_factor(n: int, max_dft: int = MAX_DFT) -> int:
    """Pick n1 | n, n1 <= max_dft, as close to sqrt(n) as possible.

    Returns 0 if ``n`` has no factor in [2, max_dft] (prime beyond the
    direct-DFT limit) -- the caller falls back to a direct O(n^2) DFT.
    """
    if n <= max_dft:
        return n
    best = 0
    f = 2
    while f * f <= n:
        if n % f == 0:
            for cand in (n // f, f):
                if cand <= max_dft and cand > best:
                    best = cand
        f += 1
    return best


def _fft_matmul_c64(x: jax.Array, max_dft: int) -> jax.Array:
    """Forward FFT along the last axis via recursive four-step matmuls."""
    n = x.shape[-1]
    if n == 1:
        return x
    n1 = split_factor(n, max_dft)
    if n1 in (0, n):
        # Direct DFT: either small enough, or prime beyond the limit.
        return jnp.einsum("...j,kj->...k", x, dft_matrix(n))
    n2 = n // n1
    a = x.reshape(x.shape[:-1] + (n1, n2))
    b = jnp.einsum("kj,...jl->...kl", dft_matrix(n1), a)
    b = b * twiddle(n1, n2)
    c = _fft_matmul_c64(b, max_dft)  # transform along last (j2 -> k2) axis
    d = jnp.swapaxes(c, -1, -2)  # (..., k2, k1): index k1 + n1*k2
    return d.reshape(x.shape[:-1] + (n,))


def fft_matmul(x: jax.Array, *, inverse: bool = False, max_dft: int = MAX_DFT) -> jax.Array:
    """FFT along the last axis, MXU matmul formulation. Unnormalized
    forward; inverse carries the 1/n factor (matches jnp.fft)."""
    x = x.astype(jnp.complex64)
    if inverse:
        n = x.shape[-1]
        return jnp.conj(_fft_matmul_c64(jnp.conj(x), max_dft)) / n
    return _fft_matmul_c64(x, max_dft)


def _fft_pallas(x: jax.Array, *, inverse: bool = False) -> jax.Array:
    # Imported lazily: kernels are optional at import time.
    from repro.kernels import ops as kops

    return kops.fft_last_axis(x, inverse=inverse)


def local_fft(
    x: jax.Array,
    *,
    axis: int = -1,
    inverse: bool = False,
    impl: LocalImpl = "jnp",
    max_dft: int = MAX_DFT,
) -> jax.Array:
    """1-D FFT along ``axis`` with a selectable implementation.

    ``jnp``    -- oracle / reference (XLA's own FFT op).
    ``matmul`` -- four-step DFT matmuls (MXU formulation, pure jnp).
    ``pallas`` -- the fused Pallas kernel (kernels/fft_stage.py).

    Every op it traces runs under the ``repro.local_fft`` layer scope.
    """
    with obs.layer(obs.LOCAL_FFT):
        x = jnp.asarray(x)
        if not jnp.issubdtype(x.dtype, jnp.complexfloating):
            x = x.astype(jnp.complex64)
        if axis != -1 and axis != x.ndim - 1:
            y = _local_fft_last(jnp.moveaxis(x, axis, -1), inverse, impl, max_dft)
            return jnp.moveaxis(y, -1, axis)
        return _local_fft_last(x, inverse, impl, max_dft)


def _local_fft_last(x: jax.Array, inverse: bool, impl: LocalImpl, max_dft: int) -> jax.Array:
    if impl == "jnp":
        return jnp.fft.ifft(x, norm="backward") if inverse else jnp.fft.fft(x)
    if impl == "matmul":
        return fft_matmul(x, inverse=inverse, max_dft=max_dft)
    if impl == "pallas":
        return _fft_pallas(x, inverse=inverse)
    raise ValueError(f"unknown local FFT impl: {impl!r}")


def local_fft2(x: jax.Array, *, inverse: bool = False, impl: LocalImpl = "jnp") -> jax.Array:
    """2-D FFT over the last two axes (single-device reference)."""
    y = local_fft(x, axis=-1, inverse=inverse, impl=impl)
    return local_fft(y, axis=-2, inverse=inverse, impl=impl)
