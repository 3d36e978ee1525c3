"""jit'd public wrappers around the Pallas FFT kernels.

``fft_last_axis(x)`` runs the four-step local FFT with both matmul stages
executed by the fused Pallas kernel (fft_stage.py):

    A = x.reshape(-1, n1, n2)
    B = stage_left(W_n1, A, T_n1n2)      # column DFT + twiddle, fused
    D = stage_right(B, W_n2)             # row DFT
    out[k1 + n1*k2] = D[k1, k2]

The kernels are compiled when the program is lowered for a TPU and run in
interpret mode on every other platform (fft_stage._pallas) -- the same
math op by op, so tests on CPU validate what the TPU runs.

Factor choice: n1 * n2 = n, both <= MAX_DFT, each either a multiple of
the 128-lane tile or small enough (<= 128) to be one whole block. A
length with no such split (n <= MAX_DFT, primes, large prime factors)
cannot use the kernel: on a TPU that raises, naming the length (plans
check it at plan time); the CPU rehearsal computes it with the pure-jnp
matmul FFT instead.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

import repro.core.fftmath as lf
from repro.kernels import fft_stage

#: MXU / lane tile: a factor above this must be a multiple of it
LANE = 128


def _split_planar(x: jax.Array):
    return jnp.real(x).astype(jnp.float32), jnp.imag(x).astype(jnp.float32)


def _kernel_factors(n: int) -> Optional[tuple[int, int]]:
    """Pick (n1, n2) for the kernel: both <= MAX_DFT and tileable, both
    multiples of the lane width when possible, then the most balanced."""
    if n <= lf.MAX_DFT:
        return None
    best, best_key = None, None
    for n1 in range(2, lf.MAX_DFT + 1):
        n2, rem = divmod(n, n1)
        if rem or n2 > lf.MAX_DFT:
            continue
        if any(f > LANE and f % LANE for f in (n1, n2)):
            continue
        key = (n1 % LANE == 0 and n2 % LANE == 0, -abs(n1 - n2), n1)
        if best_key is None or key > best_key:
            best, best_key = (n1, n2), key
    return best


def check_tileable(n: int) -> tuple[int, int]:
    """The kernel factorisation of length ``n``; raises naming the length
    when the kernel cannot tile it."""
    factors = _kernel_factors(n)
    if factors is None:
        raise ValueError(
            f"the Pallas FFT kernel cannot tile a length-{n} transform: it "
            f"needs n = n1*n2 with n1, n2 <= {lf.MAX_DFT}, each <= {LANE} or "
            f"a multiple of {LANE} (use local_impl='jnp' or 'matmul')"
        )
    return factors


@functools.partial(jax.jit, static_argnames=("inverse", "bm", "bn"))
def _fft_last_axis(x, *, inverse: bool, bm: int, bn: int):
    n = x.shape[-1]
    n1, n2 = check_tileable(n)

    v = jnp.conj(x) if inverse else x
    lead = v.shape[:-1]
    a = v.reshape((-1, n1, n2))
    w1 = jnp.asarray(lf._dft_matrix_np(n1))
    tw = jnp.asarray(lf._twiddle_np(n1, n2))
    w2 = jnp.asarray(lf._dft_matrix_np(n2))

    b_re, b_im = fft_stage.stage_left(
        _split_planar(w1), _split_planar(a), _split_planar(tw),
        bm=min(bm, n1), bn=min(bn, n2),
    )
    d_re, d_im = fft_stage.stage_right(
        (b_re, b_im), _split_planar(w2), bm=min(bm, n1), bn=min(bn, n2),
    )
    d = d_re + 1j * d_im  # (B, k1, k2); flat index k1 + n1*k2
    out = jnp.swapaxes(d, -1, -2).reshape(lead + (n,)).astype(jnp.complex64)
    if inverse:
        out = jnp.conj(out) / n
    return out


def fft_last_axis(
    x: jax.Array,
    *,
    inverse: bool = False,
    bm: int = 128,
    bn: int = 128,
) -> jax.Array:
    """FFT along the last axis via the Pallas fused-stage kernels."""
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    n = x.shape[-1]
    if _kernel_factors(n) is None and jax.default_backend() != "tpu":
        return lf.fft_matmul(x, inverse=inverse)
    return _fft_last_axis(x, inverse=inverse, bm=bm, bn=bn)


#: thin public re-exports (planar operands)
stage_left = fft_stage.stage_left
stage_right = fft_stage.stage_right
