"""Pallas kernels vs pure-jnp oracles: shape/dtype sweep in interpret mode."""

import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


def _planar(rng, shape):
    return (
        rng.standard_normal(shape).astype(np.float32),
        rng.standard_normal(shape).astype(np.float32),
    )


@pytest.mark.parametrize(
    "b,m,k,n",
    [(1, 128, 128, 128), (2, 256, 64, 128), (3, 128, 512, 256), (1, 384, 128, 384)],
)
def test_stage_left_matches_ref(rng, b, m, k, n):
    w = _planar(rng, (m, k))
    a = _planar(rng, (b, k, n))
    t = _planar(rng, (m, n))
    got = ops.stage_left(w, a, t)
    exp = ref.stage_left_ref(w, a, t)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=2e-4, atol=2e-3)


@pytest.mark.parametrize("b,m,k,n", [(1, 128, 128, 128), (2, 128, 256, 128)])
def test_stage_right_matches_ref(rng, b, m, k, n):
    a = _planar(rng, (b, m, k))
    w = _planar(rng, (n, k))
    got = ops.stage_right(a, w)
    exp = ref.stage_right_ref(a, w)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=2e-4, atol=2e-3)


def test_stage_left_block_sweep(rng):
    """BlockSpec tiling must not change results."""
    w = _planar(rng, (256, 128))
    a = _planar(rng, (1, 128, 256))
    t = _planar(rng, (256, 256))
    base = ops.stage_left(w, a, t, bm=256, bn=256)
    for bm in (64, 128):
        for bn in (64, 128, 256):
            got = ops.stage_left(w, a, t, bm=bm, bn=bn)
            for g, e in zip(got, base):
                np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize(
    "lead,rows,c,p",
    # the last case spans a 3x2 grid of CHUNK_TILE tiles
    [((3,), 4, 6, 8), ((2, 5), 8, 4, 2), ((1,), 1, 3, 4), ((1,), 256, 384, 4)],
)
def test_chunk_twiddle_pack_matches_jnp(rng, lead, rows, c, p):
    """The pipelined overlap executor's one-launch per-chunk callback:
    relayout + W_P-column x twiddle multiply == the two-op jnp path."""
    from repro.kernels import fft_stage

    chunk = (rng.standard_normal(lead + (rows, c)) + 1j * rng.standard_normal(
        lead + (rows, c))).astype(np.complex64)
    m = (rng.standard_normal((p, rows)) + 1j * rng.standard_normal((p, rows))).astype(
        np.complex64)
    got = np.asarray(fft_stage.chunk_twiddle_pack_c64(jnp.asarray(chunk), jnp.asarray(m)))
    ct = np.swapaxes(chunk, -1, -2)  # (..., c, rows)
    exp = ct[..., None, :] * m  # (..., c, p, rows)
    assert got.shape == lead + (c, p, rows)
    assert got.dtype == np.complex64
    np.testing.assert_allclose(got, exp, rtol=1e-5, atol=1e-5)


def test_chunk_twiddle_pack_rejects_wrong_dtype_and_shape(rng):
    from repro.kernels import fft_stage

    chunk = jnp.zeros((2, 4, 6), jnp.complex64)
    # a non-c64 chunk (x64 may be disabled, so use the real dtype)
    with pytest.raises(ValueError, match="planar-f32"):
        fft_stage.chunk_twiddle_pack_c64(jnp.zeros((2, 4, 6), jnp.float32),
                                         jnp.zeros((8, 4), jnp.complex64))
    with pytest.raises(ValueError, match=r"\(p, rows\)"):
        fft_stage.chunk_twiddle_pack_c64(chunk, jnp.zeros((8, 5), jnp.complex64))


@pytest.mark.parametrize("n", [1024, 4096, 16384])
@pytest.mark.parametrize("inverse", [False, True])
def test_fft_last_axis_vs_oracle(rng, n, inverse):
    x = (rng.standard_normal((2, n)) + 1j * rng.standard_normal((2, n))).astype(np.complex64)
    got = np.asarray(ops.fft_last_axis(jnp.asarray(x), inverse=inverse))
    exp = np.asarray(ref.fft_last_axis_ref(jnp.asarray(x), inverse=inverse))
    scale = np.abs(exp).max() + 1e-9
    assert np.abs(got - exp).max() / scale < 2e-5


@pytest.mark.parametrize(
    "n,factors",
    [(16384, (128, 128)), (4096, (64, 64)), (65536, (256, 256)), (1000, (40, 25))],
)
def test_kernel_factors_prefer_lane_multiples(n, factors):
    assert ops.check_tileable(n) == factors


@pytest.mark.parametrize("n", [512, 1021, 129 * 129])
def test_check_tileable_names_the_length(n):
    with pytest.raises(ValueError, match=f"length-{n} transform"):
        ops.check_tileable(n)


def test_fft_last_axis_fallback_odd_size(rng):
    # 1021 prime: wrapper falls back to the matmul path transparently
    x = (rng.standard_normal((1021,)) + 1j * rng.standard_normal((1021,))).astype(np.complex64)
    got = np.asarray(ops.fft_last_axis(jnp.asarray(x)))
    exp = np.fft.fft(x)
    assert np.abs(got - exp).max() / np.abs(exp).max() < 1e-4


@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 4),
    n=st.sampled_from([1024, 2048, 4096]),
    seed=st.integers(0, 2**31 - 1),
)
def test_fft_kernel_property_sweep(b, n, seed):
    r = np.random.default_rng(seed)
    x = (r.standard_normal((b, n)) + 1j * r.standard_normal((b, n))).astype(np.complex64)
    got = np.asarray(ops.fft_last_axis(jnp.asarray(x)))
    exp = np.fft.fft(x, axis=-1)
    assert np.abs(got - exp).max() / (np.abs(exp).max() + 1e-9) < 2e-5
