"""NAS Parallel Benchmarks FT (``repro.apps.npb_ft``) against the plain
float64 reference (``tests/npb_ft_reference.py``): every checksum and the
last field at P=1 slab, on a cube and on a grid whose three sides differ
(so that an axis or index mixed up shows), at NPB's alpha and at one
large enough that evolve moves every mode. A 4-host-device subprocess
runs the same app on a P=4 slab and a 2x2 pencil, whose spectrum layout
is axis-reversed: the checksums agree with the reference and each other.
"""

import numpy as np
import pytest

from conftest import run_subprocess

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import npb_ft_reference as ref  # noqa: E402
from repro.apps.npb_ft import NPBFT, checksum_positions, decay_factors  # noqa: E402
from repro.core import plan_fft  # noqa: E402
from repro.core.compat import make_mesh  # noqa: E402

NITER = 20
#: complex64 transforms against float64, relative to the checksums' and
#: the field's own size
TOL = 1e-5


def _field(shape, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)


def _slab_p1(shape):
    return plan_fft(shape, make_mesh((1,), ("model",)), ndim=3, decomp="slab",
                    backend="alltoall", local_impl="jnp", dtype=jnp.complex64)


@pytest.mark.parametrize("shape", [(32, 32, 32), (16, 32, 64)])
@pytest.mark.parametrize("alpha", [1e-6, 1e-3])
def test_slab_p1_matches_reference(shape, alpha):
    u = _field(shape)
    plan = _slab_p1(shape)
    sums, u2 = NPBFT(plan, alpha=alpha).run(jax.device_put(u, plan.input_sharding()), NITER)
    want_sums, want_u2 = ref.npb_ft(u, NITER, alpha)
    sums = np.asarray(sums)
    assert sums.shape == (NITER,)
    assert np.max(np.abs(sums - want_sums)) / np.max(np.abs(want_sums)) < TOL
    assert np.linalg.norm(np.asarray(u2) - want_u2) / np.linalg.norm(want_u2) < TOL
    if alpha == 1e-3:  # evolve moves the checksums far beyond the tolerance
        assert np.abs(want_sums[0] - want_sums[-1]) > 0.1 * np.max(np.abs(want_sums))


@pytest.mark.parametrize("alpha", [1e-6, 1e-3])
def test_tau_is_float32_exact(alpha):
    """tau is the float64 formula's factors, each rounded once to float32,
    multiplied in float32: within five roundings of exact (three factors,
    two products), and made with no float32 ``exp`` (a TPU's is off by up
    to 5e-6)."""
    shape = (16, 32, 64)
    plan = _slab_p1(shape)
    tau = np.asarray(NPBFT(plan, alpha=alpha).tau)
    fz, fy, fx = decay_factors(plan, alpha)
    assert tau.dtype == np.float32 and tau.shape == shape
    np.testing.assert_array_equal(tau, fz * fy * fx)
    exact = ref.twiddle(shape, alpha)
    assert np.max(np.abs(tau - exact) / exact) < 5 * 2.0**-24


def test_checksum_positions_follow_npb():
    pos = checksum_positions((16, 32, 64))
    assert pos.shape == (3, 1024)
    j = np.arange(1, 1025)
    assert (pos[0] == (5 * j) % 16).all() and (pos[1] == (3 * j) % 32).all()
    assert (pos[2] == j % 64).all()


def test_app_refuses_plans_it_cannot_step():
    mesh = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="3-D"):
        NPBFT(plan_fft((16, 16), mesh, dtype=jnp.complex64))
    with pytest.raises(ValueError, match="forward plan"):
        NPBFT(plan_fft((8, 8, 8), mesh, ndim=3, direction="inverse", dtype=jnp.complex64))


_MULTI = r"""
import sys
import numpy as np
import jax, jax.numpy as jnp
sys.path.insert(0, "tests")
import npb_ft_reference as ref
from repro.apps.npb_ft import NPBFT
from repro.core import plan_fft
from repro.core.compat import make_mesh

shape, alpha = (16, 32, 64), 1e-3
rng = np.random.default_rng(1)
u = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
want_sums, want_u2 = ref.npb_ft(u, 20, alpha)
plans = {
    "slab_p4": plan_fft(shape, make_mesh((4,), ("model",)), ndim=3, decomp="slab",
                        backend="alltoall", dtype=jnp.complex64),
    "pencil_2x2": plan_fft(shape, make_mesh((2, 2), ("rows", "cols")), ndim=3,
                           decomp="pencil", backend="alltoall", dtype=jnp.complex64),
}
got = {}
for name, plan in plans.items():
    app = NPBFT(plan, alpha=alpha)
    assert app.tau.sharding.spec == app.spectrum.spec, name
    sums, u2 = app.run(jax.device_put(u, plan.input_sharding()), 20)
    got[name] = np.asarray(sums)
    err = np.max(np.abs(got[name] - want_sums)) / np.max(np.abs(want_sums))
    field = np.linalg.norm(np.asarray(u2) - want_u2) / np.linalg.norm(want_u2)
    assert err < 1e-5 and field < 1e-5, (name, err, field)
    print(f"PASS {name} {err:.2e} {field:.2e}")
spread = np.max(np.abs(got["slab_p4"] - got["pencil_2x2"])) / np.max(np.abs(want_sums))
assert spread < 1e-5, spread
print("PASS layouts agree")
"""


def test_slab_p4_and_pencil_2x2_agree():
    out = run_subprocess(_MULTI, devices=4)
    assert "PASS slab_p4" in out and "PASS pencil_2x2" in out and "PASS layouts agree" in out
