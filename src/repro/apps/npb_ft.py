"""NAS Parallel Benchmarks FT on a distributed plan.

NPB's FT kernel (NPB 3.x ``FT/ft.f``; Bailey et al., "The NAS Parallel
Benchmarks", RNR-94-007) steps the heat equation ``du/dt = alpha lap(u)``
on a periodic ``nz x ny x nx`` grid in the spectrum:

* one forward 3-D transform ``u_hat = fftn(u)``;
* then, for ``t = 1..niter``: **evolve** ``u_hat <- u_hat * tau`` with
  ``tau[k] = exp(-4 alpha pi^2 |k|^2)`` (``k`` the signed mode numbers),
  cumulative as NPB writes it; the normalised **inverse**
  ``u2 = ifftn(u_hat)``; and the **checksum**
  ``C_t = sum_{j=1..1024} u2[5j mod nz, 3j mod ny, j mod nx]``, which is
  NPB's ``chk / ntotal`` (its ``u1(q, r, s)``, ``q = j mod nx`` fastest).

The forward is ``plan.execute`` and the inverse ``plan.inverse`` of one
:class:`repro.core.Plan`. Evolve and checksum are jitted here with the
plan's shardings and in its layouts: ``tau`` in the spectrum layout
(:func:`repro.apps.spectral.wavenumbers`), the checksum's positions
mapped into each shard of the inverse's output. So one :class:`NPBFT`
serves slab and pencil plans alike.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.apps.spectral import wavenumbers
from repro.core.compat import shard_map
from repro.obs import trace as obs

#: NPB's alpha, the same in every class
ALPHA = 1e-6
#: points summed by one checksum
N_CHECKSUM = 1024
#: point ``j`` of the checksum is ``(5j mod nz, 3j mod ny, j mod nx)``
CHECKSUM_STRIDES = (5, 3, 1)


def checksum_positions(shape, strides=CHECKSUM_STRIDES) -> np.ndarray:
    """(3, 1024) global ``(z, y, x)`` indices of the checksum's points,
    ``j = 1..1024``, in a row-major ``u[z, y, x]`` of ``shape``."""
    j = np.arange(1, N_CHECKSUM + 1)
    return np.stack([(s * j) % n for s, n in zip(strides, shape)])


def decay_factors(plan, alpha: float = ALPHA) -> Tuple[np.ndarray, ...]:
    """``tau = exp(-4 alpha pi^2 |k|^2)`` as one float32 factor per axis of
    the plan's spectrum layout (:func:`repro.apps.spectral.wavenumbers`),
    whose product is ``tau``. Each is ``exp`` in float64 on the host,
    rounded once: a TPU's float32 ``exp`` is off by up to 5e-6, which
    twenty cumulative evolves would compound past the transforms' own
    error."""
    decay = -4.0 * float(alpha) * np.pi**2
    return tuple(
        np.exp(decay * np.asarray(k, np.float64) ** 2).astype(np.float32)
        for k in wavenumbers(plan)
    )


def evolve(u_hat: jax.Array, tau: jax.Array) -> jax.Array:
    """``u_hat * tau``, under the layer scope ``repro.evolve``."""
    with obs.layer(obs.EVOLVE):
        return u_hat * tau


class NPBFT:
    """NPB FT's steps on one forward c2c 3-D ``plan`` of an unbatched grid.

    ``tau`` is made once, in float32, on the device from
    :func:`decay_factors`; :meth:`evolve` and :meth:`checksum` are
    compiled once each, with the plan's spectrum and physical shardings."""

    def __init__(self, plan, alpha: float = ALPHA):
        if plan.ndim != 3 or len(plan.global_shape) != 3 or plan.real:
            raise ValueError("NPB FT runs on an unbatched complex 3-D plan")
        if plan.direction != "forward":
            raise ValueError("NPB FT takes a forward plan: execute, then inverse")
        self.plan = plan
        self.spectrum = plan.input_sharding(opposite=True)
        self.physical = plan.input_sharding()
        factors = decay_factors(plan, alpha)

        def tau():
            with obs.layer(obs.EVOLVE):
                return jnp.asarray(factors[0]) * factors[1] * factors[2]

        self.tau = jax.jit(tau, out_shardings=self.spectrum)()
        self._evolve = jax.jit(
            evolve, in_shardings=(self.spectrum, self.spectrum), out_shardings=self.spectrum
        )
        self._checksum = jax.jit(
            self._checksum_fn(checksum_positions(plan.global_shape)),
            in_shardings=self.physical,
        )

    def _checksum_fn(self, positions: np.ndarray):
        """The checksum over the inverse's output, as each shard sees it:
        every shard sums the points that lie in its block, and the sums
        meet in one ``psum``."""
        spec = tuple(self.physical.spec) + (None,) * (3 - len(self.physical.spec))
        mesh = self.plan.mesh
        names = tuple(n for n in spec if n is not None)

        def checksum(u):
            with obs.layer(obs.CHECKSUM):
                inside = jnp.ones(positions.shape[1], bool)
                index = []
                for pos, name, n in zip(positions, spec, u.shape):
                    start = 0 if name is None else lax.axis_index(name) * n
                    i = jnp.asarray(pos) - start
                    inside &= (i >= 0) & (i < n)
                    index.append(jnp.clip(i, 0, n - 1))
                total = jnp.sum(jnp.where(inside, u[tuple(index)], 0))
                return lax.psum(total, names) if names else total

        return shard_map(checksum, mesh=mesh, in_specs=P(*spec), out_specs=P())

    def evolve(self, u_hat: jax.Array) -> jax.Array:
        """``u_hat * tau``: one step of the heat equation."""
        return self._evolve(u_hat, self.tau)

    def checksum(self, u2: jax.Array) -> jax.Array:
        """``C_t``: the sum of the 1024 checksum points of ``u2``."""
        return self._checksum(u2)

    def iterate(self, u_hat: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """One NPB iteration from the state ``u_hat`` after the last:
        (the evolved state, its inverse ``u2``, the checksum of ``u2``)."""
        u_hat = self.evolve(u_hat)
        u2 = self.plan.inverse(u_hat)
        return u_hat, u2, self.checksum(u2)

    def run(self, u: jax.Array, niter: int) -> Tuple[jax.Array, jax.Array]:
        """NPB's timed section from the initial field ``u``: the
        ``niter`` checksums (one device array) and the last ``u2``."""
        u_hat = self.plan.execute(u)
        sums = []
        for _ in range(niter):
            u_hat, u2, c = self.iterate(u_hat)
            sums.append(c)
        return jnp.stack(sums), u2

    def lower(self) -> dict:
        """name -> ``Lowered`` of the app's own jitted steps, at the
        plan's specs."""
        spec_hat = self.plan.input_spec(opposite=True)
        return {
            "evolve": self._evolve.lower(spec_hat, self.tau),
            "checksum": self._checksum.lower(self.plan.input_spec()),
        }
