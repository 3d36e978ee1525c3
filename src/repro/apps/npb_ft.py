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
:class:`repro.core.Plan`. Evolve and checksum work with the plan's
shardings and in its layouts: ``tau`` in the spectrum layout
(:func:`repro.apps.spectral.wavenumbers`), the checksum's positions
mapped into each shard of the inverse's output. So one :class:`NPBFT`
serves slab and pencil plans alike.

One iteration is one program (``jit_iterate``): evolve, the inverse and
the checksum, traced from :meth:`NPBFT.evolve`, ``plan.inverse`` and
:meth:`NPBFT.checksum`. The state it carries from one iteration to the
next is the spectrum as two float32 planes ``(re, im)``, the form the
device computes in, so that no complex64 argument is split into planes,
nor a complex64 state combined from them, at each program's boundary.
The planes and ``tau`` are carried with their axes in the order in which
the iteration lays them out when left free (read from one compile at
first use), so that in their default layout neither is copied into
another at the boundary either. The forward's complex64 output enters
that form once per run (``jit_to_planes``).
"""

from __future__ import annotations

import copy
import functools
from typing import Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.layout import Format, Layout
from jax.sharding import NamedSharding
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


#: the spectrum as its ``(re, im)`` float32 planes, carried in
#: :attr:`NPBFT.order`
Planes = Tuple[jax.Array, jax.Array]
#: the spectrum as one complex64 array, or as its planes
State = Union[jax.Array, Planes]


def evolve(u_hat: Planes, tau: jax.Array) -> Planes:
    """``u_hat * tau``, under the layer scope ``repro.evolve``. ``tau`` is
    real, so the planes are scaled each on its own, to the same values."""
    with obs.layer(obs.EVOLVE):
        return tuple(x * tau for x in u_hat)


class NPBFT:
    """NPB FT's steps on one forward c2c 3-D ``plan`` of an unbatched grid.

    Two programs run the iterations, each compiled once: ``iterate`` (one
    whole iteration on the state's planes, which it donates) and
    ``to_planes`` (the forward's complex64 output into planes). The
    planes and ``tau`` are carried with their axes in :attr:`order`, in
    :attr:`carried`. The counters :attr:`entries` and :attr:`iterations`
    count the two programs' calls."""

    def __init__(self, plan, alpha: float = ALPHA):
        if plan.ndim != 3 or len(plan.global_shape) != 3 or plan.real:
            raise ValueError("NPB FT runs on an unbatched complex 3-D plan")
        if plan.direction != "forward":
            raise ValueError("NPB FT takes a forward plan: execute, then inverse")
        self.plan = plan
        self.spectrum = plan.input_sharding(opposite=True)
        self.physical = plan.input_sharding()
        self._factors = decay_factors(plan, alpha)
        self.entries = 0  # complex64 states turned into planes
        self.iterations = 0  # calls of the fused iteration
        self._checksum = jax.jit(
            self._checksum_fn(checksum_positions(plan.global_shape)),
            in_shardings=self.physical,
        )

    @functools.cached_property
    def order(self) -> Tuple[int, ...]:
        """The spectrum's axes, major to minor, as the fused iteration
        lays out the state on the device when its layouts are left free
        (read from one compile at first use). The state and ``tau`` are
        carried transposed into this order, so that their default layout
        is the one the iteration computes in and no relayout copy is made
        at its boundary. (A non-default layout asked of ``jax.jit``
        instead would not survive JAX's persistent compilation cache: a
        result of a program read back from it is labelled with the
        default layout.)"""
        free = Format(Layout.AUTO, self.spectrum)
        plane = self._plane((0, 1, 2), self.spectrum)
        fn = self._jit_iterate((0, 1, 2), free)
        (state, _), _ = fn.lower((plane, plane), plane).compile().input_formats
        layout = state[0].layout
        return (0, 1, 2) if layout is None else tuple(layout.major_to_minor)

    @functools.cached_property
    def carried(self) -> NamedSharding:
        """The spectrum's sharding, its axes in :attr:`order`."""
        spec = tuple(self.spectrum.spec) + (None,) * (3 - len(self.spectrum.spec))
        return NamedSharding(self.plan.mesh, P(*(spec[a] for a in self.order)))

    @functools.cached_property
    def _iterate(self):
        return self._jit_iterate(self.order, self.carried)

    @functools.cached_property
    def _evolve(self):
        return jax.jit(evolve, in_shardings=self.carried, out_shardings=self.carried)

    @functools.cached_property
    def _to_planes(self):
        order = self.order

        def to_planes(u_hat):
            # defined per app, so that its jit cache holds this app's alone
            with obs.layer(obs.RELAYOUT):
                return tuple(jnp.transpose(x, order) for x in (jnp.real(u_hat), jnp.imag(u_hat)))

        return jax.jit(to_planes, in_shardings=self.spectrum, out_shardings=self.carried)

    @functools.cached_property
    def tau(self) -> jax.Array:
        """``tau`` in float32, its axes in :attr:`order`, made on the
        device from :func:`decay_factors` at first use (so that the
        programs can be lowered for devices this process cannot run
        on)."""
        factors, order = self._factors, self.order

        def tau():
            with obs.layer(obs.EVOLVE):
                return jnp.transpose(jnp.asarray(factors[0]) * factors[1] * factors[2], order)

        return jax.jit(tau, out_shardings=self.carried)()

    def _plane(self, order, sharding) -> jax.ShapeDtypeStruct:
        """One float32 plane of the spectrum, its axes in ``order``, in
        ``sharding`` (a sharding or a format)."""
        shape = self.plan.input_spec(opposite=True).shape
        return jax.ShapeDtypeStruct(tuple(shape[a] for a in order), jnp.float32,
                                    sharding=sharding)

    def _jit_iterate(self, order, sharding):
        """The fused iteration jitted with the state's planes and ``tau``
        in ``order`` and ``sharding``, the planes donated and returned
        so."""
        return jax.jit(
            self._iterate_fn(order),
            in_shardings=(sharding, sharding),
            out_shardings=(sharding, self.physical, NamedSharding(self.plan.mesh, P())),
            donate_argnums=0,
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

    def _iterate_fn(self, order):
        """One iteration as one function of ``(state planes, tau)``, both
        with their axes in ``order``, for ``jit_iterate``. It traces
        :meth:`evolve`, ``plan.inverse`` and :meth:`checksum` (their jitted
        programs inline) on a copy of the app whose ``tau`` is the argument
        (a ``tau`` closed over would be baked into the program as a
        constant) and whose evolve is the plain function."""
        back = tuple(int(a) for a in np.argsort(order))

        def iterate(state, tau):
            app = copy.copy(self)
            app.tau, app._evolve = tau, evolve
            state = app.evolve(state)
            with obs.layer(obs.RELAYOUT):
                u_hat = jnp.transpose(lax.complex(*state), back)
            u2 = app.plan.inverse(u_hat)
            return state, u2, app.checksum(u2)

        return iterate

    def _planes(self, u_hat: State) -> Planes:
        """The state as planes: a complex64 state (the forward's output)
        enters through ``to_planes``, counted in :attr:`entries`."""
        if isinstance(u_hat, tuple):
            return u_hat
        self.entries += 1
        return self._to_planes(u_hat)

    def evolve(self, u_hat: State) -> Planes:
        """``u_hat * tau``: one step of the heat equation, on the state's
        planes."""
        return self._evolve(self._planes(u_hat), self.tau)

    def checksum(self, u2: jax.Array) -> jax.Array:
        """``C_t``: the sum of the 1024 checksum points of ``u2``."""
        return self._checksum(u2)

    def iterate(self, u_hat: State) -> Tuple[Planes, jax.Array, jax.Array]:
        """One NPB iteration from the state ``u_hat`` after the last:
        (the evolved state as planes, its inverse ``u2``, the checksum of
        ``u2``). Planes given are donated to the iteration."""
        u_hat = self._planes(u_hat)
        self.iterations += 1
        return self._iterate(u_hat, self.tau)

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
        """name -> ``Lowered`` of the app's programs at the plan's specs
        (no allocation): the fused ``iterate`` and the ``entry`` into
        planes."""
        plane = self._plane(self.order, self.carried)
        return {
            "iterate": self._iterate.lower((plane, plane), plane),
            "entry": self._to_planes.lower(self.plan.input_spec(opposite=True)),
        }
