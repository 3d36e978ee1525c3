"""Driver for NAS Parallel Benchmarks FT (``repro.apps.npb_ft``) on a
planned 3-D transform: ``plan_fft(...).execute`` and ``.inverse``.

Set-up plans the transform (slab over P devices, or pencil over the
configuration's ``grid``), makes the initial field on the device in one
jitted call from the seed, builds ``tau``, and compiles and warms the
forward, evolve, inverse and checksum. In the window one request is one
**NPB step**, and steps run in runs of ``1 + niter``: step 0 of a run is
the forward transform of the initial field; step ``t = 1..niter`` evolves
the state left by step ``t - 1``, transforms it back and sums its
checksum. Every step holds one 3-D transform, and every run starts from
the same field. A step is answered when its output (and checksum) is
ready; the next step is queued behind it on the device.

The check compares with float64 numpy (``npb_reference``) every checksum
of every step completed in the window, kept on the device until then,
and projections of the last step's output.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

import npb_reference as ref
import reference
import work
from drivers.plan_fft import to_host
from traffic import rng_for

N_PROJECTIONS = 4


def setup(cfg: dict, traffic, seed: int, devices, *, log=print) -> SimpleNamespace:
    import jax
    import jax.numpy as jnp

    from repro.apps.npb_ft import NPBFT
    from repro.core import plan_fft
    from repro.core.compat import make_mesh
    from repro.core.plan import pair_key

    p = int(cfg["devices"])
    if p != len(devices):
        raise ValueError(f"config wants P={p}, the cell has {len(devices)} chips")
    shape = tuple(cfg["shape"])
    if cfg["dtype"] != "complex64" or len(shape) != 3:
        raise ValueError("this driver runs NPB FT on a 3-D complex64 grid")
    if cfg["decomp"] == "pencil":
        mesh = make_mesh(tuple(cfg["grid"]), ("rows", "cols"))
    else:
        mesh = make_mesh((p,), ("model",))
    backend = cfg["backend"]
    plan = plan_fft(
        shape, mesh, ndim=3, direction="forward", backend=backend, decomp=cfg["decomp"],
        local_impl=cfg["local_impl"], dtype=jnp.complex64,
    )
    failed = plan.why()["failed"]
    if failed or plan.backend not in (backend, pair_key(backend, backend)):
        raise RuntimeError(f"plan backend {plan.backend}, failed candidates {failed}")

    key = jax.random.key(int(rng_for(seed, 0).integers(0, 2**31)))

    def make_input(key):
        k_re, k_im = jax.random.split(key)
        re = jax.random.normal(k_re, shape, jnp.float32)
        return jax.lax.complex(re, jax.random.normal(k_im, shape, jnp.float32))

    u0 = jax.block_until_ready(jax.jit(make_input, out_shardings=plan.input_sharding())(key))
    app = NPBFT(plan, alpha=cfg["alpha"])
    for _ in range(2):  # compile, then one warm call of every step
        jax.block_until_ready(app.iterate(plan.execute(u0)))

    rng = rng_for(seed, 4)
    vecs = tuple(reference.complex_normal(rng, (N_PROJECTIONS, n)) for n in shape)
    log(f"# plan: {shape} complex64 {plan.decomp} P={p} backend={plan.backend} "
        f"local_impl={plan.local_impl}; NPB FT niter={cfg['niter']} alpha={cfg['alpha']}")
    nbytes = work.hbm_bytes_per_chip(shape, "complex64", p)
    log(f"# work per chip and step: one 3-D transform, {nbytes} B of HBM passes")
    return SimpleNamespace(cfg=cfg, plan=plan, app=app, u0=u0, niter=int(cfg["niter"]),
                           vecs=vecs)


def window(state, traffic, tracer, *, log=print) -> dict:
    """The traffic's arrival process drives NPB steps: a request is one
    step, answered when its output is ready. A ``--trace 1`` run records
    one whole run of ``1 + niter`` steps from its step 0."""
    import jax

    steps_per_run = state.niter + 1
    run = SimpleNamespace(sent=0, done=0, u_hat=None, last=None, sums={})

    def submit():
        n, t = run.sent, run.sent % steps_per_run
        run.sent += 1
        if t == 0:
            tracer.start()
        with jax.profiler.TraceAnnotation("bench.submit"):
            if t == 0:
                run.u_hat = state.plan.execute(state.u0)
                return n, t, run.u_hat, None
            run.u_hat, u2, c = state.app.iterate(run.u_hat)
            return n, t, u2, c

    def complete(handle):
        n, t, out, c = handle
        with jax.profiler.TraceAnnotation("bench.step"):
            jax.block_until_ready((out, c))
        if t:
            run.sums[n] = (t, c)
            run.last = (t, out)
        run.done += 1
        if run.done == steps_per_run:
            tracer.stop()

    done, window_s = traffic.run(submit, complete)
    log(f"# {done} NPB steps in {window_s:.6f} s")
    return {
        "attempted": done, "failed": 0, "steps": done, "window_s": window_s,
        "step_span": "bench.step", "last": run.last,
        "sums": [run.sums[n] for n in sorted(run.sums) if n < done],
    }


def programs(state) -> list:
    """Compiled HLO text of every module the window runs, for the trace
    reduction: the forward, the inverse, evolve and checksum."""
    plan = state.plan
    texts = [plan.lower().compile().as_text(), plan.lower(inverse=True).compile().as_text()]
    return texts + [low.compile().as_text() for low in state.app.lower().values()]


def check(state, record, *, log=print) -> dict:
    """Each number compared, with its limit (``limits`` of the config)."""
    import jax.numpy as jnp

    t0 = time.perf_counter()
    limits = state.cfg["limits"]
    last, sums = record.pop("last"), record.pop("sums")
    if last is None or not sums:
        log("# no NPB iteration completed in the window")
        return {name: {"value": np.inf, "limit": limits[name]} for name in limits}
    t_last, u2 = last
    ts = np.array([t for t, _ in sums])
    got = np.asarray(jnp.stack([c for _, c in sums]), np.complex128)
    u2_host, u0_host = to_host(u2, state.u0)
    del u2, last, state.u0
    log(f"# {len(ts)} checksums, the last output (t={t_last}) and the initial field on the "
        f"host in {time.perf_counter() - t0:.3f} s")
    if u2_host.shape != u0_host.shape:
        log(f"# output shape {u2_host.shape}, want {u0_host.shape}")
        return {name: {"value": np.inf, "limit": limits[name]} for name in limits}
    want = ref.reference(u0_host, state.niter, state.cfg["alpha"], t_last, state.vecs)
    checksum_err = float(np.max(np.abs(got - want["checksums"][ts - 1]) / want["scale"][ts - 1]))
    proj = ref.projections(u2_host, state.vecs)
    proj_err = float(np.max(np.abs(proj - want["proj"])) / want["norm"])
    log(f"# reference and comparison in {time.perf_counter() - t0:.3f} s")
    return {
        "checksum_err": {"value": checksum_err, "limit": limits["checksum_err"]},
        "proj_err": {"value": proj_err, "limit": limits["proj_err"]},
    }
