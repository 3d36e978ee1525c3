"""Quickstart: distributed 2-D FFT through the plan/executor front-end
with pluggable collective backends (the HPX parcelport analogue).

Run (any machine; forces 8 host devices for a visible mesh):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py
"""

import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import backends, plan_fft
from repro.core.compat import make_mesh_1d, use_compile_cache


def main():
    use_compile_cache()
    mesh = make_mesh_1d(len(jax.devices()))
    print(f"mesh: {dict(mesh.shape)}")

    rng = np.random.default_rng(0)
    n = 512
    x = jnp.asarray(
        (rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))).astype(np.complex64)
    )
    ref = np.fft.fft2(np.asarray(x))

    # the paper's comparison, over every registered backend (parcelport axis)
    for name in backends.available():
        if not backends.get(name).supports(mesh.shape["model"]):
            continue
        plan = plan_fft((n, n), mesh, backend=name)
        y = plan.execute(x)
        err = float(jnp.abs(jnp.asarray(y) - jnp.asarray(ref.T)).max())
        print(f"  fft2[{name:12s}] max err vs numpy: {err:.2e}")

    # the pipelined overlap executor: fused (default) vs unfused, and an
    # n_chunks-decoupled stream (the paper's per-chunk-compute overlap)
    plan_unfused = plan_fft((n, n), mesh, backend="scatter", pipeline=False)
    plan_fused = plan_fft((n, n), mesh, backend="scatter")  # pipeline="auto"
    plan_stream = plan_fft((n, n), mesh, backend="scatter", pipeline=32)
    y = plan_fused.execute(x)
    print(f"  fft2[scatter fused] err: {float(jnp.abs(y - ref.T).max()):.2e}  "
          f"(n_chunks={plan_stream.n_chunks} stream err: "
          f"{float(jnp.abs(plan_stream.execute(x) - ref.T).max()):.2e})")
    model_f = plan_fused.predict(fused=True)["scatter"]
    model_u = plan_unfused.predict(fused=False)["scatter"]
    print(f"  model: fused {model_f*1e6:.1f}us vs unfused {model_u*1e6:.1f}us "
          f"(overlap hides the stage compute)")

    # backend="auto": the alpha-beta cost model picks before anything runs
    plan = plan_fft((n, n), mesh, backend="auto")
    ranking = sorted(plan.predict().items(), key=lambda kv: kv[1])
    print(f"  auto -> {plan.backend!r}  (model ranking: "
          + ", ".join(f"{k}={v*1e6:.1f}us" for k, v in ranking) + ")")

    # decision provenance: every plan can explain WHY its backend won --
    # which channel decided (pinned / model-argmin / measured-race /
    # wisdom-hit / observed-overlay), over which timing table, under
    # which calibration constants (run.py --explain dumps the same)
    print("  " + plan.why_text().replace("\n", "\n  "))

    # planner="measure": FFTW_MEASURE -- time every backend on THIS mesh,
    # pick the measured argmin, remember it as wisdom
    measured = plan_fft((n, n), mesh, planner="measure")
    timed = sorted(measured.measured.items(), key=lambda kv: kv[1])
    print(f"  measure -> {measured.backend!r}  (measured: "
          + ", ".join(f"{k}={v*1e6:.0f}us" for k, v in timed) + ")")
    again = plan_fft((n, n), mesh, planner="measure")
    print(f"  second identical plan: wisdom_hit={again.wisdom_hit} (no re-measurement)")
    wisdom_path = "/tmp/fft_wisdom.json"
    from repro.core import export_wisdom
    export_wisdom(wisdom_path)
    print(f"  wisdom exported to {wisdom_path} (import_wisdom() restores it)")

    # calibrate alpha/beta on the real fabric and estimate with those
    from repro.core import CommParams
    prm = CommParams.calibrate(mesh, sizes=(4096, 65536, 1048576), iters=3)
    cal = plan_fft((n, n), mesh, params=prm)
    print(f"  calibrated alpha={prm.alpha_s*1e6:.1f}us beta={prm.beta_bytes_s/1e9:.1f}GB/s"
          f" -> estimate picks {cal.backend!r}")

    # pencil decomposition: a 2-D process grid, one backend PER grid axis
    # (the 2-D analogue of the parcelport switch; see README)
    from repro.core.compat import make_mesh
    from repro.core.grid import auto_grid_shape

    pr, pc = auto_grid_shape(len(jax.devices()))
    if pr > 1:
        gmesh = make_mesh((pr, pc), ("rows", "cols"))
        n3 = 8 * pr * pc  # divisible by both grid dims on every axis
        x3 = jnp.asarray(
            (rng.standard_normal((n3,) * 3) + 1j * rng.standard_normal((n3,) * 3))
            .astype(np.complex64)
        )
        pplan = plan_fft((n3,) * 3, gmesh, ndim=3, decomp="pencil")
        y3 = pplan.execute(x3)
        ref3 = np.fft.fftn(np.asarray(x3)).transpose(2, 1, 0)
        print(f"  pencil fft3 on {pr}x{pc} grid -> row={pplan.backend_row!r} "
              f"col={pplan.backend_col!r}, err {float(jnp.abs(y3 - ref3).max()):.2e}")

    # real input? plan_fft(real=True) ships only the Hermitian-truncated
    # N//2+1 payload -- about half the wire bytes of the c2c plan
    xr = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))
    rplan = plan_fft((n, n), mesh, real=True)
    yr = rplan.execute(xr)                      # distributed rfftn
    h = rplan.hermitian_len
    ref_r = np.fft.rfft2(np.asarray(xr))
    err_r = float(jnp.abs(yr[:h] - ref_r.T).max())  # transposed half spectrum
    back = rplan.inverse(yr)                    # distributed irfftn, real out
    print(f"  rfft2[real=True] err vs numpy.rfft2: {err_r:.2e}; "
          f"roundtrip {float(jnp.abs(back - xr).max()):.2e}")
    print(f"  wire bytes: c2c {plan.comm_bytes()/2**10:.0f} KiB vs "
          f"r2c {rplan.comm_bytes()/2**10:.0f} KiB "
          f"(ratio {rplan.comm_bytes()/plan.comm_bytes():.2f}; "
          f"H={h} padded to {rplan.padded_hermitian_len})")

    # spectral application layer: a Poisson solve through the real plan --
    # decomposition/backend/planner choices all flow through the Plan
    from repro.apps import solve_poisson

    ns = 64
    xs = np.arange(ns) * 2 * np.pi / ns
    X, Y = np.meshgrid(xs, xs, indexing="ij")
    u_true = np.sin(X) * np.cos(2 * Y)
    f = jnp.asarray((-5.0 * u_true).astype(np.float32))  # f = laplacian(u)
    pplan2 = plan_fft((ns, ns), mesh, real=True)
    u = solve_poisson(f, pplan2)
    print(f"  poisson[plan_fft(real=True)] max |u - u_true|: "
          f"{float(jnp.abs(u - u_true).max()):.2e}")

    # spectral serving: many small concurrent requests through one
    # engine -- same-shape requests coalesce into one stacked batched
    # execution, plans come from a warm LRU pool, dispatch is async
    # (futures; nothing blocks until .block()/.result())
    from repro.serve import SpectralEngine

    eng = SpectralEngine(mesh, max_batch=8, max_wait_s=0.002)
    ns2 = 64
    rhs = jnp.asarray((-5.0 * u_true).astype(np.float32))
    reqs = [eng.submit("poisson", rhs, lengths=(2 * np.pi, 2 * np.pi))
            for _ in range(3)]
    reqs += [eng.submit("rfft", jnp.asarray(
        rng.standard_normal((ns2, ns2)).astype(np.float32))) for _ in range(4)]
    eng.drain()  # flush partial batches, wait for the device
    st = eng.stats()
    print(f"  serving: {st['requests']} reqs in {st['batches']} batches "
          f"(mean batch {st['mean_batch']:.1f}); "
          f"p50 {st['latency_s']['p50']*1e3:.1f}ms "
          f"p99 {st['latency_s']['p99']*1e3:.1f}ms; "
          f"pool hits/misses {st['pool']['hits']}/{st['pool']['misses']}")
    perr = float(jnp.abs(reqs[0].result() - reqs[2].result()).max())
    print(f"  coalesced poisson requests agree to {perr:.1e}; warm engines "
          f"(wisdom=PATH) skip plan_fft on the request path entirely")

    # chaos demo: poison one coalesced request with a deterministic
    # FaultPlan -- the batch splits, siblings answer correctly, the
    # poisoned future quarantines and re-raises; nothing else notices
    from repro.runtime import FaultPlan, RetryPolicy

    chaos = SpectralEngine(mesh, max_batch=4, max_wait_s=100.0,
                           retry=RetryPolicy(max_retries=0))
    xc = [jnp.asarray((rng.standard_normal((ns2, ns2))
                       + 1j * rng.standard_normal((ns2, ns2))).astype(np.complex64))
          for _ in range(4)]
    chaos.set_faults(FaultPlan.error(match="Exchange", times=2))
    cfuts = [chaos.submit("fft", xi) for xi in xc]
    chaos.drain()  # quarantined failures are isolated to their futures
    survivors = [f for f in cfuts if not f.failed()]
    cm = chaos.metrics()
    print(f"  chaos: {len(survivors)}/4 coalesced requests survived an "
          f"injected Exchange fault (errors={cm['errors']} "
          f"batch_splits={cm['batch_splits']} quarantined={cm['quarantined']}); "
          f"breakers degrade repeat offenders to xla_auto "
          f"(degraded_dispatches={cm['degraded_dispatches']})")

    # one plan, cached executable, forward + inverse roundtrip
    z = plan.inverse(plan.execute(x))
    print(f"  ifft2(fft2(x)) roundtrip err: {float(jnp.abs(z - x).max()):.2e}")
    print(f"  per-device exchange traffic per transform: {plan.comm_bytes()/2**20:.1f} MiB "
          f"(dtype-aware: c128 would be {plan.comm_bytes(jnp.complex128)/2**20:.1f} MiB)")
    print(f"  executables compiled: {plan.compiles} (repeat executes hit the cache)")


if __name__ == "__main__":
    main()
