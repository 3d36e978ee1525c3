#!/usr/bin/env python3
"""Smoke run of the distributed FFT and the spectral server on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip path only

One chip:
  * fft2   -- the paper's problem, a 16384^2 complex64 2-D FFT (2 GiB),
              through ``plan_fft`` forward and inverse, checked against
              float64 numpy rows and columns of the spectrum and by the
              round trip;
  * serve  -- a ``SpectralEngine`` answering coalesced 1024^2 fft, rfft
              and poisson requests, each checked against numpy;
  * pallas -- a 4096^2 2-D FFT with the Pallas local FFT, compiled for
              the chip, against the same plan with the jnp local FFT.

Four chips (one process over ``jax.devices()[:4]``):
  * the 16384^2 slab fft2 at P=4 through every exchange backend and
    through ``scatter`` with the Pallas per-chunk kernel, each against
    the ``xla_auto`` GSPMD reference, which is itself checked against
    float64 rows and columns;
  * a 2x2 pencil fft3 at 512^3 complex64 (1 GiB), forward and inverse.

Every plan is pinned (no measured-planner race, no calibration sweep)
and must report no failed candidates; the server must report no errors,
degraded dispatches or quarantines; every four-chip output must sit on
four distinct devices. Each phase prints one line. Its ``exec_ms`` is a
smoke timing of one warm call and ``compile_s`` the first call less that
warm call -- neither is a benchmark result. The last line is the JSON
result. Without a TPU, or when any check fails, the script exits
non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))
# pinned plans only: no ppermute calibration sweep may run (or fail) here
os.environ["REPRO_AUTO_CALIBRATE"] = "0"

#: unit roundoff of float32
U32 = 2.0**-24


def fft_bound(n_points: int, transforms: int = 1) -> float:
    """Bound on the relative L2 error of float32 FFTs of ``n_points``.

    A length-N FFT is log2 N passes of butterflies (or, in the four-step
    form, DFT matmuls and a twiddle that round like them); each pass adds
    at most a few units of roundoff relative to the data's norm, so the
    error grows like log2(N) * u with u = 2^-24 -- about 1.7e-6 for the
    28 passes of a 16384^2 transform. The bound allows 64x that: room for
    the DFT tables' and twiddles' own rounding, and still an order of
    magnitude below what a single bfloat16 pass (u = 2^-8) would cost.
    ``transforms`` counts FFTs applied in sequence (a round trip is 2).
    """
    return 64.0 * transforms * math.log2(n_points) * U32


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.complex128)
    want = np.asarray(want, np.complex128)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def random_c64(rng: np.random.Generator, shape) -> np.ndarray:
    x = np.empty(shape, np.complex64)
    x.real = rng.standard_normal(shape, dtype=np.float32)
    x.imag = rng.standard_normal(shape, dtype=np.float32)
    return x


def _dft_rows(ks, n: int) -> np.ndarray:
    """Rows ``ks`` of the float64 DFT matrix of size ``n``."""
    return np.exp(-2j * np.pi * np.outer(ks, np.arange(n)) / n)


def fft2_lines(x: np.ndarray, rows, cols, block: int = 512):
    """Rows ``Y[r, :]`` and columns ``Y[:, c]`` of ``Y = fft2(x)`` in
    float64, from one blocked pass over ``x`` (each line is one N x N
    matrix-vector product and a 1-D FFT, not the whole spectrum)."""
    n0, n1 = x.shape
    wr, wc = _dft_rows(rows, n0), _dft_rows(cols, n1)
    v = np.zeros((len(rows), n1), np.complex128)
    u = np.empty((n0, len(cols)), np.complex128)
    for s in range(0, n0, block):
        xb = x[s : s + block].astype(np.complex128)
        v += wr[:, s : s + block] @ xb
        u[s : s + block] = xb @ wc.T
    return np.fft.fft(v, axis=1), np.fft.fft(u, axis=0).T


def fft3_lines(x: np.ndarray, pairs, block: int = 64):
    """Lines ``Y[k0, k1, :]`` of ``Y = fftn(x)`` in float64 for each
    ``(k0, k1)`` in ``pairs``."""
    n0, n1, _ = x.shape
    out = []
    for k0, k1 in pairs:
        w0 = _dft_rows([k0], n0)[0]
        t = np.zeros(x.shape[1:], np.complex128)
        for s in range(0, n0, block):
            t += np.tensordot(w0[s : s + block], x[s : s + block].astype(np.complex128), 1)
        out.append(np.fft.fft(_dft_rows([k1], n1)[0] @ t))
    return np.stack(out)


def timed(fn, *args):
    """(result, first-call seconds, warm-call seconds), both blocked."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    del out
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, first, time.perf_counter() - t0


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]


def check_plan(plan, what: str) -> None:
    failed = plan.why()["failed"]
    check(not failed, f"{what}: plan reports failed candidates {failed}")


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def spectrum_error_2d(y, x_host: np.ndarray, rng: np.random.Generator, n_lines: int = 3) -> float:
    """Worst relative L2 error over a few rows and columns of a slab
    fft2 output (``y[k1, k0] = Y[k0, k1]``) against float64 numpy."""
    n0, n1 = x_host.shape
    rows = np.concatenate([[0], rng.choice(np.arange(1, n0), n_lines - 1, replace=False)])
    cols = np.concatenate([[n1 // 2], rng.choice(n1, n_lines - 1, replace=False)])
    ref_rows, ref_cols = fft2_lines(x_host, rows, cols)
    got_rows = np.asarray(y[:, rows]).T
    got_cols = np.asarray(y[cols, :])
    return max(
        max(rel_l2(g, w) for g, w in zip(got_rows, ref_rows)),
        max(rel_l2(g, w) for g, w in zip(got_cols, ref_cols)),
    )


def device_rel_l2(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_fft2(mesh, n: int, seed: int, backend: str = "alltoall") -> None:
    import jax

    from repro.core import plan_fft

    rng = np.random.default_rng(seed)
    plan = plan_fft((n, n), mesh, ndim=2, backend=backend)
    check_plan(plan, "fft2")
    x_host = random_c64(rng, (n, n))
    x = jax.device_put(x_host, plan.input_spec().sharding)
    y, first, warm = timed(plan.execute, x)
    check(y.shape == (n, n) and y.dtype == np.complex64, f"fft2: output {y.shape} {y.dtype}")
    fwd = spectrum_error_2d(y, x_host, rng)
    z = plan.inverse(y)
    rt = device_rel_l2(z, x)
    del z
    fwd_bound, rt_bound = fft_bound(n * n), fft_bound(n * n, transforms=2)
    report(
        "fft2", shape=(n, n), dtype="complex64", P=1, backend=backend, local_impl="jnp",
        compile_s=first - warm, exec_ms=warm * 1e3, timing="smoke",
        fwd_err=f"{fwd}<={fwd_bound}", roundtrip_err=f"{rt}<={rt_bound}",
        peak_bytes=peak_bytes(mesh.devices.flat),
    )
    check(np.isfinite(fwd) and fwd <= fwd_bound, f"fft2 forward error {fwd} > {fwd_bound}")
    check(np.isfinite(rt) and rt <= rt_bound, f"fft2 round-trip error {rt} > {rt_bound}")


def _serve_requests(rng, n: int):
    """The request mix: c2c fft, real rfft, and zero-mean poisson."""
    reqs = [("fft", random_c64(rng, (n, n))) for _ in range(24)]
    reqs += [("rfft", rng.standard_normal((n, n), dtype=np.float32)) for _ in range(16)]
    for _ in range(8):
        f = rng.standard_normal((n, n), dtype=np.float32)
        reqs.append(("poisson", f - f.mean()))
    return reqs


def _serve_reference(op: str, x: np.ndarray) -> np.ndarray:
    """float64 numpy answer in the engine's output layout (slab spectra
    come transposed; the poisson solution in natural layout)."""
    if op == "fft":
        return np.fft.fft2(x.astype(np.complex128)).T
    if op == "rfft":
        return np.fft.rfft2(x.astype(np.float64)).T
    n0, n1 = x.shape  # poisson on the unit box: laplacian(u) = f
    k0 = 2 * np.pi * np.fft.fftfreq(n0) * n0
    k1 = 2 * np.pi * np.fft.rfftfreq(n1) * n1
    k2 = k0[:, None] ** 2 + k1[None, :] ** 2
    scale = np.where(k2 > 0, -1.0 / np.where(k2 > 0, k2, 1.0), 0.0)
    return np.fft.irfft2(np.fft.rfft2(x.astype(np.float64)) * scale, s=x.shape)


def phase_serve(mesh, n: int, seed: int) -> None:
    from repro.serve import SpectralEngine

    rng = np.random.default_rng(seed + 1)
    engine = SpectralEngine(
        mesh, max_batch=8, max_wait_s=60.0, plan_kwargs={"backend": "alltoall"}
    )
    walls, worst = [], {}
    for _ in range(2):  # the first round compiles, the second is warm
        reqs = _serve_requests(rng, n)
        t0 = time.perf_counter()
        futs = [
            engine.submit(op, x, lengths=(1.0, 1.0) if op == "poisson" else None)
            for op, x in reqs
        ]
        engine.drain(raise_errors=True)
        walls.append(time.perf_counter() - t0)
        for (op, x), fut in zip(reqs, futs):
            err = rel_l2(fut.result(), _serve_reference(op, x))
            worst[op] = max(worst.get(op, 0.0), err)
    bounds = {
        "fft": fft_bound(n * n), "rfft": fft_bound(n * n), "poisson": fft_bound(n * n, 2)
    }
    m = engine.metrics()
    report(
        "serve", shape=(n, n), requests=m["requests"], batches=m["batches"],
        backend="alltoall", first_round_s=walls[0], warm_round_s=walls[1], timing="smoke",
        latency_p50_s=m["latency_p50_s"], latency_p99_s=m["latency_p99_s"],
        errors=m["errors"], degraded_dispatches=m["degraded_dispatches"],
        quarantined=m["quarantined"],
        **{f"{op}_err": f"{worst[op]}<={bounds[op]}" for op in bounds},
        peak_bytes=peak_bytes(mesh.devices.flat),
    )
    for key in ("errors", "degraded_dispatches", "quarantined", "failed_requests"):
        check(m[key] == 0, f"serve: {key} = {m[key]}")
    check(m["completed"] == m["requests"], f"serve: {m['completed']}/{m['requests']} completed")
    channels = {k: v for k, v in m.items() if k.startswith("plan_channel_")}
    check(channels == {"plan_channel_pinned": len(engine.pool)}, f"serve: plans {channels}")
    for op, err in worst.items():
        check(np.isfinite(err) and err <= bounds[op], f"serve {op} error {err} > {bounds[op]}")


def compiled_kernels(plan) -> int:
    """Mosaic kernels in the plan's compiled forward program."""
    return plan.lower().compile().as_text().count("tpu_custom_call")


def phase_pallas(mesh, n: int, seed: int) -> None:
    import jax

    from repro.core import plan_fft

    rng = np.random.default_rng(seed + 2)
    kw = dict(ndim=2, backend="alltoall")
    plan = plan_fft((n, n), mesh, local_impl="pallas", **kw)
    ref = plan_fft((n, n), mesh, local_impl="jnp", **kw)
    check_plan(plan, "pallas")
    x = jax.device_put(random_c64(rng, (n, n)), plan.input_spec().sharding)
    y, first, warm = timed(plan.execute, x)
    kernels = compiled_kernels(plan)
    err = device_rel_l2(y, ref.execute(x))
    bound = fft_bound(n * n, transforms=2)  # two f32 transforms compared
    report(
        "pallas", shape=(n, n), dtype="complex64", P=1, backend="alltoall",
        local_impl="pallas", tpu_custom_calls=kernels, compile_s=first - warm,
        exec_ms=warm * 1e3, timing="smoke", err_vs_jnp=f"{err}<={bound}",
        peak_bytes=peak_bytes(mesh.devices.flat),
    )
    # two local FFT passes, each a stage_left + stage_right kernel; the
    # interpreter would lower to plain HLO with no Mosaic call at all
    check(kernels >= 4, f"pallas: {kernels} compiled kernels in the program, want >= 4")
    check(np.isfinite(err) and err <= bound, f"pallas error vs jnp {err} > {bound}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def on_distinct_devices(y, p: int, what: str) -> None:
    devs = {s.device for s in y.addressable_shards}
    check(len(devs) == p, f"{what}: output shards on {len(devs)} devices, want {p}")


def phase_fft2_multi(mesh, n: int, seed: int) -> None:
    import jax

    from repro.core import backends, plan_fft

    p = mesh.size
    rng = np.random.default_rng(seed)
    x_host = random_c64(rng, (n, n))
    ref_plan = plan_fft((n, n), mesh, ndim=2, backend="xla_auto")
    check_plan(ref_plan, "xla_auto")
    x = jax.device_put(x_host, ref_plan.input_spec().sharding)
    y_ref, first, warm = timed(ref_plan.execute, x)
    on_distinct_devices(y_ref, p, "xla_auto")
    fwd_bound = fft_bound(n * n)
    lines = spectrum_error_2d(y_ref, x_host, rng)
    report(
        "fft2", shape=(n, n), P=p, backend="xla_auto", local_impl="jnp",
        compile_s=first - warm, exec_ms=warm * 1e3, timing="smoke",
        fwd_err=f"{lines}<={fwd_bound}",
    )
    check(np.isfinite(lines) and lines <= fwd_bound, f"xla_auto forward error {lines}")
    cases = [(b, "jnp") for b in backends.available(kind="shard_map") if backends.get(b).supports(p)]
    cases.append(("scatter", "pallas"))
    for backend, impl in cases:
        plan = plan_fft((n, n), mesh, ndim=2, backend=backend, local_impl=impl)
        check_plan(plan, backend)
        spec = plan.input_spec().sharding
        xb = x if spec == x.sharding else jax.device_put(x_host, spec)
        y, first, warm = timed(plan.execute, xb)
        del xb
        on_distinct_devices(y, p, backend)
        err = device_rel_l2(y, y_ref)
        bound = fft_bound(n * n, transforms=2)  # two f32 transforms compared
        extra = {}
        if impl == "pallas":
            extra["tpu_custom_calls"] = compiled_kernels(plan)
            check(plan.fused, "scatter/pallas: the per-chunk kernel path did not fuse")
            check(extra["tpu_custom_calls"] > 0, "scatter/pallas: no compiled kernel")
        report(
            "fft2", shape=(n, n), P=p, backend=backend, local_impl=impl, fused=plan.fused,
            compile_s=first - warm, exec_ms=warm * 1e3, timing="smoke",
            err_vs_xla_auto=f"{err}<={bound}", **extra,
        )
        check(np.isfinite(err) and err <= bound, f"{backend}/{impl}: error vs xla_auto {err}")
        del y
    report("fft2", P=p, peak_bytes=peak_bytes(mesh.devices.flat))


def phase_fft3_pencil(n: int, seed: int) -> None:
    import jax

    from repro.core import plan_fft
    from repro.core.compat import make_mesh

    mesh = make_mesh((2, 2), ("rows", "cols"))
    rng = np.random.default_rng(seed + 3)
    plan = plan_fft(
        (n, n, n), mesh, ndim=3, decomp="pencil", backend="alltoall+alltoall"
    )
    check_plan(plan, "fft3 pencil")
    x_host = random_c64(rng, (n, n, n))
    x = jax.device_put(x_host, plan.input_spec().sharding)
    y, first, warm = timed(plan.execute, x)
    on_distinct_devices(y, 4, "fft3 pencil")
    # y[k2, k1, k0] = Y[k0, k1, k2]: lines Y[k0, k1, :] are y[:, k1, k0]
    pairs = [(0, 0)] + [tuple(rng.choice(n, 2)) for _ in range(2)]
    ref = fft3_lines(x_host, pairs)
    got = np.stack([np.asarray(y[:, k1, k0]) for k0, k1 in pairs])
    fwd = max(rel_l2(g, w) for g, w in zip(got, ref))
    z = plan.inverse(y)
    on_distinct_devices(z, 4, "fft3 pencil inverse")
    rt = device_rel_l2(z, x)
    fwd_bound, rt_bound = fft_bound(n**3), fft_bound(n**3, transforms=2)
    report(
        "fft3", shape=(n, n, n), grid="2x2", decomp="pencil", backend=plan.backend,
        compile_s=first - warm, exec_ms=warm * 1e3, timing="smoke",
        fwd_err=f"{fwd}<={fwd_bound}", roundtrip_err=f"{rt}<={rt_bound}",
        peak_bytes=peak_bytes(mesh.devices.flat),
    )
    check(np.isfinite(fwd) and fwd <= fwd_bound, f"fft3 forward error {fwd}")
    check(np.isfinite(rt) and rt <= rt_bound, f"fft3 round-trip error {rt}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.core.compat import make_mesh_1d, use_compile_cache

    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {len(devices)} devices, --chips {args.chips}", file=sys.stderr)
        return 2
    print(f"# {devices[0].device_kind} x{len(devices)}, compile cache {cache}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 1:
        mesh = make_mesh_1d(1)
        phase_fft2(mesh, 16384, args.seed)
        phase_serve(mesh, 1024, args.seed)
        phase_pallas(mesh, 4096, args.seed)
    else:
        phase_fft2_multi(make_mesh_1d(4), 16384, args.seed)
        phase_fft3_pencil(512, args.seed)
    used = peak_bytes(devices[: args.chips])
    check(all(b > 0 for b in used), f"peak_bytes_in_use per chip {used}: a chip did no work")
    print(f"# total_s={time.perf_counter() - t0}", flush=True)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
