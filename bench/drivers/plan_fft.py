"""Driver for a planned distributed transform: ``plan_fft(...).execute``.

Set-up plans the transform on the first P devices, makes the input on
the device in one jitted call from the seed, and runs the plan twice
(compile, then warm). In the window the traffic's arrival process sends
forward transforms, each answered at its ``block_until_ready``. The check compares what the timed
path produced with float64 numpy (``reference.fft2_reference``): the
final output whole through projections and on sampled rows and columns,
and the same columns taken at two steps of the window drawn from the seed.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

import reference as ref
import work
from traffic import rng_for

#: rows/columns and projections compared; steps of the window whose
#: lines are kept on the device and compared too
N_LINES = 3
N_PROJECTIONS = 4
N_SAMPLED_STEPS = 2
#: the sampled steps are drawn from the first steps of any window
SAMPLED_STEPS_FROM = 8
#: steps of the window recorded by a --trace 1 run
TRACED_STEPS = 5


def setup(cfg: dict, traffic, seed: int, devices, *, log=print) -> SimpleNamespace:
    import jax
    import jax.numpy as jnp

    from repro.core import plan_fft
    from repro.core.compat import make_mesh

    p = int(cfg["devices"])
    if p != len(devices):
        raise ValueError(f"config wants P={p}, the cell has {len(devices)} chips")
    shape = tuple(cfg["shape"])
    if cfg["dtype"] != "complex64" or len(shape) != 2:
        raise ValueError("this driver runs 2-D complex64 transforms")
    mesh = make_mesh((p,), ("model",))
    plan = plan_fft(
        shape, mesh, ndim=2, direction=cfg["direction"], backend=cfg["backend"],
        local_impl=cfg["local_impl"], transpose_back=cfg["transpose_back"],
        decomp=cfg["decomp"], dtype=jnp.complex64,
    )
    failed = plan.why()["failed"]
    if failed or plan.backend != cfg["backend"]:
        raise RuntimeError(f"plan backend {plan.backend}, failed candidates {failed}")

    key = jax.random.key(int(rng_for(seed, 0).integers(0, 2**31)))

    def make_input(key):
        k_re, k_im = jax.random.split(key)
        re = jax.random.normal(k_re, shape, jnp.float32)
        return jax.lax.complex(re, jax.random.normal(k_im, shape, jnp.float32))

    make_input = jax.jit(make_input, out_shardings=plan.input_sharding())
    x = jax.block_until_ready(make_input(key))
    for _ in range(2):  # compile, then one warm call
        y = jax.block_until_ready(plan.execute(x))
        del y

    rng = rng_for(seed, 4)
    n0, n1 = shape
    rows = np.concatenate([[0], rng.choice(np.arange(1, n0), N_LINES - 1, replace=False)])
    cols = np.concatenate([[n1 // 2], rng.choice(n1, N_LINES - 1, replace=False)])
    def sampled_lines(y):  # columns of Y: rows of y, cheap to gather
        return y[cols, :]

    lines = jax.jit(sampled_lines)
    jax.block_until_ready(lines(jax.block_until_ready(plan.execute(x))))
    steps = sorted(rng.choice(SAMPLED_STEPS_FROM, N_SAMPLED_STEPS, replace=False).tolist())
    log(f"# plan: {shape} complex64 P={p} backend={plan.backend} fused={plan.fused} "
        f"local_impl={plan.local_impl}")
    nbytes, flops = work.hbm_bytes_per_chip(shape, "complex64", p), work.flops_per_chip(shape, p)
    log(f"# work per chip and transform: {nbytes} B of HBM passes, {flops} flop "
        f"(5 N log2 N), {flops / nbytes} flop/B")
    return SimpleNamespace(
        cfg=cfg, plan=plan, step=plan.execute, x=x, lines=lines, rows=rows, cols=cols,
        sampled_steps=steps, us=[ref.complex_normal(rng, n0) for _ in range(N_PROJECTIONS)],
        vs=[ref.complex_normal(rng, n1) for _ in range(N_PROJECTIONS)],
    )


def window(state, traffic, tracer, *, log=print) -> dict:
    """The traffic's arrival process drives the transform: a request is
    one forward transform of the input, answered when it is ready."""
    import jax

    run = SimpleNamespace(n=0, y=None, kept={})

    def submit():
        run.y = None  # the last answer's buffer is free for this one
        tracer.start()
        with jax.profiler.TraceAnnotation("bench.submit"):
            return state.step(state.x)

    def complete(y):
        with jax.profiler.TraceAnnotation("bench.step"):
            run.y = jax.block_until_ready(y)
        if run.n in state.sampled_steps:
            # waited for, so that no answer outlives its step pending on
            # a copy queued behind the next transform
            run.kept[run.n] = jax.block_until_ready(state.lines(run.y))
        run.n += 1
        if run.n == TRACED_STEPS:
            tracer.stop()

    done, window_s = traffic.run(submit, complete)
    log(f"# {done} transforms in {window_s:.6f} s")
    return {
        "attempted": done, "failed": 0, "steps": done, "window_s": window_s,
        "step_span": "bench.step", "last": run.y, "kept": run.kept,
    }


def programs(state) -> list:
    """Compiled HLO text of what the window runs, for the trace reduction."""
    return [state.plan.lower().compile().as_text(),
            state.lines.lower(state.x).compile().as_text()]


def to_host(*arrays) -> list:
    """complex64 device arrays on the host, each fetched as two float32
    planes, all four transfers at once: the runtime's own conversion of
    a complex64 array to the host's interleaved layout is far slower on
    the TPU."""
    import jax.numpy as jnp

    planes = [p for a in arrays for p in (jnp.real(a), jnp.imag(a))]
    for p in planes:
        p.copy_to_host_async()
    out = []
    for re, im in zip(planes[::2], planes[1::2]):
        host = np.empty(re.shape, np.complex64)
        host.real, host.imag = np.asarray(re), np.asarray(im)
        out.append(host)
    return out


def check(state, record, *, log=print) -> dict:
    """Each number compared, with its limit (``limits`` of the config)."""
    t0 = time.perf_counter()
    y = record.pop("last")
    kept = {k: np.asarray(v) for k, v in record.pop("kept").items()}
    y_host, x_host = to_host(y, state.x)
    del y, state.x
    log(f"# output and input on the host in {time.perf_counter() - t0:.3f} s")
    limits = state.cfg["limits"]
    if y_host.shape != x_host.shape[::-1]:
        log(f"# output shape {y_host.shape}, want {x_host.shape[::-1]}")
        return {name: {"value": np.inf, "limit": limits[name]} for name in limits}
    want = ref.fft2_reference(x_host, state.rows, state.cols, state.us, state.vs)
    got_rows, got_cols = ref.transposed_lines(y_host, state.rows, state.cols)
    line_err = _line_err(got_rows, got_cols, want)
    for cols in kept.values():
        line_err = max(line_err, _line_err([], cols, want))
    proj = ref.transposed_projections(y_host, state.us, state.vs)
    log(f"# reference and comparison in {time.perf_counter() - t0:.3f} s")
    proj_err = float(np.max(np.abs(proj - want["proj"])) / want["norm"])
    log(f"# compared: final output and steps {sorted(kept)} of the window")
    return {
        "line_err": {"value": line_err, "limit": limits["line_err"]},
        "proj_err": {"value": proj_err, "limit": limits["proj_err"]},
        "sampled_steps_missing": {
            "value": len(set(state.sampled_steps) - set(kept)), "limit": 0,
        },
    }


def _line_err(rows, cols, want) -> float:
    errs = [ref.rel_l2(g, w) for g, w in zip(rows, want["rows"])]
    errs += [ref.rel_l2(g, w) for g, w in zip(cols, want["cols"])]
    return max(errs)
