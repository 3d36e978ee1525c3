"""Slab-decomposed distributed FFT entry points over the stage-schedule
IR (the paper's application, §2).

Global data model for ``fft2``: x has shape (..., R, C) with R sharded
over ``axis_name`` (P shards); leading axes are batch. The paper's four
steps per dimension map to:

    1. local FFT along the contiguous axis (C)
    2/3. chunk + communicate: ``distributed_transpose`` (strategy-switchable)
    4. chunk re-transpose -- folded into the strategy (the ``scatter``
       strategy transposes each chunk as it arrives; the fused collectives
       transpose after assembly)

then the second dimension's local FFT. Output is the transposed spectrum
F^T (C sharded) by default -- standard for pencil FFT libraries -- or the
natural layout with ``transpose_back=True`` (one more exchange).

``fused=True`` (beyond-paper, any chunk-streaming strategy) goes further
than the paper's "transpose chunks on arrival": it folds the *next
dimension's DFT itself* into the exchange via decimation across source
ranks (R = P*r, DFT_R = DFT_P across ranks x twiddle x DFT_r within
chunks). Each arriving (sub-)chunk contributes W_P[:, src] (x) chunk to
the accumulator, so the post-communication serial FFT_R disappears into
the flight time -- the pipelined overlap executor
(:func:`repro.core.transpose.transpose_then_fft`), shared by the 3-D
slab chain, both pencil legs and the r2c subsystem. ``fuse_dft`` is the
legacy fft2-only spelling and is honoured as an alias; ``n_chunks``
decouples the streamed chunk count from P (see ``plan_fft(pipeline=)``).

Every transform here is a thin builder over
:mod:`repro.core.schedule`: the entry point lowers its arguments to a
declarative stage schedule and hands it to the one interpreter
(:func:`repro.core.schedule.run_schedule`), which is also what the cost
model and the byte accounting walk.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import repro.core.fftmath as lf
import repro.core.schedule as sch
from repro.core import backends


@dataclasses.dataclass(frozen=True)
class FFTConfig:
    """Transform config carrier. New code should use ``plan_fft`` (see
    :mod:`repro.core.plan`, which resolves ``pipeline=`` into the
    ``fused``/``n_chunks`` fields here); kept as a thin carrier so
    existing call sites keep working. ``strategy`` names any backend
    registered in :mod:`repro.core.backends`.

    ``fused`` folds each exchange's following FFT stage into the arriving
    chunks on streaming backends (the pipelined overlap executor);
    ``n_chunks`` decouples the streamed chunk count from P (sub-chunked
    transport + finer compute grain). ``fuse_dft`` is the legacy
    fft2-only spelling of ``fused`` and is honoured as an alias."""

    strategy: str = "alltoall"
    local_impl: lf.LocalImpl = "jnp"
    fuse_dft: bool = False  # legacy alias: fold 2nd-dim DFT into the ring
    transpose_back: bool = False  # return natural (row-sharded) layout
    fused: bool = False  # streaming backends: fuse the next FFT stage
    n_chunks: Optional[int] = None  # total-chunk target (None = P)


def _wants_fused(cfg: FFTConfig) -> bool:
    return cfg.fused or cfg.fuse_dft


def _check(cfg: FFTConfig) -> backends.CollectiveBackend:
    backend = backends.get(cfg.strategy)  # raises listing the registry
    if _wants_fused(cfg) and not (backend.kind == "shard_map" and backend.supports_chunk_fn):
        raise ValueError(
            f"fuse_dft/fused requires a chunk-streaming backend "
            f"(got {cfg.strategy!r}; streaming: "
            f"{[b for b in backends.available() if backends.get(b).supports_chunk_fn]})"
        )
    return backend


def _build(x: jax.Array, mesh: Mesh, axis_name: str, cfg: FFTConfig, *,
           ndim: int, inverse: bool, rows: Optional[int] = None) -> sch.Schedule:
    return sch.build_schedule(
        x.shape, ndim=ndim, inverse=inverse, decomp="slab",
        axis_name=axis_name, p=mesh.shape[axis_name], backend=cfg.strategy,
        fused=_wants_fused(cfg), n_chunks=cfg.n_chunks,
        transpose_back=cfg.transpose_back, rows=rows,
        transpose_first=sch.transposed_first_pays(x.shape, ndim, cfg.local_impl),
    )


def fft2(
    x: jax.Array,
    mesh: Mesh,
    axis_name: str,
    cfg: FFTConfig = FFTConfig(),
    *,
    inverse: bool = False,
) -> jax.Array:
    """Distributed 2-D FFT of (..., R, C), R sharded over ``axis_name``.

    Returns F^T (= fft2(x).swapaxes(-1,-2)) with C sharded, unless
    ``cfg.transpose_back`` -- mirroring the paper's pencil layout. With
    ``inverse``, computes the unitary-unnormalized ifft2 (1/(R*C) factor),
    same layout conventions.
    """
    _check(cfg)
    plan = _build(x, mesh, axis_name, cfg, ndim=2, inverse=inverse)
    return sch.run_schedule(x, plan, mesh, impl=cfg.local_impl)


def ifft2(x: jax.Array, mesh: Mesh, axis_name: str, cfg: FFTConfig = FFTConfig()) -> jax.Array:
    return fft2(x, mesh, axis_name, cfg, inverse=True)


def fft3(
    x: jax.Array,
    mesh: Mesh,
    axis_name: str,
    cfg: FFTConfig = FFTConfig(),
    *,
    inverse: bool = False,
) -> jax.Array:
    """Slab-decomposed 3-D FFT of (..., D0, D1, D2), D0 sharded.

    Local batched 2-D FFT over (D1, D2), then one strategy-switched
    exchange to localize D0, FFT, and the exchange back (natural layout is
    always restored: 3-D users expect it)."""
    _check(cfg)
    plan = _build(x, mesh, axis_name, cfg, ndim=3, inverse=inverse)
    return sch.run_schedule(x, plan, mesh, impl=cfg.local_impl)


def fft1d_large(
    x: jax.Array,
    mesh: Mesh,
    axis_name: str,
    cfg: FFTConfig = FFTConfig(),
    *,
    rows: Optional[int] = None,
) -> jax.Array:
    """Distributed 1-D FFT of a signal too large for one device.

    x: (..., N) viewed as (R, C) row-major with R = rows (default: R = P *
    ceil-balanced) sharded. Six-step algorithm: transpose, FFT_R, twiddle
    (fused into the second exchange's chunks under ``scatter``), transpose,
    FFT_C, transpose. Returns the standard-ordered spectrum, R-sharded.
    """
    _check(cfg)
    plan = _build(x, mesh, axis_name, cfg, ndim=1, inverse=False, rows=rows)
    return sch.run_schedule(x, plan, mesh, impl=cfg.local_impl)


def reference_fft2(x: jax.Array, *, inverse: bool = False) -> jax.Array:
    """Single-device oracle (numpy semantics) for tests/benchmarks."""
    return jnp.fft.ifft2(x) if inverse else jnp.fft.fft2(x)
