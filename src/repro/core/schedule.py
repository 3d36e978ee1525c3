"""Stage-schedule IR: one declarative pipeline compiler behind every
distributed transform, cost model, and planner candidate.

The paper realizes every distributed FFT as the same composable pattern
-- local FFT passes stitched together by collective exchanges, expressed
as HPX futures over scatter/all-to-all -- and its task-graph predecessor
makes that dataflow *explicit* rather than hand-coding each transform.
This module is that idea for our stack: every pipeline (slab
``fft2``/``fft3``/``fft1d_large``, pencil ``fft2``/``fft3`` and the
eight r2c/c2r chains) lowers to a declarative tuple of **Stage**
records, and a single interpreter (:func:`execute_schedule`) compiles
any schedule into the shard_map body, reusing the existing
:func:`repro.core.transpose.transpose_then_fft` /
``distributed_transpose`` machinery.

Stage vocabulary (the paper's futures/collectives, as data):

``LocalFFT(axis, inverse)``
    One local c2c FFT pass -- the compute future between exchanges.
``LocalR2C()`` / ``LocalC2R(n_last)``
    The real-to-complex truncation pass and its inverse (the only
    passes whose input/output is real).
``Exchange(axis, role, backend, p, elems, payload, fft, ...)``
    One collective transpose over a mesh axis, dispatched through the
    backend registry -- the parcelport switch. ``fft=True`` folds the
    *following* FFT pass into the arriving chunks when the backend
    streams (the pipelined overlap executor); ``elems``/``payload``
    record the per-device wire payload so the cost model and the HLO
    byte accounting walk the very object that executes.
``Twiddle(n, r, c)``
    The six-step 1-D twiddle; fused into the next Exchange's per-chunk
    compute on streaming backends, applied up-front otherwise.
``HermitianPack(h, hp)`` / ``Trim(h)``
    Zero-pad the half spectrum to the shard-divisible length / trim the
    pad where the axis lands local again.
``Relayout(op, dims)``
    Free local data movement (swaps/reshapes) between stages.

Because the builders are *pure* (shapes + names + ring sizes in,
Schedule out -- no mesh, no devices), schedules hash stably
(:meth:`Schedule.schedule_hash`), snapshot into golden tests, and
rewrite mechanically: planner candidate variants (``name@u``,
``name@f2P``, pencil pairs) are :func:`with_backends` /
:func:`with_pipeline` rewrites of the same schedule the plan executes,
so ``predict()`` can never drift from execution.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.core.fftmath as lf
import repro.core.transpose as tr
from repro.core.compat import shard_map


# ---------------------------------------------------------------------------
# Hermitian-length helpers (shared by the validator and the builders;
# re-exported by repro.core.real for its public API)
# ---------------------------------------------------------------------------


def rfft_len(n: int) -> int:
    """Length of the Hermitian-non-redundant rfft output for a real
    length-``n`` axis (numpy's ``n//2 + 1``)."""
    return int(n) // 2 + 1


def padded_rfft_len(n: int, multiple: int, weight: int = 1) -> int:
    """Smallest ``hp >= rfft_len(n)`` with ``(weight * hp) % multiple == 0``.

    ``weight`` covers the slab fft3 case where the *flattened* axis
    ``D1 * Hp`` (not ``Hp`` itself) must divide the shard count."""
    hp = rfft_len(n)
    while (weight * hp) % multiple:
        hp += 1
    return hp


def _pad_disabled_hint(n: int, multiple: int, weight: int = 1) -> str:
    return (
        f"pass pad=True (pads the half spectrum to "
        f"{padded_rfft_len(n, multiple, weight)}, plan-recorded trim)"
    )


# ---------------------------------------------------------------------------
# The one shard-divisibility validator (slab/pencil x c2c/r2c)
# ---------------------------------------------------------------------------


def check_divisible(
    global_shape,
    ndim: int,
    *,
    p: Optional[int] = None,
    axis_name=None,
    p_rows: Optional[int] = None,
    p_cols: Optional[int] = None,
    row_axis=None,
    col_axis=None,
    real: bool = False,
    pad: bool = True,
):
    """Validate that ``global_shape`` can be sharded for this transform;
    the single schedule-level copy of what used to live in three places
    (``pencil.check_divisible``, ``real.check_divisible_slab``,
    ``real.check_divisible_pencil`` -- all now delegating wrappers) plus
    the slab c2c checks inlined in ``Plan``. Raises a ``ValueError``
    naming the offending data axis and mesh/grid dimension -- the
    plan-time guard, so the failure never surfaces as an opaque chunking
    error deep inside :mod:`repro.core.transpose`.

    Returns ``(h, hp)`` for real problems (the Hermitian and
    shard-padded Hermitian lengths), ``None`` for c2c."""
    shape = tuple(global_shape)
    pencil = p_rows is not None

    if not real:
        if pencil:
            pr, pc = p_rows, p_cols

            def need(axis_from_end: int, divisor: int, why: str) -> None:
                size = shape[len(shape) - axis_from_end]
                if size % divisor:
                    raise ValueError(
                        f"pencil fft{ndim}: data axis -{axis_from_end} (global size "
                        f"{size}) is not divisible by {why} -- shape "
                        f"{shape} on grid {pr}x{pc} "
                        f"(row_axis={row_axis!r}, col_axis={col_axis!r})"
                    )

            if ndim == 3:
                need(3, pr, f"P_row={pr} ({row_axis!r})")
                need(2, pc, f"P_col={pc} ({col_axis!r})")
                need(2, pr, f"P_row={pr} ({row_axis!r}; the rows exchange re-shards it)")
                need(1, pc, f"P_col={pc} ({col_axis!r}; the cols exchange re-shards it)")
            elif ndim == 2:
                need(2, pr * pc, f"P_row*P_col={pr * pc} (both sub-rings re-shard it)")
                need(1, pr * pc, f"P_row*P_col={pr * pc} (both sub-rings re-shard it)")
            else:
                raise ValueError(f"pencil decomposition supports ndim 2 or 3, got {ndim}")
            return None
        ax = axis_name
        if ndim == 2:
            r, c = shape[-2:]
            for off, size in ((2, r), (1, c)):
                if size % p:
                    raise ValueError(
                        f"slab fft2: data axis -{off} (global size {size}) is not "
                        f"divisible by mesh axis {ax!r} (P={p}) -- shape {shape}"
                    )
        elif ndim == 3:
            d0, d1, d2 = shape[-3:]
            if d0 % p:
                raise ValueError(
                    f"slab fft3: data axis -3 (global size {d0}) is not divisible "
                    f"by mesh axis {ax!r} (P={p}) -- shape {shape}"
                )
            if (d1 * d2) % p:
                raise ValueError(
                    f"slab fft3: flattened axes (-2,-1) (size {d1}*{d2}={d1 * d2}) "
                    f"not divisible by mesh axis {ax!r} (P={p}) -- shape {shape}"
                )
        else:
            n = shape[-1]
            if n % (p * p):
                raise ValueError(
                    f"fft1d_large: data axis -1 (size {n}) must be divisible by "
                    f"P^2={p * p} of mesh axis {ax!r} -- shape {shape}"
                )
        return None

    if not pencil:
        if ndim == 2:
            r, c = shape[-2:]
            if r % p:
                raise ValueError(
                    f"real slab rfft2: data axis -2 (global size {r}) is not "
                    f"divisible by mesh axis {axis_name!r} (P={p}) -- shape {shape}"
                )
            h = rfft_len(c)
            if not pad and h % p:
                raise ValueError(
                    f"real slab rfft2: Hermitian axis -1 (N={c} -> N//2+1={h}) is "
                    f"not divisible by mesh axis {axis_name!r} (P={p}) and "
                    f"pad=False -- shape {shape}; {_pad_disabled_hint(c, p)}"
                )
            return h, (padded_rfft_len(c, p) if pad else h)
        if ndim == 3:
            d0, d1, d2 = shape[-3:]
            if d0 % p:
                raise ValueError(
                    f"real slab rfft3: data axis -3 (global size {d0}) is not "
                    f"divisible by mesh axis {axis_name!r} (P={p}) -- shape {shape}"
                )
            h = rfft_len(d2)
            if not pad and (d1 * h) % p:
                raise ValueError(
                    f"real slab rfft3: flattened axes (-2,-1) (size {d1}*{h}={d1 * h} "
                    f"after the Hermitian truncation of N={d2}) not divisible by "
                    f"mesh axis {axis_name!r} (P={p}) and pad=False -- shape "
                    f"{shape}; {_pad_disabled_hint(d2, p, d1)}"
                )
            return h, (padded_rfft_len(d2, p, weight=d1) if pad else h)
        raise NotImplementedError(
            f"real transforms support ndim 2 or 3, got ndim={ndim} "
            f"(1-D real: run the c2c fft1d_large on a complexified signal)"
        )

    pr, pc = p_rows, p_cols
    where = (
        f"shape {shape} on grid {pr}x{pc} "
        f"(row_axis={row_axis!r}, col_axis={col_axis!r})"
    )
    if ndim == 3:
        d0, d1, d2 = shape[-3:]
        if d0 % pr:
            raise ValueError(
                f"real pencil rfft3: data axis -3 (global size {d0}) is not "
                f"divisible by P_row={pr} ({row_axis!r}) -- {where}"
            )
        for divisor, why in ((pc, f"P_col={pc} ({col_axis!r})"),
                             (pr, f"P_row={pr} ({row_axis!r}; the rows "
                                  f"exchange re-shards it)")):
            if d1 % divisor:
                raise ValueError(
                    f"real pencil rfft3: data axis -2 (global size {d1}) is "
                    f"not divisible by {why} -- {where}"
                )
        h = rfft_len(d2)
        if not pad and h % pc:
            raise ValueError(
                f"real pencil rfft3: Hermitian axis -1 (N={d2} -> N//2+1={h}) "
                f"is not divisible by P_col={pc} ({col_axis!r}) and "
                f"pad=False -- {where}; {_pad_disabled_hint(d2, pc)}"
            )
        return h, (padded_rfft_len(d2, pc) if pad else h)
    if ndim == 2:
        r, c = shape[-2:]
        if r % (pr * pc):
            raise ValueError(
                f"real pencil rfft2: data axis -2 (global size {r}) is not "
                f"divisible by P_row*P_col={pr * pc} (both sub-rings re-shard "
                f"it) -- {where}"
            )
        if c % pc:
            raise ValueError(
                f"real pencil rfft2: data axis -1 (global size {c}) is not "
                f"divisible by P_col={pc} ({col_axis!r}) -- {where}"
            )
        h = rfft_len(c)
        if not pad and h % (pr * pc):
            raise ValueError(
                f"real pencil rfft2: Hermitian axis -1 (N={c} -> N//2+1={h}) "
                f"is not divisible by P_row*P_col={pr * pc} (both sub-rings "
                f"re-shard it) and pad=False -- {where}; "
                f"{_pad_disabled_hint(c, pr * pc)}"
            )
        return h, (padded_rfft_len(c, pr * pc) if pad else h)
    raise NotImplementedError(f"real pencil transforms support ndim 2 or 3, got {ndim}")


# ---------------------------------------------------------------------------
# Stage records
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LocalFFT:
    """One local c2c FFT pass along ``axis`` (1/n factor when inverse)."""

    axis: int = -1
    inverse: bool = False


@dataclasses.dataclass(frozen=True)
class LocalR2C:
    """Local real-to-complex pass along the last axis (keeps H = N//2+1)."""


@dataclasses.dataclass(frozen=True)
class LocalC2R:
    """Local complex-to-real pass: half spectrum (length ``n_last//2+1``)
    to a real length-``n_last`` signal, carrying the 1/n factor."""

    n_last: int


@dataclasses.dataclass(frozen=True)
class HermitianPack:
    """Zero-pad the Hermitian axis from ``h`` to the shard-divisible
    ``hp`` (the pad is exactly zero, so downstream FFTs stay exact)."""

    h: int
    hp: int


@dataclasses.dataclass(frozen=True)
class Trim:
    """Keep the first ``h`` entries of the last axis (drop the shard pad
    where the Hermitian axis lands local again)."""

    h: int


@dataclasses.dataclass(frozen=True)
class Relayout:
    """Free local data movement: ``swap_last2`` / ``swap_outer``
    (axes -3,-2) / ``flatten2`` (merge the last two axes) /
    ``unflatten2`` (split the last axis into ``dims``)."""

    op: str
    dims: Tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class Twiddle:
    """Six-step twiddle w_n^(j2*k1) of the 1-D large transform (N = r*c
    viewed row-major). Always immediately precedes an Exchange: on a
    chunk-streaming backend the executor folds it into that exchange's
    per-chunk compute (the paper's 'hide computation behind
    communication'); otherwise it is applied up-front to the block."""

    n: int
    r: int
    c: int


@dataclasses.dataclass(frozen=True)
class Exchange:
    """One collective transpose over mesh axis ``axis`` (ring size
    ``p``), dispatched through the backend registry. ``fft=True`` runs
    :func:`repro.core.transpose.transpose_then_fft` -- the following FFT
    pass folded into the arriving chunks when ``fused`` and the backend
    streams (conjugated tables when ``inverse``). ``elems`` is the
    per-device payload element count and ``payload`` its wire dtype
    class (``"complex"`` or ``"real"``) -- the byte truth the cost model
    and HLO accounting walk."""

    axis: str
    role: str  # 'slab' | 'row' | 'col'
    backend: str
    p: int
    elems: float
    payload: str = "complex"
    fft: bool = False
    inverse: bool = False
    fused: bool = False
    n_chunks: Optional[int] = None


_STAGE_TYPES = (LocalFFT, LocalR2C, LocalC2R, HermitianPack, Trim, Relayout, Twiddle, Exchange)


# ---------------------------------------------------------------------------
# Schedule container
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Schedule:
    """A lowered transform: stage tuple + the metadata the runner and
    the analyzers need. ``global_shape`` is the full *data-side* shape
    (the real array's shape for r2c/c2r chains, batch dims included);
    ``in_tail``/``out_tail`` are the trailing PartitionSpec entries of
    the transform's input/output (leading batch dims are replicated).
    ``conj``/``scale`` implement the c2c inverse as the conjugate-wrap
    of the forward schedule; real chains instead carry per-stage
    ``inverse`` flags (structurally reversed schedule, conjugated
    tables). ``global_backend`` marks whole-transform (GSPMD reference)
    backends: the stage list still carries the abstract exchange
    structure for cost/byte accounting, but execution routes through the
    one :func:`_xla_reference` path instead of the interpreter."""

    kind: str
    global_shape: Tuple[int, ...]
    ndim: int
    decomp: str
    real: bool
    inverse: bool
    transpose_back: bool
    stages: Tuple[object, ...]
    in_tail: Tuple[Optional[str], ...]
    out_tail: Tuple[Optional[str], ...]
    conj: bool = False
    scale: Optional[float] = None
    n_last: Optional[int] = None
    h: Optional[int] = None
    hp: Optional[int] = None
    global_backend: Optional[str] = None

    # -- identity ----------------------------------------------------------
    def canonical(self) -> str:
        """Stable text form: header + one dataclass repr per stage. This
        is what hashes, and what the golden snapshots diff."""
        head = (
            f"kind={self.kind}|shape={self.global_shape}|ndim={self.ndim}|"
            f"decomp={self.decomp}|real={self.real}|inverse={self.inverse}|"
            f"tb={self.transpose_back}|conj={self.conj}|scale={self.scale}|"
            f"n_last={self.n_last}|h={self.h}|hp={self.hp}|"
            f"in={self.in_tail}|out={self.out_tail}|gb={self.global_backend}"
        )
        return "\n".join([head] + [repr(st) for st in self.stages])

    def schedule_hash(self) -> str:
        """12-hex content hash of :meth:`canonical` -- two plans with the
        same hash execute the same pipeline."""
        return hashlib.sha256(self.canonical().encode()).hexdigest()[:12]

    # -- queries -----------------------------------------------------------
    def exchanges(self, role: Optional[str] = None) -> Tuple[Exchange, ...]:
        return tuple(
            st for st in self.stages
            if isinstance(st, Exchange) and (role is None or st.role == role)
        )

    def describe(
        self,
        *,
        params=None,
        chunk_compute_s: float = 0.0,
        real_itemsize: int = 8,
        complex_itemsize: int = 8,
    ) -> str:
        return describe_schedule(
            self, params=params, chunk_compute_s=chunk_compute_s,
            real_itemsize=real_itemsize, complex_itemsize=complex_itemsize,
        )


# ---------------------------------------------------------------------------
# Cost / byte walks (the SAME object that executes)
# ---------------------------------------------------------------------------


def exchange_block_bytes(st: Exchange, real_itemsize: int, complex_itemsize: int) -> float:
    """Full per-device block bytes one Exchange re-shards (the alpha-beta
    ``m_bytes``); the wire ships ``(1 - 1/p)`` of it."""
    item = complex_itemsize if st.payload == "complex" else real_itemsize
    return st.elems * item


def exchange_wire_bytes(st: Exchange, real_itemsize: int, complex_itemsize: int) -> float:
    return exchange_block_bytes(st, real_itemsize, complex_itemsize) * (1 - 1 / st.p)


def schedule_comm_bytes(sched: Schedule, real_itemsize: int, complex_itemsize: int) -> float:
    """Total bytes each device ships per transform -- the sum of every
    Exchange stage's wire payload. ``Plan.comm_bytes`` and the HLO-parser
    cross-checks both consume this walk."""
    return sum(
        exchange_wire_bytes(st, real_itemsize, complex_itemsize)
        for st in sched.exchanges()
    )


def stage_seconds(
    st: Exchange,
    params,
    chunk_compute_s: float,
    real_itemsize: int,
    complex_itemsize: int,
) -> float:
    """Alpha-beta predicted seconds of one Exchange stage, costed by its
    own backend at its own ring size with its own pipeline fields."""
    from repro.core import backends

    b = backends.get(st.backend)
    return b.cost(
        exchange_block_bytes(st, real_itemsize, complex_itemsize),
        st.p, params, chunk_compute_s,
        n_chunks=st.n_chunks, fused=st.fused,
    )


def predict_seconds(
    sched: Schedule,
    params,
    chunk_compute_s: float,
    real_itemsize: int,
    complex_itemsize: int,
    role: Optional[str] = None,
) -> float:
    """Whole-schedule (or one grid axis's) predicted seconds: the sum of
    :func:`stage_seconds` over its Exchange stages. ``Plan.predict`` is
    this walk over backend/pipeline rewrites of the plan's own schedule,
    so prediction and execution cannot drift."""
    return sum(
        stage_seconds(st, params, chunk_compute_s, real_itemsize, complex_itemsize)
        for st in sched.exchanges(role)
    )


# ---------------------------------------------------------------------------
# Rewrites (planner candidates as schedule transformations)
# ---------------------------------------------------------------------------


def with_pipeline(sched: Schedule, fused: bool, n_chunks: Optional[int]) -> Schedule:
    """Rewrite every Exchange's pipeline fields -- the ``@u`` (unfused)
    and ``@f<k>`` (sub-chunked) planner variants as schedule rewrites."""
    stages = tuple(
        dataclasses.replace(st, fused=bool(fused), n_chunks=n_chunks)
        if isinstance(st, Exchange) else st
        for st in sched.stages
    )
    return dataclasses.replace(sched, stages=stages)


def with_backends(
    sched: Schedule,
    *,
    slab: Optional[str] = None,
    row: Optional[str] = None,
    col: Optional[str] = None,
) -> Schedule:
    """Rewrite Exchange backends by role -- backend candidates (and
    pencil ``"row+col"`` pairs) as schedule rewrites."""
    sub = {"slab": slab, "row": row, "col": col}

    def rw(st):
        if not isinstance(st, Exchange):
            return st
        nm = sub.get(st.role)
        return st if nm is None else dataclasses.replace(st, backend=nm)

    return dataclasses.replace(sched, stages=tuple(rw(st) for st in sched.stages))


def apply_variant(sched: Schedule, candidate: str, *, pipeline="auto") -> Schedule:
    """Measured-planner candidate id (``name``, ``name@u``,
    ``name@f<k>``, ``"row+col"`` pair key, with or without variant
    suffix) -> the rewritten schedule that candidate would execute."""
    from repro.core.plan import pipeline_is_default, split_pair
    from repro.core.planner import parse_variant

    base, pipe = parse_variant(candidate)
    if pipe is None and not pipeline_is_default(pipeline):
        pipe = pipeline
    fused = True if pipe is None else pipe not in (False, 0)
    n_chunks = (
        pipe if isinstance(pipe, int) and not isinstance(pipe, bool) and pipe > 0 else None
    )
    if sched.decomp == "pencil":
        br, bc = split_pair(base)
        out = with_backends(sched, row=br, col=bc)
    else:
        out = with_backends(sched, slab=base)
    return with_pipeline(out, fused, n_chunks)


# ---------------------------------------------------------------------------
# Builders (pure: shapes + names + ring sizes in, Schedule out)
# ---------------------------------------------------------------------------


def build_schedule(
    global_shape,
    *,
    ndim: int,
    inverse: bool = False,
    real: bool = False,
    decomp: str = "slab",
    axis_name=None,
    p: int = 1,
    row_axis=None,
    col_axis=None,
    p_rows: int = 1,
    p_cols: int = 1,
    backend: str = "alltoall",
    backend_row: str = "alltoall",
    backend_col: str = "alltoall",
    fused: bool = False,
    n_chunks: Optional[int] = None,
    transpose_back: bool = False,
    pad: bool = True,
    rows: Optional[int] = None,
) -> Schedule:
    """Lower one distributed transform to its stage schedule.

    ``global_shape`` is the full data-side shape (real-side for r2c/c2r,
    batch dims included); for a pencil schedule pass the grid axes/sizes,
    for slab the mesh axis and its size. Real problems are validated
    here (the builder needs ``h``/``hp`` anyway); slab c2c divisibility
    stays with the plan layer so direct entry-point callers keep the
    transpose-level errors they always had."""
    shape = tuple(global_shape)
    if decomp == "pencil":
        if real:
            return _pencil_real(
                shape, ndim, inverse, row_axis, col_axis, p_rows, p_cols,
                backend_row, backend_col, fused, n_chunks, transpose_back, pad,
            )
        return _pencil_c2c(
            shape, ndim, inverse, row_axis, col_axis, p_rows, p_cols,
            backend_row, backend_col, fused, n_chunks, transpose_back,
        )
    if real:
        return _slab_real(
            shape, ndim, inverse, axis_name, p, backend, fused, n_chunks,
            transpose_back, pad,
        )
    return _slab_c2c(
        shape, ndim, inverse, axis_name, p, backend, fused, n_chunks,
        transpose_back, rows,
    )


def _global_kind(backend: str) -> Optional[str]:
    from repro.core import backends

    try:
        b = backends.get(backend)
    except (KeyError, ValueError):
        return None
    return backend if b.kind == "global" else None


def _slab_c2c(shape, ndim, inverse, ax, p, backend, fused, n_chunks, tb, rows):
    gb = _global_kind(backend)
    m = float(np.prod(shape)) / p

    def ex(fft=False, fuse=False):
        return Exchange(
            axis=ax, role="slab", backend=backend, p=p, elems=m,
            fft=fft, fused=fuse, n_chunks=n_chunks,
        )

    meta = dict(
        global_shape=shape, ndim=ndim, decomp="slab", real=False,
        inverse=inverse, transpose_back=tb, global_backend=gb,
    )
    if ndim == 2:
        stages = [LocalFFT(axis=-1), ex(fft=True, fuse=fused)]
        if tb:
            stages.append(ex())
        return Schedule(
            kind="fft2", stages=tuple(stages), in_tail=(ax, None),
            out_tail=(ax, None), conj=inverse,
            scale=float(shape[-1] * shape[-2]) if inverse else None, **meta,
        )
    if ndim == 3:
        d0, d1, d2 = shape[-3:]
        stages = (
            LocalFFT(axis=-1), LocalFFT(axis=-2), Relayout("flatten2"),
            ex(fft=True, fuse=fused), ex(), Relayout("unflatten2", (d1, d2)),
        )
        return Schedule(
            kind="fft3", stages=stages, in_tail=(ax, None, None),
            out_tail=(ax, None, None), conj=inverse,
            scale=float(d0 * d1 * d2) if inverse else None, **meta,
        )
    # ndim == 1: the six-step large transform (forward only)
    if inverse:
        raise NotImplementedError("1-D large inverse: conjugate externally")
    n = shape[-1]
    r = rows or p
    if n % r or (n // r) % p or r % p:
        if gb is not None:
            # the GSPMD reference FFTs any length -- keep the legacy
            # behavior of not imposing the six-step factorization on it
            # (no abstract exchange structure to record in that case)
            return Schedule(
                kind="fft1d", stages=(), in_tail=(ax,), out_tail=(ax,), **meta
            )
        raise ValueError(f"N={n} must factor as rows({r}) x cols with both divisible by P={p}")
    c = n // r
    stages = (
        Relayout("unflatten2", (r // p, c)),
        ex(fft=True, fuse=fused),
        Twiddle(n=n, r=r, c=c),
        ex(),
        LocalFFT(axis=-1),
        ex(),
        Relayout("flatten2"),
    )
    return Schedule(kind="fft1d", stages=stages, in_tail=(ax,), out_tail=(ax,), **meta)


def _slab_real(shape, ndim, inverse, ax, p, backend, fused, n_chunks, tb, pad):
    gb = _global_kind(backend)
    h, hp = check_divisible(shape, ndim, p=p, axis_name=ax, real=True, pad=pad)
    he = float(np.prod(shape[:-1])) * hp / p
    n_last = shape[-1]

    def ex(fft=False, fuse=False, inv=False):
        return Exchange(
            axis=ax, role="slab", backend=backend, p=p, elems=he,
            fft=fft, inverse=inv, fused=fuse, n_chunks=n_chunks,
        )

    meta = dict(
        global_shape=shape, ndim=ndim, decomp="slab", real=True,
        inverse=inverse, transpose_back=tb, n_last=n_last, h=h, hp=hp,
        global_backend=gb,
    )
    if ndim == 2:
        if not inverse:
            stages = [LocalR2C(), HermitianPack(h, hp), ex(fft=True, fuse=fused)]
            if tb:
                stages += [ex(), Trim(h)]
            return Schedule(
                kind="rfft2", stages=tuple(stages), in_tail=(ax, None),
                out_tail=(ax, None), **meta,
            )
        if tb:
            stages = [HermitianPack(h, hp), ex(fft=True, fuse=fused, inv=True)]
        else:
            stages = [LocalFFT(axis=-1, inverse=True)]
        stages += [ex(), Trim(h), LocalC2R(n_last)]
        return Schedule(
            kind="irfft2", stages=tuple(stages), in_tail=(ax, None),
            out_tail=(ax, None), **meta,
        )
    d1 = shape[-2]
    if not inverse:
        stages = (
            LocalR2C(), HermitianPack(h, hp), LocalFFT(axis=-2),
            Relayout("flatten2"), ex(fft=True, fuse=fused), ex(),
            Relayout("unflatten2", (d1, hp)), Trim(h),
        )
        return Schedule(
            kind="rfft3", stages=stages, in_tail=(ax, None, None),
            out_tail=(ax, None, None), **meta,
        )
    stages = (
        HermitianPack(h, hp), Relayout("flatten2"),
        ex(fft=True, fuse=fused, inv=True), ex(),
        Relayout("unflatten2", (d1, hp)), LocalFFT(axis=-2, inverse=True),
        Trim(h), LocalC2R(n_last),
    )
    return Schedule(
        kind="irfft3", stages=stages, in_tail=(ax, None, None),
        out_tail=(ax, None, None), **meta,
    )


def _pencil_c2c(shape, ndim, inverse, row, col, pr, pc, br, bc, fused, n_chunks, tb):
    check_divisible(shape, ndim, p_rows=pr, p_cols=pc, row_axis=row, col_axis=col)
    m = float(np.prod(shape)) / (pr * pc)

    def exr(fft=False, fuse=False):
        return Exchange(axis=row, role="row", backend=br, p=pr, elems=m,
                        fft=fft, fused=fuse, n_chunks=n_chunks)

    def exc(fft=False, fuse=False):
        return Exchange(axis=col, role="col", backend=bc, p=pc, elems=m,
                        fft=fft, fused=fuse, n_chunks=n_chunks)

    meta = dict(
        global_shape=shape, ndim=ndim, decomp="pencil", real=False,
        inverse=inverse, transpose_back=tb,
    )
    if ndim == 3:
        d0, d1, d2 = shape[-3:]
        stages = [
            LocalFFT(axis=-1), exc(fft=True, fuse=fused),
            Relayout("swap_outer"), exr(fft=True, fuse=fused),
        ]
        if tb:
            stages += [exr(), Relayout("swap_outer"), exc()]
        in_tail = (row, col, None)
        return Schedule(
            kind="fft3", stages=tuple(stages), in_tail=in_tail,
            out_tail=in_tail if tb else (col, row, None), conj=inverse,
            scale=float(d0 * d1 * d2) if inverse else None, **meta,
        )
    if tb:
        raise ValueError(
            "pencil fft2 already returns the natural layout; "
            "transpose_back applies to slab transforms and pencil fft3 only"
        )
    r_glob, c_glob = shape[-2:]
    stages = (
        Relayout("swap_last2"), exc(fft=True, fuse=fused), exc(),
        Relayout("swap_last2"), exr(fft=True, fuse=fused), exr(),
    )
    return Schedule(
        kind="fft2", stages=stages, in_tail=(row, col), out_tail=(row, col),
        conj=inverse, scale=float(r_glob * c_glob) if inverse else None, **meta,
    )


def _pencil_real(shape, ndim, inverse, row, col, pr, pc, br, bc, fused, n_chunks, tb, pad):
    h, hp = check_divisible(
        shape, ndim, p_rows=pr, p_cols=pc, row_axis=row, col_axis=col,
        real=True, pad=pad,
    )
    shards = pr * pc
    he = float(np.prod(shape[:-1])) * hp / shards
    n_last = shape[-1]

    def exr(fft=False, fuse=False, inv=False):
        return Exchange(axis=row, role="row", backend=br, p=pr, elems=he,
                        fft=fft, inverse=inv, fused=fuse, n_chunks=n_chunks)

    def exc(fft=False, fuse=False, inv=False, payload="complex", elems=None):
        return Exchange(axis=col, role="col", backend=bc, p=pc,
                        elems=he if elems is None else elems, payload=payload,
                        fft=fft, inverse=inv, fused=fuse, n_chunks=n_chunks)

    meta = dict(
        global_shape=shape, ndim=ndim, decomp="pencil", real=True,
        inverse=inverse, transpose_back=tb, n_last=n_last, h=h, hp=hp,
    )
    if ndim == 3:
        if not inverse:
            stages = [
                LocalR2C(), HermitianPack(h, hp), exc(fft=True, fuse=fused),
                Relayout("swap_outer"), exr(fft=True, fuse=fused),
            ]
            if tb:
                stages += [exr(), Relayout("swap_outer"), exc(), Trim(h)]
            in_tail = (row, col, None)
            return Schedule(
                kind="rfft3", stages=tuple(stages), in_tail=in_tail,
                out_tail=in_tail if tb else (col, row, None), **meta,
            )
        if tb:
            stages = [
                HermitianPack(h, hp), exc(), Relayout("swap_outer"),
                exr(fft=True, fuse=fused, inv=True),
            ]
        else:
            stages = [LocalFFT(axis=-1, inverse=True)]
        stages += [
            exr(fft=True, fuse=fused, inv=True), Relayout("swap_outer"),
            exc(), Trim(h), LocalC2R(n_last),
        ]
        return Schedule(
            kind="irfft3", stages=tuple(stages),
            in_tail=(row, col, None) if tb else (col, row, None),
            out_tail=(row, col, None), **meta,
        )
    if tb:
        raise ValueError(
            "pencil rfft2 already returns the natural layout; "
            "transpose_back applies to slab transforms and pencil rfft3 only"
        )
    real_elems = float(np.prod(shape)) / shards
    if not inverse:
        stages = (
            Relayout("swap_last2"), exc(payload="real", elems=real_elems),
            LocalR2C(), HermitianPack(h, hp), exc(), Relayout("swap_last2"),
            exr(fft=True, fuse=fused), exr(),
        )
        return Schedule(
            kind="rfft2", stages=stages, in_tail=(row, col),
            out_tail=(row, col), **meta,
        )
    stages = (
        exr(fft=True, fuse=fused, inv=True), exr(), Relayout("swap_last2"),
        exc(), Trim(h), LocalC2R(n_last),
        exc(payload="real", elems=real_elems), Relayout("swap_last2"),
    )
    return Schedule(
        kind="irfft2", stages=stages, in_tail=(row, col), out_tail=(row, col), **meta
    )


# ---------------------------------------------------------------------------
# Local r2c/c2r building blocks (shared with repro.core.real, which
# re-exports them; they live here so the executor has no real.py import)
# ---------------------------------------------------------------------------


def local_rfft(x: jax.Array, impl) -> jax.Array:
    """r2c along the last axis. ``jnp`` uses the native rfft; the matmul
    and pallas impls have no r2c codelet, so they transform the
    complexified axis and keep the non-redundant half."""
    if impl == "jnp":
        return jnp.fft.rfft(x, axis=-1)
    return lf.local_fft(x, axis=-1, impl=impl)[..., : rfft_len(x.shape[-1])]


def local_irfft(x: jax.Array, n: int, impl) -> jax.Array:
    """c2r along the last axis: half spectrum (length ``n//2+1``) to a
    real length-``n`` signal, carrying the 1/n factor."""
    if impl == "jnp":
        return jnp.fft.irfft(x, n=n, axis=-1)
    h = x.shape[-1]
    # rebuild the redundant half (X[n-k] = conj(X[k]), k = 1..n-h) and
    # run the impl's c2c inverse; the result is real up to roundoff
    tail = jnp.conj(x[..., 1 : n - h + 1])[..., ::-1]
    full = jnp.concatenate([x, tail], axis=-1)
    return jnp.real(lf.local_fft(full, axis=-1, inverse=True, impl=impl))


def pad_last(v: jax.Array, count: int) -> jax.Array:
    if count == 0:
        return v
    return jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, count)])


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------


def _relayout(v: jax.Array, st: Relayout) -> jax.Array:
    if st.op == "swap_last2":
        return jnp.swapaxes(v, -1, -2)
    if st.op == "swap_outer":
        return jnp.swapaxes(v, -3, -2)
    if st.op == "flatten2":
        return v.reshape(v.shape[:-2] + (v.shape[-2] * v.shape[-1],))
    if st.op == "unflatten2":
        a, b = st.dims
        return v.reshape(v.shape[:-1] + (a, b))
    raise ValueError(f"unknown relayout op {st.op!r}")


def _twiddled_exchange(v: jax.Array, tw: Twiddle, ex: Exchange) -> jax.Array:
    """Twiddle + the exchange it rides: fused into the per-chunk compute
    on streaming backends (applied to each sub-chunk as it arrives),
    up-front to the whole block otherwise."""
    from repro.core import backends

    n, r, c, p = tw.n, tw.r, tw.c, ex.p
    me = lax.axis_index(ex.axis)
    if backends.get(ex.backend).supports_chunk_fn:

        def tw_chunk(chunk: jax.Array, src: jax.Array, offset: int) -> jax.Array:
            # chunk (..., R/p, rows): my k1 block x src's j2 rows
            # [offset, offset+rows) of its C/p block.
            k1 = me * (r // p) + jnp.arange(r // p)
            j2 = src * (c // p) + offset + jnp.arange(chunk.shape[-1])
            t = jnp.exp(-2j * jnp.pi * (k1[:, None] * j2[None, :]) / n)
            return chunk * t.astype(chunk.dtype)

        return tr.distributed_transpose(
            v, ex.axis, strategy=ex.backend, chunk_fn=tw_chunk, n_chunks=ex.n_chunks
        )
    j2 = me * (c // p) + jnp.arange(c // p)
    k1 = jnp.arange(r)
    t = jnp.exp(-2j * jnp.pi * (j2[:, None] * k1[None, :]) / n).astype(v.dtype)
    return tr.distributed_transpose(v * t, ex.axis, strategy=ex.backend)


def _execute_stages(v: jax.Array, stages: Tuple[object, ...], *, impl="jnp") -> jax.Array:
    """Interpret a run of stages over one device's local block. The
    whole-schedule executor and the trace-mode segment runner both call
    this, so traced segments execute exactly the ops the untraced body
    would."""
    i = 0
    while i < len(stages):
        st = stages[i]
        if isinstance(st, LocalFFT):
            v = lf.local_fft(v, axis=st.axis, inverse=st.inverse, impl=impl)
        elif isinstance(st, LocalR2C):
            v = local_rfft(v, impl)
        elif isinstance(st, LocalC2R):
            v = local_irfft(v, st.n_last, impl)
        elif isinstance(st, HermitianPack):
            v = pad_last(v, st.hp - st.h)
        elif isinstance(st, Trim):
            v = v[..., : st.h]
        elif isinstance(st, Relayout):
            v = _relayout(v, st)
        elif isinstance(st, Twiddle):
            nxt = stages[i + 1] if i + 1 < len(stages) else None
            if not isinstance(nxt, Exchange):
                raise ValueError("Twiddle must immediately precede an Exchange")
            v = _twiddled_exchange(v, st, nxt)
            i += 2
            continue
        elif isinstance(st, Exchange):
            if st.fft:
                v = tr.transpose_then_fft(
                    v, st.axis, strategy=st.backend, impl=impl,
                    fused=st.fused, n_chunks=st.n_chunks, inverse=st.inverse,
                )
            else:
                v = tr.distributed_transpose(
                    v, st.axis, strategy=st.backend, n_chunks=st.n_chunks
                )
        else:
            raise TypeError(f"unknown stage {st!r}")
        i += 1
    return v


def execute_schedule(xl: jax.Array, sched: Schedule, *, impl="jnp") -> jax.Array:
    """Interpret a schedule over one device's local block -- the single
    shard_map body behind every distributed transform. Must be called
    inside ``shard_map`` (use :func:`run_schedule` from outside)."""
    v = jnp.conj(xl) if sched.conj else xl
    v = _execute_stages(v, sched.stages, impl=impl)
    if sched.conj:
        v = jnp.conj(v)
    if sched.scale is not None:
        v = v / sched.scale
    return v


def _specs(sched: Schedule, ndim: int) -> Tuple[P, P]:
    i = P(*([None] * (ndim - len(sched.in_tail))), *sched.in_tail)
    o = P(*([None] * (ndim - len(sched.out_tail))), *sched.out_tail)
    return i, o


def simulate_specs(sched: Schedule, ndim: int) -> Tuple[Tuple[Optional[str], ...], ...]:
    """Walk the stage list symbolically and return the full-length
    partition spec at every stage boundary: ``specs[0]`` is the input
    spec, ``specs[i + 1]`` the spec after stage ``i``. This is what lets
    the trace-mode executor cut the schedule into per-stage shard_map
    segments without any resharding between them.

    The rules mirror the executor's data movement:

    - an :class:`Exchange` transposes the *data* of the last two local
      dims but keeps the same spec positions sharded -- the local block
      goes ``(..., r, C)`` with R sharded to ``(..., c, R)`` with C
      sharded over the same mesh axis (see
      :mod:`repro.core.transpose`), so the tail spec is unchanged;
    - a :class:`Relayout` permutes/merges/splits spec entries exactly as
      it moves the local dims;
    - local stages (FFT/r2c/c2r/pad/trim) never touch sharding.

    The final spec must land on the schedule's own ``out_tail`` -- a
    mismatch means the simulation rules and a builder disagree, so we
    fail loudly rather than emit a silently-resharding trace."""
    spec = [None] * (ndim - len(sched.in_tail)) + list(sched.in_tail)
    out = [tuple(spec)]
    for st in sched.stages:
        if isinstance(st, Relayout):
            if st.op == "swap_last2":
                spec[-1], spec[-2] = spec[-2], spec[-1]
            elif st.op == "swap_outer":
                spec[-3], spec[-2] = spec[-2], spec[-3]
            elif st.op == "flatten2":
                if spec[-1] is not None:
                    raise ValueError(
                        "flatten2 with the minor axis sharded has no "
                        "block-contiguous partition spec"
                    )
                spec = spec[:-2] + [spec[-2]]
            elif st.op == "unflatten2":
                spec = spec[:-1] + [spec[-1], None]
            else:  # pragma: no cover - _relayout already rejects these
                raise ValueError(f"unknown relayout op {st.op!r}")
        elif isinstance(st, (Twiddle, Exchange)):
            ex = st if isinstance(st, Exchange) else None
            if ex is not None and ex.p > 1 and spec[-2] != ex.axis:
                raise ValueError(
                    f"exchange over mesh axis {ex.axis!r} but simulated "
                    f"spec has {spec[-2]!r} sharded at position -2"
                )
        out.append(tuple(spec))
    expected = [None] * (len(out[-1]) - len(sched.out_tail)) + list(sched.out_tail)
    if list(out[-1]) != expected:
        raise ValueError(
            f"spec simulation of {sched.kind} schedule landed on "
            f"{out[-1]} but the schedule declares out_tail={sched.out_tail}"
        )
    return tuple(out)


def _xla_reference(x: jax.Array, sched: Schedule, mesh: Mesh) -> jax.Array:
    """The one GSPMD reference path (the 'FFTW3 reference' analogue):
    hand the sharded array to XLA's own FFT op under jit and let GSPMD
    choose the communication schedule. Replaces the per-transform
    ``_fft2_xla_auto`` / ``_rfft2_xla_auto`` / ``_irfft2_xla_auto``
    one-offs -- every whole-transform backend now routes through the
    same schedule object as the shard_map executor."""
    return _reference_executable(sched, mesh, x.ndim)(x)


@functools.lru_cache(maxsize=128)
def _reference_executable(sched: Schedule, mesh: Mesh, ndim: int):
    """Jitted GSPMD reference, cached on the (hashable, frozen) schedule
    so repeated traced executions (``Plan.profile`` reps) hit the compile
    cache instead of re-jitting a fresh closure every call."""
    in_spec, out_spec = _specs(sched, ndim)
    sh_in = NamedSharding(mesh, in_spec)
    sh_out = NamedSharding(mesh, out_spec)
    k, inv, tb = sched.kind, sched.inverse, sched.transpose_back
    if k == "fft2":

        def fn(v):
            out = jnp.fft.ifft2(v) if inv else jnp.fft.fft2(v)
            if not tb:
                out = jnp.swapaxes(out, -1, -2)
            return out

    elif k == "fft3":
        f3 = jnp.fft.ifftn if inv else jnp.fft.fftn
        fn = lambda v: f3(v, axes=(-3, -2, -1))  # noqa: E731
    elif k == "fft1d":
        fn = jnp.fft.fft
    elif k == "rfft2":
        hp = sched.hp

        def fn(v):
            y = jnp.fft.rfft2(v)
            if tb:
                return y
            y = jnp.swapaxes(y, -1, -2)
            return jnp.pad(y, [(0, 0)] * (y.ndim - 2) + [(0, hp - y.shape[-2]), (0, 0)])

    elif k == "irfft2":
        h, n_last = sched.h, sched.n_last
        r_glob = sched.global_shape[-2]

        def fn(v):
            if not tb:
                v = jnp.swapaxes(v[..., :h, :], -1, -2)
            return jnp.fft.irfft2(v, s=(r_glob, n_last))

    elif k == "rfft3":
        fn = lambda v: jnp.fft.rfftn(v, axes=(-3, -2, -1))  # noqa: E731
    elif k == "irfft3":
        s = sched.global_shape[-3:]
        fn = lambda v: jnp.fft.irfftn(v, s=s, axes=(-3, -2, -1))  # noqa: E731
    else:  # pragma: no cover - builders only emit the kinds above
        raise ValueError(f"no whole-transform reference for schedule kind {k!r}")
    return jax.jit(fn, in_shardings=sh_in, out_shardings=sh_out)


def run_schedule(
    x: jax.Array, sched: Schedule, mesh: Mesh, *, impl="jnp", trace=None, faults=None
) -> jax.Array:
    """Run a schedule on a globally-sharded array: shard_map the
    interpreter with the schedule's own partition specs, or dispatch the
    whole transform to the GSPMD reference for ``kind="global"``
    backends.

    With ``trace`` (a :class:`repro.obs.trace.TraceRecorder`) the
    schedule instead executes *segmented*: one shard_map per stage with
    ``jax.block_until_ready`` between them, stamping a wall-clock span
    per stage -- Exchange spans carry backend/role/wire-bytes/pipeline
    attributes (the paper's comm-vs-compute breakdown, per stage). The
    default ``trace=None`` path is byte-identical to the untraced
    executor and stays jittable.

    With ``faults`` (an *armed* :class:`repro.runtime.faults.FaultPlan`)
    the schedule also executes segmented, consulting the fault plan
    before every Exchange segment (and before a ``global:`` reference
    dispatch) so a matching spec can raise, stall, or report device loss
    at exactly the stage it names -- deterministic chaos on the IR. An
    exhausted (``active() == False``) or absent fault plan costs
    nothing: the fast path runs unchanged."""
    if faults is not None and faults.active():
        if trace is not None:
            return _run_schedule_traced(
                x, sched, mesh, impl=impl, trace=trace, faults=faults
            )
        return _run_schedule_faulted(x, sched, mesh, impl=impl, faults=faults)
    if trace is not None:
        return _run_schedule_traced(x, sched, mesh, impl=impl, trace=trace)
    if sched.global_backend is not None:
        return _xla_reference(x, sched, mesh)
    in_spec, out_spec = _specs(sched, x.ndim)

    def fn(xl: jax.Array) -> jax.Array:
        return execute_schedule(xl, sched, impl=impl)

    return shard_map(
        fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec, check_vma=_check_vma(impl)
    )(x)


def _check_vma(impl: str) -> bool:
    """shard_map's vma check, off for the Pallas impl: the Pallas
    interpreter (CPU) slices varying blocks with unvarying indices,
    which the check refuses. The compiled TPU kernels pass it."""
    return impl != "pallas"


def _run_schedule_faulted(
    x: jax.Array, sched: Schedule, mesh: Mesh, *, impl, faults
) -> jax.Array:
    """Chaos-mode executor: the trace-mode segment walk without spans or
    fences, calling ``faults.on_stage(label, index=...)`` before every
    Exchange segment (Twiddles ride their Exchange, as in tracing).
    Injected faults therefore surface as *host* exceptions at dispatch
    time -- synchronously and deterministically -- while the segments
    themselves still launch async; numerics of a non-firing run match
    the untraced executor (same per-segment shard_maps over the same
    simulated boundary specs)."""
    if sched.global_backend is not None:
        faults.on_stage(f"global:{sched.kind}", index=0)
        return _xla_reference(x, sched, mesh)
    bounds = simulate_specs(sched, x.ndim)
    v = jnp.conj(x) if sched.conj else x
    for start, seg in _segments(sched):
        report = seg[-1]
        if isinstance(report, Exchange):
            faults.on_stage(_stage_label(report), index=start + len(seg) - 1)
        fn = _segment_executable(
            sched, start, len(seg), impl, mesh,
            P(*bounds[start]), P(*bounds[start + len(seg)]),
        )
        v = fn(v)
    if sched.conj:
        v = jnp.conj(v)
    if sched.scale is not None:
        v = v / sched.scale
    return v


def _segments(sched: Schedule) -> Tuple[Tuple[int, Tuple[object, ...]], ...]:
    """Cut the stage list into trace segments: every stage is its own
    segment except a Twiddle, which rides its following Exchange (the
    executor fuses them; the merged span reports on the Exchange)."""
    segs = []
    stages = sched.stages
    i = 0
    while i < len(stages):
        if isinstance(stages[i], Twiddle):
            segs.append((i, stages[i : i + 2]))
            i += 2
        else:
            segs.append((i, stages[i : i + 1]))
            i += 1
    return tuple(segs)


def _itemsizes(x: jax.Array) -> Tuple[int, int]:
    """(real, complex) itemsizes implied by the runtime dtype."""
    if jnp.iscomplexobj(x):
        return x.dtype.itemsize // 2, x.dtype.itemsize
    return x.dtype.itemsize, 2 * x.dtype.itemsize


def exchange_span_args(st: Exchange, real_itemsize: int, complex_itemsize: int) -> Dict[str, object]:
    """The attribute payload every Exchange span carries -- the same
    byte walk the cost model uses, so observed spans and
    ``schedule_comm_bytes`` can never disagree."""
    return {
        "stage": "Exchange",
        "backend": st.backend,
        "role": st.role,
        "axis": st.axis,
        "p": st.p,
        "payload": st.payload,
        "fft": st.fft,
        "inverse": st.inverse,
        "fused": st.fused,
        "n_chunks": st.n_chunks,
        "block_bytes": exchange_block_bytes(st, real_itemsize, complex_itemsize),
        "wire_bytes": exchange_wire_bytes(st, real_itemsize, complex_itemsize),
    }


@functools.lru_cache(maxsize=512)
def _segment_executable(
    sched: Schedule, start: int, seg_len: int, impl: str, mesh: Mesh,
    in_spec: P, out_spec: P,
):
    """One jitted shard_map per trace segment, cached on the frozen
    schedule + boundary specs. Without this every traced execution
    rebuilds fresh closures, so jit's cache never hits and each
    ``Plan.profile`` rep re-pays tracing + compilation -- the observed
    spans would time the compiler, not the stage."""
    seg = sched.stages[start : start + seg_len]
    return jax.jit(shard_map(
        lambda xl: _execute_stages(xl, seg, impl=impl),
        mesh=mesh, in_specs=in_spec, out_specs=out_spec, check_vma=_check_vma(impl),
    ))


def _run_schedule_traced(
    x: jax.Array, sched: Schedule, mesh: Mesh, *, impl, trace, faults=None
) -> jax.Array:
    """Trace-mode executor: host-side segmentation with a wall-clock
    span per stage. Each segment is its own shard_map over the
    spec-simulated boundary shardings (no resharding between segments --
    :func:`simulate_specs` guarantees consecutive segments agree on the
    layout), and ``block_until_ready`` fences each span so durations
    measure that stage's work rather than dispatch latency. First
    execution of a segment pays its compile; profile with warmup reps
    (``Plan.profile`` does) for steady-state numbers."""
    r_item, c_item = _itemsizes(x)
    if sched.global_backend is not None:
        if faults is not None:
            faults.on_stage(f"global:{sched.kind}", index=0)
        with trace.span(
            f"global:{sched.kind}",
            cat="stage",
            stage="Global",
            backend=sched.global_backend,
            schedule=sched.schedule_hash(),
        ):
            out = _xla_reference(x, sched, mesh)
            jax.block_until_ready(out)
        return out
    bounds = simulate_specs(sched, x.ndim)
    v = x
    jax.block_until_ready(v)
    if sched.conj:
        with trace.span("Conj(in)", cat="stage", stage="Conj"):
            v = jnp.conj(v)
            jax.block_until_ready(v)
    for start, seg in _segments(sched):
        in_spec = P(*bounds[start])
        out_spec = P(*bounds[start + len(seg)])
        report = seg[-1]  # the Exchange of a Twiddle+Exchange pair
        if isinstance(report, Exchange):
            cat = "exchange"
            args = exchange_span_args(report, r_item, c_item)
            if len(seg) > 1:
                args["twiddle"] = True
        else:
            cat = "stage"
            args = {"stage": type(report).__name__}
        args["index"] = start + len(seg) - 1
        if faults is not None and isinstance(report, Exchange):
            # consult the chaos hook OUTSIDE the span: an injected raise
            # must not leave a half-open span in the recorder
            faults.on_stage(_stage_label(report), index=start + len(seg) - 1)
        fn = _segment_executable(sched, start, len(seg), impl, mesh, in_spec, out_spec)
        with trace.span(_stage_label(report), cat=cat, **args):
            v = fn(v)
            jax.block_until_ready(v)
    if sched.conj or sched.scale is not None:
        with trace.span("Epilogue(conj/scale)", cat="stage", stage="Epilogue"):
            if sched.conj:
                v = jnp.conj(v)
            if sched.scale is not None:
                v = v / sched.scale
            jax.block_until_ready(v)
    return v


# ---------------------------------------------------------------------------
# Pretty-printing (Plan.describe / benchmarks --explain)
# ---------------------------------------------------------------------------


def _stage_label(st) -> str:
    if isinstance(st, Exchange):
        bits = [f"{st.role}:{st.axis}", st.backend, f"p={st.p}"]
        if st.fft:
            bits.append("ifft" if st.inverse else "fft")
        if st.fused:
            bits.append("fused" + (f"@{st.n_chunks}" if st.n_chunks else ""))
        if st.payload != "complex":
            bits.append(st.payload)
        return f"Exchange({', '.join(bits)})"
    if isinstance(st, LocalFFT):
        return f"LocalFFT(axis={st.axis}{', inverse' if st.inverse else ''})"
    if isinstance(st, LocalR2C):
        return "LocalR2C()"
    if isinstance(st, LocalC2R):
        return f"LocalC2R(n={st.n_last})"
    if isinstance(st, HermitianPack):
        return f"HermitianPack(h={st.h}, hp={st.hp})"
    if isinstance(st, Trim):
        return f"Trim(h={st.h})"
    if isinstance(st, Relayout):
        d = f", dims={st.dims}" if st.dims else ""
        return f"Relayout({st.op}{d})"
    if isinstance(st, Twiddle):
        return f"Twiddle(n={st.n}, r={st.r}, c={st.c})"
    return repr(st)


def describe_schedule(
    sched: Schedule,
    *,
    params=None,
    chunk_compute_s: float = 0.0,
    real_itemsize: int = 8,
    complex_itemsize: int = 8,
) -> str:
    """Human-readable stage dump with per-stage predicted microseconds
    and wire bytes -- the per-stage observability hook. Local stages
    show '-' in the modeled columns (the alpha-beta model prices
    exchanges; local compute rides ``chunk_compute_s``)."""
    from repro.core import comm_model as cm

    prm = params or cm.CommParams()
    head = (
        f"schedule {sched.kind} [{sched.decomp}"
        f"{', r2c' if sched.real else ''}"
        f"{', inverse' if sched.inverse else ''}"
        f"{', transpose_back' if sched.transpose_back else ''}] "
        f"shape={sched.global_shape} hash={sched.schedule_hash()}"
    )
    lines = [head]
    if sched.global_backend is not None:
        lines.append(f"  (whole-transform reference backend: {sched.global_backend})")
    lines.append(f"  {'#':>2}  {'stage':<52} {'model us':>10} {'wire bytes':>12}")
    t_total = 0.0
    b_total = 0.0
    for i, st in enumerate(sched.stages):
        if isinstance(st, Exchange):
            t = stage_seconds(st, prm, chunk_compute_s, real_itemsize, complex_itemsize)
            b = exchange_wire_bytes(st, real_itemsize, complex_itemsize)
            t_total += t
            b_total += b
            lines.append(
                f"  {i:>2}  {_stage_label(st):<52} {t * 1e6:>10.2f} {b:>12.0f}"
            )
        else:
            lines.append(f"  {i:>2}  {_stage_label(st):<52} {'-':>10} {'-':>12}")
    lines.append(
        f"  total modeled exchange time {t_total * 1e6:.2f} us, "
        f"wire bytes/device {b_total:.0f}"
    )
    return "\n".join(lines)
