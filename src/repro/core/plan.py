"""FFTW-style plan/executor front-end over the collective-backend registry.

The paper's FFTW3 reference works through plans; this is the same UX for
the distributed transforms, rebuilt on :mod:`repro.core.backends`:

    plan = plan_fft((n, n), mesh, ndim=2, backend="auto")
    y = plan.execute(x)          # cached jitted executable
    x2 = plan.inverse(y)

A :class:`Plan`:

- validates the (global shape, mesh, shard axes, decomposition, backend)
  combination **once**, at construction -- including shard-divisibility,
  so a bad shape fails here naming the offending data axis and mesh/grid
  dimension instead of deep inside the transpose chunking;
- resolves the decomposition: ``decomp="slab"`` (one mesh axis, the
  paper's layout), ``"pencil"`` (a 2-D
  :class:`~repro.core.grid.ProcessGrid`, sub-axis exchanges with
  independently selected per-axis backends), or ``"auto"`` (pencil
  whenever the mesh offers a valid 2-D grid, else slab);
- resolves ``backend="auto"`` to the alpha-beta cost-model argmin --
  over every registered backend supporting the shard count (slab), or
  per grid axis via :func:`repro.core.backends.cheapest_pair` (pencil;
  pass a ``(backend_row, backend_col)`` tuple to pin the pair);
- caches one jitted executable per (direction, dtype), so repeated
  ``execute`` calls never re-trace or re-compile;
- exposes ``lower``/``roofline`` for dry-run analysis of the compiled
  communication schedule.

``FFTPlan``/``make_plan`` remain as deprecation shims for one release.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.core.schedule as sch
from repro.core import backends
from repro.core import comm_model as cm
from repro.core.distributed_fft import FFTConfig
from repro.obs import trace as obs

#: Pair-key separator for pencil backend pairs ("scatter+bisection") --
#: registry names are identifiers, so '+' cannot appear inside one.
PAIR_SEP = "+"

_DTYPE_PARTNERS = {
    "float32": "complex64", "complex64": "float32",
    "float64": "complex128", "complex128": "float64",
}


def real_complex_pair(dtype) -> Tuple[jnp.dtype, jnp.dtype]:
    """The (real, complex) dtype pair containing ``dtype`` -- the single
    copy of the r2c dtype mapping (plan validation and byte accounting
    must agree on it). Raises for dtypes with no real/complex partner."""
    d = jnp.dtype(dtype)
    partner = _DTYPE_PARTNERS.get(d.name)
    if partner is None:
        raise ValueError(
            f"no real/complex dtype pair for {d.name}; real plans support "
            f"{sorted(n for n in _DTYPE_PARTNERS if not n.startswith('c'))}"
        )
    return (jnp.dtype(partner), d) if d.kind == "c" else (d, jnp.dtype(partner))


def pair_key(backend_row: str, backend_col: str) -> str:
    return f"{backend_row}{PAIR_SEP}{backend_col}"


def pipeline_is_default(pipeline) -> bool:
    """Whether a ``pipeline=`` value is the default ("auto") setting.
    Identity-checked for True/None: ``1 == True`` in Python, but
    ``pipeline=1`` is an explicit one-chunk request, not the default."""
    return pipeline == "auto" or pipeline is True or pipeline is None


def _warn_real_fuse_dft() -> bool:
    """The old hard error ("real transforms have no fused path") is dead:
    the pipelined overlap executor IS that path, and it is on by default
    wherever a streaming backend is selected. One warning, attributed to
    the caller of whichever entry point (plan_fft / Plan) saw the flag
    (stacklevel: helper -> entry point -> caller). Returns the
    replacement fuse_dft value."""
    warnings.warn(
        "fuse_dft on real plans is deprecated and ignored: r2c/c2r "
        "chains fuse streaming exchanges by default -- control it "
        "with plan_fft(..., pipeline=...)",
        DeprecationWarning,
        stacklevel=3,
    )
    return False


class SpectralAxis(NamedTuple):
    """One output axis of a plan's frequency-domain (spectrum) layout.

    ``orig`` is the original data axis it carries (negative index into
    the trailing transform dims), ``n`` that axis's real/complex global
    length, ``n_out`` the length in the spectrum layout (``rfft_len(n)``
    or its shard-padded version for the Hermitian axis of a real plan,
    ``n`` otherwise), and ``half`` whether the axis is
    Hermitian-truncated. The apps layer builds wavenumber grids from
    this -- see :func:`repro.apps.spectral.wavenumbers`."""

    orig: int
    n: int
    n_out: int
    half: bool


def split_pair(key) -> Tuple[str, str]:
    """(row, col) from a pair key, a 2-tuple/list, or a single name
    (applied to both axes)."""
    if isinstance(key, (tuple, list)):
        if len(key) != 2:
            raise ValueError(f"pencil backend pair must have 2 entries, got {key!r}")
        return str(key[0]), str(key[1])
    if PAIR_SEP in key:
        row, _, col = key.partition(PAIR_SEP)
        return row, col
    return key, key


class Plan:
    """A validated, backend-resolved, executable-caching FFT plan.

    Construct through :func:`plan_fft`. ``direction`` fixes what
    ``execute`` computes ("forward" or "inverse"); ``inverse`` always
    computes the opposite of ``execute``.

    Slab plans (``decomp="slab"``) expose ``backend`` (one registry
    name); pencil plans expose ``backend_row``/``backend_col`` plus
    ``backend`` as the combined ``"row+col"`` pair key, and ``grid``
    (the resolved :class:`~repro.core.grid.ProcessGrid`).

    Partial surface: the 1-D large transform has no inverse -- planning
    ``ndim=1, direction="inverse"`` is rejected at construction, and
    calling ``inverse()`` on a forward 1-D plan raises
    ``NotImplementedError`` before anything executes (conjugate
    externally instead). Pencil supports ndim 2 and 3.
    """

    def __init__(
        self,
        global_shape: Tuple[int, ...],
        mesh: Mesh,
        *,
        ndim: int = 2,
        direction: str = "forward",
        backend: str = "auto",
        axis_name: Optional[str] = None,
        local_impl: str = "jnp",
        fuse_dft: bool = False,
        transpose_back: bool = False,
        dtype=jnp.complex64,
        params: Optional[cm.CommParams] = None,
        chunk_compute_s: float = 0.0,
        decomp: str = "slab",
        row_axis: Optional[str] = None,
        col_axis: Optional[str] = None,
        real: bool = False,
        pad: bool = True,
        pipeline="auto",
    ):
        from repro.core.sharding import fft_axis

        if ndim not in (1, 2, 3):
            raise ValueError("ndim must be 1, 2 or 3")
        if direction not in ("forward", "inverse"):
            raise ValueError(f"direction must be 'forward' or 'inverse', got {direction!r}")
        if decomp not in ("slab", "pencil", "auto"):
            raise ValueError(f"decomp must be 'slab', 'pencil' or 'auto', got {decomp!r}")
        if real and ndim == 1:
            raise NotImplementedError(
                "1-D real transform is not implemented: complexify and use ndim=1 c2c"
            )
        if real and fuse_dft:
            fuse_dft = _warn_real_fuse_dft()
        if isinstance(backend, str) and "@" in backend:
            # measured-planner candidate ids ("scatter@u", "scatter@f16",
            # Plan.backend of a variant winner) are valid backend specs:
            # the suffix is a pipeline override, so backend=plan.backend
            # always round-trips
            from repro.core.planner import parse_variant

            backend, pipe_override = parse_variant(backend)
            if not pipeline_is_default(pipeline):
                raise ValueError(
                    f"backend variant suffix and pipeline={pipeline!r} "
                    f"both specify the pipeline; pass one or the other"
                )
            pipeline = pipe_override
        if not (
            pipeline in ("auto", True, False, None)
            or (isinstance(pipeline, int) and not isinstance(pipeline, bool) and pipeline >= 0)
        ):
            raise ValueError(
                f"pipeline must be 'auto', True/False, or a chunk-count int "
                f">= 0, got {pipeline!r}"
            )
        if ndim == 1 and direction == "inverse":
            # fail at plan time, not first execute (validate-once contract)
            raise NotImplementedError(
                "1-D large inverse is not implemented: plan forward and conjugate externally"
            )
        if (row_axis is None) != (col_axis is None):
            raise ValueError("pass both row_axis and col_axis, or neither")
        self.global_shape = tuple(global_shape)
        self.mesh = mesh
        self.axis_name = axis_name or fft_axis(mesh)
        self.ndim = ndim
        self.direction = direction
        self.real = bool(real)
        self.pad = bool(pad)
        self.dtype = jnp.dtype(dtype)
        if self.real:
            # a real plan's dtype is the REAL input dtype; the matching
            # complex dtype (the spectrum side) is derived. Passing the
            # complex default through plan_fft maps to its real partner.
            try:
                self.dtype, self.cdtype = real_complex_pair(self.dtype)
            except ValueError:
                raise ValueError(
                    f"real plans take a real input dtype (float32/float64), "
                    f"got {self.dtype.name}"
                ) from None
        else:
            self.cdtype = self.dtype
        self.hermitian_len: Optional[int] = None
        self.padded_hermitian_len: Optional[int] = None
        self.local_impl = local_impl
        self.fuse_dft = fuse_dft
        self.transpose_back = transpose_back
        if params is None:
            # default-on persisted calibration: when this fabric's
            # alpha/beta have been fitted (CommParams.calibrate via
            # planner.ensure_calibrated, refine_online, or an imported
            # wisdom file's calibration section), every default-params
            # plan prices with the measured constants
            from repro.core import planner as _planner

            params = _planner.calibration_for(_planner.device_kind(mesh))
        self.params = params or cm.CommParams()
        self.chunk_compute_s = chunk_compute_s
        self.pipeline = "auto" if (pipeline is True or pipeline is None) else pipeline
        #: resolved by _resolve_pipeline once the backend(s) are known
        self.fused: bool = False
        self.n_chunks: Optional[int] = None
        # set by the measured planner (repro.core.planner.plan_measured)
        self.planner = "estimate"
        self.measured: Optional[Dict[str, float]] = None
        #: candidate id -> "ExcType: msg" for candidates that raised
        #: mid-race (recorded as inf, excluded from the argmin)
        self.race_failures: Dict[str, str] = {}
        self.wisdom_hit = False
        self.wisdom_key: Optional[str] = None
        #: chaos hook (repro.runtime.faults.FaultPlan). While armed,
        #: execute/inverse run the segmented chaos executor so the plan
        #: consults it before every Exchange; once exhausted (or None)
        #: the cached jitted executables run untouched.
        self.faults = None
        #: decision provenance: which channel picked this plan's backend
        #: -- "pinned" (caller named it), "model-argmin" (alpha-beta
        #: auto), or -- overwritten by plan_measured -- "measured-race" /
        #: "wisdom-hit" / "observed-overlay". Rendered by :meth:`why`.
        self.selection_channel = "pinned"
        #: direction -> lowered stage schedule (the single pipeline truth
        #: that execution, the cost model and the byte accounting share);
        #: cleared whenever the decomposition/backends are (re)resolved
        self._schedules: Dict[bool, sch.Schedule] = {}

        self.grid = None
        if decomp == "slab":
            if row_axis is not None or col_axis is not None:
                raise ValueError("row_axis/col_axis apply to decomp='pencil' (or 'auto') only")
            self.decomp = "slab"
            self._init_slab(backend)
        elif decomp == "pencil":
            self.decomp = "pencil"
            self._init_pencil(backend, row_axis, col_axis)
        else:
            # auto: pencil when the WHOLE pencil plan validates (grid,
            # divisibility, per-axis backends), else slab -- a pinned
            # backend that only works under one decomposition steers the
            # choice instead of erroring
            if row_axis is not None:
                # explicitly configured grid axes are a user argument,
                # not an infeasibility signal: bad names must raise, not
                # silently fall back to slab
                from repro.core import grid as _grid

                _grid.grid_from_mesh(mesh, row_axis, col_axis)
            pencil_err: Optional[ValueError] = None
            if ndim in (2, 3) and not fuse_dft and not (ndim == 2 and transpose_back):
                try:
                    self.decomp = "pencil"
                    self._init_pencil(backend, row_axis, col_axis)
                except ValueError as e:
                    pencil_err = e
                    self.grid = None
                    self.decomp = None
            else:
                self.decomp = None
            if self.decomp == "pencil":
                # cost-aware tie-break: a structurally-valid pencil grid
                # can still lose to slab (a degenerate (P,1) grid doubles
                # the fft2 exchanges over the same ring). Adopt slab when
                # it keeps at least the same parallelism and its resolved
                # backend predicts cheaper than the pencil pair. The
                # trial shards over the larger of fft_axis and the grid
                # axes -- fft_axis's last-axis fallback would otherwise
                # pick a size-1 axis on e.g. a (P,1) ("rows","cols") mesh
                # and lose the comparison to a phantom parallelism gap
                trial_ax = axis_name
                if trial_ax is None:
                    candidates = (fft_axis(mesh), self.grid.row_axis, self.grid.col_axis)
                    trial_ax = max(candidates, key=lambda a: mesh.shape[a])
                try:
                    trial = Plan(
                        global_shape, mesh, ndim=ndim, direction=direction,
                        backend=backend, axis_name=trial_ax, local_impl=local_impl,
                        fuse_dft=fuse_dft, transpose_back=transpose_back, dtype=dtype,
                        params=params, chunk_compute_s=chunk_compute_s, decomp="slab",
                        real=real, pad=pad, pipeline=self.pipeline,
                    )
                except (ValueError, NotImplementedError):
                    trial = None
                if (
                    trial is not None
                    and trial.shards >= self.shards
                    and trial.predict()[trial.backend] < self.predict()[self.backend]
                ):
                    self.grid = None
                    self.axis_name = trial_ax
                    self.decomp = "slab"
                    self._init_slab(backend)
            if self.decomp is None:
                self.decomp = "slab"
                try:
                    self._init_slab(backend)
                except ValueError as e:
                    if pencil_err is not None:
                        raise ValueError(
                            f"decomp='auto': neither decomposition fits this "
                            f"problem -- pencil: {pencil_err} -- slab: {e}"
                        ) from e
                    raise
        self._cache: Dict[Tuple[str, str], jax.stages.Wrapped] = {}
        self.compiles = 0  # jit wrappers created (not per-shape recompiles)
        self.calls = 0  # execute/inverse calls, the id of each repro.execute span
        if local_impl == "pallas" and mesh.devices.flat[0].platform == "tpu":
            # the kernel tiles only some local FFT lengths (kernels/ops.py);
            # tracing the planned direction once raises for any other here,
            # naming the length, instead of at the first execute
            jax.eval_shape(self._fn(direction == "inverse"), self.input_spec())

    # -- pipelined overlap resolution -------------------------------------------
    def _pipeline_enabled(self) -> bool:
        """Whether ``pipeline=`` allows fusing at all (off only when the
        caller passed False/0)."""
        return self.pipeline not in (False, 0)

    def _pipeline_n_chunks(self) -> Optional[int]:
        if isinstance(self.pipeline, int) and not isinstance(self.pipeline, bool):
            return int(self.pipeline) if self.pipeline > 0 else None
        return None

    def _resolve_pipeline(self) -> None:
        """Resolve ``pipeline=`` against the selected backend(s): fused
        execution wherever a chunk-streaming backend rides a >1-shard
        ring (pencil legs resolve independently inside the transforms --
        ``fused`` here records whether ANY leg fuses, which is what the
        cost model overlaps)."""
        self.n_chunks = self._pipeline_n_chunks()
        if not self._pipeline_enabled():
            # explicit pipeline=False wins over the legacy fuse_dft alias
            # too (the config layer gets fuse_dft=False below), so one
            # knob disables fusion everywhere
            self.fused = False
            return
        if self.decomp == "pencil":
            legs = (
                (self.backend_row, self.grid.p_rows),
                (self.backend_col, self.grid.p_cols),
            )
            self.fused = any(
                backends.get(b).supports_chunk_fn and p > 1 for b, p in legs
            )
        else:
            b = self.backend_obj
            self.fused = bool(
                b.kind == "shard_map" and b.supports_chunk_fn and self.shards > 1
            )

    def _auto_chunk_compute_s(self, dtype=None) -> float:
        """Per-peer-chunk seconds of the fused stage's compute: the
        caller's ``chunk_compute_s`` when given, else a memory-bound
        napkin -- each arriving chunk's outer-product contribution
        writes one local block's worth of accumulator
        (``_cost_bytes / HBM_BW``). This is what lets ``predict()`` and
        ``backend='auto'`` price fused (overlapped) against unfused
        (serialized) stage compute without the user measuring anything.
        Zero when no exchange ring exceeds one shard -- there is no
        exchange to fuse into, and charging phantom per-chunk compute
        would skew degenerate-grid decomp='auto' comparisons."""
        if self.chunk_compute_s:
            return self.chunk_compute_s
        rings = (
            max(self.grid.p_rows, self.grid.p_cols)
            if self.decomp == "pencil"
            else self.shards
        )
        if rings <= 1:
            return 0.0
        return self._cost_bytes(dtype) / cm.HBM_BW

    def _init_slab(self, backend: str) -> None:
        self._schedules.clear()
        p = self.shards
        shape, ax = self.global_shape, self.axis_name
        if self.real:
            self.hermitian_len, self.padded_hermitian_len = sch.check_divisible(
                shape, self.ndim, p=p, axis_name=ax, real=True, pad=self.pad
            )
        else:
            sch.check_divisible(shape, self.ndim, p=p, axis_name=ax)

        if not isinstance(backend, str) or PAIR_SEP in backend:
            raise ValueError(
                f"slab plans take one backend name, got {backend!r} "
                f"(per-axis pairs are decomp='pencil')"
            )
        if backend == "auto":
            self.selection_channel = "model-argmin"
            backend = "scatter" if self.fuse_dft else backends.cheapest(
                self._cost_bytes(), p, self.params,
                chunk_compute_s=self._auto_chunk_compute_s(),
                n_chunks=self._pipeline_n_chunks(),
                fused=self._pipeline_enabled(),
            )
        self.backend_obj = backends.get(backend)  # raises listing the registry
        self.backend = backend
        self.backend_row = self.backend_col = None
        if not self.backend_obj.supports(p):
            raise ValueError(f"backend {backend!r} does not support P={p}")
        if self.fuse_dft and not self.backend_obj.supports_chunk_fn:
            raise ValueError(
                f"fuse_dft requires a chunk-streaming backend (got "
                f"{backend!r}; streaming: "
                f"{[b for b in backends.available() if backends.get(b).supports_chunk_fn]})"
            )
        self._resolve_pipeline()

        self._cfg = FFTConfig(
            strategy=backend,
            local_impl=self.local_impl,  # type: ignore[arg-type]
            # pipeline=False disables the legacy alias at the config
            # layer too, so the plan's fused flag IS the execution truth
            fuse_dft=self.fuse_dft and self._pipeline_enabled(),
            transpose_back=self.transpose_back,
            fused=self.fused,
            n_chunks=self.n_chunks,
        )

    def _init_pencil(self, backend, row_axis: Optional[str], col_axis: Optional[str]) -> None:
        from repro.core import grid as _grid
        from repro.core import pencil as _pencil

        if self.ndim == 1:
            raise ValueError("pencil decomposition supports ndim 2 or 3 (1-D is slab-only)")
        if self.fuse_dft:
            raise ValueError("fuse_dft is a slab scatter-only feature; use decomp='slab'")
        if self.ndim == 2 and self.transpose_back:
            raise ValueError(
                "pencil fft2 already returns the natural layout; "
                "transpose_back applies to slab plans and pencil fft3 only"
            )
        self._schedules.clear()
        self.grid = _grid.grid_from_mesh(self.mesh, row_axis, col_axis)
        g = self.grid
        if self.real:
            self.hermitian_len, self.padded_hermitian_len = sch.check_divisible(
                self.global_shape, self.ndim, p_rows=g.p_rows, p_cols=g.p_cols,
                row_axis=g.row_axis, col_axis=g.col_axis, real=True, pad=self.pad,
            )
        else:
            sch.check_divisible(
                self.global_shape, self.ndim, p_rows=g.p_rows, p_cols=g.p_cols,
                row_axis=g.row_axis, col_axis=g.col_axis,
            )

        if backend == "auto":
            self.selection_channel = "model-argmin"
            br, bc = backends.cheapest_pair(
                self._cost_bytes(),
                self.grid.p_rows,
                self.grid.p_cols,
                self.params,
                chunk_compute_s=self._auto_chunk_compute_s(),
                n_chunks=self._pipeline_n_chunks(),
                fused=self._pipeline_enabled(),
            )
        else:
            br, bc = split_pair(backend)
        self.backend_row, self.backend_col = br, bc
        self.backend = pair_key(br, bc)
        self.backend_obj = None  # per-axis backends; see backend_row/col
        self._resolve_pipeline()
        self._cfg = _pencil.PencilConfig(
            backend_row=br,
            backend_col=bc,
            local_impl=self.local_impl,  # type: ignore[arg-type]
            transpose_back=self.transpose_back,
            fused=self.fused,
            n_chunks=self.n_chunks,
        )
        _pencil._check_backends(self._cfg, self.grid)  # raises naming the axis

    # -- geometry --------------------------------------------------------------
    @property
    def shards(self) -> int:
        if self.decomp == "pencil":
            return self.grid.size
        return self.mesh.shape[self.axis_name]

    def local_bytes(self, dtype=None) -> float:
        """Bytes of one device's local block of the input (the real
        block, for a real plan)."""
        itemsize = self._dtype_pair(dtype)[0].itemsize if self.real else jnp.dtype(
            dtype or self.dtype
        ).itemsize
        return float(np.prod(self.global_shape)) * itemsize / self.shards

    def _dtype_pair(self, dtype=None) -> Tuple[jnp.dtype, jnp.dtype]:
        """(real, complex) dtype pair for a byte query: either side of
        the pair may be passed; None means the plan's own."""
        if dtype is None:
            return self.dtype, self.cdtype
        return real_complex_pair(dtype)

    def _cost_bytes(self, dtype=None) -> float:
        """Per-device block bytes the exchanges actually move -- the
        input block for c2c plans, the Hermitian-truncated (shard-padded)
        complex block for real plans. This is what feeds the alpha-beta
        costs and ``backend='auto'``."""
        if not self.real:
            return self.local_bytes(dtype)
        citem = self._dtype_pair(dtype)[1].itemsize
        elems = float(np.prod(self.global_shape[:-1])) * self.padded_hermitian_len
        return elems * citem / self.shards

    def _byte_sizes(self, dtype=None) -> Tuple[int, int]:
        """(real_itemsize, complex_itemsize) a byte/cost query prices the
        schedule's Exchange payloads with; either side of the r2c pair
        may be passed, None means the plan's own dtypes."""
        if self.real:
            r, c = self._dtype_pair(dtype)
            return r.itemsize, c.itemsize
        item = jnp.dtype(dtype or self.dtype).itemsize
        return item, item

    def comm_bytes(self, dtype=None) -> float:
        """Total bytes each device ships over the fabric per transform,
        summed over every Exchange stage of the plan's own schedule --
        each exchange re-shards its block over its ring (P for slab,
        P_row/P_col per sub-exchange for pencil), shipping (1-1/P_ring)
        of it. Same units under both decompositions, so slab-vs-pencil
        comparisons are direct.

        Real plans count the Hermitian payload: every complex exchange
        moves the truncated ``Hp`` block (~half the c2c bytes at the
        same shape); the pencil rfft2's first cols exchange moves the
        full-width block at the *real* dtype (also half). The c2r
        inverse mirrors the chain, so the total is direction-agnostic."""
        r_item, c_item = self._byte_sizes(dtype)
        return sch.schedule_comm_bytes(self.schedule(), r_item, c_item)

    # -- cost model ------------------------------------------------------------
    def predict(
        self,
        dtype=None,
        chunk_compute_s: Optional[float] = None,
        *,
        fused: Optional[bool] = None,
        n_chunks: Optional[int] = None,
    ) -> Dict[str, float]:
        """Alpha-beta predicted seconds per backend for this problem.

        Slab: ``n_exchanges * backend.cost(local_bytes, P, params,
        chunk_compute_s)`` for every registered backend that supports
        this shard count. Pencil: one entry per ``"row+col"`` pair of
        shard_map backends, each axis costed at its own sub-ring size
        (P_row / P_col) by :func:`repro.core.comm_model.t_pencil` --
        see :meth:`predict_axes` for the per-axis decomposition.

        ``chunk_compute_s`` (default: the plan's own, else the
        memory-bound stage estimate) is per-chunk compute;
        ``fused``/``n_chunks`` (default: the plan's own resolution)
        report the fused vs unfused variants of the same problem:
        ``fused=True`` overlaps the stage compute on streaming backends,
        ``fused=False`` serializes it everywhere (the monolithic
        discipline), so ``predict(fused=True)`` vs ``predict(fused=False)``
        is the modeled overlap win. Uses the plan's ``params`` -- pass a
        calibrated :meth:`~repro.core.comm_model.CommParams.calibrate`
        result at plan time for measured (rather than v5e napkin)
        constants."""
        fused = self.fused if fused is None else fused
        n_chunks = self.n_chunks if n_chunks is None else n_chunks
        if self.decomp == "pencil":
            row_costs, col_costs = self.predict_axes(
                dtype, chunk_compute_s, fused=fused, n_chunks=n_chunks
            )
            return {
                pair_key(r, c): row_costs[r] + col_costs[c]
                for r in row_costs
                for c in col_costs
            }
        cc = self._auto_chunk_compute_s(dtype) if chunk_compute_s is None else chunk_compute_s
        r_item, c_item = self._byte_sizes(dtype)
        base = sch.with_pipeline(self.schedule(), fused, n_chunks)
        p = self.shards
        out = {}
        for name in backends.available():
            if backends.get(name).supports(p):
                out[name] = sch.predict_seconds(
                    sch.with_backends(base, slab=name),
                    self.params, cc, r_item, c_item,
                )
        return out

    def predict_axes(
        self,
        dtype=None,
        chunk_compute_s: Optional[float] = None,
        *,
        fused: Optional[bool] = None,
        n_chunks: Optional[int] = None,
    ) -> Tuple[Dict[str, float], Dict[str, float]]:
        """Pencil only: (row_costs, col_costs) -- per-backend predicted
        seconds of all of this transform's exchanges over that grid axis,
        each at its own sub-ring size. ``predict()[f"{r}+{c}"] ==
        row_costs[r] + col_costs[c]`` by construction. ``fused`` /
        ``n_chunks`` as in :meth:`predict` (per-leg: a streaming backend
        overlaps its own axis's stage compute independently)."""
        if self.decomp != "pencil":
            raise ValueError("predict_axes is a pencil-plan method; use predict()")
        fused = self.fused if fused is None else fused
        n_chunks = self.n_chunks if n_chunks is None else n_chunks
        cc = self._auto_chunk_compute_s(dtype) if chunk_compute_s is None else chunk_compute_s
        r_item, c_item = self._byte_sizes(dtype)
        base = sch.with_pipeline(self.schedule(), fused, n_chunks)
        out = []
        for role, p_axis in (("row", self.grid.p_rows), ("col", self.grid.p_cols)):
            out.append({
                name: sch.predict_seconds(
                    sch.with_backends(base, **{role: name}),
                    self.params, cc, r_item, c_item, role,
                )
                for name in backends.supporting(p_axis, kind="shard_map")
            })
        return out[0], out[1]

    # -- sharding specs --------------------------------------------------------
    def _opposite_reverses_layout(self) -> bool:
        """Whether the opposite direction consumes the reversed-axes
        pencil layout (3-D pencil without transpose_back: the forward
        output is fftn reversed, sharded (cols, rows))."""
        return self.decomp == "pencil" and self.ndim == 3 and not self.transpose_back

    def _spectrum_side(self, opposite: bool) -> bool:
        """Real plans only: whether the (possibly opposite) direction's
        input is the half spectrum (the c2r side) rather than the real
        array."""
        return (self.direction == "inverse") != opposite

    def spectral_axes(self) -> Tuple[SpectralAxis, ...]:
        """The plan's frequency-domain layout: one :class:`SpectralAxis`
        per trailing output dim of the forward transform (equivalently,
        per trailing input dim of the inverse), in output order. Works
        for c2c and real plans -- the apps layer keys off it."""
        nd = self.ndim
        dims = self.global_shape[-nd:]
        natural = list(range(-nd, 0))
        if self.decomp == "pencil":
            order = natural if (nd == 2 or self.transpose_back) else natural[::-1]
        else:
            order = [-1, -2] if (nd == 2 and not self.transpose_back) else natural
        # output dims the decomposition keeps sharded: the Hermitian axis
        # must stay padded there (trimming would break divisibility)
        sharded = {0, 1} if self.decomp == "pencil" else ({0} if nd > 1 else set())
        axes = []
        for pos, orig in enumerate(order):
            n = dims[orig]
            half = self.real and orig == -1
            if half:
                n_out = self.padded_hermitian_len if pos in sharded else self.hermitian_len
            else:
                n_out = n
            axes.append(SpectralAxis(orig, n, n_out, half))
        return tuple(axes)

    def spectrum_shape(self) -> Tuple[int, ...]:
        """Global shape of the frequency-domain array (forward output /
        inverse input), batch dims included."""
        return self.global_shape[: -self.ndim] + tuple(a.n_out for a in self.spectral_axes())

    def input_sharding(self, opposite: bool = False) -> NamedSharding:
        """Sharding of the planned direction's input; ``opposite=True``
        gives the opposite direction's input (differs only when that
        direction consumes the reversed-axes pencil layout)."""
        nd = len(self.global_shape)
        spec = [None] * nd
        if self.decomp == "pencil":
            # shard the two leading transform dims over the grid; the
            # reversed layout arrives sharded (cols, rows)
            row, col = self.grid.row_axis, self.grid.col_axis
            if self.real:
                reversed_spectrum = self.ndim == 3 and not self.transpose_back
                if self._spectrum_side(opposite) and reversed_spectrum:
                    row, col = col, row
            elif opposite and self._opposite_reverses_layout():
                row, col = col, row
            spec[nd - self.ndim] = row
            spec[nd - self.ndim + 1] = col
        else:
            spec[nd - self.ndim] = self.axis_name  # shard the leading transform dim
        return NamedSharding(self.mesh, P(*spec))

    def input_spec(self, dtype=None, opposite: bool = False) -> jax.ShapeDtypeStruct:
        shape = self.global_shape
        if self.real:
            if self._spectrum_side(opposite):
                shape = self.spectrum_shape()
                dt = dtype or self.cdtype
            else:
                dt = dtype or self.dtype
            return jax.ShapeDtypeStruct(shape, dt, sharding=self.input_sharding(opposite))
        if opposite and self._opposite_reverses_layout():
            shape = shape[:-3] + tuple(reversed(shape[-3:]))
        return jax.ShapeDtypeStruct(
            shape, dtype or self.dtype, sharding=self.input_sharding(opposite)
        )

    # -- the stage schedule (the single pipeline truth) ------------------------
    def schedule(self, inverse: Optional[bool] = None) -> sch.Schedule:
        """The stage schedule the given direction executes (None: the
        planned direction) -- the declarative pipeline IR
        (:class:`repro.core.schedule.Schedule`) that ``execute`` runs,
        :meth:`predict`/:meth:`comm_bytes` walk, and the planner
        rewrites. Built once per direction and cached."""
        inv = (self.direction == "inverse") if inverse is None else bool(inverse)
        cached = self._schedules.get(inv)
        if cached is not None:
            return cached
        if self.ndim == 1 and inv:
            raise NotImplementedError("1-D large inverse: conjugate externally")
        if self.decomp == "pencil":
            grid, shape = self.grid, self.global_shape
            row, col = grid.row_axis, grid.col_axis
            pr, pc = grid.p_rows, grid.p_cols
            br, bc = self.backend_row, self.backend_col
            opposite = inv != (self.direction == "inverse")
            if not self.real and opposite and self._opposite_reverses_layout():
                # the opposite direction consumes the reversed-axes
                # output, sharded (cols, rows): swap the grid roles (and
                # the per-axis backends with them) so the transform
                # reads that sharding directly -- no hidden reshard, and
                # the forward divisibility constraints already imply the
                # reversed ones, so round trips always plan. (Real plans
                # never swap: each irfft consumes exactly the layout its
                # rfft produces -- an explicit reverse chain.)
                shape = shape[:-3] + tuple(reversed(shape[-3:]))
                row, col, pr, pc, br, bc = col, row, pc, pr, bc, br
            built = sch.build_schedule(
                shape, ndim=self.ndim, inverse=inv, real=self.real,
                decomp="pencil", row_axis=row, col_axis=col,
                p_rows=pr, p_cols=pc, backend_row=br, backend_col=bc,
                fused=self.fused, n_chunks=self.n_chunks,
                transpose_back=self.transpose_back, pad=self.pad,
            )
        else:
            built = sch.build_schedule(
                self.global_shape, ndim=self.ndim, inverse=inv,
                real=self.real, decomp="slab", axis_name=self.axis_name,
                # _cfg.strategy, not self.backend: a measured variant
                # winner reports its candidate id ("scatter@u") on
                # .backend, but the schedule carries the base name
                p=self.shards, backend=self._cfg.strategy,
                fused=self.fused or self._cfg.fuse_dft,
                n_chunks=self.n_chunks,
                transpose_back=self.transpose_back, pad=self.pad,
                transpose_first=sch.transposed_first_pays(
                    self.global_shape, self.ndim, self.local_impl
                ),
            )
        self._schedules[inv] = built
        return built

    def schedule_hash(self, inverse: Optional[bool] = None) -> str:
        """Content hash of the direction's stage schedule: two plans with
        equal hashes execute the same pipeline (serve pools record it)."""
        return self.schedule(inverse).schedule_hash()

    def predict_stages(self, inverse: Optional[bool] = None, dtype=None):
        """Per-stage cost decomposition: ``[(Exchange, predicted seconds,
        wire bytes), ...]`` over the direction's schedule at the plan's
        own backends and pipeline. The seconds sum to
        ``predict()[self.backend]`` and the bytes to :meth:`comm_bytes`
        -- the invariant the schedule tests pin."""
        r_item, c_item = self._byte_sizes(dtype)
        cc = self._auto_chunk_compute_s(dtype)
        base = sch.with_pipeline(self.schedule(inverse), self.fused, self.n_chunks)
        return [
            (
                st,
                sch.stage_seconds(st, self.params, cc, r_item, c_item),
                sch.exchange_wire_bytes(st, r_item, c_item),
            )
            for st in base.exchanges()
        ]

    def describe(self, inverse: Optional[bool] = None, dtype=None) -> str:
        """Human-readable stage dump of the direction's schedule with
        per-stage predicted microseconds and wire bytes (the
        observability hook; also ``benchmarks/run.py --explain``)."""
        r_item, c_item = self._byte_sizes(dtype)
        return self.schedule(inverse).describe(
            params=self.params,
            chunk_compute_s=self._auto_chunk_compute_s(dtype),
            real_itemsize=r_item,
            complex_itemsize=c_item,
        )

    def why(self) -> dict:
        """Decision provenance: *why this backend won* -- the selection
        channel (``pinned`` / ``model-argmin`` / ``measured-race`` /
        ``wisdom-hit`` / ``observed-overlay``), the timing table the
        decision argmin'd over (measured seconds for a measured plan,
        alpha-beta model seconds otherwise), the wisdom key consulted,
        the calibration constants in force (with whether they are
        fitted fabric constants or the module defaults), and whether the
        planned schedule is the one-shard transposed-first form
        (``transposed_first``). Rendered by
        :meth:`why_text`; dumped by ``benchmarks/run.py --explain``;
        aggregated as gauges in serve ``metrics()``."""
        from repro.core import planner as _planner

        if self.planner == "measure" and self.measured:
            # failed candidates carry timing inf -- keep them out of the
            # table and the argmin; they are reported under "failed"
            timings = {
                k: float(v)
                for k, v in self.measured.items()
                if isinstance(v, (int, float)) and math.isfinite(v)
            }
            timings_kind = "measured"
        else:
            timings = {k: float(v) for k, v in self.predict().items()}
            timings_kind = "model"
        argmin = min(sorted(timings), key=timings.__getitem__) if timings else None
        dev = _planner.device_kind(self.mesh)
        cell = _planner.calibration_cell(dev)
        return {
            "channel": self.selection_channel,
            "backend": self.backend,
            "decomp": self.decomp,
            "planner": self.planner,
            "fused": self.fused,
            "n_chunks": self.n_chunks,
            "transposed_first": self.schedule().transposed_first,
            "timings_kind": timings_kind,
            "timings": timings,
            "argmin": argmin,
            "failed": dict(self.race_failures),
            "wisdom_key": self.wisdom_key,
            "wisdom_hit": self.wisdom_hit,
            "calibration": {
                "device_kind": dev,
                "alpha_s": float(self.params.alpha_s),
                "beta_bytes_s": float(self.params.beta_bytes_s),
                "source": (cell or {}).get("source", "default"),
                "calibrated": cell is not None,
            },
        }

    def why_text(self) -> str:
        """One-paragraph rendering of :meth:`why` (the ``--explain``
        format): channel, winner, the top of the timing table, and the
        calibration constants in force."""
        w = self.why()
        cal = w["calibration"]
        unit = 1e6  # report microseconds either way
        table = sorted(w["timings"].items(), key=lambda kv: kv[1])
        shown = ", ".join(f"{k}={v * unit:.1f}us" for k, v in table[:4])
        if len(table) > 4:
            shown += f", ... ({len(table) - 4} more)"
        lines = [
            f"why: backend={w['backend']} via {w['channel']} "
            f"(decomp={w['decomp']}, planner={w['planner']})",
            f"  {w['timings_kind']} table argmin={w['argmin']}: {shown}"
            if table
            else "  (no timing table)",
            f"  calibration[{cal['device_kind']}]: alpha={cal['alpha_s'] * 1e6:.2f}us "
            f"beta={cal['beta_bytes_s'] / 1e9:.1f}GB/s "
            f"({cal['source'] if cal['calibrated'] else 'default'})",
        ]
        if w["failed"]:
            lines.append(
                "  failed candidates (excluded from argmin): "
                + ", ".join(f"{k} ({v})" for k, v in sorted(w["failed"].items()))
            )
        if w["wisdom_key"]:
            lines.append(f"  wisdom_key: {w['wisdom_key']}")
        return "\n".join(lines)

    def profile(
        self,
        x: Optional[jax.Array] = None,
        *,
        reps: int = 3,
        warmup: int = 1,
        inverse: Optional[bool] = None,
        trace=None,
        record: bool = True,
    ) -> "ProfileResult":
        """Execute the direction through the trace-mode (segmented)
        executor and return one *observed* row per schedule stage next
        to :meth:`predict_stages`' model -- the paper's comm-vs-compute
        breakdown, measured on this plan.

        ``x=None`` profiles a zeros input built from :meth:`input_spec`.
        Spans land in ``trace`` (a fresh
        :class:`repro.obs.trace.TraceRecorder` if None; the returned
        result keeps it for export). ``warmup`` untimed traced runs pay
        the per-segment compiles first, then ``reps`` timed runs are
        aggregated by median. ``record=True`` folds the total observed
        seconds into the planner's wisdom observed channel
        (:func:`repro.core.planner.record_observed`; a no-op unless this
        plan came from ``planner="measure"``).

        Profiling never touches the plan's cached untraced executables
        -- the jitted hot path compiles to exactly the same HLO before
        and after (pinned by a regression test). Segmented wall-clock
        time exceeds the fused execution (per-stage host fences defeat
        inter-stage overlap), so treat observed sums as an attribution
        of cost, not a throughput measurement."""
        from repro.obs.trace import TraceRecorder

        inv = (self.direction == "inverse") if inverse is None else bool(inverse)
        opposite = inv != (self.direction == "inverse")
        if x is None:
            spec = self.input_spec(opposite=opposite)
            x = jax.device_put(jnp.zeros(spec.shape, spec.dtype), spec.sharding)
        else:
            x = jnp.asarray(x)
        built = self.schedule(inv)
        rec = trace if trace is not None else TraceRecorder()
        for _ in range(max(0, warmup)):
            sch.run_schedule(
                x, built, self.mesh, impl=self.local_impl, trace=TraceRecorder()
            )
        per_rep = []
        for _ in range(max(1, reps)):
            m = rec.mark()
            sch.run_schedule(x, built, self.mesh, impl=self.local_impl, trace=rec)
            per_rep.append(rec.spans_since(m))
        preds = self.predict_stages(inv, x.dtype)
        rows = []
        k_ex = 0
        for pos, sp in enumerate(per_rep[0]):
            durs = sorted(spans[pos].dur for spans in per_rep)
            obs = durs[len(durs) // 2]
            pred_s = wire = None
            if sp.cat == "exchange":
                pred_s = preds[k_ex][1]
                wire = sp.args.get("wire_bytes")
                k_ex += 1
            rows.append(ProfileRow(
                index=int(sp.args.get("index", pos)),
                stage=sp.name,
                kind=str(sp.args.get("stage", type(sp).__name__)),
                observed_s=obs,
                predicted_s=pred_s,
                wire_bytes=wire,
                args=dict(sp.args),
            ))
        result = ProfileResult(
            rows=tuple(rows), schedule=built, trace=rec, reps=len(per_rep)
        )
        if record:
            from repro.core import planner

            planner.record_observed(self, result.observed_s)
        return result

    # -- execution -------------------------------------------------------------
    def _fn(self, inverse: bool):
        built = self.schedule(inverse)  # ndim=1 inverse raises here
        mesh, impl = self.mesh, self.local_impl
        return lambda x: sch.run_schedule(x, built, mesh, impl=impl)

    def _executable(self, inverse: bool, dtype) -> jax.stages.Wrapped:
        key = ("inverse" if inverse else "forward", jnp.dtype(dtype).name)
        fn = self._cache.get(key)
        if fn is None:
            fn = jax.jit(self._fn(inverse))
            self._cache[key] = fn
            self.compiles += 1
        return fn

    def _faults_armed(self) -> bool:
        return self.faults is not None and self.faults.active()

    def execute(self, x: jax.Array) -> jax.Array:
        """Run the planned direction through the cached executable (or,
        while a :attr:`faults` plan is armed, through the segmented
        chaos executor so injected failures fire deterministically)."""
        return self._run(x, self.direction == "inverse")

    def inverse(self, x: jax.Array) -> jax.Array:
        """Run the opposite of the planned direction. Not available for
        ``ndim=1`` (raises before executing anything -- see class doc)."""
        return self._run(x, self.direction != "inverse")

    def _run(self, x: jax.Array, inv: bool) -> jax.Array:
        """The host work of one call, from entry to the return of the
        jitted call, under the host span ``repro.execute`` numbered by
        :attr:`calls`."""
        self.calls += 1
        with obs.span(obs.EXECUTE, call=self.calls):
            x = jnp.asarray(x)
            if self._faults_armed():
                return sch.run_schedule(
                    x, self.schedule(inv), self.mesh,
                    impl=self.local_impl, faults=self.faults,
                )
            return self._executable(inv, x.dtype)(x)

    def executable_stats(self) -> Dict[Tuple[str, str], int]:
        """(direction, dtype) -> number of compiled specializations held
        by that cached executable (1 == no recompilation happened)."""
        stats = {}
        for key, fn in self._cache.items():
            try:
                stats[key] = fn._cache_size()
            except AttributeError:  # pragma: no cover - future jax
                stats[key] = 1
        return stats

    # -- analysis --------------------------------------------------------------
    def lower(self, inverse: Optional[bool] = None, dtype=None):
        """Abstract lowering for dry-run / roofline (no allocation).

        Goes through the same cached jit wrapper ``execute`` uses, so a
        later ``execute`` at this (direction, dtype) reuses the wrapper
        (and ``compiles`` counts it exactly once). Lowering the opposite
        direction uses that direction's actual input layout (the
        reversed-axes pencil output where applicable)."""
        inv = (self.direction == "inverse") if inverse is None else inverse
        opposite = inv != (self.direction == "inverse")
        spec = self.input_spec(dtype, opposite=opposite)
        # key the cache with the direction's ACTUAL input dtype (a real
        # plan's c2r side consumes the complex spectrum, not self.dtype),
        # so a later execute/inverse reuses this wrapper
        return self._executable(inv, spec.dtype).lower(spec)

    def roofline(self, inverse: Optional[bool] = None) -> cm.Roofline:
        """Compile abstractly and derive the three roofline terms from
        the scheduled HLO (loop-aware collective accounting)."""
        from repro.core import hlo_analysis

        compiled = self.lower(inverse).compile()
        cost = hlo_analysis.analyze_compiled(compiled, default_group=self.shards)
        return cm.Roofline(
            flops=cost.flops,
            hbm_bytes=cost.hbm_bytes,
            coll_bytes=cost.coll_bytes,
            chips=int(self.mesh.size),
        )

    def __repr__(self) -> str:
        where = (
            f"grid={self.grid.p_rows}x{self.grid.p_cols}"
            if self.decomp == "pencil"
            else f"P={self.shards}"
        )
        kind = "r2c" if self.real else "c2c"
        return (
            f"Plan({kind}, shape={self.global_shape}, ndim={self.ndim}, "
            f"decomp={self.decomp!r}, {where}, "
            f"backend={self.backend!r}, direction={self.direction!r}, "
            f"dtype={self.dtype.name})"
        )


@dataclasses.dataclass(frozen=True)
class ProfileRow:
    """One schedule stage's observed wall-clock vs model prediction.
    ``predicted_s``/``wire_bytes`` are None for non-Exchange stages (the
    alpha-beta model prices exchanges; local compute has no model row).
    ``args`` is the span's full attribute payload (backend, role, p,
    fused, n_chunks, ... for exchanges)."""

    index: int
    stage: str
    kind: str
    observed_s: float
    predicted_s: Optional[float] = None
    wire_bytes: Optional[float] = None
    args: Dict[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class ProfileResult:
    """``Plan.profile`` output: per-stage rows + the recorder holding
    the raw spans (exportable via ``result.trace.write_chrome_trace``)."""

    rows: Tuple[ProfileRow, ...]
    schedule: sch.Schedule
    trace: object
    reps: int

    @property
    def observed_s(self) -> float:
        return sum(r.observed_s for r in self.rows)

    @property
    def exchange_observed_s(self) -> float:
        return sum(r.observed_s for r in self.rows if r.kind == "Exchange")

    @property
    def predicted_s(self) -> float:
        return sum(r.predicted_s or 0.0 for r in self.rows)

    def exchange_rows(self) -> Tuple[ProfileRow, ...]:
        return tuple(r for r in self.rows if r.kind == "Exchange")

    def table(self) -> str:
        """The observed-vs-predicted stage table (README's worked
        example renders this)."""
        s = self.schedule
        head = (
            f"profile {s.kind} [{s.decomp}"
            f"{', r2c' if s.real else ''}{', inverse' if s.inverse else ''}] "
            f"shape={s.global_shape} hash={s.schedule_hash()} reps={self.reps}"
        )
        lines = [head]
        lines.append(
            f"  {'#':>2}  {'stage':<52} {'observed us':>12} {'model us':>10} "
            f"{'wire bytes':>12}"
        )
        for r in self.rows:
            pred = f"{r.predicted_s * 1e6:.2f}" if r.predicted_s is not None else "-"
            wire = f"{r.wire_bytes:.0f}" if r.wire_bytes is not None else "-"
            lines.append(
                f"  {r.index:>2}  {r.stage:<52} {r.observed_s * 1e6:>12.2f} "
                f"{pred:>10} {wire:>12}"
            )
        lines.append(
            f"  total observed {self.observed_s * 1e6:.2f} us "
            f"(exchanges {self.exchange_observed_s * 1e6:.2f} us, "
            f"model {self.predicted_s * 1e6:.2f} us)"
        )
        return "\n".join(lines)

    def __str__(self) -> str:
        return self.table()


def plan_fft(
    global_shape: Tuple[int, ...],
    mesh: Mesh,
    *,
    ndim: int = 2,
    direction: str = "forward",
    backend: str = "auto",
    axis_name: Optional[str] = None,
    local_impl: str = "jnp",
    fuse_dft: bool = False,
    transpose_back: bool = False,
    dtype=jnp.complex64,
    params: Optional[cm.CommParams] = None,
    chunk_compute_s: float = 0.0,
    planner: str = "estimate",
    timer=None,
    use_wisdom: bool = True,
    decomp: str = "slab",
    row_axis: Optional[str] = None,
    col_axis: Optional[str] = None,
    real: bool = False,
    pad: bool = True,
    pipeline="auto",
    faults=None,
) -> Plan:
    """Plan a distributed FFT (the FFTW ``plan`` analogue).

    ``pipeline`` controls the pipelined overlap executor -- whether each
    exchange streams its chunks and fuses the following FFT stage into
    their flight time (the paper's HPX-futures overlap, as dataflow):

    ``"auto"`` (default)
        Chunk-streamed, compute-fused exchanges wherever the selected
        backend streams (``supports_chunk_fn``) over a >1-shard ring;
        one chunk per peer. Monolithic backends are unaffected.
    ``int n``
        Fused, with the streamed chunk count decoupled from P: each
        peer block is sub-chunked toward ``n`` total chunks per
        exchange, so flight time amortizes even at small P (and the
        per-arrival compute grain shrinks). ``Plan.n_chunks`` records
        it; the executed sub-chunk count additionally snaps to a
        divisor of the peer block rows.
    ``False`` (or ``0``)
        Disable: plain transpose + whole-axis local FFT, the
        pre-pipeline behavior (what the ``overlap`` benchmark calls the
        unfused monolithic run).

    ``Plan.predict(fused=..., n_chunks=...)`` reports the model's fused
    vs unfused cost for the same problem.

    ``real=True`` plans the r2c/c2r pair (:mod:`repro.core.real`):
    ``execute`` computes the distributed ``rfftn`` of a real array (and
    ``inverse`` the matching ``irfftn``; ``direction="inverse"`` swaps
    the two), every exchange after the local r2c pass shipping only the
    Hermitian-truncated ``N//2+1`` payload -- ~half the c2c wire bytes
    at the same shape. ``dtype`` is then the real input dtype
    (float32/float64; the complex default maps to its real partner).
    The ``N//2+1`` axis rarely divides the shard count: ``pad=True``
    (default) zero-pads it to the next divisible length (recorded as
    ``Plan.padded_hermitian_len``, trimmed wherever the axis lands
    local -- see the module docs for the per-layout contract);
    ``pad=False`` raises at plan time naming the offending axis.

    ``decomp`` picks the process decomposition:

    ``"slab"`` (default)
        One sharded data dim over one mesh axis (``axis_name``, the
        paper's layout): parallelism caps at P <= N, one global exchange
        over all P ranks per transpose.
    ``"pencil"``
        Two sharded data dims over a 2-D process grid (``row_axis`` /
        ``col_axis``, conventionally ``("rows", "cols")`` -- see
        :mod:`repro.core.grid`): each transpose is a sub-axis exchange
        over only P_row or P_col ranks, and each axis gets its own
        backend -- pass ``backend=("scatter", "bisection")`` (or the
        ``"scatter+bisection"`` pair key) to pin, ``backend="auto"``
        for the per-axis cost-model argmin. ndim 2 or 3.
    ``"auto"``
        Pencil whenever the mesh offers a valid 2-D grid for this
        shape/ndim AND the cost model does not predict a slab plan of at
        least equal parallelism to be strictly cheaper (a degenerate
        (P,1) grid, for example, doubles the fft2 exchanges over the
        same ring, so slab wins it); else slab.

    ``planner`` picks the selection discipline (FFTW's ESTIMATE/MEASURE):

    ``"estimate"`` (default)
        ``backend="auto"`` = alpha-beta cost-model argmin over every
        registered backend supporting this shard count (per grid axis at
        its own sub-ring size under pencil) -- the same set (and costs)
        ``Plan.predict()`` ranks. Pass a
        :meth:`CommParams.calibrate <repro.core.comm_model.CommParams.calibrate>`
        result as ``params`` to estimate with measured constants.
    ``"measure"``
        Times every candidate backend (every per-axis pair, under
        pencil) on the real mesh (warmup + median) and pins the measured
        argmin; per-candidate timings land on ``Plan.measured``.
        Consults the wisdom store first (:mod:`repro.core.planner`) --
        keys carry the decomposition, grid shape and per-axis backend
        pair -- so a second identical plan never re-measures;
        ``use_wisdom=False`` forces re-measurement and
        ``timer(plan) -> seconds`` replaces the real clock (tests).

    Pass any name from ``repro.core.backends.available()`` as
    ``backend=`` to pin the backend under either planner. ``faults=``
    installs a chaos hook (:class:`repro.runtime.faults.FaultPlan`) on
    the returned plan: while armed, execute/inverse consult it before
    every Exchange stage (see :attr:`Plan.faults`).
    """
    if real and fuse_dft:
        fuse_dft = _warn_real_fuse_dft()
    if planner not in ("estimate", "measure"):
        raise ValueError(f"planner must be 'estimate' or 'measure', got {planner!r}")
    if planner == "estimate" and (timer is not None or use_wisdom is not True):
        # a forgotten planner="measure" would otherwise silently fall back
        # to model-based selection with the injected timer never called
        raise ValueError("timer= and use_wisdom= require planner='measure'")
    if planner == "measure":
        from repro.core import planner as _planner

        plan = _planner.plan_measured(
            global_shape,
            mesh,
            ndim=ndim,
            direction=direction,
            backend=backend,
            axis_name=axis_name,
            local_impl=local_impl,
            fuse_dft=fuse_dft,
            transpose_back=transpose_back,
            dtype=dtype,
            params=params,
            chunk_compute_s=chunk_compute_s,
            timer=timer,
            use_wisdom=use_wisdom,
            decomp=decomp,
            row_axis=row_axis,
            col_axis=col_axis,
            real=real,
            pad=pad,
            pipeline=pipeline,
        )
        plan.faults = faults
        return plan
    plan = Plan(
        global_shape,
        mesh,
        ndim=ndim,
        direction=direction,
        backend=backend,
        axis_name=axis_name,
        local_impl=local_impl,
        fuse_dft=fuse_dft,
        transpose_back=transpose_back,
        dtype=dtype,
        params=params,
        chunk_compute_s=chunk_compute_s,
        decomp=decomp,
        row_axis=row_axis,
        col_axis=col_axis,
        real=real,
        pad=pad,
        pipeline=pipeline,
    )
    plan.faults = faults
    return plan


# ---------------------------------------------------------------------------
# Legacy shims (one release): FFTPlan dataclass + make_plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FFTPlan:
    """Deprecated: thin shim over :class:`Plan` preserving the old field
    layout. Use :func:`plan_fft` instead."""

    global_shape: Tuple[int, ...]
    mesh: Mesh
    axis_name: str
    cfg: FFTConfig = FFTConfig()
    ndim_transform: int = 2

    def __post_init__(self):
        plan = Plan(
            self.global_shape,
            self.mesh,
            ndim=self.ndim_transform,
            backend=self.cfg.strategy,
            axis_name=self.axis_name,
            local_impl=self.cfg.local_impl,
            fuse_dft=self.cfg.fuse_dft,
            transpose_back=self.cfg.transpose_back,
        )
        object.__setattr__(self, "_plan", plan)

    def input_sharding(self) -> NamedSharding:
        return self._plan.input_sharding()

    def input_spec(self, dtype=jnp.complex64) -> jax.ShapeDtypeStruct:
        return self._plan.input_spec(dtype)

    def execute(self, x: jax.Array) -> jax.Array:
        return self._plan.execute(x)

    def inverse(self, x: jax.Array) -> jax.Array:
        return self._plan.inverse(x)

    def lower(self, inverse: bool = False):
        return self._plan.lower(inverse)

    def comm_bytes(self, dtype=jnp.complex64) -> float:
        return self._plan.comm_bytes(dtype)


def make_plan(
    global_shape: Tuple[int, ...],
    mesh: Mesh,
    *,
    axis_name: Optional[str] = None,
    strategy: str = "alltoall",
    local_impl: str = "jnp",
    fuse_dft: bool = False,
    transpose_back: bool = False,
    ndim_transform: int = 2,
) -> FFTPlan:
    """Deprecated: use :func:`plan_fft` (``strategy`` -> ``backend``,
    ``ndim_transform`` -> ``ndim``)."""
    from repro.core.sharding import fft_axis

    warnings.warn(
        "make_plan is deprecated; use repro.core.plan_fft(shape, mesh, "
        "ndim=..., backend=...) instead",
        DeprecationWarning,
        stacklevel=2,
    )
    return FFTPlan(
        global_shape=tuple(global_shape),
        mesh=mesh,
        axis_name=axis_name or fft_axis(mesh),
        cfg=FFTConfig(
            strategy=strategy,
            local_impl=local_impl,  # type: ignore[arg-type]
            fuse_dft=fuse_dft,
            transpose_back=transpose_back,
        ),
        ndim_transform=ndim_transform,
    )
