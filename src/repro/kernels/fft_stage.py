"""Pallas TPU kernel: fused complex DFT-matmul + twiddle stage.

This is the compute hot-spot of the matmul-formulated local FFT
(core/local_fft.py): one four-step stage computes

    left  mode:  out = (W @ A) * T        (column DFT + twiddle, fused)
    right mode:  out = A @ W^T            (row DFT; final stage, T = 1)

with complex operands stored as separate (re, im) f32 planes -- the TPU
MXU has no complex type, so the complex product is lowered to the
3-matmul Karatsuba form:

    p1 = Wr@Ar;  p2 = Wi@Ai;  p3 = (Wr+Wi)@(Ar+Ai)
    re = p1 - p2;  im = p3 - p1 - p2

saving 25% of MXU work vs. the naive 4-matmul form. The twiddle multiply
(elementwise complex) runs on the VPU over the same VMEM-resident tile,
so the stage never round-trips the intermediate through HBM -- that
fusion is the kernel's reason to exist.

Blocking: grid (B, M/bm, N/bn); the contraction dim K (the DFT radix,
<= MAX_DFT = 512) stays whole inside a block, so no accumulation loop is
needed and every dot hits the MXU with K >= 128. VMEM per step at the
default bm=bn=128, K=512: 2*(bm*K + K*bn + 2*bm*bn + bm*bn)*4B ~ 1.3 MiB,
double-buffered ~2.6 MiB. Every kernel here is sized against the 16 MiB
default scoped-VMEM limit that Mosaic gives a kernel on TPU v5e (the
chip has 128 MiB of VMEM in all); ``chunk_twiddle_pack_c64`` tiles its
chunk for the same reason.

Interpret mode is decided in one place, :func:`_pallas`: each call is
staged for both modes and ``lax.platform_dependent`` keeps the compiled
kernel when the program is lowered for a TPU and the Pallas interpreter
for every other platform. No caller chooses, so nothing can run the
interpreter on a TPU.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows/columns of one ``chunk_twiddle_pack_c64`` tile: the double-buffered
#: (tc, p, tr) output planes (p pads to 8 sublanes) stay ~2 MiB at p <= 8
CHUNK_TILE = 128


def _bs(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _out(shape, *like):
    """Planar-f32 output struct carrying the union of the inputs' varying
    mesh axes, so the kernels type-check inside ``shard_map``."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)


def _pallas(kernel, args, **spec):
    """``pl.pallas_call(kernel, **spec)(*args)``: compiled by Mosaic when
    lowered for a TPU, run by the Pallas interpreter on any other
    platform (the CPU rehearsals). The only place ``interpret`` is set.

    The interpreter slices varying blocks with unvarying grid indices,
    which ``shard_map``'s vma check refuses; schedules that run these
    kernels therefore use ``check_vma=False``
    (:func:`repro.core.schedule.run_schedule`)."""

    def call(interpret):
        return lambda *a: pl.pallas_call(kernel, interpret=interpret, **spec)(*a)

    return lax.platform_dependent(*args, tpu=call(False), default=call(True))


def _karatsuba(wr, wi, ar, ai):
    """(wr + i*wi) @ (ar + i*ai) via 3 real matmuls, f32 accumulate. The
    MXU runs f32 operands at full precision only when asked: a bf16 pass
    would cost the transform three decimal digits."""
    dot = functools.partial(
        jnp.dot, preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST
    )
    p1 = dot(wr, ar)
    p2 = dot(wi, ai)
    p3 = dot(wr + wi, ar + ai)
    return p1 - p2, p3 - p1 - p2


def _stage_left_kernel(wr_ref, wi_ref, ar_ref, ai_ref, tr_ref, ti_ref, or_ref, oi_ref):
    """out[b, m, n] = sum_k W[m, k] A[b, k, n] * T[m, n] (complex)."""
    wr, wi = wr_ref[...], wi_ref[...]
    ar, ai = ar_ref[0], ai_ref[0]
    re, im = _karatsuba(wr, wi, ar, ai)
    tr, ti = tr_ref[...], ti_ref[...]
    or_ref[0] = re * tr - im * ti
    oi_ref[0] = re * ti + im * tr


def _stage_right_kernel(wr_ref, wi_ref, ar_ref, ai_ref, or_ref, oi_ref):
    """out[b, m, n] = sum_k A[b, m, k] W[n, k]  (complex, no twiddle)."""
    # A @ W^T == (W @ A^T)^T; keep operands MXU-shaped via dot on transposes.
    wr, wi = wr_ref[...], wi_ref[...]
    ar, ai = ar_ref[0], ai_ref[0]
    re_t, im_t = _karatsuba(wr, wi, ar.T, ai.T)
    or_ref[0] = re_t.T
    oi_ref[0] = im_t.T


def stage_left(
    w: Tuple[jax.Array, jax.Array],
    a: Tuple[jax.Array, jax.Array],
    t: Tuple[jax.Array, jax.Array],
    *,
    bm: int = 128,
    bn: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """Fused (W @ A) * T over planar-complex operands.

    w: (M, K) re/im;  a: (B, K, N) re/im;  t: (M, N) re/im -> (B, M, N).
    """
    wr, wi = w
    ar, ai = a
    tr, ti = t
    B, K, N = ar.shape
    M = wr.shape[0]
    bm = min(bm, M)
    bn = min(bn, N)
    if M % bm or N % bn:
        raise ValueError(f"(M={M}, N={N}) must tile by (bm={bm}, bn={bn})")
    out = _out((B, M, N), ar, ai)
    return tuple(_pallas(
        _stage_left_kernel,
        (wr, wi, ar, ai, tr, ti),
        grid=(B, M // bm, N // bn),
        in_specs=[
            _bs((bm, K), lambda b, i, j: (i, 0)),  # W re
            _bs((bm, K), lambda b, i, j: (i, 0)),  # W im
            _bs((1, K, bn), lambda b, i, j: (b, 0, j)),  # A re
            _bs((1, K, bn), lambda b, i, j: (b, 0, j)),  # A im
            _bs((bm, bn), lambda b, i, j: (i, j)),  # T re
            _bs((bm, bn), lambda b, i, j: (i, j)),  # T im
        ],
        out_specs=[
            _bs((1, bm, bn), lambda b, i, j: (b, i, j)),
            _bs((1, bm, bn), lambda b, i, j: (b, i, j)),
        ],
        out_shape=[out, out],
    ))


def _chunk_twiddle_pack_kernel(cr_ref, ci_ref, mr_ref, mi_ref, or_ref, oi_ref):
    """out[b, j, k, t] = chunk[b, t, j] * m[k, t] (complex, planar).

    One launch fuses the per-arrival work of the pipelined overlap
    executor's chunk callback: the (rows, c) -> (c, rows) relayout of the
    received chunk AND the W_P-column x twiddle broadcast multiply that
    spreads it across the k1 dimension -- previously a transpose copy
    plus a separate elementwise multiply, each round-tripping the chunk
    through memory."""
    cr, ci = cr_ref[0], ci_ref[0]  # (rows, c)
    mr, mi = mr_ref[...], mi_ref[...]  # (p, rows)
    ctr, cti = cr.T, ci.T  # (c, rows) -- the pack, in-register
    a = ctr[:, None, :]  # (c, 1, rows)
    b = cti[:, None, :]
    or_ref[0] = a * mr[None] - b * mi[None]  # (c, p, rows)
    oi_ref[0] = a * mi[None] + b * mr[None]


def chunk_twiddle_pack_c64(chunk: jax.Array, m: jax.Array) -> jax.Array:
    """Fused twiddle+pack for one arriving exchange chunk (complex64).

    ``chunk``: (..., rows, c) -- the raw received piece (rows of the
    source block x my column block); ``m``: (p, rows) -- the W_P column
    for this source times the four-step twiddle slice for these rows.
    Returns (..., c, p, rows): the chunk's contribution to the fused
    DFT stage's accumulator (see
    :func:`repro.core.transpose.transpose_then_fft`), computed in a
    single kernel launch instead of a relayout copy + twiddle multiply.

    The grid tiles rows and columns by :data:`CHUNK_TILE` where they
    divide (a whole axis otherwise), so one step's blocks fit scoped
    VMEM at any chunk size: at 16384^2 over P=4 a chunk is 4096 x 4096
    and its output 4x that, far beyond VMEM as a single block.
    """
    if chunk.dtype != jnp.complex64 or m.dtype != jnp.complex64:
        raise ValueError(
            f"chunk_twiddle_pack_c64 is a planar-f32 kernel; got "
            f"{chunk.dtype}/{m.dtype} (c128 callers use the jnp path)"
        )
    lead = chunk.shape[:-2]
    rows, c = chunk.shape[-2:]
    p = m.shape[0]
    if m.shape != (p, rows):
        raise ValueError(f"m must be (p, rows)=({p}, {rows}), got {m.shape}")
    flat = chunk.reshape((-1, rows, c))
    B = flat.shape[0]
    cr, ci = jnp.real(flat), jnp.imag(flat)
    mr, mi = jnp.real(m), jnp.imag(m)
    tr = CHUNK_TILE if rows % CHUNK_TILE == 0 else rows
    tc = CHUNK_TILE if c % CHUNK_TILE == 0 else c
    out = _out((B, c, p, rows), cr, ci, mr, mi)
    o_re, o_im = _pallas(
        _chunk_twiddle_pack_kernel,
        (cr, ci, mr, mi),
        grid=(B, c // tc, rows // tr),
        in_specs=[
            _bs((1, tr, tc), lambda b, i, j: (b, j, i)),
            _bs((1, tr, tc), lambda b, i, j: (b, j, i)),
            _bs((p, tr), lambda b, i, j: (0, j)),
            _bs((p, tr), lambda b, i, j: (0, j)),
        ],
        out_specs=[
            _bs((1, tc, p, tr), lambda b, i, j: (b, i, 0, j)),
            _bs((1, tc, p, tr), lambda b, i, j: (b, i, 0, j)),
        ],
        out_shape=[out, out],
    )
    out = jax.lax.complex(o_re, o_im)  # complex64 even under x64
    return out.reshape(lead + (c, p, rows))


def stage_right(
    a: Tuple[jax.Array, jax.Array],
    w: Tuple[jax.Array, jax.Array],
    *,
    bm: int = 128,
    bn: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """A @ W^T over planar-complex operands.

    a: (B, M, K) re/im;  w: (N, K) re/im -> (B, M, N).
    """
    ar, ai = a
    wr, wi = w
    B, M, K = ar.shape
    N = wr.shape[0]
    bm = min(bm, M)
    bn = min(bn, N)
    if M % bm or N % bn:
        raise ValueError(f"(M={M}, N={N}) must tile by (bm={bm}, bn={bn})")
    out = _out((B, M, N), ar, ai)
    return tuple(_pallas(
        _stage_right_kernel,
        (wr, wi, ar, ai),
        grid=(B, M // bm, N // bn),
        in_specs=[
            _bs((bn, K), lambda b, i, j: (j, 0)),  # W re (rows = output cols)
            _bs((bn, K), lambda b, i, j: (j, 0)),  # W im
            _bs((1, bm, K), lambda b, i, j: (b, i, 0)),  # A re
            _bs((1, bm, K), lambda b, i, j: (b, i, 0)),  # A im
        ],
        out_specs=[
            _bs((1, bm, bn), lambda b, i, j: (b, i, j)),
            _bs((1, bm, bn), lambda b, i, j: (b, i, j)),
        ],
        out_shape=[out, out],
    ))
