"""Distributed transpose strategies -- the paper's experimental axis.

The FFT pencil exchange moves chunk *i* of every node's local block to
node *i* (each node keeps 1/P and ships (1-1/P) of its data). The paper
realizes this with either one synchronized ``all-to-all`` or with N
``scatter`` collectives that let arriving chunks be transposed while the
rest of the communication is still in flight.

TPU adaptation (see DESIGN.md #2): the switchable "parcelport" becomes a
switchable *collective lowering strategy* over the fixed ICI fabric:

``alltoall``
    One fused ``jax.lax.all_to_all`` -- the paper's synchronized baseline.
``scatter``
    P-1 direct ``ppermute`` sends (a ring walk over distances 1..P-1).
    The per-chunk callback runs as soon as chunk *k* lands, so XLA's
    async collective-permute overlaps step k+1's communication with
    chunk k's compute -- the paper's N-scatter overlap, as dataflow.
``bisection``
    Bruck / hypercube exchange: ceil(log2 P) rounds of half-the-buffer
    messages. Fewer, larger messages -- wins when per-message latency
    (the paper's TCP-overhead regime, Fig. 3) dominates. Beyond-paper.

**Pipelining (``n_chunks``).** The streaming exchanges decouple the chunk
count from P: each peer block can be sub-chunked into ``q`` pieces so the
exchange ships ``(P-1)*q`` smaller messages. Every send still uses a
pre-existing slice of the input (double buffering as dataflow: no send
depends on any chunk_fn result), so sub-chunk t's compute hides behind
sub-chunk t+1's flight -- even at P=2, where the classic per-peer
streaming has a single round and nothing to overlap.

**Compute fusion.** :func:`transpose_then_fft` folds the *next FFT
pass* into the exchange on streaming backends: the length-R DFT after a
transpose decomposes over source ranks (decimation in time, j = src*r +
j2), so each arriving chunk contributes a rank-1 outer product with one
DFT-matrix column -- cheap, and fully overlapped with the remaining
sends. Monolithic backends fall back to transpose + local FFT.

All strategies are SPMD-uniform (masks/permutations do not branch on the
device id except through ``lax.axis_index`` arithmetic) and are validated
against each other and a numpy routing simulation in tests.

Inside ``shard_map`` the local block is ``(..., r, C)`` where the global
rows ``R = P*r`` are sharded over ``axis_name``; the transposed result is
``(..., c, R)`` with the global columns ``C = P*c`` now sharded.

Layer scopes (:mod:`repro.obs.trace`): the collective call alone runs
under ``repro.exchange``; the local transposes, packs and unpacks
around it under ``repro.relayout``; a chunk_fn scopes its own work
(the fused DFT's per-chunk compute is ``repro.local_fft``).
"""

from __future__ import annotations

import inspect
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.compat import axis_size as _axis_size
from repro.obs import trace as obs

#: A registered backend name (see ``repro.core.backends.available()``).
#: Plain ``str`` on purpose: the registry, not a hand-kept enumeration,
#: defines the valid set.
Strategy = str

#: chunk_fn(chunk, src) -> processed chunk. ``chunk`` is the
#: (..., r, c) block received from shard ``src_index``, already transposed
#: to (..., c, r) when ``pre_transposed`` -- see _scatter below. A
#: chunk_fn may instead take (chunk, src, offset): under sub-chunked
#: pipelining it then receives each (..., c, r/q) piece as it arrives,
#: with ``offset`` the (static) starting index within the source block's
#: r rows -- position-dependent fusions (twiddles, DFT columns) stay
#: correct per sub-chunk. Two-argument chunk_fns are only ever handed
#: whole peer blocks (sub-chunking then pipelines the transport alone).
ChunkFn = Callable[..., jax.Array]


def _split_chunks(x: jax.Array, p: int) -> jax.Array:
    """(..., r, C) -> (p, ..., r, c): chunk j holds columns [j*c, (j+1)*c)."""
    *lead, r, C = x.shape
    c = C // p
    with obs.layer(obs.RELAYOUT):
        x = x.reshape(*lead, r, p, c)
        return jnp.moveaxis(x, -2, 0)


def _merge_rows(chunks: jax.Array) -> jax.Array:
    """(p, ..., r, c) -> (..., p*r, c): stack chunk j as rows [j*r, (j+1)*r)."""
    p = chunks.shape[0]
    with obs.layer(obs.RELAYOUT):
        chunks = jnp.moveaxis(chunks, 0, -3)  # (..., p, r, c)
        *lead, _, r, c = chunks.shape
        return chunks.reshape(*lead, p * r, c)


def _transpose_local(x: jax.Array) -> jax.Array:
    with obs.layer(obs.RELAYOUT):
        return jnp.swapaxes(x, -1, -2)


def _ppermute(x: jax.Array, axis_name: str, perm) -> jax.Array:
    with obs.layer(obs.EXCHANGE):
        return lax.ppermute(x, axis_name, perm)


# ---------------------------------------------------------------------------
# Pipelining helpers
# ---------------------------------------------------------------------------


def subchunks_per_peer(r: int, p: int, n_chunks: Optional[int]) -> int:
    """Sub-chunks q per peer block for an ``n_chunks`` total-chunk target:
    the largest divisor of ``r`` (the peer block's row count) not above
    ceil(n_chunks / p). ``None`` or ``n_chunks <= p`` keeps the classic
    one-chunk-per-peer schedule. Shared by the exchanges and the cost
    model (:func:`repro.core.comm_model.effective_chunks`) so the modeled
    message count is the executed one."""
    if not n_chunks or n_chunks <= p:
        return 1
    q = min(max(1, -(-int(n_chunks) // p)), r)
    while r % q:
        q -= 1
    return q


def _chunk_fn_arity(fn: ChunkFn) -> int:
    """2 when ``fn`` takes (chunk, src), 3 when it also takes the
    sub-chunk row offset (see :data:`ChunkFn`)."""
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):  # builtins / exotic callables
        return 2
    n = 0
    for prm in sig.parameters.values():
        if prm.kind == inspect.Parameter.VAR_POSITIONAL:
            return 3
        if prm.kind in (
            inspect.Parameter.POSITIONAL_ONLY,
            inspect.Parameter.POSITIONAL_OR_KEYWORD,
        ):
            n += 1
    return 3 if n >= 3 else 2


def _call_chunk_fn(fn: ChunkFn, arity: int, chunk, src, offset: int):
    if arity >= 3:
        return fn(chunk, src, offset)
    return fn(chunk, src)


# ---------------------------------------------------------------------------
# Strategy: fused all-to-all (the paper's synchronized collective)
# ---------------------------------------------------------------------------


def _alltoall(x: jax.Array, axis_name: str) -> jax.Array:
    # (..., r, C) --split cols/concat rows--> (..., R, c) --local T--> (..., c, R)
    with obs.layer(obs.EXCHANGE):
        y = lax.all_to_all(
            x, axis_name, split_axis=x.ndim - 1, concat_axis=x.ndim - 2, tiled=True
        )
    return _transpose_local(y)


# ---------------------------------------------------------------------------
# Strategy: N-scatter ring (the paper's proposed decomposition)
# ---------------------------------------------------------------------------


def _chunked_exchange(
    x: jax.Array,
    axis_name: str,
    chunk_fn: Optional[ChunkFn],
    schedule,
    n_chunks: Optional[int] = None,
) -> jax.Array:
    """Shared chunk-streaming exchange: P-1 peer rounds, each shipped as
    ``q`` sub-chunk messages (``q`` from :func:`subchunks_per_peer`).

    ``schedule(me, s, p)`` defines round s: the static ppermute ``perm``,
    the chunk slot this rank ships, and the source rank of the chunk it
    receives. Each received piece is transposed (and optionally further
    processed by ``chunk_fn``) immediately -- 'the arriving data chunks
    can be transposed as soon as they are received' (paper, §3).

    Dataflow note (the double buffer): every send uses a *pre-existing*
    slice of the input, so no ppermute depends on any chunk_fn result.
    XLA is free to issue the next message while the previous piece's
    transpose/compute runs; on TPU the sends lower to async
    collective-permute-start/done pairs, giving the overlapped pipeline
    without explicit buffer management.
    """
    p = _axis_size(axis_name)
    with obs.layer(obs.RELAYOUT):
        me = lax.axis_index(axis_name)
    chunks = _split_chunks(x, p)  # (p, ..., r, c)
    r, c = x.shape[-2], x.shape[-1] // p
    q = subchunks_per_peer(r, p, n_chunks)
    rq = r // q
    arity = _chunk_fn_arity(chunk_fn) if chunk_fn is not None else 3
    per_sub = chunk_fn is None or arity >= 3

    def sub(block: jax.Array, t: int) -> jax.Array:
        with obs.layer(obs.RELAYOUT):
            return lax.slice_in_dim(block, t * rq, (t + 1) * rq, axis=-2)

    def process(piece: jax.Array, src: jax.Array, offset: int) -> jax.Array:
        out = _transpose_local(piece)  # (..., c, rows)
        if chunk_fn is not None:
            out = _call_chunk_fn(chunk_fn, arity, out, src, offset)
        return out

    # parts: (src, col_offset, processed (..., c, rows)) in arrival order.
    parts = []

    def rounds(block: jax.Array, src, perm=None):
        if per_sub:
            for t in range(q):
                piece = sub(block, t)
                if perm is not None:
                    piece = _ppermute(piece, axis_name, perm)
                parts.append((src, t * rq, process(piece, src, t * rq)))
        else:
            # 2-arg chunk_fn: stream the transport, process the whole
            # reassembled peer block (position-blind fusions only)
            pieces = []
            for t in range(q):
                piece = sub(block, t)
                if perm is not None:
                    piece = _ppermute(piece, axis_name, perm)
                pieces.append(_transpose_local(piece))
            with obs.layer(obs.RELAYOUT):
                whole = pieces[0] if q == 1 else jnp.concatenate(pieces, axis=-1)
            parts.append((src, 0, chunk_fn(whole, src)))

    # Own chunk (round 0) -- compute immediately, no communication.
    with obs.layer(obs.RELAYOUT):
        own = jnp.take(chunks, me, axis=0)
    rounds(own, me)
    for s in range(1, p):
        with obs.layer(obs.RELAYOUT):
            perm, send_slot, src = schedule(me, s, p)
            send = jnp.take(chunks, send_slot, axis=0)
        rounds(send, src, perm)

    # Assemble (..., c, R): the piece from src j at row offset o supplies
    # columns [j*r + o, j*r + o + rows).
    out_shape = x.shape[:-2] + (c, p * r)
    with obs.layer(obs.RELAYOUT):
        out = jnp.zeros(out_shape, parts[0][2].dtype)
        for src, off, part in parts:
            out = lax.dynamic_update_slice_in_dim(
                out, part, src * r + off, axis=out.ndim - 1
            )
    return out


def _chunked_reduce(
    x: jax.Array,
    axis_name: str,
    chunk_fn: ChunkFn,
    schedule,
    n_chunks: Optional[int] = None,
) -> jax.Array:
    """Streaming exchange-and-accumulate: like :func:`_chunked_exchange`
    but the per-source results are *summed*, not concatenated -- the
    shape the fused DFT stage needs (each arriving chunk contributes to
    every output frequency of the cross-rank dimension).

    ``chunk_fn(chunk, src, offset)`` receives the RAW (untransposed)
    received piece (..., rows, c) -- rows ``[offset, offset + rows)`` of
    source ``src``'s block -- and returns an array whose LAST axis is
    that source-row axis. Results sum over sources at equal offsets and
    concatenate along the last axis across offsets. Sub-chunking via
    ``n_chunks`` splits each peer block so compute streams into flight
    time even at small P.

    The sum over sources is the fused DFT's and runs under the
    ``repro.local_fft`` layer scope.
    """
    p = _axis_size(axis_name)
    with obs.layer(obs.RELAYOUT):
        me = lax.axis_index(axis_name)
    chunks = _split_chunks(x, p)  # (p, ..., r, c)
    r = x.shape[-2]
    q = subchunks_per_peer(r, p, n_chunks)
    rq = r // q

    def sub(block: jax.Array, t: int) -> jax.Array:
        with obs.layer(obs.RELAYOUT):
            return lax.slice_in_dim(block, t * rq, (t + 1) * rq, axis=-2)

    with obs.layer(obs.RELAYOUT):
        own = jnp.take(chunks, me, axis=0)
    parts = [chunk_fn(sub(own, t), me, t * rq) for t in range(q)]
    for s in range(1, p):
        with obs.layer(obs.RELAYOUT):
            perm, send_slot, src = schedule(me, s, p)
            send = jnp.take(chunks, send_slot, axis=0)
        for t in range(q):
            recv = _ppermute(sub(send, t), axis_name, perm)
            got = chunk_fn(recv, src, t * rq)
            with obs.layer(obs.LOCAL_FFT):
                parts[t] = parts[t] + got
    with obs.layer(obs.RELAYOUT):
        return parts[0] if q == 1 else jnp.concatenate(parts, axis=-1)


def _ring_schedule(me, s, p):
    # round s: ship the chunk destined to me+s; receive from me-s
    return [(i, (i + s) % p) for i in range(p)], (me + s) % p, (me - s) % p


def _swap_schedule(me, s, p):
    # round s: both ship to and receive from the same partner me^s
    return [(i, i ^ s) for i in range(p)], me ^ s, me ^ s


def _scatter(
    x: jax.Array,
    axis_name: str,
    chunk_fn: Optional[ChunkFn] = None,
    n_chunks: Optional[int] = None,
) -> jax.Array:
    """P-1 direct sends, a one-directional ring walk over distances
    1..P-1 -- the paper's N-scatter decomposition."""
    return _chunked_exchange(x, axis_name, chunk_fn, _ring_schedule, n_chunks)


# ---------------------------------------------------------------------------
# Strategy: Bruck / bisection exchange (beyond-paper)
# ---------------------------------------------------------------------------


def _bisection(x: jax.Array, axis_name: str) -> jax.Array:
    """Bruck all-to-all: ceil(log2 P) rounds, each shipping the slots whose
    round-bit is set. Message count log P (vs P-1), bytes P/2 slots per
    round (vs 1 slot per step) -- the latency/bandwidth trade the paper
    probes with its chunk-size benchmark.

    Slot invariant: after the initial rotation, slot j at rank i holds the
    chunk destined to (i + j) mod P; slot j travels a total distance j by
    moving +2^t on each set bit t; the final flip+rotation orders the
    received chunks by source rank.
    """
    p = _axis_size(axis_name)
    chunks = _split_chunks(x, p)  # (p, ..., r, c), slot d = chunk destined to d

    # Phase 1: rotate so slot j holds destination (me + j) mod p.
    with obs.layer(obs.RELAYOUT):
        me = lax.axis_index(axis_name)
        buf = jnp.roll(chunks, -me, axis=0)

    # Phase 2: log rounds of exchange with rank (me + 2^t). The travelling
    # slot set {j : bit t of j set} is static and identical on every rank,
    # so we ship exactly those slots (half the buffer), not a masked copy.
    t = 0
    while (1 << t) < p:
        step = 1 << t
        idx = tuple(j for j in range(p) if (j >> t) & 1)
        perm = [(i, (i + step) % p) for i in range(p)]
        with obs.layer(obs.RELAYOUT):
            travelling = buf[idx, ...]
        recv = _ppermute(travelling, axis_name, perm)
        with obs.layer(obs.RELAYOUT):
            buf = buf.at[idx, ...].set(recv)
        t += 1

    # Phase 3: slot j now holds the chunk from source (me - j) mod p.
    with obs.layer(obs.RELAYOUT):
        by_src = jnp.flip(jnp.roll(buf, -(me + 1), axis=0), axis=0)  # slot s = from rank s
    stacked = _merge_rows(by_src)  # (..., R, c)
    return _transpose_local(stacked)  # (..., c, R)


# ---------------------------------------------------------------------------
# Strategy: pairwise XOR exchange (beyond-paper)
# ---------------------------------------------------------------------------


def _pairwise_xor(
    x: jax.Array,
    axis_name: str,
    chunk_fn: Optional[ChunkFn] = None,
    n_chunks: Optional[int] = None,
) -> jax.Array:
    """Pairwise exchange: round s swaps one chunk with partner (me XOR s).

    XOR with a fixed s is an involution, so every round is a symmetric
    bidirectional swap (both halves of each link busy), unlike the ring's
    one-directional walk. Requires power-of-two P (XOR must stay a
    permutation of the ranks). Chunks arrive incrementally, so per-chunk
    ``chunk_fn`` processing overlaps the next round exactly as in
    ``scatter``.
    """
    return _chunked_exchange(x, axis_name, chunk_fn, _swap_schedule, n_chunks)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def distributed_transpose(
    x: jax.Array,
    axis_name: str,
    *,
    strategy: str = "alltoall",
    chunk_fn: Optional[ChunkFn] = None,
    n_chunks: Optional[int] = None,
) -> jax.Array:
    """Transpose a (..., R, C) array whose R axis is sharded over
    ``axis_name`` into a (..., C, R) array with C sharded. Must be called
    inside ``shard_map``; local in (..., r, C), local out (..., c, R).

    ``strategy`` names a registered :mod:`repro.core.backends` backend;
    ``chunk_fn`` is only honoured by chunk-streaming backends
    (``backend.supports_chunk_fn`` -- the monolithic collectives have
    nothing to interleave, exactly the paper's point). ``n_chunks``
    (streaming backends, a performance hint elsewhere ignored) decouples
    the message count from P: each peer block is shipped as
    ~``n_chunks/P`` sub-messages so per-chunk compute pipelines into
    flight time even on short rings.
    """
    from repro.core import backends  # late import: backends registers over us

    backend = backends.get(strategy)
    if backend.kind != "shard_map":
        raise ValueError(
            f"backend {strategy!r} is a whole-transform backend with no "
            f"shard_map transpose; use it through fft2/fft3/plan_fft"
        )
    p = _axis_size(axis_name)
    if x.shape[-1] % p:
        raise ValueError(
            f"column count {x.shape[-1]} not divisible by the {p} shards of "
            f"mesh axis {axis_name!r} (plan-level shapes are validated by "
            f"plan_fft; direct callers must pre-chunk)"
        )
    if chunk_fn is not None and not backend.supports_chunk_fn:
        raise ValueError(
            f"chunk_fn requires a chunk-streaming backend "
            f"(got {strategy!r}; streaming: "
            f"{[b for b in backends.available() if backends.get(b).supports_chunk_fn]})"
        )
    if p == 1:
        y = _transpose_local(x)
        if chunk_fn is not None:
            y = _call_chunk_fn(chunk_fn, _chunk_fn_arity(chunk_fn), y, jnp.asarray(0), 0)
        return y
    if not backend.supports(p):
        raise ValueError(f"backend {strategy!r} does not support P={p}")
    return backend.transpose(x, axis_name, chunk_fn, n_chunks=n_chunks)


def transpose_then_fft(
    x: jax.Array,
    axis_name: str,
    *,
    strategy: str,
    impl: str = "jnp",
    fused: bool = False,
    n_chunks: Optional[int] = None,
    inverse: bool = False,
) -> jax.Array:
    """The pipelined overlap executor's unit step: transpose
    (..., r, C) -> (..., c, R) and FFT the result along its last (R)
    axis -- with the cross-rank stage of that FFT folded into the
    arriving chunks when ``fused`` and the backend streams.

    Decimation in time over source ranks (global row j = src*r + j2,
    output frequency k = k1 + P*k2):

        F[k1 + P*k2] = DFT_r over j2 [ T[k1, j2] * sum_src W_P[k1, src] * chunk_src[j2] ]

    The inner sum streams through :func:`_chunked_reduce`: each arriving
    chunk's contribution is a rank-1 outer product with one W_P column
    (times the elementwise twiddle) -- cheap VPU work hidden behind the
    remaining sends. After the exchange only a *local* length-r FFT and
    the k-order relayout remain. The same identity conjugated gives the
    inverse transform (tables conjugate; the trailing local FFT carries
    1/r and the stage adds the remaining 1/P).

    Unfused (or monolithic-backend, or P=1) calls lower to the plain
    transpose followed by a whole-axis local FFT -- numerically the same
    transform, nothing overlapped.
    """
    import repro.core.fftmath as lf
    from repro.core import backends  # late import: backends registers over us

    backend = backends.get(strategy)
    p = _axis_size(axis_name)
    if not (fused and backend.supports_chunk_fn and p > 1):
        y = distributed_transpose(x, axis_name, strategy=strategy, n_chunks=n_chunks)
        return lf.local_fft(y, axis=-1, inverse=inverse, impl=impl)
    # same guards the plain transpose enforces -- the fused path must not
    # trade its friendly errors for a reshape blow-up in _split_chunks
    if x.shape[-1] % p:
        raise ValueError(
            f"column count {x.shape[-1]} not divisible by the {p} shards of "
            f"mesh axis {axis_name!r} (plan-level shapes are validated by "
            f"plan_fft; direct callers must pre-chunk)"
        )
    if not backend.supports(p):
        raise ValueError(f"backend {strategy!r} does not support P={p}")

    r = x.shape[-2]
    cdtype = jnp.result_type(x.dtype, jnp.complex64)
    with obs.layer(obs.LOCAL_FFT):
        w_p = jnp.asarray(lf.dft_matrix(p, cdtype))  # (k1, src)
        tw = jnp.asarray(lf.twiddle(p, r, cdtype))  # (k1, j2)
        if inverse:
            w_p, tw = jnp.conj(w_p), jnp.conj(tw)
        x = x.astype(cdtype)

    use_pallas = impl == "pallas" and jnp.dtype(cdtype) == jnp.complex64

    def chunk_fn(chunk: jax.Array, src: jax.Array, offset: int) -> jax.Array:
        # chunk (..., rows, c) = rows [offset, offset+rows) of src's block.
        rows = chunk.shape[-2]
        with obs.layer(obs.LOCAL_FFT):
            col = lax.dynamic_slice_in_dim(w_p, src, 1, axis=1)[:, 0]  # (k1=p,)
            tws = lax.slice_in_dim(tw, offset, offset + rows, axis=1)  # (p, rows)
            m = col[:, None] * tws  # (k1, j2) for this piece
            if use_pallas:
                from repro.kernels import fft_stage

                return fft_stage.chunk_twiddle_pack_c64(chunk, m)
        ct = _transpose_local(chunk)  # (..., c, rows)
        with obs.layer(obs.LOCAL_FFT):
            return ct[..., None, :] * m  # (..., c, k1=p, j2=rows)

    acc = backend.stream_reduce(x, axis_name, chunk_fn, n_chunks=n_chunks)
    acc = lf.local_fft(acc, axis=-1, inverse=inverse, impl=impl)  # j2 -> k2 (1/r if inverse)
    # F index k = k1 + P*k2 -> order (k2 major, k1 minor).
    out = _transpose_local(acc)  # (..., c, k2=r, k1=p)
    with obs.layer(obs.RELAYOUT):
        out = out.reshape(out.shape[:-2] + (p * r,))
    if inverse:
        with obs.layer(obs.LOCAL_FFT):
            out = out / p  # completes the 1/(p*r) = 1/R factor
    return out
