"""Thin wrappers for the jax APIs the core layer leans on.

One place to construct meshes and shard_maps, so every caller uses the
same axis types and vma settings, and to place the persistent
compilation cache. Written for jax 0.9.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Sequence

import jax
from jax import lax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None):
    """``jax.shard_map``; ``check_vma`` is passed through when given."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs)


def axis_size(axis_name: str) -> int:
    """Static size of a shard_map axis."""
    return lax.axis_size(axis_name)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` over the first ``prod(axis_shapes)`` devices with
    auto axis types (shard_map + jit sharding propagation both work)."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names), axis_types=(AxisType.Auto,) * len(axis_names)
    )


def make_mesh_1d(p: int, axis_name: str = "model"):
    """The FFT benchmarks' standard 1-D mesh over the first ``p`` devices."""
    return make_mesh((p,), (axis_name,))


#: where the entry points keep JAX's persistent compilation cache when
#: ``JAX_COMPILATION_CACHE_DIR`` is not set: a fixed directory of this
#: checkout, since the path is part of what a later run must find again
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    JAX itself reads ``JAX_COMPILATION_CACHE_DIR`` when it is set, and
    that directory wins; otherwise the cache goes to :data:`CACHE_DIR`.
    Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
    call this once at start-up; library modules never do.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
