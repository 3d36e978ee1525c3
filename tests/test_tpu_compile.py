"""Compile the main path's kernels and the paper's transform for a
described TPU v5e chip (no chip needed: the TPU compiler runs on the
host and refuses what the chip would refuse -- blocks too large for
VMEM, slices the tiling cannot take, programs beyond HBM).

Every compile runs under its own time limit, so an oversized kernel
fails fast instead of stalling the suite. Topology description lives in
a fixture of this one file: describing it loads the TPU library, which
one process at a time may hold.
"""

import os
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from jax.sharding import Mesh, SingleDeviceSharding  # noqa: E402

from repro.core import plan_fft  # noqa: E402
from repro.core.comm_model import shape_bytes  # noqa: E402
from repro.core.hlo_analysis import parse_hlo  # noqa: E402
from repro.kernels import fft_stage  # noqa: E402

#: v5e HBM per chip
HBM_BYTES = 16 * 2**30
#: per-compile limit; each of these compiles takes seconds when it fits
COMPILE_LIMIT_S = 120.0
N = 16384  # the paper's 2-D problem, 16384^2 complex64
N1 = N2 = 128  # its kernel factorisation


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(lowerable, *args):
    """``lowerable.lower(*args).compile()`` on a daemon thread, failing
    the test after COMPILE_LIMIT_S (the thread is abandoned, not joined)."""
    out = {}

    def run():
        try:
            out["compiled"] = lowerable.lower(*args).compile()
        except BaseException as e:  # re-raised in the test's thread
            out["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(COMPILE_LIMIT_S)
    if t.is_alive():
        pytest.fail(f"compile still running after {COMPILE_LIMIT_S} s")
    if "error" in out:
        raise out["error"]
    return out["compiled"]


def _check(compiled, min_kernels: int):
    ma = compiled.memory_analysis()
    total = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
    assert total < HBM_BYTES, total
    assert compiled.as_text().count("tpu_custom_call") >= min_kernels


def _planar(shape, sharding):
    s = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    return (s, s)


def test_stage_left_compiles_at_16384(one_chip):
    w = _planar((N1, N1), one_chip)
    a = _planar((N, N1, N2), one_chip)
    t = _planar((N1, N2), one_chip)
    _check(_compile(jax.jit(fft_stage.stage_left), w, a, t), 1)


def test_stage_right_compiles_at_16384(one_chip):
    a = _planar((N, N1, N2), one_chip)
    w = _planar((N2, N2), one_chip)
    _check(_compile(jax.jit(fft_stage.stage_right), a, w), 1)


def test_chunk_twiddle_pack_compiles_at_p4_chunk(one_chip):
    # 16384^2 over P=4: one arriving chunk is (rows, c) = 4096 x 4096
    p, rows, c = 4, N // 4, N // 4
    chunk = jax.ShapeDtypeStruct((rows, c), jnp.complex64, sharding=one_chip)
    m = jax.ShapeDtypeStruct((p, rows), jnp.complex64, sharding=one_chip)
    _check(_compile(jax.jit(fft_stage.chunk_twiddle_pack_c64), chunk, m), 1)


def test_pallas_plan_refuses_untileable_length_on_tpu(topo, monkeypatch):
    # on a TPU the kernel never falls back to the jnp matmul FFT: the plan
    # raises, naming the local FFT length it cannot tile
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices[:1]), ("model",))
    with pytest.raises(ValueError, match="length-1021 transform"):
        plan_fft((1024, 1021), mesh, ndim=2, backend="alltoall", local_impl="pallas")


def test_fft2_16384_forward_compiles_for_one_chip(topo, one_chip):
    mesh = Mesh(np.array(topo.devices[:1]), ("model",))
    plan = plan_fft((N, N), mesh, ndim=2, backend="alltoall")
    compiled = _compile(jax.jit(plan.execute), plan.input_spec())
    _check(compiled, 0)
    # the one-shard schedule is transposed first, so XLA's FFT expansion
    # takes the argument's planes as they are split and its combine
    # writes the row-major result: no retile copy of a plane, no copy of
    # the result
    comps, entry = parse_hlo(compiled.as_text())
    ops = {op.name: op for op in comps[entry].ops}
    (root,) = [op for op in ops.values() if op.raw.startswith("ROOT")]
    assert root.kind == "custom-call" and 'custom_call_target="X64Combine"' in root.raw, root.raw
    splits = {op.name for op in ops.values() if 'custom_call_target="X64Split' in op.raw}
    retiled = {op.name for op in ops.values()
               if op.kind == "bitcast" and ",8,128,128]" in op.result_type
               and splits.intersection(op.operands)}
    assert not [op.name for op in ops.values()
                if op.kind == "copy" and retiled.intersection(op.operands)]
    big_copies = [op.name for op in ops.values()
                  if op.kind == "copy" and shape_bytes(op.result_type) >= 2**30]
    assert len(big_copies) <= 11, big_copies
