"""Stage-schedule IR tests: golden snapshots over the full pipeline
grid, the one shard-divisibility validator's exact messages, rewrite
semantics, and the cost/byte invariants (per-stage contributions sum to
the whole-plan prediction; model bytes match both HLO parsers).

Regenerate the golden file after an INTENTIONAL pipeline change with:

    PYTHONPATH=src python tests/test_schedule.py --regen
"""

import json
import os
import re

import pytest

import repro.core.schedule as sch

GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden_schedules.json")


# ---------------------------------------------------------------------------
# The snapshot grid: every (decomp x real x direction x fused) pipeline,
# built purely (shapes + names + ring sizes in -- no mesh, no devices)
# ---------------------------------------------------------------------------


def snapshot_cases():
    """key -> build_schedule kwargs for every supported combination."""
    cases = {}
    for ndim, shape in ((2, (16, 16)), (3, (8, 8, 8))):
        for real in (False, True):
            for inverse in (False, True):
                for fused in (False, True):
                    tbs = (False, True) if ndim == 2 else (False,)
                    for tb in tbs:
                        key = (
                            f"slab/ndim{ndim}/{'r2c' if real else 'c2c'}/"
                            f"{'inv' if inverse else 'fwd'}/"
                            f"{'fused' if fused else 'unfused'}"
                            + ("/tb" if tb else "")
                        )
                        cases[key] = dict(
                            global_shape=shape, ndim=ndim, inverse=inverse,
                            real=real, decomp="slab", axis_name="x", p=4,
                            backend="scatter", fused=fused, transpose_back=tb,
                        )
    for fused in (False, True):
        key = f"slab/ndim1/c2c/fwd/{'fused' if fused else 'unfused'}"
        cases[key] = dict(
            global_shape=(64,), ndim=1, inverse=False, decomp="slab",
            axis_name="x", p=4, backend="scatter", fused=fused,
        )
    for ndim, shape in ((2, (16, 16)), (3, (8, 8, 8))):
        for real in (False, True):
            for inverse in (False, True):
                for fused in (False, True):
                    tbs = (False, True) if ndim == 3 else (False,)
                    for tb in tbs:
                        key = (
                            f"pencil/ndim{ndim}/{'r2c' if real else 'c2c'}/"
                            f"{'inv' if inverse else 'fwd'}/"
                            f"{'fused' if fused else 'unfused'}"
                            + ("/tb" if tb else "")
                        )
                        cases[key] = dict(
                            global_shape=shape, ndim=ndim, inverse=inverse,
                            real=real, decomp="pencil",
                            row_axis="rows", col_axis="cols",
                            p_rows=2, p_cols=2,
                            backend_row="scatter", backend_col="alltoall",
                            fused=fused, transpose_back=tb,
                        )
    # the GSPMD whole-transform reference route (empty abstract exchanges
    # still carry cost structure; execution goes through _xla_reference)
    cases["slab/ndim2/c2c/fwd/xla_auto"] = dict(
        global_shape=(16, 16), ndim=2, inverse=False, decomp="slab",
        axis_name="x", p=4, backend="xla_auto",
    )
    cases["slab/ndim2/r2c/fwd/xla_auto"] = dict(
        global_shape=(16, 16), ndim=2, inverse=False, real=True,
        decomp="slab", axis_name="x", p=4, backend="xla_auto",
    )
    # one shard: asked to, the 2-D c2c slab transform is transposed
    # first; unasked, and in the transpose_back, r2c and GSPMD forms, it
    # keeps its P>1 structure
    for key, kw in P1_CASES.items():
        cases[f"slab/ndim2/{key}/p1"] = dict(
            global_shape=(16, 16), ndim=2, decomp="slab", axis_name="x", p=1, **kw
        )
    return cases


#: the one-shard pipelines of the snapshot grid (key -> build kwargs)
P1_CASES = {
    "c2c/fwd": dict(backend="alltoall"),
    "c2c/fwd/tf": dict(backend="alltoall", transpose_first=True),
    "c2c/inv/tf": dict(backend="alltoall", inverse=True, transpose_first=True),
    "c2c/fwd/tb/tf": dict(backend="alltoall", transpose_back=True, transpose_first=True),
    "r2c/fwd/tf": dict(backend="alltoall", real=True, transpose_first=True),
    "c2c/fwd/xla_auto/tf": dict(backend="xla_auto", transpose_first=True),
}


def build_snapshots():
    return {k: sch.build_schedule(**kw).canonical() for k, kw in sorted(snapshot_cases().items())}


def test_golden_schedules_drift():
    """Every pipeline's lowered stage schedule is byte-identical to the
    committed snapshot -- any change to what executes (stage order,
    exchange payloads, ring sizes, conj/scale) must be intentional and
    show up in review as a golden-file diff."""
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    built = build_snapshots()
    assert set(built) == set(golden), (
        f"pipeline grid changed: new={sorted(set(built) - set(golden))} "
        f"gone={sorted(set(golden) - set(built))} -- regenerate "
        f"tests/golden_schedules.json if intentional"
    )
    for key in sorted(built):
        assert built[key] == golden[key], (
            f"schedule drift in {key}:\n--- golden ---\n{golden[key]}\n"
            f"--- built ---\n{built[key]}"
        )


def test_schedule_hash_tracks_content():
    a = sch.build_schedule((16, 16), ndim=2, decomp="slab", axis_name="x",
                           p=4, backend="scatter")
    same = sch.build_schedule((16, 16), ndim=2, decomp="slab", axis_name="x",
                              p=4, backend="scatter")
    other = sch.build_schedule((16, 16), ndim=2, decomp="slab", axis_name="x",
                               p=4, backend="alltoall")
    assert a.schedule_hash() == same.schedule_hash()
    assert a.schedule_hash() != other.schedule_hash()
    assert re.fullmatch(r"[0-9a-f]{12}", a.schedule_hash())


# ---------------------------------------------------------------------------
# Rewrites
# ---------------------------------------------------------------------------


def test_with_pipeline_rewrites_every_exchange():
    s = sch.build_schedule((8, 8, 8), ndim=3, decomp="slab", axis_name="x",
                           p=4, backend="scatter", fused=True)
    u = sch.with_pipeline(s, False, None)
    assert all(not ex.fused and ex.n_chunks is None for ex in u.exchanges())
    f = sch.with_pipeline(s, True, 16)
    assert all(ex.fused and ex.n_chunks == 16 for ex in f.exchanges())
    # non-Exchange stages and the header survive untouched
    assert u.global_shape == s.global_shape and len(u.stages) == len(s.stages)


def test_with_backends_by_role():
    s = sch.build_schedule((8, 8, 8), ndim=3, decomp="pencil",
                           row_axis="r", col_axis="c", p_rows=2, p_cols=2,
                           backend_row="alltoall", backend_col="alltoall")
    rw = sch.with_backends(s, row="scatter")
    assert all(ex.backend == "scatter" for ex in rw.exchanges("row"))
    assert all(ex.backend == "alltoall" for ex in rw.exchanges("col"))


def test_apply_variant_matches_manual_rewrite():
    s = sch.build_schedule((16, 16), ndim=2, decomp="slab", axis_name="x",
                           p=4, backend="alltoall")
    v = sch.apply_variant(s, "scatter@f8")
    assert all(ex.backend == "scatter" and ex.fused and ex.n_chunks == 8
               for ex in v.exchanges())
    u = sch.apply_variant(s, "scatter@u")
    assert all(ex.backend == "scatter" and not ex.fused for ex in u.exchanges())
    p = sch.build_schedule((8, 8, 8), ndim=3, decomp="pencil",
                           row_axis="r", col_axis="c", p_rows=2, p_cols=2,
                           backend_row="alltoall", backend_col="alltoall")
    pv = sch.apply_variant(p, "scatter+bisection@u")
    assert all(ex.backend == "scatter" for ex in pv.exchanges("row"))
    assert all(ex.backend == "bisection" for ex in pv.exchanges("col"))
    assert all(not ex.fused for ex in pv.exchanges())


# ---------------------------------------------------------------------------
# The one validator: exact legacy messages (regression-pinned)
# ---------------------------------------------------------------------------


def test_validator_slab_c2c_messages():
    with pytest.raises(ValueError, match=re.escape(
            "slab fft2: data axis -2 (global size 10) is not divisible by "
            "mesh axis 'x' (P=4) -- shape (10, 16)")):
        sch.check_divisible((10, 16), 2, p=4, axis_name="x")
    with pytest.raises(ValueError, match=re.escape(
            "slab fft2: data axis -1 (global size 10)")):
        sch.check_divisible((16, 10), 2, p=4, axis_name="x")
    with pytest.raises(ValueError, match=re.escape(
            "slab fft3: data axis -3 (global size 10)")):
        sch.check_divisible((10, 8, 8), 3, p=4, axis_name="x")
    with pytest.raises(ValueError, match=re.escape(
            "slab fft3: flattened axes (-2,-1) (size 5*2=10)")):
        sch.check_divisible((8, 5, 2), 3, p=4, axis_name="x")
    with pytest.raises(ValueError, match=re.escape(
            "fft1d_large: data axis -1 (size 24) must be divisible by P^2=16")):
        sch.check_divisible((24,), 1, p=4, axis_name="x")


def test_validator_pencil_c2c_messages():
    with pytest.raises(ValueError, match=re.escape(
            "pencil fft3: data axis -3 (global size 9) is not divisible by "
            "P_row=2 ('rows')")):
        sch.check_divisible((9, 8, 8), 3, p_rows=2, p_cols=2,
                            row_axis="rows", col_axis="cols")
    with pytest.raises(ValueError, match=re.escape("P_col=2 ('cols')")):
        sch.check_divisible((8, 9, 8), 3, p_rows=2, p_cols=2,
                            row_axis="rows", col_axis="cols")
    with pytest.raises(ValueError, match=re.escape(
            "P_row*P_col=4 (both sub-rings re-shard it)")):
        sch.check_divisible((10, 8), 2, p_rows=2, p_cols=2,
                            row_axis="rows", col_axis="cols")
    with pytest.raises(ValueError, match=re.escape(
            "pencil decomposition supports ndim 2 or 3, got 1")):
        sch.check_divisible((16,), 1, p_rows=2, p_cols=2,
                            row_axis="rows", col_axis="cols")


def test_validator_real_messages():
    # slab r2c: the rows axis must divide P; the Hermitian axis must
    # divide (or pad) -- messages name the data axis and the mesh axis
    with pytest.raises(ValueError, match=re.escape(
            "real slab rfft2: data axis -2 (global size 10) is not divisible "
            "by mesh axis 'x' (P=4)")):
        sch.check_divisible((10, 16), 2, p=4, axis_name="x", real=True)
    with pytest.raises(ValueError, match=re.escape(
            "real slab rfft2: Hermitian axis -1 (N=10 -> N//2+1=6)")):
        sch.check_divisible((16, 10), 2, p=4, axis_name="x", real=True, pad=False)
    with pytest.raises(NotImplementedError, match="real transforms support ndim 2 or 3"):
        sch.check_divisible((64,), 1, p=4, axis_name="x", real=True)
    # pencil r2c: (8,8,8) on a 2x2 grid has h = 8//2+1 = 5, not divisible
    with pytest.raises(ValueError, match=re.escape(
            "real pencil rfft3: Hermitian axis -1 (N=8 -> N//2+1=5)")):
        sch.check_divisible((8, 8, 8), 3, p_rows=2, p_cols=2,
                            row_axis="rows", col_axis="cols", real=True, pad=False)
    with pytest.raises(NotImplementedError, match="real pencil transforms support ndim 2 or 3"):
        sch.check_divisible((64,), 1, p_rows=2, p_cols=2,
                            row_axis="rows", col_axis="cols", real=True)
    # padding resolves the Hermitian axis: returns (h, hp)
    h, hp = sch.check_divisible((16, 16), 2, p=4, axis_name="x", real=True)
    assert (h, hp) == (9, 12)


def test_validator_is_the_single_source():
    """The legacy validator spellings all delegate here -- same checks,
    same messages (the dedup satellite)."""
    from repro.core import pencil as pencil_mod
    from repro.core import real as real_mod

    with pytest.raises(ValueError, match="Hermitian axis -1"):
        real_mod.check_divisible_slab((16, 10), 4, 2, "x", pad=False)
    with pytest.raises(ValueError, match="real pencil rfft3"):
        real_mod.check_divisible_pencil((8, 8, 8), type(
            "G", (), dict(p_rows=2, p_cols=2, row_axis="r", col_axis="c"))(), 3,
            pad=False)

    class FakeGrid:
        p_rows, p_cols = 2, 2
        row_axis, col_axis = "rows", "cols"

    with pytest.raises(ValueError, match="P_row=2"):
        pencil_mod.check_divisible((9, 8, 8), FakeGrid(), 3)


# ---------------------------------------------------------------------------
# Cost/byte invariants (pure walks; the executed-vs-modeled cross-check
# against both HLO parsers runs on 8 devices below)
# ---------------------------------------------------------------------------


def test_stage_walk_sums_to_whole_schedule():
    from repro.core import comm_model as cm

    prm = cm.CommParams()
    s = sch.build_schedule((8, 8, 8), ndim=3, decomp="pencil",
                           row_axis="r", col_axis="c", p_rows=2, p_cols=2,
                           backend_row="scatter", backend_col="alltoall",
                           fused=True)
    total = sch.predict_seconds(s, prm, 1e-6, 8, 8)
    per_stage = sum(sch.stage_seconds(ex, prm, 1e-6, 8, 8) for ex in s.exchanges())
    assert total == per_stage
    assert (sch.predict_seconds(s, prm, 1e-6, 8, 8, "row")
            + sch.predict_seconds(s, prm, 1e-6, 8, 8, "col")) == total
    bytes_total = sch.schedule_comm_bytes(s, 8, 8)
    assert bytes_total == sum(sch.exchange_wire_bytes(ex, 8, 8) for ex in s.exchanges())
    assert bytes_total > 0


def test_describe_renders_stage_table():
    s = sch.build_schedule((16, 16), ndim=2, decomp="slab", axis_name="x",
                           p=4, backend="scatter", fused=True)
    text = s.describe()
    assert s.schedule_hash() in text
    assert "LocalFFT" in text and "Exchange" in text
    assert "wire bytes" in text and "total modeled exchange time" in text


TRANSPOSED_FIRST = (
    sch.Relayout("swap_last2"), sch.LocalFFT(axis=-1), sch.LocalFFT(axis=-2),
)


@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
@pytest.mark.parametrize("p", [1, 4])
def test_slab_fft2_stage_order(p, inverse):
    """One shard, asked to: transpose, then both passes local (the
    exchange would move no data). More shards: first pass, then the
    exchange that carries the second."""
    s = sch.build_schedule((16, 16), ndim=2, inverse=inverse, decomp="slab",
                           axis_name="x", p=p, backend="alltoall", transpose_first=True)
    assert s.conj == inverse and s.scale == (256.0 if inverse else None)
    if p == 1:
        assert s.stages == TRANSPOSED_FIRST and s.transposed_first
        assert s.exchanges() == () and s.out_tail == (None, "x")
        assert "Relayout(swap_last2)" in s.describe()
    else:
        assert [type(st) for st in s.stages] == [sch.LocalFFT, sch.Exchange]
        assert s.stages[1].fft and s.stages[1].p == 4 and s.out_tail == ("x", None)
        assert not s.transposed_first and "Relayout" not in s.describe()
    assert sch.simulate_specs(s, 2)[-1] == s.out_tail


@pytest.mark.parametrize("shape", [(32, 32), (32, 48), (3, 32, 48)],
                         ids=["square", "wide", "batched"])
@pytest.mark.parametrize("direction", ["forward", "inverse"])
def test_one_shard_fft2_matches_numpy(shape, direction):
    """The transposed-first schedule gives the transposed spectrum
    (forward) and the transposed inverse at the distributed tests'
    tolerance."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.compat import make_mesh

    inverse = direction == "inverse"
    s = sch.build_schedule(shape, ndim=2, inverse=inverse, decomp="slab", axis_name="model",
                           p=1, backend="alltoall", transpose_first=True)
    assert s.stages == TRANSPOSED_FIRST
    rng = np.random.default_rng(len(shape) * 100 + shape[-1])
    x = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
    ref = np.swapaxes(np.fft.ifft2(x) if inverse else np.fft.fft2(x), -1, -2)
    y = np.asarray(sch.run_schedule(jnp.asarray(x), s, make_mesh((1,), ("model",))))
    assert y.shape == ref.shape
    assert np.abs(y - ref).max() < 1e-4 * np.abs(ref).max()


@pytest.mark.parametrize("shape,impl,first", [
    ((16384, 8), "jnp", True),
    ((32768, 8), "jnp", True),
    ((8, 16384), "jnp", False),
    ((4096, 8), "jnp", False),
    ((64, 64), "jnp", False),
    ((64, 64), "pallas", True),
    ((2, 64, 64), "pallas", False),
    ((16384, 8), "matmul", False),
])
def test_one_shard_plan_transposes_first_where_it_pays(shape, impl, first):
    """A plan runs transposed first with the Pallas FFT on an unbatched
    input, and with XLA's FFT where the first-pass length is a multiple
    of 128 * 128; elsewhere it keeps the exchange, and says which in
    why() and its stage table."""
    from repro.core import plan_fft
    from repro.core.compat import make_mesh

    plan = plan_fft(shape, make_mesh((1,), ("model",)), ndim=2, backend="alltoall",
                    local_impl=impl)
    assert plan.why()["transposed_first"] is first
    assert ("Relayout(swap_last2)" in plan.describe()) is first
    assert (plan.schedule().stages == TRANSPOSED_FIRST) is first
    assert (plan.schedule(inverse=True).stages == TRANSPOSED_FIRST) is first


def test_one_shard_plan_says_transposed_first():
    """A transposed-first plan says so, computes the transposed spectrum
    and round-trips, and prices no exchange."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import plan_fft
    from repro.core.compat import make_mesh

    plan = plan_fft((16384, 8), make_mesh((1,), ("model",)), ndim=2, backend="alltoall")
    assert plan.why()["transposed_first"]
    assert "Relayout(swap_last2)" in plan.describe()
    rng = np.random.default_rng(7)
    x = (rng.standard_normal((16384, 8)) + 1j * rng.standard_normal((16384, 8))).astype(np.complex64)
    ref = np.swapaxes(np.fft.fft2(x), -1, -2)
    y = np.asarray(plan.execute(jnp.asarray(x)))
    assert np.abs(y - ref).max() < 1e-4 * np.abs(ref).max()
    z = np.asarray(plan.inverse(jnp.asarray(y)))
    assert np.abs(z - x).max() < 1e-4 * np.abs(x).max()
    assert plan.predict() == dict.fromkeys(plan.predict(), 0.0)
    assert plan.comm_bytes() == 0.0


def test_plan_level_invariants_8dev():
    """Per-stage predict() contributions sum to the whole-plan
    prediction, per-stage model bytes sum to comm_bytes, and (alltoall
    pipelines) both HLO parsers count exactly those bytes."""
    from conftest import run_subprocess

    code = r"""
from repro.core import plan_fft, comm_model, hlo_analysis
from repro.core.compat import make_mesh

mesh = make_mesh((8,), ("x",))
gmesh = make_mesh((4, 2), ("rows", "cols"))
cases = [
    dict(shape=(32, 32), mesh=mesh, ndim=2, backend="scatter"),
    dict(shape=(32, 32), mesh=mesh, ndim=2, backend="alltoall"),
    dict(shape=(16, 16, 16), mesh=mesh, ndim=3, backend="alltoall"),
    dict(shape=(64 * 8,), mesh=mesh, ndim=1, backend="scatter"),
    dict(shape=(32, 32), mesh=mesh, ndim=2, backend="alltoall", real=True),
    dict(shape=(32, 32), mesh=mesh, ndim=2, backend="alltoall", real=True,
         direction="inverse"),
    dict(shape=(16, 16, 16), mesh=gmesh, ndim=3, decomp="pencil",
         backend=("alltoall", "alltoall")),
    dict(shape=(16, 16, 16), mesh=gmesh, ndim=3, decomp="pencil",
         backend=("scatter", "bisection")),
    dict(shape=(16, 16, 16), mesh=gmesh, ndim=3, decomp="pencil", real=True,
         backend=("alltoall", "alltoall")),
    dict(shape=(32, 32), mesh=gmesh, ndim=2, decomp="pencil", real=True,
         backend=("alltoall", "alltoall")),
]
for kw in cases:
    shape, m = kw.pop("shape"), kw.pop("mesh")
    plan = plan_fft(shape, m, **kw)
    stages = plan.predict_stages()
    secs = sum(s for _, s, _ in stages)
    byts = sum(b for _, _, b in stages)
    whole = plan.predict()[plan.backend]
    assert abs(secs - whole) <= 1e-15 + 1e-9 * whole, (plan, secs, whole)
    assert abs(byts - plan.comm_bytes()) <= 1e-6, (plan, byts, plan.comm_bytes())
    all_a2a = all(kw_b == "alltoall" for kw_b in (
        [plan.backend] if plan.decomp == "slab"
        else [plan.backend_row, plan.backend_col]))
    if all_a2a and plan.shards > 1:
        comp = plan.lower().compile()
        group = plan.shards
        parsed = comm_model.parse_collectives(comp.as_text(), default_group=group).total_bytes
        hlo = hlo_analysis.analyze_compiled(comp, default_group=group).coll_bytes
        assert abs(parsed - byts) <= 1e-6 * max(byts, 1.0), (plan, parsed, byts)
        assert abs(hlo - byts) <= 1e-6 * max(byts, 1.0), (plan, hlo, byts)
    print("PASS", plan)
print("PASS all invariants")
"""
    out = run_subprocess(code, devices=8)
    assert "PASS all invariants" in out


def test_plan_schedule_identity_and_hash_8dev():
    """Plan.schedule() is the executed object: fused and unfused plans
    hash differently, forward/inverse round-trip through genuinely
    reversed real chains, and the serve pool records the hash."""
    from conftest import run_subprocess

    code = r"""
import numpy as np
import jax.numpy as jnp
from repro.core import plan_fft
from repro.core.compat import make_mesh
from repro.serve.spectral import PlanPool

mesh = make_mesh((8,), ("x",))
pf = plan_fft((32, 32), mesh, backend="scatter")
pu = plan_fft((32, 32), mesh, backend="scatter", pipeline=False)
assert pf.schedule_hash() != pu.schedule_hash()
assert pf.schedule_hash() == plan_fft((32, 32), mesh, backend="scatter").schedule_hash()
assert pf.schedule_hash(inverse=True) != pf.schedule_hash(inverse=False)

pr = plan_fft((32, 32), mesh, backend="scatter", real=True)
fwd, inv = pr.schedule(False), pr.schedule(True)
assert fwd.stages != inv.stages  # real inverse is a reversed chain, not a conj-wrap
assert fwd.kind == "rfft2" and inv.kind == "irfft2"

pool = PlanPool(mesh, capacity=4)
plan, hit = pool.get((32, 32), 2, jnp.complex64, False)
key = pool.key((32, 32), 2, jnp.complex64, False)
assert not hit and pool.schedule_hash(key) == plan.schedule_hash()
assert pool.stats()["distinct_schedules"] == 1
assert key.startswith("shape=32x32|ndim=2|")  # pool key format is frozen
print("PASS schedule identity")
"""
    out = run_subprocess(code, devices=8)
    assert "PASS schedule identity" in out


if __name__ == "__main__":
    import sys

    if "--regen" in sys.argv:
        snaps = build_snapshots()
        with open(GOLDEN_PATH, "w") as f:
            json.dump(snaps, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {len(snaps)} schedules to {GOLDEN_PATH}")
    else:
        print(__doc__)
