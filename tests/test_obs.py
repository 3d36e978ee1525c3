"""Observability subsystem tests: TraceRecorder exports (Chrome-trace
schema, multi-process adoption), the spec simulation
behind the segmented trace-mode executor, the alpha/beta online re-fit
from observed Exchange spans, the wisdom observed-timings channel, and
-- in an 8-device subprocess -- the acceptance contract: traced
execution stamps exactly one Exchange span per schedule Exchange stage
whose wire bytes match ``schedule_comm_bytes`` exactly, ``Plan.profile``
returns one observed row per schedule stage, and the untraced hot path
compiles to byte-identical HLO before and after profiling."""

import dataclasses
import json
import math
import sys
import types

import pytest

from conftest import REPO, run_subprocess

if REPO not in sys.path:
    sys.path.insert(0, REPO)

from repro.core import planner  # noqa: E402
from repro.core import schedule as sch  # noqa: E402
from repro.core.comm_model import (  # noqa: E402
    CommParams,
    exchange_fit_terms,
    payload_class,
)
from repro.obs import Span, TraceRecorder  # noqa: E402
from test_schedule import snapshot_cases  # noqa: E402


# ---------------------------------------------------------------------------
# TraceRecorder: recording + exports
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_span_contextmanager_and_fake_clock():
    clk = FakeClock()
    rec = TraceRecorder(clk)
    with rec.span("fft", cat="stage", stage="LocalFFT") as sp:
        clk.t += 0.25
        sp.args["extra"] = 7  # annotatable before the block exits
    assert len(rec.spans) == 1
    s = rec.spans[0]
    assert s.name == "fft" and s.t0 == 0.0 and s.dur == 0.25
    assert s.args == {"stage": "LocalFFT", "extra": 7}
    # spans exit even when the body raises
    with pytest.raises(RuntimeError):
        with rec.span("boom"):
            clk.t += 1.0
            raise RuntimeError("x")
    assert [s.name for s in rec.spans] == ["fft", "boom"]
    assert sum(s.dur for s in rec.spans) == pytest.approx(1.25)


def test_mark_and_exchange_filter():
    clk = FakeClock()
    rec = TraceRecorder(clk)
    rec.add_span("a", 0.0, 0.1, cat="stage")
    m = rec.mark()
    rec.add_span("b", 0.1, 0.2, cat="exchange", args={"backend": "scatter"})
    rec.add_span("c", 0.3, 0.1, cat="stage")
    assert [s.name for s in rec.spans_since(m)] == ["b", "c"]
    assert [s.name for s in rec.exchange_spans()] == ["b"]


def test_chrome_trace_schema():
    """Every exported event carries the fields the Perfetto/Chrome JSON
    loaders require: complete ('X') events have name/ts/dur/pid/tid/args
    with microsecond times, process names 'M'."""
    clk = FakeClock()
    rec = TraceRecorder(clk, pid=3)
    rec.set_process_name(3, "harness")
    with rec.span("row:x", cat="exchange", backend="scatter", wire_bytes=1024.0):
        clk.t += 0.001
    doc = rec.to_chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert {e["ph"] for e in events} == {"X", "M"}
    for e in events:
        assert isinstance(e["name"], str) and isinstance(e["pid"], int)
        assert isinstance(e["tid"], int) and isinstance(e["args"], dict)
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    (x,) = [e for e in events if e["ph"] == "X"]
    assert x["ts"] == 0.0 and x["dur"] == pytest.approx(1000.0)  # microseconds
    assert x["args"]["wire_bytes"] == 1024.0
    (m,) = [e for e in events if e["ph"] == "M"]
    assert m["name"] == "process_name" and m["args"] == {"name": "harness"}
    json.dumps(doc)  # must be serialisable as-is


def test_adopt_rehomes_foreign_events():
    rec = TraceRecorder(FakeClock())
    rec.add_span("local", 0.0, 0.1)
    foreign = [
        {"name": "sub", "ph": "X", "ts": 0.0, "dur": 5.0, "pid": 0, "tid": 0, "args": {}}
    ]
    rec.adopt(foreign, name="fft_measure p=8")
    doc = rec.to_chrome_trace()
    sub = [e for e in doc["traceEvents"] if e.get("name") == "sub"]
    assert len(sub) == 1 and sub[0]["pid"] != rec.pid  # re-homed, not clobbered
    names = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert names and names[0]["args"]["name"] == "fft_measure p=8"
    assert foreign[0]["pid"] == 0  # caller's event dict untouched


# ---------------------------------------------------------------------------
# Spec simulation (what makes per-stage segmentation shard-safe)
# ---------------------------------------------------------------------------


def test_simulate_specs_over_every_golden_schedule():
    """The symbolic spec walk must accept every schedule the builders can
    emit (the golden snapshot grid) and land exactly on the schedule's
    declared out_tail -- otherwise the trace-mode executor would reshard
    between segments."""
    n_checked = 0
    for key, kw in sorted(snapshot_cases().items()):
        s = sch.build_schedule(**kw)
        if s.global_backend is not None:
            continue  # GSPMD reference: traced as one whole-transform span
        specs = sch.simulate_specs(s, kw["ndim"])
        assert len(specs) == len(s.stages) + 1, key
        assert specs[0][-len(s.in_tail):] == s.in_tail, key
        assert specs[-1][-len(s.out_tail):] == s.out_tail, key
        n_checked += 1
    assert n_checked >= 30  # the grid is the whole pipeline surface


def test_simulate_specs_rejects_mislaid_exchange():
    s = sch.build_schedule(
        global_shape=(16, 16), ndim=2, decomp="slab", axis_name="x",
        p=4, backend="scatter",
    )
    bad_stages = tuple(
        dataclasses.replace(st, axis="nope") if isinstance(st, sch.Exchange) else st
        for st in s.stages
    )
    bad = dataclasses.replace(s, stages=bad_stages)
    with pytest.raises(ValueError, match="mesh axis"):
        sch.simulate_specs(bad, 2)


# ---------------------------------------------------------------------------
# Online alpha/beta refinement from observed Exchange spans
# ---------------------------------------------------------------------------


def _span(backend, p, block_bytes, dur, n_chunks=None):
    args = {"backend": backend, "p": p, "block_bytes": float(block_bytes),
            "wire_bytes": float(block_bytes) * (1 - 1 / p)}
    if n_chunks is not None:
        args["n_chunks"] = n_chunks
    return {"cat": "exchange", "args": args, "dur": dur}


def test_refine_online_recovers_synthetic_constants():
    alpha, beta = 2e-6, 1e10
    spans = []
    for block in (100 * 1024, 400 * 1024, 1 << 20):
        msgs, fit_bytes = exchange_fit_terms("scatter", 8, float(block), 8)
        spans.append(_span("scatter", 8, block, alpha * msgs + fit_bytes / beta, 8))
    assert len({payload_class(s["args"]["wire_bytes"]) for s in spans}) == 1
    base = CommParams()
    fits = base.refine_online(spans)
    key = ("scatter", payload_class(spans[0]["args"]["wire_bytes"]))
    assert key in fits and ("*", "*") in fits
    fitted = fits[key]
    assert fitted is not base  # frozen: a new instance, self untouched
    assert fitted.alpha_s == pytest.approx(alpha, rel=1e-6)
    assert fitted.beta_bytes_s == pytest.approx(beta, rel=1e-6)
    pooled = fits[("*", "*")]
    assert pooled.alpha_s == pytest.approx(alpha, rel=1e-6)


def test_refine_online_degenerate_keeps_defaults():
    base = CommParams()
    # one span: under min_spans -> keep the frozen constants
    fits = base.refine_online([_span("scatter", 8, 1 << 20, 1e-3, 8)])
    assert fits[("*", "*")] is base
    # rank-1 system (identical sizes) -> unidentifiable, keep constants
    fits = base.refine_online([_span("alltoall", 8, 1 << 20, 1e-3)] * 3)
    assert fits[("alltoall", payload_class((1 << 20) * (1 - 1 / 8)))] is base
    # junk spans are skipped, not crashed on
    fits = base.refine_online([{"cat": "exchange", "args": {}, "dur": -1}])
    assert fits[("*", "*")] is base


def test_refine_online_accepts_trace_recorder():
    rec = TraceRecorder(FakeClock())
    alpha, beta = 5e-6, 2e10
    for block in (128 * 1024, 512 * 1024, 1 << 21):
        msgs, fit_bytes = exchange_fit_terms("bisection", 8, float(block))
        rec.add_span(
            "row:x", 0.0, alpha * msgs + fit_bytes / beta, cat="exchange",
            args={"backend": "bisection", "p": 8, "block_bytes": float(block),
                  "wire_bytes": float(block) * (1 - 1 / 8)},
        )
    rec.add_span("LocalFFT", 0.0, 9.9, cat="stage")  # must not pollute the fit
    fits = CommParams().refine_online(rec)
    pooled = fits[("*", "*")]
    assert pooled.alpha_s == pytest.approx(alpha, rel=1e-6)
    assert pooled.beta_bytes_s == pytest.approx(beta, rel=1e-6)


def test_exchange_fit_terms_shapes():
    # ring: (p-1)*q messages of the wire payload
    msgs, b = exchange_fit_terms("scatter", 8, 1024.0, 8)
    assert msgs == 7.0 and b == pytest.approx(1024.0 * 7 / 8)
    # bisection: log2(p) rounds of half the block
    msgs, b = exchange_fit_terms("bisection", 8, 1024.0)
    assert msgs == 3.0 and b == pytest.approx(3 * 512.0)
    # single shard: no communication
    assert exchange_fit_terms("scatter", 1, 1024.0) == (0.0, 0.0)
    # unknown backends take the one-phase all-to-all shape
    assert exchange_fit_terms("mystery", 4, 1024.0)[0] == 1.0


# ---------------------------------------------------------------------------
# Wisdom observed-timings channel
# ---------------------------------------------------------------------------


def _fake_plan(key, backend):
    return types.SimpleNamespace(wisdom_key=key, backend=backend)


def test_record_observed_running_mean_and_reargmin():
    planner.forget_wisdom()
    key = ("test", "obs")
    planner._WISDOM[key] = {
        "timings": {"scatter": 1.0, "bisection": 2.0},
        "backend": "scatter",
    }
    try:
        plan = _fake_plan(key, "scatter")
        assert planner.record_observed(plan, 3.0)
        assert planner.record_observed(plan, 5.0)
        entry = planner._WISDOM[key]
        cell = entry["observed"]["scatter"]
        assert cell["n"] == 2 and cell["s"] == pytest.approx(4.0)
        # observed mean outranks the race median in the effective table...
        eff = planner.effective_timings(entry)
        assert eff == {"scatter": pytest.approx(4.0), "bisection": 2.0}
        # ...so the pinned decision flips to what production actually saw
        assert entry["backend"] == "bisection"
    finally:
        planner.forget_wisdom()


def test_record_observed_no_ops():
    planner.forget_wisdom()
    try:
        # no wisdom_key (estimate-planner plan) -> False
        assert not planner.record_observed(types.SimpleNamespace(backend="x"), 1.0)
        key = ("k",)
        planner._WISDOM[key] = {"timings": {"scatter": 1.0}, "backend": "scatter"}
        plan = _fake_plan(key, "scatter")
        assert not planner.record_observed(plan, 0.0)
        assert not planner.record_observed(plan, float("nan"))
        assert not planner.record_observed(_fake_plan(("gone",), "scatter"), 1.0)
        assert "observed" not in planner._WISDOM[key]
    finally:
        planner.forget_wisdom()


def test_merge_wisdom_entry_unions_observed():
    a = {"timings": {"scatter": 1.0, "alltoall": 3.0}, "backend": "scatter",
         "count": 1, "observed": {"scatter": {"n": 1, "s": 9.0}}}
    b = {"timings": {"scatter": 2.0, "alltoall": 3.0}, "backend": "scatter",
         "count": 1, "observed": {"scatter": {"n": 3, "s": 1.0},
                                  "bad": "junk"}}
    merged = planner.merge_wisdom_entry(a, b)
    cell = merged["observed"]["scatter"]
    assert cell["n"] == 4 and cell["s"] == pytest.approx(3.0)
    assert "bad" not in merged["observed"]
    # argmin runs over the effective table: observed scatter mean (3.0)
    # equal to alltoall race (3.0) -> tie broken by sorted name order
    assert merged["backend"] == "alltoall"


# ---------------------------------------------------------------------------
# 8-device acceptance: traced executor + Plan.profile + HLO stability
# ---------------------------------------------------------------------------

_TRACED_CODE = r"""
import dataclasses, hashlib
import jax, jax.numpy as jnp
import numpy as np
from repro.core import plan_fft
from repro.core import schedule as sch
from repro.core.compat import make_mesh
from repro.obs import TraceRecorder

mesh = make_mesh((8,), ("x",))
plan = plan_fft((64, 64), mesh, backend="scatter")
built = plan.schedule(False)

h0 = hashlib.sha256(plan.lower().as_text().encode()).hexdigest()
res = plan.profile(reps=2, warmup=1, record=False)
h1 = hashlib.sha256(plan.lower().as_text().encode()).hexdigest()
assert h0 == h1, "profiling changed the untraced hot path's HLO"
print("PASS hlo-stable")

exchanges = [st for st in built.stages if isinstance(st, sch.Exchange)]
assert len(exchanges) >= 1
# exactly one Exchange span per schedule Exchange stage per timed rep
ex_spans = res.trace.exchange_spans()
assert len(ex_spans) == res.reps * len(exchanges), (len(ex_spans), len(exchanges))
print("PASS span-count")

rows = res.exchange_rows()
assert len(rows) == len(exchanges)
c_item = jnp.dtype(jnp.complex64).itemsize
total = sum(r.wire_bytes for r in rows)
want = sch.schedule_comm_bytes(built, c_item // 2, c_item)
assert total == want, (total, want)  # exact, not approx: same byte walk
print("PASS wire-bytes")

# one observed row per schedule stage: Twiddle rides its Exchange, the
# conj/scale epilogue is its own span
n_tw = sum(isinstance(st, sch.Twiddle) for st in built.stages)
n_extra = int(built.conj) + int(built.conj or built.scale is not None)
assert len(res.rows) == len(built.stages) - n_tw + n_extra, (
    len(res.rows), len(built.stages), n_tw, n_extra)
assert all(r.observed_s > 0 for r in res.rows)
assert all(r.predicted_s is not None for r in res.exchange_rows())
tbl = res.table()
assert "observed us" in tbl and "wire bytes" in tbl
print("PASS row-per-stage")

# traced and untraced executors agree numerically
rng = np.random.default_rng(0)
hx = (rng.standard_normal((64, 64)) + 1j * rng.standard_normal((64, 64))).astype("complex64")
x = jax.device_put(jnp.asarray(hx), plan.input_spec().sharding)
rec = TraceRecorder()
y_t = np.asarray(sch.run_schedule(x, built, mesh, trace=rec))
y_u = np.asarray(sch.run_schedule(x, built, mesh))
np.testing.assert_allclose(y_t, y_u, rtol=2e-4, atol=2e-4)
assert len(rec.exchange_spans()) == len(exchanges)
print("PASS traced-numerics")

# trace artifact is loadable Chrome JSON with the Exchange attributes
doc = res.trace.to_chrome_trace()
exev = [e for e in doc["traceEvents"] if e.get("cat") == "exchange"]
assert exev and all(
    e["args"]["backend"] == "scatter" and e["args"]["wire_bytes"] > 0
    and "role" in e["args"] and "n_chunks" in e["args"] for e in exev)
print("PASS chrome-args")
"""


def test_traced_executor_acceptance_8dev():
    out = run_subprocess(_TRACED_CODE, devices=8)
    for tag in ("hlo-stable", "span-count", "wire-bytes", "row-per-stage",
                "traced-numerics", "chrome-args"):
        assert f"PASS {tag}" in out, out


_MEASURED_CODE = r"""
from repro.core import plan_fft, planner
from repro.core.comm_model import CommParams
from repro.core.compat import make_mesh

mesh = make_mesh((8,), ("x",))
plan = plan_fft((32, 32), mesh, planner="measure")
assert plan.wisdom_key is not None
res = plan.profile(reps=1, warmup=1)  # record=True folds into wisdom
entry = dict(planner.wisdom_items())[plan.wisdom_key]
obs = entry.get("observed", {})
assert plan.backend in obs and obs[plan.backend]["n"] == 1
eff = planner.effective_timings(entry)
assert eff[plan.backend] == obs[plan.backend]["s"]
print("PASS observed-channel")

fits = CommParams().refine_online(res.trace)
assert ("*", "*") in fits and all(
    isinstance(v, CommParams) for v in fits.values())
print("PASS refine-online")
"""


@pytest.mark.slow
def test_profile_feeds_wisdom_observed_8dev():
    out = run_subprocess(_MEASURED_CODE, devices=8)
    assert "PASS observed-channel" in out and "PASS refine-online" in out, out


_GLOBAL_CODE = r"""
import jax, jax.numpy as jnp
import numpy as np
from repro.core import plan_fft
from repro.core.compat import make_mesh

mesh = make_mesh((8,), ("x",))
plan = plan_fft((32, 32), mesh, backend="xla_auto")
res = plan.profile(reps=1, warmup=1, record=False)
(row,) = res.rows
assert row.stage.startswith("global:") and row.kind == "Global"
assert row.observed_s > 0
print("PASS global-span")
"""


def test_global_backend_traces_one_span_8dev():
    out = run_subprocess(_GLOBAL_CODE, devices=8)
    assert "PASS global-span" in out, out
