"""Fault-tolerant execution: chaos injection on the schedule IR
(FaultPlan modes, determinism, trace spans), per-request isolation and
graceful degradation in serving (batch poison -> solo retry ->
quarantine; circuit breaker -> xla_auto), planner race failure
isolation, recovery primitives (backoff, Resume, FailureInjector,
corrupt-skip checkpoints), and the 8-device elastic remesh-and-replan
acceptance: a P=8 run that loses half its devices resumes at P=4 from
checkpoint bitwise identical to an uninterrupted P=4 run."""

import numpy as np
import pytest

from conftest import run_subprocess

jax = pytest.importorskip("jax")
jnp = jax.numpy

from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.core import backends, plan_fft, planner, schedule as sch  # noqa: E402
from repro.core.compat import make_mesh, make_mesh_1d  # noqa: E402
from repro.obs.trace import TraceRecorder  # noqa: E402
from repro.runtime import (  # noqa: E402
    CircuitBreaker,
    DeviceLossFault,
    FailureInjector,
    FaultPlan,
    FaultSpec,
    InjectedFault,
    Resume,
    RetryPolicy,
    SimulatedFailure,
    backoff_delay,
    elastic_mesh,
    run_with_recovery,
)
from repro.serve import SpectralEngine  # noqa: E402


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class AutoClock:
    """Advances on every read -- makes wall-clock budgets elapse without
    sleeping."""

    def __init__(self, dt=1.0):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


@pytest.fixture
def mesh1():
    return make_mesh((1,), ("model",))


@pytest.fixture(autouse=True)
def _fresh_wisdom():
    planner.forget_wisdom()
    yield
    planner.forget_wisdom()


def _x(n=16, seed=0, batch=None):
    return _rect((n, n) if batch is None else (batch, n, n), seed)


def _rect(shape, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)


def _want(x):
    """Slab fft2 output layout (no transpose_back): transposed spectrum."""
    return np.swapaxes(np.fft.fft2(x), -1, -2)


# ------------------------------------------------------------ FaultPlan
class TestFaultPlan:
    def test_error_fires_records_then_exhausts(self, mesh1):
        plan = plan_fft((16, 16), mesh1, faults=FaultPlan.error(match="Exchange"))
        x = _x()
        with pytest.raises(InjectedFault, match="Exchange"):
            plan.execute(jnp.asarray(x))
        assert plan.faults.injected == 1
        [ev] = plan.faults.events
        assert ev["mode"] == "error" and "Exchange" in ev["stage"]
        # exhausted -> active() False -> back on the fast jitted path,
        # numerics clean
        assert not plan.faults.active()
        np.testing.assert_allclose(
            np.asarray(plan.execute(jnp.asarray(x))), _want(x), rtol=1e-5, atol=1e-6
        )

    def test_stall_uses_injected_sleep_and_still_computes(self, mesh1):
        slept = []
        fp = FaultPlan.stall(0.25, match="Exchange", sleep=slept.append)
        plan = plan_fft((16, 16), mesh1, faults=fp)
        x = _x()
        got = np.asarray(plan.execute(jnp.asarray(x)))
        assert slept == [0.25] and fp.stalled_s == 0.25
        np.testing.assert_allclose(got, _want(x), rtol=1e-5, atol=1e-6)

    def test_device_loss_carries_survivor_count(self, mesh1):
        plan = plan_fft((16, 16), mesh1, faults=FaultPlan.device_loss(4))
        with pytest.raises(DeviceLossFault) as ei:
            plan.execute(jnp.asarray(_x()))
        assert ei.value.alive == 4
        assert isinstance(ei.value, InjectedFault)  # one except-clause catches both

    def test_match_selectivity(self, mesh1):
        fp = FaultPlan.error(match="no-such-stage")
        plan = plan_fft((16, 16), mesh1, faults=fp)
        x = _x()
        np.testing.assert_allclose(
            np.asarray(plan.execute(jnp.asarray(x))), _want(x), rtol=1e-5, atol=1e-6
        )
        assert fp.events == [] and fp.active()  # armed but never matched

    def test_global_backend_label(self, mesh1):
        fp = FaultPlan.error(match="global:")
        plan = plan_fft((16, 16), mesh1, backend="xla_auto", faults=fp)
        with pytest.raises(InjectedFault, match="global:"):
            plan.execute(jnp.asarray(_x()))

    def test_times_caps_consecutive_firings(self):
        fp = FaultPlan((FaultSpec("error", match="Exchange", times=2),))
        fired = []
        for k in range(4):
            try:
                fp.on_stage("Exchange(test)", index=k)
            except InjectedFault:
                fired.append(k)
        assert fired == [0, 1]  # matches 0 and 1 fire, then exhausted
        assert not fp.active()

    def test_at_every_schedule(self):
        fp = FaultPlan((FaultSpec("error", match="", at=1, every=2, times=2),))
        fired = []
        for k in range(6):
            try:
                fp.on_stage("anything", index=k)
            except InjectedFault:
                fired.append(k)
        assert fired == [1, 3]

    def test_rate_is_seed_deterministic(self):
        def pattern(seed):
            fp = FaultPlan.rate(0.5, seed=seed)
            out = []
            for _ in range(32):
                try:
                    fp.on_stage("Exchange(x)")
                    out.append(0)
                except InjectedFault:
                    out.append(1)
            return out

        assert pattern(7) == pattern(7)
        assert 0 < sum(pattern(7)) < 32  # actually probabilistic
        assert pattern(7) != pattern(8)

    def test_reset_replays_identically(self):
        fp = FaultPlan.rate(0.5, seed=3)
        first = [_fires(fp) for _ in range(16)]
        fp.reset()
        assert [_fires(fp) for _ in range(16)] == first
        assert fp.events != []  # reset cleared, replay re-recorded

    def test_recorder_stamps_fault_spans(self, mesh1):
        rec = TraceRecorder()
        fp = FaultPlan.error(match="Exchange", recorder=rec)
        plan = plan_fft((16, 16), mesh1, faults=fp)
        with pytest.raises(InjectedFault):
            plan.execute(jnp.asarray(_x()))
        faults = [s for s in rec.spans if s.cat == "fault"]
        assert len(faults) == 1 and faults[0].name == "fault:error"

    def test_traced_injection_leaves_no_half_open_span(self, mesh1):
        rec = TraceRecorder()
        plan = plan_fft((16, 16), mesh1)
        fp = FaultPlan.error(match="Exchange")
        with pytest.raises(InjectedFault):
            sch.run_schedule(
                jnp.asarray(_x()), plan.schedule(), mesh1, trace=rec, faults=fp
            )
        # the raise happened outside any span context: everything
        # recorded is complete (dur stamped), nothing dangling
        assert all(s.dur >= 0.0 for s in rec.spans)
        assert not any(s.cat == "exchange" for s in rec.spans)

    @pytest.mark.parametrize("shape,transpose_back,first", [
        ((16384, 8), False, True),
        ((16384, 8), True, False),
        ((16, 16), False, False),
    ])
    def test_one_device_exchange_point_fires(self, mesh1, shape, transpose_back, first):
        # transposed first, the one-shard schedule has no Exchange stage:
        # its second pass stands where the exchange stood and is the
        # fault point; otherwise the one-shard exchange still is
        fp = FaultPlan.error(match="Exchange(slab:model")
        plan = plan_fft(shape, mesh1, faults=fp, transpose_back=transpose_back)
        assert plan.schedule().transposed_first is first
        x = _rect(shape)
        with pytest.raises(InjectedFault, match="p=1"):
            plan.execute(jnp.asarray(x))
        assert [e["index"] for e in fp.events] == [2 if first else 1]
        want = np.fft.fft2(x) if transpose_back else _want(x)
        np.testing.assert_allclose(
            np.asarray(plan.execute(jnp.asarray(x))), want, rtol=1e-4, atol=1e-4
        )

    def test_bad_specs_rejected(self):
        with pytest.raises(ValueError, match="mode"):
            FaultSpec("explode")
        with pytest.raises(ValueError, match="rate"):
            FaultSpec("error", rate=1.5)


def _fires(fp):
    try:
        fp.on_stage("Exchange(x)")
        return 0
    except InjectedFault:
        return 1


# ------------------------------------------- serving: isolation + retry
class TestServeIsolation:
    def test_batch_poison_isolated_siblings_resolve(self, mesh1):
        eng = SpectralEngine(mesh1, max_batch=4, max_wait_s=100.0,
                             retry=RetryPolicy(max_retries=0))
        xs = [_x(seed=i) for i in range(4)]
        # fault #1 poisons the coalesced batch -> split; fault #2
        # poisons the first solo retry -> that one request quarantines
        eng.set_faults(FaultPlan.error(match="Exchange", times=2))
        futs = [eng.submit("fft", x) for x in xs]
        eng.drain()
        failed = [f for f in futs if f.failed()]
        ok = [f for f in futs if not f.failed()]
        assert len(failed) == 1 and len(ok) == 3
        for f in ok:
            np.testing.assert_allclose(
                np.asarray(f.result()),
                _want(np.asarray(f.request.operands[0])),
                rtol=1e-5, atol=1e-6,
            )
        with pytest.raises(InjectedFault):
            failed[0].result()
        with pytest.raises(InjectedFault):
            failed[0].block()
        m = eng.metrics()
        assert m["errors"] == 2 and m["batch_splits"] == 1
        assert m["quarantined"] == 1 and m["failed_requests"] == 1

    def test_retry_heals_transient_fault(self, mesh1):
        eng = SpectralEngine(mesh1, max_batch=1, retry=RetryPolicy(max_retries=1))
        x = _x()
        eng.submit("fft", x).block()  # warm, healthy
        eng.set_faults(FaultPlan.error(match="Exchange", times=1))
        fut = eng.submit("fft", x)
        eng.drain()
        assert not fut.failed()
        np.testing.assert_allclose(np.asarray(fut.result()), _want(x),
                                   rtol=1e-5, atol=1e-6)
        assert eng.retries == 1 and eng.quarantined == 0 and eng.errors == 1

    @pytest.mark.parametrize("transpose_back", [False, True])
    def test_retry_heals_transient_fault_transposed_first(self, mesh1, transpose_back):
        # the paper's row length: one shard runs transposed first unless
        # transpose_back, and an Exchange fault fires either way
        eng = SpectralEngine(mesh1, max_batch=1, retry=RetryPolicy(max_retries=1),
                             plan_kwargs={"transpose_back": transpose_back})
        x = _rect((16384, 8))
        eng.submit("fft", x).block()
        eng.set_faults(FaultPlan.error(match="Exchange", times=1))
        fut = eng.submit("fft", x)
        eng.drain()
        want = np.fft.fft2(x) if transpose_back else _want(x)
        np.testing.assert_allclose(np.asarray(fut.result()), want, rtol=1e-4, atol=1e-4)
        assert eng.retries == 1 and eng.errors == 1

    def test_retry_deadline_abandons(self, mesh1):
        # every clock read advances 1s -> the 0.5s budget is already
        # spent when the retry loop first checks it
        eng = SpectralEngine(
            mesh1, max_batch=1, clock=AutoClock(1.0),
            retry=RetryPolicy(max_retries=10, deadline_s=0.5),
        )
        x = _x()
        eng.submit("fft", x).block()
        eng.set_faults(FaultPlan.error(match="Exchange", times=5))
        fut = eng.submit("fft", x)
        eng.drain()
        assert fut.failed() and eng.retries == 0 and eng.quarantined == 1

    def test_drain_raise_errors_after_siblings(self, mesh1):
        eng = SpectralEngine(mesh1, max_batch=2, max_wait_s=100.0,
                             retry=RetryPolicy(max_retries=0))
        xs = [_x(seed=i) for i in range(2)]
        eng.set_faults(FaultPlan.error(match="Exchange", times=2))
        futs = [eng.submit("fft", x) for x in xs]
        with pytest.raises(InjectedFault):
            eng.drain(raise_errors=True)
        done = [f for f in futs if not f.failed()]
        assert len(done) == 1 and done[0].done()  # sibling still resolved


# --------------------------------------------------- circuit breaker
class TestCircuitBreaker:
    def test_open_after_threshold_consecutive(self):
        clk = FakeClock()
        br = CircuitBreaker(failure_threshold=3, reset_after_s=10.0, clock=clk)
        for _ in range(2):
            br.record_failure("k")
        assert br.state("k") == "closed" and br.allow("k")
        br.record_success("k")  # success resets the consecutive count
        for _ in range(2):
            br.record_failure("k")
        assert br.state("k") == "closed"
        br.record_failure("k")
        assert br.state("k") == "open" and not br.allow("k")
        assert br.stats() == {"open": 1, "half_open": 0, "opened": 1,
                              "reclosed": 0, "probes": 0}

    def test_half_open_probe_recloses(self):
        clk = FakeClock()
        br = CircuitBreaker(failure_threshold=1, reset_after_s=5.0, clock=clk)
        br.record_failure("k")
        assert not br.allow("k")
        clk.advance(5.0)
        assert br.allow("k") and br.state("k") == "half-open"
        assert not br.allow("k")  # exactly one probe admitted
        br.record_success("k")
        assert br.state("k") == "closed" and br.allow("k")
        st = br.stats()
        assert st["probes"] == 1 and st["reclosed"] == 1

    def test_half_open_probe_failure_reopens(self):
        clk = FakeClock()
        br = CircuitBreaker(failure_threshold=1, reset_after_s=5.0, clock=clk)
        br.record_failure("k")
        clk.advance(5.0)
        assert br.allow("k")
        br.record_failure("k")  # probe failed -> re-open, restart timer
        assert br.state("k") == "open" and not br.allow("k")
        clk.advance(4.9)
        assert not br.allow("k")
        clk.advance(0.2)
        assert br.allow("k")
        assert br.stats()["opened"] == 2

    def test_keys_independent_and_reset(self):
        br = CircuitBreaker(failure_threshold=1, clock=FakeClock())
        br.record_failure("a")
        assert not br.allow("a") and br.allow("b")
        br.reset()
        assert br.allow("a") and br.stats()["open"] == 0


class TestServeDegradation:
    def test_breaker_degrades_to_xla_auto_then_reprobes(self, mesh1):
        clk = FakeClock()
        eng = SpectralEngine(
            mesh1, max_batch=1, clock=clk, retry=RetryPolicy(max_retries=0),
            breaker=CircuitBreaker(failure_threshold=2, reset_after_s=5.0, clock=clk),
        )
        x = _x()
        eng.submit("fft", x).block()  # warm, healthy
        eng.set_faults(FaultPlan.error(match="Exchange", times=2))
        assert eng.submit("fft", x) and eng.drain() is None
        f2 = eng.submit("fft", x)
        eng.drain()
        assert f2.failed()
        # breaker open -> third request degrades to the xla_auto
        # reference schedule (its "global:fft" label dodges the
        # Exchange-matched chaos) and still answers correctly
        f3 = eng.submit("fft", x)
        eng.drain()
        assert not f3.failed() and f3.degraded and f3.backend == "xla_auto"
        np.testing.assert_allclose(np.asarray(f3.result()), _want(x),
                                   rtol=1e-5, atol=1e-6)
        m = eng.metrics()
        assert m["degraded_dispatches"] > 0 and m["breaker_open"] == 1
        assert m["breaker_opened"] == 1
        # cool-down elapses, faults are exhausted: the half-open probe
        # runs the primary backend again and re-closes the key
        clk.advance(6.0)
        f4 = eng.submit("fft", x)
        eng.drain()
        assert not f4.failed() and f4.degraded is False
        st = eng.breaker.stats()
        assert st["open"] == 0 and st["reclosed"] == 1 and st["probes"] == 1
        assert eng.stats()["faults"]["breaker"] == st


# ------------------------------------------------- planner race isolation
class TestPlannerRaceIsolation:
    def _timer(self, table, broken):
        def timer(plan):
            if plan.backend in broken:
                raise RuntimeError("backend exploded")
            return table[plan.backend]

        return timer

    def test_failed_candidate_excluded_not_fatal(self):
        mesh = make_mesh_1d(1)
        names = [n for n in backends.available() if backends.get(n).supports(1)]
        broken = sorted(names)[0]
        table = {n: 1.0 + i for i, n in enumerate(sorted(names))}
        plan = plan_fft((32, 32), mesh, planner="measure",
                        timer=self._timer(table, {broken}))
        assert plan.backend != broken
        assert plan.measured[broken] == float("inf")
        assert "exploded" in plan.race_failures[broken]
        why = plan.why()
        assert broken in why["failed"]
        assert broken not in why["timings"]  # inf excluded from argmin set
        assert "failed candidates" in plan.why_text()
        # wisdom remembers the failure note (finite timings only on disk)
        plan2 = plan_fft((32, 32), mesh, planner="measure",
                         timer=self._timer(table, {broken}))
        assert plan2.wisdom_hit and broken in plan2.race_failures

    def test_all_candidates_failing_raises(self):
        mesh = make_mesh_1d(1)

        def timer(plan):
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="every candidate failed"):
            plan_fft((32, 32), mesh, planner="measure", timer=timer)


# --------------------------------------------------- recovery primitives
class TestElasticPrimitives:
    def test_backoff_deterministic_capped(self):
        import random

        a = [backoff_delay(r, 1.0, cap_s=5.0, rng=random.Random(3)) for r in (1, 2, 3, 4)]
        b = [backoff_delay(r, 1.0, cap_s=5.0, rng=random.Random(3)) for r in (1, 2, 3, 4)]
        assert a == b  # seeded jitter is reproducible
        assert all(d <= 5.0 for d in a)
        assert backoff_delay(10, 1.0, cap_s=5.0) == 5.0  # capped, jitterless
        assert backoff_delay(3, 0.0) == 0.0

    def test_run_with_recovery_resume_and_sleep_sequence(self):
        slept, resumes = [], []

        def loop(resume):
            resumes.append(resume)
            if len(resumes) < 3:
                raise SimulatedFailure(f"crash {len(resumes)}")

        restarts = run_with_recovery(
            loop, max_restarts=3, backoff_s=1.0, jitter=0.0, sleep=slept.append
        )
        assert restarts == 2
        assert slept == [1.0, 2.0]  # exponential, deterministic
        assert resumes[0] is None
        assert resumes[1] == Resume(restarts=1, cause="SimulatedFailure: crash 1")
        assert resumes[2].restarts == 2 and resumes[2].step is None

    def test_run_with_recovery_exhausts(self):
        def loop(resume):
            raise SimulatedFailure("always")

        with pytest.raises(SimulatedFailure):
            run_with_recovery(loop, max_restarts=1, sleep=lambda s: None)

    def test_failure_injector_schedule(self):
        inj = FailureInjector(3, every=2, times=2)
        fired = []
        for s in range(10):
            try:
                inj.maybe_fail(s)
            except SimulatedFailure:
                fired.append(s)
        assert fired == [3, 5] and inj.fired_steps == [3, 5] and inj.fired

    def test_failure_injector_default_once(self):
        inj = FailureInjector(2)
        with pytest.raises(SimulatedFailure):
            inj.maybe_fail(2)
        inj.maybe_fail(2)  # repeatable schedule, but times=1 exhausted
        assert not FailureInjector(None).scheduled(0)

    def test_elastic_mesh_rejects_empty_group(self):
        with pytest.raises(ValueError, match="model_parallel"):
            elastic_mesh(("data", "model"), model_parallel=2,
                         devices=jax.devices()[:1])


# ------------------------------------------------- checkpoint corrupt-skip
class TestCheckpointRobustness:
    def _tree(self):
        return {"x": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)}

    def test_tmp_dirs_invisible(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, self._tree(), blocking=True)
        (tmp_path / "step_0000000009.tmpabc123").mkdir()
        assert mgr.all_steps() == [1]
        assert mgr.latest_step() == 1

    def test_corrupt_manifest_skipped(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=5)
        for s in (1, 2, 3):
            mgr.save(s, self._tree(), blocking=True)
        (tmp_path / "step_0000000003" / "manifest.json").write_text("{not json")
        assert mgr.all_steps() == [1, 2, 3]
        assert mgr.valid_steps() == [1, 2] and mgr.latest_step() == 2
        step, restored = mgr.restore_latest(self._tree())
        assert step == 2
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(self._tree()["x"]))

    def test_missing_shard_skipped(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=5)
        for s in (1, 2):
            mgr.save(s, self._tree(), blocking=True)
        (tmp_path / "step_0000000002" / "proc0.npz").unlink()
        assert mgr.latest_step() == 1

    def test_truncated_npz_falls_back_at_load(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=5)
        for s in (1, 2):
            mgr.save(s, self._tree(), blocking=True)
        npz = tmp_path / "step_0000000002" / "proc0.npz"
        npz.write_bytes(npz.read_bytes()[:20])  # valid-looking, unreadable
        assert mgr.latest_step() == 2  # cheap check cannot see inside
        step, restored = mgr.restore_latest(self._tree())
        assert step == 1 and restored is not None

    def test_no_survivor_returns_none(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        assert mgr.restore_latest(self._tree()) == (None, None)

    def test_atomic_unique_staging(self, tmp_path):
        # two managers racing the same step: neither corrupts the other
        a = CheckpointManager(str(tmp_path))
        b = CheckpointManager(str(tmp_path))
        a.save(1, self._tree(), blocking=True)
        b.save(1, {"x": jnp.ones((2, 3), jnp.float32)}, blocking=True)
        step, restored = a.restore_latest(self._tree())
        assert step == 1
        assert not [f for f in tmp_path.iterdir() if ".tmp" in f.name]


# --------------------------------------------------- 8-device subprocess
CHAOS_CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import plan_fft, planner
from repro.core.compat import make_mesh
from repro.runtime import (CircuitBreaker, FaultPlan, InjectedFault,
                           RetryPolicy, elastic_mesh)
from repro.serve import SpectralEngine

class FakeClock:
    def __init__(self): self.t = 0.0
    def __call__(self): return self.t
    def advance(self, dt): self.t += dt

mesh = make_mesh((8,), ("model",))
n = 32
rng = np.random.default_rng(0)
xs = [(rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
      ).astype(np.complex64) for _ in range(4)]
want = [np.swapaxes(np.fft.fft2(x), -1, -2) for x in xs]

# -- batch poison isolation at P=8 -------------------------------------
eng = SpectralEngine(mesh, max_batch=4, max_wait_s=100.0,
                     retry=RetryPolicy(max_retries=0))
hf = [eng.submit("fft", x) for x in xs]
eng.drain()  # warm + healthy baseline
for f, w in zip(hf, want):
    assert np.allclose(np.asarray(f.result()), w, rtol=1e-4, atol=1e-5)
eng.set_faults(FaultPlan.error(match="Exchange", times=2))
futs = [eng.submit("fft", x) for x in xs]
eng.drain()
failed = [i for i, f in enumerate(futs) if f.failed()]
assert len(failed) == 1, failed
for i, f in enumerate(futs):
    if i in failed:
        try:
            f.result(); raise SystemExit("poisoned future did not re-raise")
        except InjectedFault:
            pass
    else:
        assert np.allclose(np.asarray(f.result()), want[i], rtol=1e-4, atol=1e-5)
m = eng.metrics()
assert m["errors"] == 2 and m["batch_splits"] == 1 and m["quarantined"] == 1
print("PASS poison")

# -- breaker degradation at P=8 ----------------------------------------
clk = FakeClock()
deg = SpectralEngine(mesh, max_batch=1, clock=clk,
                     retry=RetryPolicy(max_retries=0),
                     breaker=CircuitBreaker(failure_threshold=2,
                                            reset_after_s=5.0, clock=clk))
deg.submit("fft", xs[0]).block()
deg.set_faults(FaultPlan.error(match="Exchange", times=2))
for _ in range(2):
    deg.submit("fft", xs[0]); deg.drain()
f3 = deg.submit("fft", xs[0]); deg.drain()
assert f3.degraded and f3.backend == "xla_auto"
assert np.allclose(np.asarray(f3.result()), want[0], rtol=1e-4, atol=1e-5)
dm = deg.metrics()
assert dm["degraded_dispatches"] > 0 and dm["breaker_open"] == 1
clk.advance(6.0)
f4 = deg.submit("fft", xs[0]); deg.drain()
assert not f4.failed() and not f4.degraded
assert deg.breaker.stats()["reclosed"] == 1
print("PASS breaker")

# -- elastic remesh: invalidate + re-warm from wisdom at the new P ------
mesh4 = elastic_mesh(("model",), max_devices=4)
assert mesh4.size == 4
planner.forget_wisdom()
plan_fft((1, n, n), mesh4, planner="measure")  # measured race seeds P=4 wisdom
warmed = eng.remesh(mesh4, wisdom=None, compile=True)
assert warmed >= 1, warmed
assert eng.pool.mesh is mesh4 and eng.mesh is mesh4
assert eng.breaker.stats()["open"] == 0
misses = eng.pool.misses
eng.set_faults(None)
rf = eng.submit("fft", xs[1])
eng.drain()
assert rf.pool_hit and eng.pool.misses == misses  # warm at the new P
assert np.allclose(np.asarray(rf.result()), want[1], rtol=1e-4, atol=1e-5)
print("PASS remesh")
"""

ELASTIC_CODE = r"""
import tempfile
import numpy as np, jax, jax.numpy as jnp
from repro.checkpoint import CheckpointManager
from repro.runtime import (FailureInjector, SimulatedFailure, elastic_mesh,
                           run_with_recovery)
from repro.serve import PlanPool

n = 32
STEPS = 6
FAIL_AT = 3
rng = np.random.default_rng(42)
x0 = (rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
      ).astype(np.complex64)
forcing = [(rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
           ).astype(np.complex64) for _ in range(STEPS)]

# monolithic alltoall, no pipelining: local FFTs + pure data movement,
# so results are bitwise identical at any P (the fused streaming DFT
# decomposes the sum over source ranks and would break parity)
PLAN_KW = dict(decomp="slab", backend="alltoall", pipeline=False)


def run(ckdir, alive, injector=None):
    ckpt = CheckpointManager(ckdir, keep=5)
    out = {}

    def loop(resume):
        mesh = elastic_mesh(("model",), max_devices=alive["n"])
        pool = PlanPool(mesh, plan_kwargs=PLAN_KW)
        plan, _ = pool.get((n, n), 2, jnp.complex64, False)
        state = jnp.asarray(x0)
        start = 0
        latest, restored = ckpt.restore_latest({"x": state})
        if latest is not None:
            state, start = restored["x"], latest
            out.setdefault("resumed_at", (start, mesh.size))
        for step in range(start, STEPS):
            if injector is not None:
                try:
                    injector.maybe_fail(step)
                except SimulatedFailure:
                    alive["n"] = 4  # the crash takes half the ring with it
                    raise
            spec = plan.execute(state + jnp.asarray(forcing[step]))
            state = plan.inverse(spec) * 0.5
            ckpt.save(step + 1, {"x": state}, blocking=True)
        out["x"] = np.asarray(state)

    out["restarts"] = run_with_recovery(loop, max_restarts=2,
                                        sleep=lambda s: None)
    return out


alive = {"n": 8}
inj = FailureInjector(FAIL_AT)
got = run(tempfile.mkdtemp(), alive, inj)
assert inj.fired_steps == [FAIL_AT] and got["restarts"] == 1
assert got["resumed_at"] == (FAIL_AT, 4)  # resumed mid-run on 4 devices
ref = run(tempfile.mkdtemp(), {"n": 4})   # uninterrupted P=4 run
assert ref["restarts"] == 0 and "resumed_at" not in ref
assert np.array_equal(got["x"], ref["x"]), np.max(np.abs(got["x"] - ref["x"]))
ref8 = run(tempfile.mkdtemp(), {"n": 8})  # P=8 parity too: pure movement
assert np.array_equal(got["x"], ref8["x"])
print("PASS elastic")
"""


def test_serve_chaos_8dev():
    out = run_subprocess(CHAOS_CODE, devices=8, timeout=900)
    assert "PASS poison" in out and "PASS breaker" in out and "PASS remesh" in out


def test_elastic_resume_bitwise_parity_8dev():
    out = run_subprocess(ELASTIC_CODE, devices=8, timeout=900)
    assert "PASS elastic" in out
