"""The reduction of a profiler trace to the program's own scopes and
host spans (``scope_reduce``): on events made by hand, where every number
is known, and on a trace recorded on four TPU v5e chips of the program
that names its layers (the fft2 cell at 1024^2, recorded the way
``bench/record_fixture.py`` records, with the ``repro.execute`` spans).

The trace names each op by its compiled instruction; the scopes come
from the plan's compiled HLO. Scopes are metadata, which the persistent
compile cache leaves out of its key, so an executable read from the
cache carries the op paths of whichever version compiled it; the HLO
here is a fresh compile, whose instructions are the recorded ones."""

from pathlib import Path
from types import SimpleNamespace

import pytest

import scope_reduce as sr
import trace_reduce as tr

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
FIXTURE = TESTDATA / "fft2_1024_p4_scoped.xplane.pb"
#: the plan's compiled HLO at that size, from the TPU compiler for a
#: described v5e:2x2
FIXTURE_HLO = TESTDATA / "fft2_1024_p4_scoped.hlo.txt"
PLAN = "jit(<lambda>)/shard_map/"


def ev(name, start, end):
    return SimpleNamespace(name=name, start_ns=start, end_ns=end)


def hlo(module, paths):
    lines = [f"HloModule {module}, entry_computation_layout={{()}}", "ENTRY %main {"]
    lines += [f'  %{i} = f32[8]{{0}} copy(f32[8]{{0}} %a), metadata={{op_name="{p}"}}'
              for i, p in paths.items()]
    return "\n".join(lines + ["}"])


@pytest.mark.parametrize("opcode,path,want", [
    ("all-to-all", PLAN + "repro.stage1.Exchange/repro.exchange/all_to_all", "exchange"),
    ("copy", PLAN + "repro.stage1.Exchange/repro.exchange/all_to_all", "relayout"),
    ("copy", PLAN + "repro.stage1.Exchange/repro.relayout/transpose", "relayout"),
    ("fusion", PLAN + "repro.stage0.LocalFFT/repro.local_fft/jit(fft)/dot", "local_fft"),
    ("fusion", PLAN + "repro.stage3.Exchange/repro.stage2.Twiddle/repro.twiddle/mul", "twiddle"),
    ("custom-call", "x", "unscoped"),
    ("copy", "", "unscoped"),
])
def test_bucket(opcode, path, want):
    assert sr.bucket("op.1", opcode, path) == want


def test_stage_of_takes_the_innermost():
    assert sr.stage_of(PLAN + "repro.stage3.Exchange/repro.stage2.Twiddle/repro.twiddle/mul") \
        == "repro.stage2.Twiddle"
    assert sr.stage_of(PLAN + "repro.relayout/conj") == ""


def test_innermost_cover():
    spans = [(0, 100, "bench.submit"), (20, 60, "repro.execute")]
    assert sr.innermost_cover(10, 80, spans) == {"bench.submit": 30, "repro.execute": 40}
    assert sr.innermost_cover(200, 210, spans) == {"no host span": 10}


@pytest.fixture
def by_hand():
    # one step, window 0..10_000 ns: the plan's module runs a split of the
    # argument (unscoped) 1000-2000, a local FFT 2000-5000, the collective
    # 5000-6000, its pack 6000-6500, a transpose 6500-7000; the harness's
    # own module 8000-9000. The host dispatches from 0 to 1000, 200-900
    # of it inside repro.execute, and waits from 1000 to 10_000.
    paths = {"custom-call.1": "x",
             "fusion.1": PLAN + "repro.stage0.LocalFFT/repro.local_fft/jit(fft)/dot",
             "all-to-all.1": PLAN + "repro.stage1.Exchange/repro.exchange/all_to_all",
             "copy.1": PLAN + "repro.stage1.Exchange/repro.exchange/all_to_all",
             "copy.2": PLAN + "repro.stage1.Exchange/repro.relayout/transpose"}
    ops = [ev('%custom-call.1 = f32[8] custom-call(c64[8] %x), custom_call_target="X64SplitLow"',
              1000, 2000),
           ev("%fusion.1 = f32[8] fusion(f32[8] %a)", 2000, 5000),
           ev("%all-to-all.1 = f32[8] all-to-all(f32[8] %b)", 5000, 6000),
           ev("%copy.1 = f32[8] copy(f32[8] %c)", 6000, 6500),
           ev("%copy.2 = f32[8] copy(f32[8] %d)", 6500, 7000),
           ev("%copy.3 = f32[8] copy(f32[8] %e)", 8000, 9000)]
    modules = [ev("jit__lambda(1)", 1000, 7000), ev("jit_sampled_lines(2)", 8000, 9000)]
    dev = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name=tr.OP_LINE, events=ops),
        SimpleNamespace(name=tr.MODULE_LINE, events=modules)])
    host = [ev("bench.window", 0, 10_000), ev("bench.submit", 0, 1000),
            ev("repro.execute", 200, 900), ev("repro.row:x", 300, 400),
            ev("bench.step", 1000, 10_000), ev("jit_fft", 250, 350)]
    hostp = SimpleNamespace(name="/host:CPU",
                            lines=[SimpleNamespace(name="python", events=host)])
    texts = [hlo("jit__lambda", paths), hlo("jit_sampled_lines", {"copy.3": "jit(f)/gather"})]
    return sr.reduce_profile(SimpleNamespace(planes=[hostp, dev]), "bench.step", texts)


def test_metrics_by_hand(by_hand):
    m = by_hand.metrics()
    assert by_hand.steps == 1
    assert m["local_fft_scoped_ms"] == pytest.approx(3e-3)
    assert m["relayout_scoped_ms"] == pytest.approx(1e-3)
    assert m["unscoped_ms"] == pytest.approx(1e-3)
    # 700 ns of repro.execute, less the 100 ns of a nested repro. span
    assert m["dispatch_ms"] == pytest.approx(6e-4)
    assert by_hand.bucket_ms("exchange") == pytest.approx(1e-3)


def test_consistency_by_hand(by_hand):
    c = by_hand.consistency()
    assert c["buckets_ms"] == pytest.approx(6e-3) and c["rel_diff"] == pytest.approx(0.0)


def test_stages_by_hand(by_hand):
    assert by_hand.stages() == [["repro.stage0.LocalFFT", pytest.approx(3e-3)],
                                ["repro.stage1.Exchange", pytest.approx(2e-3)]]
    assert ["repro.stage1.Exchange", "exchange", pytest.approx(1e-3)] in by_hand.stage_layers()
    assert by_hand.unscoped_ops() == [["custom-call[X64SplitLow]", pytest.approx(1e-3)]]


def test_idle_gaps_by_hand(by_hand):
    gaps, by_span = by_hand.idle()
    # idle 0-1000 (under bench.submit and, 200-900, repro.execute and a
    # nested span), 7000-8000 and 9000-10_000 (under bench.step)
    assert sorted(name for name, _ in gaps) == ["bench.step", "bench.step", "repro.execute"]
    assert [s for _, s in gaps] == [pytest.approx(1e-6)] * 3
    assert by_span == {"bench.submit": pytest.approx(3e-7), "repro.execute": pytest.approx(6e-7),
                       "repro.row:x": pytest.approx(1e-7), "bench.step": pytest.approx(2e-6)}


def test_no_scoped_module_reads_nothing():
    # a program that names nothing (the trace of an unscoped program):
    # no device metric, no stage
    ops = [ev("%fusion.1 = f32[8] fusion(f32[8] %a)", 0, 10)]
    dev = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name=tr.OP_LINE, events=ops),
        SimpleNamespace(name=tr.MODULE_LINE, events=[ev("jit__lambda(1)", 0, 10)])])
    host = SimpleNamespace(name="/host:CPU", lines=[SimpleNamespace(
        name="python", events=[ev("bench.window", 0, 20), ev("bench.step", 0, 20)])])
    red = sr.reduce_profile(SimpleNamespace(planes=[host, dev]), "bench.step",
                            [hlo("jit__lambda", {"fusion.1": "jit(<lambda>)/jit(fft)/dot"})])
    assert red.metrics() == {} and red.stages() == []


@pytest.fixture(scope="module")
def recorded():
    return sr.reduce_xspace(FIXTURE, "bench.step", [FIXTURE_HLO.read_text()])


def test_fixture_reads_the_four_metrics(recorded):
    m = recorded.metrics()
    assert set(m) == {"dispatch_ms", "local_fft_scoped_ms", "relayout_scoped_ms", "unscoped_ms"}
    assert all(v > 0 for v in m.values())
    assert len(recorded.chips) == 4 and recorded.steps >= 3


def test_fixture_buckets_account_for_the_plan(recorded):
    c = recorded.consistency()
    assert c["rel_diff"] <= 0.01, c


def test_fixture_old_classes_unchanged(recorded):
    # the reduction by op path reads the scoped program as it read the
    # unscoped one: its local FFT time is the scoped local FFT time, and
    # its exchange holds the plan's collectives (and the harness's own, in
    # jit(sampled_lines), which the scope reduction leaves out)
    old = tr.reduce_xspace(FIXTURE, "bench.step", tr.hlo_op_paths([FIXTURE_HLO.read_text()]))
    assert old.per_step_max(lambda d: d.class_s("local_fft")) * 1e3 == pytest.approx(
        recorded.bucket_ms("local_fft"))
    assert 0 < recorded.bucket_ms("exchange") <= old.per_step_max(
        lambda d: d.class_s("exchange")) * 1e3


def test_fixture_stages(recorded):
    stages = recorded.stages()
    assert [s for s, _ in stages] == ["repro.stage0.LocalFFT", "repro.stage1.Exchange"]
    assert all(ms > 0 for _, ms in stages)


def test_fixture_gap_inside_execute(recorded):
    gaps, by_span = recorded.idle()
    assert by_span.get(sr.EXECUTE_SPAN, 0) > 0
    assert sr.EXECUTE_SPAN in [name for name, _ in gaps]
