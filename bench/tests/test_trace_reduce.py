"""The reduction from a profiler trace to per-layer times: on events made
by hand, where every number is known, and on a trace recorded on four
TPU v5e chips (``bench/record_fixture.py``: the fft2 cell at 1024^2)."""

from pathlib import Path
from types import SimpleNamespace

import pytest

import trace_reduce as tr
import work

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
FIXTURE = TESTDATA / "fft2_1024_p4.xplane.pb"
#: the plan's compiled HLO at that size, from the TPU compiler for a
#: described v5e:2x2 (the same compiler as on the chip)
FIXTURE_HLO = TESTDATA / "fft2_1024_p4.hlo.txt"


def ev(name, start, end, **stats):
    return SimpleNamespace(name=name, start_ns=start, end_ns=end, duration_ns=end - start,
                           stats=stats)


def profile(device_ops, host):
    line = SimpleNamespace(name=tr.OP_LINE, events=device_ops)
    other = SimpleNamespace(name="XLA Modules", events=[ev("jit_fft", 0, 10_000)])
    dev = SimpleNamespace(name="/device:TPU:0", lines=[line, other])
    hostp = SimpleNamespace(name="/host:CPU", lines=[SimpleNamespace(name="python", events=host)])
    return SimpleNamespace(planes=[hostp, dev])


@pytest.mark.parametrize("text,path,want", [
    ("%fft.3 = c64[64,64]{1,0} fft(c64[64,64]{1,0} %p), fft_type=FFT", "", "local_fft"),
    ("%fusion.7 = f32[16384,128,128]{1,2,0} fusion(f32[16384,128,128]{2,1,0} "
     "%convolution_add_fusion.3), kind=kOutput", "jit(<lambda>)/jit(fft)", "local_fft"),
    ("%fusion.7 = f32[16384,128,128]{1,2,0} fusion(f32[16384,128,128]{2,1,0} "
     "%convolution_add_fusion.3), kind=kOutput", "", "relayout"),
    ("%convolution_add_fusion.3 = f32[16384,128,128]{2,1,0} fusion(f32[1] %a)", "", "local_fft"),
    ("%all-to-all.1 = c64[4,256,1024]{2,1,0} all-to-all(c64[4,256,1024]{2,1,0} %x)", "",
     "exchange"),
    ("%all-gather-start.2 = (f32[8], f32[32]) all-gather-start(f32[8] %y)", "", "exchange"),
    ("%collective-permute-done = f32[8] collective-permute-done(f32[8] %z)", "", "exchange"),
    ("%copy.33 = c64[16384,16384]{1,0:T(8,128)} copy(c64[16384,16384]{0,1:T(8,128)} %c)",
     "jit(<lambda>)/jit(fft)", "local_fft"),
    ("%copy.21 = c64[16384,16384]{1,0} copy(c64[16384,16384]{0,1} %b)",
     "jit(<lambda>)/transpose", "relayout"),
    ('%custom-call.1 = f32[8]{0} custom-call(c64[8]{0} %x.1), custom_call_target="X64SplitLow"',
     "x", "relayout"),
    ('%custom-call.3 = f32[8]{0} custom-call(f32[8]{0} %a), custom_call_target="tpu_custom_call"',
     "", "local_fft"),
])
def test_op_class(text, path, want):
    instr, opcode, target = tr.parse_event(text)
    assert tr.op_class(instr, opcode, path, target) == want


def test_op_paths_from_hlo():
    text = ("HloModule jit__lambda, entry_computation_layout={()}\n"
            "ENTRY %main {\n"
            '  %copy.21 = c64[8]{0} copy(c64[8]{0} %b), metadata={op_name="jit(<lambda>)/transpose"}\n'
            '  ROOT %fusion.7 = f32[8]{0} fusion(f32[8]{0} %a), metadata={op_name="jit(f)/jit(fft)/dot.4"}\n'
            "}\n")
    assert tr.hlo_op_paths([text]) == {
        "jit__lambda": {"copy.21": "jit(<lambda>)/transpose", "fusion.7": "jit(f)/jit(fft)/dot.4"}
    }


def test_exposed_exchange_and_idle_by_hand():
    # window 1000..11000 ns; fft 1000-4000, collective 3000-7000 (1000 ns
    # under the fft, 3000 exposed), copy 8000-9000; idle 7000-8000 and
    # 9000-11000, the last under the host's copy span
    ops = [ev("%fft.1 = c64[8] fft(c64[8] %a)", 1000, 4000),
           ev("%all-to-all.1 = c64[8] all-to-all(c64[8] %b)", 3000, 7000),
           ev("%copy.1 = c64[8] copy(c64[8] %c)", 8000, 9000),
           ev("%fft.2 = c64[8] fft(c64[8] %d)", 12_000, 13_000)]  # outside the window
    host = [ev("bench.window", 1000, 11_000), ev("bench.step", 1000, 6000),
            ev("bench.step", 6000, 10_500), ev("bench.copy_to_host", 9000, 11_000)]
    red = tr.reduce_profile(profile(ops, host), steps_span="bench.step")
    (dev,) = red.devices
    assert red.steps == 2 and red.window_s == pytest.approx(1e-5)
    assert dev.class_s("local_fft") == pytest.approx(3e-6)
    assert dev.class_s("exchange") == pytest.approx(4e-6)
    assert dev.exposed_exchange_s == pytest.approx(3e-6)
    assert dev.busy_s == pytest.approx(7e-6)
    assert red.idle_share_pct() == pytest.approx(30.0)
    gaps = red.breakdown()["idle_gaps"]
    assert gaps[0] == ["bench.copy_to_host", pytest.approx(2e-6)]
    assert gaps[1][1] == pytest.approx(1e-6)
    assert red.per_step_max(lambda d: d.class_s("exchange")) == pytest.approx(2e-6)


@pytest.fixture(scope="module")
def recorded():
    paths = tr.hlo_op_paths([FIXTURE_HLO.read_text()])
    return tr.reduce_xspace(FIXTURE, "bench.step", paths)


def test_fixture_classes(recorded):
    assert len(recorded.devices) == 4 and recorded.steps >= 3
    for dev in recorded.devices:
        for cls in ("local_fft", "exchange", "relayout"):
            assert dev.class_s(cls) > 0, (dev.name, cls)
        assert 0 <= dev.exposed_exchange_s <= dev.class_s("exchange") + 1e-12
        assert dev.busy_s <= recorded.window_s
    assert 0 < recorded.busy_s < recorded.window_s
    assert 0 < recorded.idle_share_pct() < 100


def test_fixture_roofline_at_most_100(recorded):
    per_step = recorded.per_step_max(lambda d: d.class_s("local_fft"))
    least = work.hbm_roofline_s([1024, 1024], "complex64", 4, 819e9)
    assert 0 < 100 * least / per_step <= 100


def test_fixture_breakdown(recorded):
    out = recorded.breakdown()
    assert 0 < len(out["device_ops"]) <= tr.TOP and len(out["idle_gaps"]) <= tr.TOP
    assert all(isinstance(s, float) and s > 0 for _, s in out["device_ops"] + out["idle_gaps"])
