"""The harness refuses to run where it cannot measure: no TPU, a device
kind without published peaks, or fewer chips than the cell asks for.
Each such run exits non-zero and prints no result line."""

import json
from types import SimpleNamespace

import pytest

import run


def test_no_tpu_no_result(capsys):
    assert run.main(["--workload", "fft2_16k_p1", "--seed", "1", "--seconds", "1"]) != 0
    out = capsys.readouterr()
    assert out.out.strip() == "" or not out.out.strip().splitlines()[-1].startswith("{")
    assert "no TPU" in out.err


def fake_devices(kind: str, n: int):
    dev = SimpleNamespace(platform="tpu", device_kind=kind, memory_stats=lambda: {})
    return lambda: [dev] * n


@pytest.mark.parametrize(
    "kind,n,why", [("TPU v99 imaginary", 1, "not in bench/peaks.json"),
                   ("TPU v5 lite", 1, "the cell asks for 4")],
)
def test_refusals_print_no_result(monkeypatch, capsys, kind, n, why):
    import jax

    monkeypatch.setattr(jax, "devices", fake_devices(kind, n))
    assert run.main(["--workload", "fft2_16k_p4", "--seed", "1", "--seconds", "1"]) != 0
    out = capsys.readouterr()
    assert why in out.err
    assert not [line for line in out.out.splitlines() if line.startswith("{")]


def test_peaks_have_a_source():
    peaks = run.load_json(run.BENCH / "peaks.json")
    assert "cloud.google.com" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12


def test_result_line_is_json_last(monkeypatch, capsys):
    """What ``main`` prints for a run: the checks last on stderr, the
    result as the last stdout line, its checks under the last key."""
    fake = {"correct": True, "attempted": 1, "failed": 0, "metrics": {},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "checks": {"line_err": {"value": 1e-7, "limit": 1e-5}}}
    monkeypatch.setattr(run, "run_cell", lambda *a, **k: fake)
    assert run.main(["--workload", "fft2_16k_p1", "--seed", "1", "--seconds", "1"]) == 0
    out = capsys.readouterr()
    assert json.loads(out.out.splitlines()[-1]) == fake
    assert out.err.splitlines()[-1].startswith("check line_err = 1e-07 limit 1e-05")
