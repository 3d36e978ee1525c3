"""The check that decides ``correct``, at a size a CPU holds: sound runs
pass it, and the control and every fault that a cell can have fail it.
The control's and the faults' readings at the cells' own sizes are in
PERF.md (chip runs through ``bench/faults.py``)."""

import json

import pytest

import faults
import run

SMALL = {
    "fft2_16k_p1": ("paper_fft2_16k_p1", {"shape": [64, 64]}),
    "fft2_16k_p4": ("paper_fft2_16k_p4", {"shape": [64, 64]}),
}
CELL_FAULTS = {
    "fft2_16k_p1": ["control", "state_unchanged", "altered_answer"],
    "fft2_16k_p4": ["control", "state_unchanged", "no_exchange", "altered_answer"],
}
SECONDS = 0.5


def small_config(workload: str) -> dict:
    name, changes = SMALL[workload]
    return dict(run.load_json(run.BENCH / "configs" / f"{name}.json"), **changes)


def quiet(*_):
    pass


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_sound_run_is_correct(workload):
    result = run.run_cell(workload, 2**31 + 7, SECONDS, False, config=small_config(workload),
                          require_tpu=False, log=quiet)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    json.dumps(result)


@pytest.mark.parametrize(
    "workload,fault", [(w, f) for w in sorted(CELL_FAULTS) for f in CELL_FAULTS[w]]
)
def test_fault_is_caught(workload, fault):
    result = faults.run_with_fault(workload, fault, 3, SECONDS, config=small_config(workload),
                                   require_tpu=False, log=quiet)
    assert not result["correct"], result["checks"]
