"""The NPB FT cell at a size a CPU holds: it resolves by name, a sound
run is ``correct``, every planted fault (``bench/npb_faults.py``) is not,
and the reference's binned and separable forms equal its whole-array
form. The faults' readings at the cell's own size are in PERF.md (chip
runs through ``bench/npb_faults.py``)."""

import json

import numpy as np
import pytest

import npb_faults
import npb_reference as ref
import run

CELL = "npb_ft_c_p1"
SECONDS = 0.5


def small_config(shape=(32, 32, 32)) -> dict:
    cfg = run.load_json(run.BENCH / "configs" / "npb_ft_c_p1.json")
    return dict(cfg, shape=list(shape))


def quiet(*_):
    pass


def test_cell_resolves_by_name():
    cell = run.resolve(CELL)
    assert cell.config["system"] == "npb_ft" and cell.cell["chips"] == 1
    assert cell.config["shape"] == [512, 512, 512] and cell.config["niter"] == 20
    assert cell.config["alpha"] == 1e-6 and cell.config["reduced"] == []
    assert (run.BENCH / "drivers" / "npb_ft.py").is_file()
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "fft_ms", "peak_hbm_gib"}
    assert {m["name"] for m in cell.per_layer} == {
        "local_fft_ms", "local_fft_hbm_roofline", "relayout_ms", "idle_share.fft2"}


@pytest.mark.parametrize("shape", [(32, 32, 32), (16, 32, 64)])
def test_sound_run_is_correct(shape):
    result = run.run_cell(CELL, 2**31 + 7, SECONDS, False, config=small_config(shape),
                          require_tpu=False, log=quiet)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 21 and result["failed"] == 0
    assert set(result["checks"]) == {"checksum_err", "proj_err", "compiles_in_window"}
    json.dumps(result)


@pytest.mark.parametrize("fault", npb_faults.FAULTS)
def test_fault_is_caught(fault):
    result = npb_faults.run_with_fault(CELL, fault, 3, SECONDS, config=small_config(),
                                       require_tpu=False, log=quiet)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("shape,alpha", [((32, 32, 32), 1e-6), ((16, 32, 64), 1e-3)])
def test_reference_forms_agree(shape, alpha):
    rng = np.random.default_rng(5)
    u = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
    vecs = [rng.standard_normal((4, n)) + 1j * rng.standard_normal((4, n)) for n in shape]
    fast = ref.reference(u, 20, alpha, 13, vecs)
    whole = ref.whole_array(u, 20, alpha, 13, vecs)
    for key in ("checksums", "scale", "proj", "norm"):
        np.testing.assert_allclose(fast[key], whole[key], rtol=1e-10, atol=0, err_msg=key)
    u2 = np.fft.ifftn(np.fft.fftn(u.astype(np.complex128)) * np.exp(
        ref.decay(alpha) * 13 * sum(np.meshgrid(*(ref.modes(n) ** 2 for n in shape),
                                                indexing="ij"))))
    np.testing.assert_allclose(ref.projections(u2, vecs), whole["proj"], rtol=1e-10)
