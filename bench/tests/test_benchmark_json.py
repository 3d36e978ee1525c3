"""BENCHMARK.json against the benchmark's contract, and every name in it
resolved to its file: configurations, traffic mixes, drivers, metric
readers."""

import json
import re
from pathlib import Path

import pytest

import run
from traffic import Traffic

SPEC = run.load_json(run.REPO / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|projection|head|expansion")


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_shape_and_limits():
    assert set(SPEC) == KEYS["top"]
    assert len(json.dumps(SPEC)) <= 64 * 1024
    assert SPEC["command"][1:] == ["bench/run.py"] and len(SPEC["command"]) <= 32
    assert all(PATH.match(p) and ".." not in p and not p.startswith("/") for p in SPEC["paths"])
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[section]]
        assert len(names) == len(set(names)), section
        for entry in SPEC[section]:
            extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
            assert KEYS[section] <= set(entry) <= KEYS[section] | extra, entry
            assert NAME.match(entry["name"]), entry["name"]
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)


def test_full_check_fits_its_time():
    cells = 24
    runs = 2 + 14 * cells
    assert runs * (SPEC["run_seconds"] + 60) + cells * 2 * 90 + 1200 <= 43200


def test_metrics():
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        assert (run.BENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25 and "workloads" not in setup[0]
    for m in SPEC["per_layer"]:
        assert one_line(m["layer"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_reports_enough():
    def reported(cell, section):
        return [m for m in SPEC[section] if cell in m.get("workloads", [cell])]

    for w in SPEC["workloads"]:
        e2e = [m["name"] for m in reported(w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert reported(w["name"], "per_layer"), w["name"]


def test_per_layer_metrics_move_what_their_cells_report():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = [w["name"] for w in SPEC["workloads"]]
    layers = {}
    for m in SPEC["per_layer"]:
        assert "workloads" in m, m["name"]
        assert m["moves"] in e2e, m["name"]
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", cells), (m["name"], cell)
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_cells_resolve_by_name():
    configs = {c["name"]: c for c in SPEC["configs"]}
    used, pairs = set(), set()
    for w in SPEC["workloads"]:
        assert one_line(w["why"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        used.add(w["config"])
        cell = run.resolve(w["name"])
        assert (run.BENCH / "drivers" / f"{cell.config['system']}.py").is_file()
        assert cell.config["devices"] == w["chips"]
        Traffic(cell.traffic, 2**31 + 11, SPEC["run_seconds"])
    assert used == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
        body = run.load_json(run.REPO / c["file"])
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if WIDTH.search(k)]


def test_four_chip_cells_at_most_half():
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("clients", [1, 2, 3])
def test_closed_loop_keeps_clients_out(clients):
    """Every traffic file names an arrival process found by that name; a
    closed loop keeps ``clients`` requests out, counts what completes
    inside the window and waits for the rest."""
    for w in SPEC["workloads"]:
        Traffic(run.resolve(w["name"]).traffic, 2**31 + 13, 1)
    traffic = Traffic({"arrival": "closed", "clients": clients}, 2**31 + 3, 0.05)
    out, answered = [], []

    def submit():
        out.append(len(out))
        assert len(out) - len(answered) <= clients
        return out[-1]

    done, window_s = traffic.run(submit, answered.append)
    assert window_s >= 0.05 and done >= 1
    assert answered == out and len(out) == done + clients - 1


def test_unknown_arrival_is_refused():
    with pytest.raises(ValueError, match="unknown arrival"):
        Traffic({"arrival": "no_such_process"}, 1, 1)
