#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python bench/run.py --workload fft2_16k_p1 --seed 7 --seconds 10 --trace 0

Every part of a cell is found by its name, so that a cell, a deployment,
a traffic mix or a metric is added by adding files and entries:

    BENCHMARK.json             cells, metrics, bounds
    bench/configs/<c>.json     the deployment; its "system" names the driver
    bench/drivers/<s>.py       set-up, measured window and check of a system
    bench/traffic/<t>.json     a traffic mix: parameters, read by bench/traffic.py
    bench/arrivals/<a>.py      the arrival process a traffic mix names
    bench/metrics/<m>.py       one reader per metric: read(ctx) -> number|None
    bench/peaks.json           published peaks, keyed by device kind

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
the first part of the window with the JAX profiler and prints its
per-layer metrics, ``busy_s``/``window_s`` and a ``breakdown``. Either
way the outputs of the timed path are compared with a float64 reference
after the window, and the last line of standard output is one JSON
object. Without a TPU, with fewer chips than the cell asks for, or on a
device kind that ``peaks.json`` does not list, the run exits non-zero
and prints no result. ``BENCH_KEEP_TRACE=<dir>`` keeps a copy of the raw
trace of a ``--trace 1`` run there (``bench/record_fixture.py`` uses it).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent

class BenchError(RuntimeError):
    """The cell cannot run here; no result is printed."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(REPO)}")
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, spec: dict | None = None) -> SimpleNamespace:
    """The cell ``workload`` with its configuration, traffic and metrics."""
    spec = spec if spec is not None else load_json(REPO / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(REPO / configs[cell["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return SimpleNamespace(
        cell=cell,
        config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)],
    )


def device_kind_peaks(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def use_compile_cache() -> str:
    """The program's persistent compile cache, at its fixed in-checkout
    path unless ``JAX_COMPILATION_CACHE_DIR`` names one, and every
    program cached however fast it compiled (the cell's small eager ops
    too), so that only a checkout's first run compiles."""
    import jax

    from repro.core.compat import use_compile_cache as program_cache

    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class CompileCounter:
    """Counts backend compilations, so a compile inside the window shows."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1


class Tracer:
    """Profiler control handed to a driver: ``start()`` and ``stop()``
    bound the part of the window that a ``--trace 1`` run records (once;
    both do nothing in a ``--trace 0`` run). Drivers mark their host
    phases with ``jax.profiler.TraceAnnotation("bench.<phase>")``; the
    reduction names each idle gap of the device after the phase the host
    was in."""

    def __init__(self, enabled: bool, out_dir: Path):
        self.enabled = enabled
        self.out_dir = out_dir
        self.recorded = False
        self._span = None

    def start(self) -> None:
        if not self.enabled or self.recorded or self._span is not None:
            return
        import jax

        from trace_reduce import WINDOW_SPAN

        shutil.rmtree(self.out_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.out_dir))
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        if self._span is None:
            return
        import jax

        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()
        self.recorded = True

    def xspace(self) -> Path | None:
        found = sorted(self.out_dir.glob("plugins/profile/*/*.xplane.pb"))
        return found[-1] if found else None


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    spec: dict | None = None,
    config: dict | None = None,
    traffic: dict | None = None,
    require_tpu: bool = True,
    log=print,
) -> dict:
    """Set up, measure and check one cell; returns the result object.

    ``config`` and ``traffic`` replace the cell's configuration and
    traffic mix (the tests run a cell at a size and load a CPU holds),
    and ``require_tpu=False`` skips the look for a chip; the benchmark's
    own runs use none of them.
    """
    t0 = time.perf_counter()
    for path in (str(BENCH), str(REPO / "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    cell = resolve(workload, spec)
    if config is not None:
        cell.config = config
    if traffic is not None:
        cell.traffic = traffic
    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    if require_tpu:
        if devices[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX platform is {devices[0].platform!r}")
        peaks = device_kind_peaks(kind)
    else:
        peaks = load_json(BENCH / "peaks.json")["devices"].get(kind)
    chips = cell.cell["chips"]
    if len(devices) < chips:
        raise BenchError(f"{len(devices)} devices here, the cell asks for {chips}")
    used = devices[:chips]
    log(f"# {workload}: {kind} x{len(devices)} ({chips} used), compile cache {cache}")

    driver = load_module(BENCH / "drivers" / f"{cell.config['system']}.py")
    from traffic import Traffic

    traffic = Traffic(cell.traffic, seed, seconds)
    compiles = CompileCounter()
    state = driver.setup(cell.config, traffic, seed, used, log=log)
    setup_s = time.perf_counter() - t0
    compiles_before = compiles.count

    tracer = Tracer(trace, REPO / ".bench_trace" / workload)
    try:
        record = driver.window(state, traffic, tracer, log=log)
    finally:
        tracer.stop()
    compiles_in_window = compiles.count - compiles_before
    memory_peak = peak_bytes(used)
    log(f"# window: {record['attempted']} attempted, {record['failed']} failed, "
        f"{compiles_in_window} compiles inside the window")

    reduced = None
    if trace:
        from trace_reduce import hlo_op_paths, reduce_xspace

        path = tracer.xspace()
        if path is None:
            raise BenchError("--trace 1 recorded no profile")
        texts = driver.programs(state) if hasattr(driver, "programs") else []
        reduced = reduce_xspace(path, record.get("step_span"), hlo_op_paths(texts))
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            shutil.copy(path, Path(keep) / f"{workload}-{seed}.xplane.pb")
        shutil.rmtree(tracer.out_dir, ignore_errors=True)
        if require_tpu and not reduced.devices:
            raise BenchError("the trace holds no device operation")

    t_check = time.perf_counter()
    checks = driver.check(state, record, log=log)
    del state
    log(f"# check took {time.perf_counter() - t_check:.3f} s")

    ctx = SimpleNamespace(
        record=record, setup_s=setup_s, peak_bytes=memory_peak, trace=reduced,
        config=cell.config, cell=cell.cell, peaks=peaks, chips=chips,
    )
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    checks["compiles_in_window"] = {"value": compiles_in_window, "limit": 0}
    correct = all(
        c["value"] is not None and math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()
    )
    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": int(memory_peak),
    }
    result = {
        "correct": bool(correct),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
